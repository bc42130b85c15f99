"""A training cell: ``TrainEngine`` with the mix's plan, driven from the
seed through its first steps (the steps the reference follows) and then
for the measured window, on one object.

Correctness (bench/compare.py): the loss of each of the first
``check_steps`` steps; the norm of the first gradient per leaf, worked
out from the optimizer's state after one step (momentum minus weight
decay times the previous parameters, which every CDP plan keeps); the norm
of each leaf's change over the first steps. The reference (the family's
``train_steps``) runs the same steps from the same weights on the same
batches under the mix's update rule over its ``mesh_data`` micro-batches.

The mix states the plan, its ``rule`` and its ``placement``; a program
whose plan differs is refused. ``batch`` is rows per chip: a step takes
``batch`` x ``mesh_data`` rows. A stage-sharded plan (``zero_cdp``) keeps
parameters, previous parameters and momentum as ``[n, chunk]`` float32
stage arrays; their norms are read per leaf of the program's own stage
layout, each stage's part on the chip that owns it, and the reference
keeps float32 masters as the program's owners do.
"""
from __future__ import annotations

import gc

import compare
import harness
import traffic
import weights
from harness import log, now

# the dtype in which a placement keeps the parameters the optimizer
# updates (None: the layout's own); a stage's owner holds float32 masters
MASTER = {"replicated": None, "stage_sharded": "float32"}


def step_mix(mix):
    """The mix with ``batch`` the rows of a whole step (every chip's)."""
    return dict(mix, batch=mix["batch"] * mix["mesh_data"])


def _batches(mix, vocab, seed):
    """The program's data stream: batch i of the seed, i = 0, 1, ... The
    engine draws batch 0 to trace its step, so step s trains batch s + 1."""
    import jax
    i = 0
    while True:
        with jax.profiler.TraceAnnotation("feed"):
            b = traffic.markov_batch(step_mix(mix), vocab, seed, i)
        yield b
        i += 1


def program_side(m, mix, seed, devs, window_s, tracer=None):
    """Build, run the checked steps, then the window. Returns the readings,
    the timing and the program's first-step numbers."""
    import jax
    import jax.numpy as jnp
    from repro.engine import RunSpec, TrainEngine
    from repro.models import init_params

    cfg = harness.program_config(m)
    # the engine's seed feeds only its init and data, both replaced below
    spec = RunSpec(config=cfg, mesh_data=mix["mesh_data"], mesh_model=1,
                   plan=mix["plan"], kernels=mix["kernels"],
                   seed=0)
    layout = jax.eval_shape(lambda: init_params(
        spec.resolve_config(), jax.random.PRNGKey(0)))
    spread = _spread(mix, spec, layout)
    params = weights.make_params(layout, seed, spread)
    lr, mu, wd = mix["lr"], mix["momentum"], mix["weight_decay"]
    eng = TrainEngine(spec, steps=10 ** 9, batch=mix["batch"] * len(devs),
                      seq=mix["seq"], lr_schedule=lambda s: jnp.float32(lr),
                      momentum=mu, weight_decay=wd, log_every=10 ** 9,
                      data_tokens=mix["seq"] + 2, verbose=False)
    if (eng.plan.rule, eng.plan.placement) != (mix["rule"], mix["placement"]):
        raise harness.Refused(
            f"plan {eng.plan.name} runs rule {eng.plan.rule} on placement "
            f"{eng.plan.placement}; the mix states {mix['rule']} on "
            f"{mix['placement']}")
    with harness.supplied(params, _batches(mix, m["vocab"], seed)):
        eng.build()
    del params
    if spread is not None:
        # the program's own placement of its state, made before it lowers
        # the step: cut from spread weights, the stages lie otherwise
        eng.state = jax.device_put(eng.state, eng.state_sh)
    eng.compile()
    hlo = eng.hlo_text()
    if devs[0].platform == "tpu" and "tpu_custom_call" not in hlo:
        raise harness.Refused("the compiled train step holds no Pallas "
                              "kernel")
    import hlo as hlo_mod
    coll = hlo_mod.parse_collectives(hlo)
    log(f"train step collectives: {coll.op_counts}")

    n = mix["check_steps"]
    eng.run(1)
    gnorm = grad_norms(eng, wd)
    for s in range(2, n + 1):
        eng.run(s)
    losses = [h["loss"] for h in eng.history][:n]
    p0 = weights.make_params(layout, seed, spread)
    change = change_norms(eng, p0)
    del p0
    t_setup = now()

    counter = harness.CompileCounter()
    counter.armed = True
    if tracer:
        tracer.start()
    done, t0, marks = n, now(), []
    with jax.profiler.TraceAnnotation("window"):
        while True:
            with jax.profiler.TraceAnnotation("step"):
                eng.run(done + mix["chunk_steps"])   # ends in a host sync
            done += mix["chunk_steps"]
            marks.append(now())
            if marks[-1] - t0 >= window_s:
                break
    t1 = now()
    chunks = [b - a for a, b in zip([t0] + marks, marks)]
    log(f"window chunks of {mix['chunk_steps']} steps (s): "
        f"{[round(c, 4) for c in chunks]}")
    if tracer:
        tracer.stop()
    counter.armed = False
    info = harness.device_info(devs)
    steps = done - n
    eng.close()
    del eng
    gc.collect()
    return {"losses": losses, "grad_norms": gnorm, "change_norms": change,
            "t_setup": t_setup, "window_s": t1 - t0, "steps": steps,
            "compiles_in_window": counter.before(t1), "device": info,
            "layout": layout}


def _leafname(path):
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _named_norms(tree):
    import jax
    return {_leafname(p): float(v) for p, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def _spread(mix, spec, layout):
    """Where the benchmark's weights go. On a stage-sharded plan they are
    spread over the data axis along their first dimension, as a sharded
    checkpoint is loaded, so the program cuts its stages where they lie
    (gathered on one chip, its init would hold all three float32 stage
    arrays there first: PERF.md section 7); else where JAX puts them."""
    if mix["placement"] != "stage_sharded":
        return None
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = spec.build_mesh()
    n = mesh.shape["data"]
    return jax.tree.map(lambda s: NamedSharding(
        mesh, P("data") if s.shape and s.shape[0] % n == 0 else P()),
        layout)


def grad_norms(eng, wd):
    """g0 = momentum - wd * params_prev, per leaf, after one step."""
    import jax.numpy as jnp
    st = eng.state
    return leaf_norms(eng, lambda mo, p: mo - wd * p.astype(jnp.float32),
                      st["opt"]["mom"], st["params_prev"])


def change_norms(eng, p0):
    """Per leaf, the norm of the parameters' change from ``p0``."""
    import jax.numpy as jnp
    return leaf_norms(eng, lambda x, y: x.astype(jnp.float32) -
                      y.astype(jnp.float32), eng.state["params"], p0)


def leaf_norms(eng, f, a, b):
    """Per leaf of the program's params layout, ``||f(a, b)||``. ``a`` is
    a state entry (params, params_prev or momentum); ``b`` another, or a
    params pytree. On a stage-sharded plan both are ``[n, chunk]`` stage
    arrays (a pytree is first cut by the program's ``chunk_params``), and
    each leaf's sum of squares is summed over the segments of the
    program's stage layout, each stage's part on the chip that owns it."""
    import jax
    import jax.numpy as jnp
    if eng.plan.placement != "stage_sharded":
        @jax.jit
        def g(a, b):
            return jax.tree.map(
                lambda x, y: jnp.sqrt(jnp.sum(f(x, y) ** 2)), a, b)
        return _named_norms(g(a, b))
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.parallel import zero_cdp
    axis = eng.trainer.data_axis
    n = eng.mesh.shape[axis]
    lay = zero_cdp.build_stage_layout(eng.cfg, n)
    if "stages" not in b:
        b = {"stages": jax.jit(
            lambda t: zero_cdp.chunk_params(lay, t),
            out_shardings=NamedSharding(eng.mesh, P(axis, None)))(b)}

    def part(stage):
        segs = [g for g in lay.segments if g.stage == stage]

        def sums(x):
            acc = [jnp.float32(0.0)] * len(lay.shapes)
            for g in segs:
                piece = x[g.offset:g.offset + g.stop - g.start]
                acc[g.leaf] = acc[g.leaf] + jnp.sum(piece ** 2)
            return jnp.stack(acc)
        return sums

    def local(x, y):
        return jax.lax.switch(jax.lax.axis_index(axis),
                              [part(j) for j in range(n)],
                              f(x[0], y[0]))[None]
    per_stage = jax.jit(jax.shard_map(
        local, mesh=eng.mesh, in_specs=(P(axis, None), P(axis, None)),
        out_specs=P(axis, None)))(a["stages"], b["stages"])
    sq = jax.device_get(per_stage).astype("float64").sum(0)
    return _named_norms(jax.tree.unflatten(lay.treedef,
                                           [x ** 0.5 for x in sq]))


def reference_side(m, mix, seed, layout, prec="f32", rows=None,
                   devices=None):
    """The plain model's readings for the same steps, its state spread
    over ``devices`` (the cell's chips)."""
    fam = harness.family(m)
    hyper = (mix["lr"], mix["momentum"], mix["weight_decay"])
    batches = [traffic.markov_batch(step_mix(mix), m["vocab"], seed, i)
               for i in range(1, mix["check_steps"] + 1)]
    losses, gnorm, st = fam.train_steps(
        m, weights.make_params(layout, seed), batches, hyper, mix["rule"],
        mix["mesh_data"], prec=prec, rows=rows,
        master=MASTER[mix["placement"]], devices=devices)
    # drawn again, not kept through the steps: one chip holds less
    p0 = weights.make_params(layout, seed)
    change = fam.change_norms(p0, st)
    del st, p0
    gc.collect()
    return {"losses": losses, "grad_norms": gnorm, "change_norms": change}


def run(ctx):
    """One measured run of a training cell; returns the harness's result
    pieces."""
    m, mix, seed = ctx["model"], ctx["mix"], ctx["seed"]
    prog = program_side(m, mix, seed, ctx["devices"], ctx["seconds"],
                        ctx.get("tracer"))
    t_ref = now()
    ref = reference_side(m, mix, seed, prog["layout"],
                         devices=ctx["devices"])
    log(f"reference: {now() - t_ref:.1f}s")
    readings = compare.train_readings(prog, ref)
    left_out = sorted(set(ref["grad_norms"]) -
                      set(compare.kept_leaves(ref["grad_norms"])))
    log(f"leaves left out of change_norm_gap: {left_out}")
    tokens = prog["steps"] * mix["batch"] * mix["seq"] * len(ctx["devices"])
    return {
        "setup_s": prog["t_setup"] - ctx["t_start"],
        "metrics": {"train_tok_s": tokens / prog["window_s"]},
        "attempted": prog["steps"], "failed": 0,
        "device": prog["device"], "readings": readings,
        "compiles_in_window": prog["compiles_in_window"],
        "window_s": prog["window_s"],
        "counters": {"steps": prog["steps"], "tokens": tokens},
    }
