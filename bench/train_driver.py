"""A training cell: ``TrainEngine`` with the mix's plan, driven from the
seed through its first steps (the steps the reference follows) and then
for the measured window, on one object.

Correctness (bench/compare.py): the loss of each of the first
``check_steps`` steps; the norm of the first gradient per leaf, worked
out from the optimizer's state after one step (momentum minus weight
decay times the previous parameters, which cdp_v2 keeps); the norm of each
leaf's change over the first steps. The reference (bench/reference.py)
runs the same steps from the same weights on the same batches.
"""
from __future__ import annotations

import gc

import compare
import harness
import reference
import traffic
import weights
from harness import log, now


def _batches(mix, vocab, seed):
    """The program's data stream: batch i of the seed, i = 0, 1, ... The
    engine draws batch 0 to trace its step, so step s trains batch s + 1."""
    import jax
    i = 0
    while True:
        with jax.profiler.TraceAnnotation("feed"):
            b = traffic.markov_batch(mix, vocab, seed, i)
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
    params = weights.make_params(layout, seed)
    lr, mu, wd = mix["lr"], mix["momentum"], mix["weight_decay"]
    eng = TrainEngine(spec, steps=10 ** 9, batch=mix["batch"] * len(devs),
                      seq=mix["seq"], lr_schedule=lambda s: jnp.float32(lr),
                      momentum=mu, weight_decay=wd, log_every=10 ** 9,
                      data_tokens=mix["seq"] + 2, verbose=False)
    with harness.supplied(params, _batches(mix, m["vocab"], seed)):
        eng.compile()
    del params
    hlo = eng.hlo_text()
    if devs[0].platform == "tpu" and "tpu_custom_call" not in hlo:
        raise harness.Refused("the compiled train step holds no Pallas "
                              "kernel")
    import hlo as hlo_mod
    coll = hlo_mod.parse_collectives(hlo)
    log(f"train step collectives: {coll.op_counts}")

    n = mix["check_steps"]
    eng.run(1)
    gnorm = _grad_norms(eng.state, wd)
    for s in range(2, n + 1):
        eng.run(s)
    losses = [h["loss"] for h in eng.history][:n]
    p0 = weights.make_params(layout, seed)
    change = _change_norms(eng.state["params"], p0)
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


def _grad_norms(state, wd):
    """g0 = momentum - wd * params_prev, per leaf, after one step."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(mom, prev):
        return jax.tree.map(
            lambda mo, p: jnp.sqrt(jnp.sum(
                (mo - wd * p.astype(jnp.float32)) ** 2)), mom, prev)
    return _named_norms(f(state["opt"]["mom"], state["params_prev"]))


def _change_norms(params, p0):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(a, b):
        return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(
            (x.astype(jnp.float32) - y.astype(jnp.float32)) ** 2)), a, b)
    return _named_norms(f(params, p0))


def reference_side(m, mix, seed, layout, prec="f32", rows=None):
    """The plain model's readings for the same steps."""
    hyper = (mix["lr"], mix["momentum"], mix["weight_decay"])
    batches = [traffic.markov_batch(mix, m["vocab"], seed, i)
               for i in range(1, mix["check_steps"] + 1)]
    p0 = weights.make_params(layout, seed)
    losses, gnorm, st = reference.train_steps(
        m, p0, batches, hyper, prec=prec, rows=rows)
    change = reference.change_norms(p0, st)
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
    ref = reference_side(m, mix, seed, prog["layout"])
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
