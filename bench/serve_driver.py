"""A serving cell: the paged, chunked ``ServeEngine`` under an open-loop
mix, greedy decoding, wall clock.

Set-up builds the engine on the benchmark's weights and serves a warm-up
burst drawn from another token stream than the window's (so the window
earns every prefix hit itself); the burst repeats one prompt, which takes
the copy-on-write path, so no program first runs inside the window. The
window is one ``serve()`` call whose requests are due over ``--seconds``;
each request must finish within the mix's ``deadline_s`` of its due time
or counts as failed. Every emitted token is timed by the harness's own
``on_token`` hook.

Correctness: a sample of the finished requests, drawn from the seed and
holding the longest, is run through the plain reference teacher-forced
(prompt + served tokens), in the configuration's own precision; the mean
gap by which a served token's reference logit lies below the reference's
best is compared. The widest gap is logged beside it, not compared: on a
sound seed it reached a third of the float8 control's least (PERF.md).
"""
from __future__ import annotations

import gc
import math

import numpy as np

import compare
import harness
import traffic
import weights
from harness import log, now


# the reference's arithmetic for each precision a configuration serves in
REF_PREC = {"bfloat16": "bf16", "float32": "f32"}


def _engine(m, mix, seed, params):
    from repro.engine import RunSpec, ServeEngine
    cfg = harness.program_config(m)
    # The engine's own seed feeds only what the benchmark replaces and the
    # sampler's key, which its compiled programs hold as a constant: one
    # fixed value keeps them in the cache for every --seed.
    spec = RunSpec(config=cfg, mesh_data=1, mesh_model=1,
                   kernels=mix["kernels"], seed=0)
    eng = ServeEngine(spec, prompt_len=mix["prompt_len"], gen=mix["gen"],
                      paged=True, kv_block_size=mix["kv_block_size"],
                      kv_pool_blocks=mix["kv_pool_blocks"], verbose=False)
    with harness.supplied(params):
        eng.build()
    return eng


def _policy(mix, deadline=True):
    from repro.engine import ServePolicy
    return ServePolicy(max_slots=mix["max_slots"],
                       prefill_chunk=mix["prefill_chunk"], clock="wall",
                       deadline_s=mix["deadline_s"] if deadline else None)


def _requests(turns, times=None):
    from repro.engine import Request

    def hook(rid, tok, step, wall_t):
        times[rid].append(now())

    reqs = []
    for i, t in enumerate(turns):
        if times is not None:
            times[i] = []
        reqs.append(Request(rid=i, prompt=t.prompt, max_gen=t.max_gen,
                            arrival_time=t.due, temperature=0.0,
                            on_token=hook if times is not None else None))
    return reqs


def warm_up(eng, mix, seed, vocab):
    """A burst of ``max_slots`` + 1 requests, due at once, from the
    warm-up token stream; the last repeats the first prompt (a whole-prompt
    prefix hit, the copy-on-write path)."""
    turns = traffic.serve_requests(mix, vocab, seed, horizon=60.0, salt=1)
    turns = turns[:mix["max_slots"]]
    turns.append(turns[0])
    burst = [traffic.Turn(0.0, t.prompt, min(t.max_gen, 8), 0, 0)
             for t in turns]
    t0 = now()
    res = eng.serve(_requests(burst, {}), policy=_policy(mix, False))
    bad = [r.status for r in res["requests"] if r.status != "ok"]
    if bad:
        raise harness.Refused(f"warm-up requests ended {bad}")
    log(f"warm-up: {len(burst)} requests in {now() - t0:.1f}s")


def program_side(m, mix, seed, devs, window_s, tracer=None):
    import jax
    from repro.models import init_params
    from repro.models.model import padded_vocab

    cfg = harness.program_config(m)
    if padded_vocab(cfg) != m["vocab"]:
        raise harness.Refused("padded vocab differs from the vocab")
    layout = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    params = weights.make_params(layout, seed)
    eng = _engine(m, mix, seed, params)
    warm_up(eng, mix, seed, m["vocab"])
    turns = traffic.serve_requests(mix, m["vocab"], seed, horizon=window_s)
    times = {}
    reqs = _requests(turns, times)
    t_setup = now()

    counter = harness.CompileCounter()
    counter.armed = True
    if tracer:
        tracer.start()
    t0 = now()
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("serve.call"):
            res = eng.serve(reqs, policy=_policy(mix))
    t1 = now()
    if tracer:
        tracer.stop()
    counter.armed = False
    info = harness.device_info(devs)
    eng.pool_sleep(level=2)          # frees the KV pool for the reference
    del eng
    gc.collect()
    # serve() stacks its token history after its loop, which compiles for
    # each new length; that lies after the last token, outside every metric
    last = max((ts[-1] for ts in times.values() if ts), default=t1)
    return {"turns": turns, "requests": res["requests"], "times": times,
            "metrics": res["metrics"], "t0": t0, "t1": t1,
            "t_setup": t_setup, "params": params, "device": info,
            "compiles_in_window": counter.before(last)}


def timings(prog):
    """TTFT from each request's due time, every inter-token gap, and the
    output rate up to the last completion."""
    t0, ttft, itl, out_tok, last = prog["t0"], [], [], 0, prog["t0"]
    for r, t in zip(prog["requests"], prog["turns"]):
        ts = prog["times"][r.rid]
        if r.status != "ok" or not ts:
            ttft.append(math.inf)
        else:
            ttft.append(ts[0] - (t0 + t.due))
            out_tok += len(r.tokens)
            last = max(last, ts[-1])
        itl += [b - a for a, b in zip(ts, ts[1:])]
    return {"ttft_s": ttft, "itl_s": itl, "out_tokens": out_tok,
            "out_span_s": last - t0}


def check_sample(prog, mix, seed):
    """Rows of the sampled finished requests: tokens [N, prompt_len + gen]
    (prompt, then the served tokens), served positions and tokens."""
    done = [r for r in prog["requests"] if r.status == "ok"
            and len(r.tokens)]
    if not done:
        return None
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    k = min(mix["check_requests"] - 1, len(rest))
    pick = traffic.rng_for(seed, 4).choice(len(rest), k, replace=False)
    rows = [longest] + [rest[i] for i in sorted(pick)]
    T, G = mix["prompt_len"] + mix["gen"], max(len(r.tokens) for r in rows)
    tokens = np.zeros((len(rows), T), np.int32)
    pos = np.zeros((len(rows), G), np.int32)
    served = np.zeros((len(rows), G), np.int32)
    valid = np.zeros((len(rows), G), bool)
    for i, r in enumerate(rows):
        p, g = len(r.prompt), len(r.tokens)
        tokens[i, :p] = r.prompt
        tokens[i, p:p + g] = r.tokens
        pos[i, :g] = p - 1 + np.arange(g)
        pos[i, g:] = p - 1
        served[i, :g] = r.tokens
        valid[i, :g] = True
    return {"tokens": tokens, "pos": pos, "served": served, "valid": valid}


def readings(m, params, sample, control=None):
    """served_gap_mean for the sample, against the plain reference in the
    configuration's own precision; the widest gaps and the number of
    tokens off the reference's best are logged. ``control`` names the
    precision of a reference put in the program's place: its argmax is the
    served token at every position (it need not decode)."""
    prec = REF_PREC[m["dtype"]]
    ref = harness.family(m)
    if control is not None:
        picked = ref.forward_served(m, params, sample["tokens"],
                                    sample["pos"], control)
        gaps = ref.served_gaps(m, params, sample["tokens"], sample["pos"],
                               sample["served"], prec, picked=picked)
    else:
        gaps = ref.served_gaps(m, params, sample["tokens"], sample["pos"],
                               sample["served"], prec)
    gaps = gaps[sample["valid"]]
    top = np.sort(gaps)[::-1][:5]
    log(f"{control or 'program'} gaps: widest {float(top[0])!r}, "
        f"{int((gaps > 0).sum())} of {gaps.size} tokens off the "
        f"reference's best, largest {[float(g) for g in top]}")
    return {"served_gap_mean": float(gaps.mean())}


def run(ctx):
    m, mix, seed = ctx["model"], ctx["mix"], ctx["seed"]
    prog = program_side(m, mix, seed, ctx["devices"], ctx["seconds"],
                        ctx.get("tracer"))
    tm = timings(prog)
    sample = check_sample(prog, mix, seed)
    t_ref = now()
    rd = (readings(m, prog["params"], sample) if sample is not None
          else {"served_gap_mean": float("nan")})
    log(f"reference: {now() - t_ref:.1f}s over "
        f"{0 if sample is None else int(sample['valid'].sum())} tokens")
    failed = sum(r.status != "ok" for r in prog["requests"])
    pg = prog["metrics"]["paging"]
    log(f"serve: {len(prog['requests'])} requests, {failed} failed, "
        f"status {prog['metrics']['status_counts']}, decode steps "
        f"{prog['metrics']['decode_steps']}, prefill calls "
        f"{prog['metrics']['prefill_calls']}, prefix hit rate "
        f"{pg['prefix_hit_rate']}, preemptions {pg['preemptions']}, "
        f"blocks in use peak {pg['blocks_in_use_peak']}/{pg['pool_blocks']}")
    span = max(tm["out_span_s"], 1e-9)
    log(f"serve timings: ttft p95 "
        f"{1e3 * harness.nearest_rank(tm['ttft_s'], 95)!r} ms, itl p95 "
        f"{1e3 * harness.nearest_rank(tm['itl_s'], 95)!r} ms, itl p50 "
        f"{1e3 * harness.nearest_rank(tm['itl_s'], 50)!r} ms over "
        f"{len(tm['itl_s'])} gaps, out {tm['out_tokens'] / span!r} "
        f"tokens/s over {span!r} s")
    return {
        "setup_s": prog["t_setup"] - ctx["t_start"],
        "metrics": {
            "ttft_p95_ms": harness.finite(
                1e3 * harness.nearest_rank(tm["ttft_s"], 95)),
            "itl_p95_ms": harness.finite(
                1e3 * harness.nearest_rank(tm["itl_s"], 95)),
            "serve_out_tok_s": tm["out_tokens"] / span,
        },
        "attempted": len(prog["requests"]), "failed": failed,
        "device": prog["device"], "readings": rd,
        "compiles_in_window": prog["compiles_in_window"],
        "window_s": prog["t1"] - prog["t0"],
        "counters": {"paging": pg, "decode_steps":
                     prog["metrics"]["decode_steps"],
                     "prefill_calls": prog["metrics"]["prefill_calls"],
                     "requests": prog["requests"], "turns": prog["turns"],
                     "times": prog["times"], "t0": prog["t0"]},
    }
