#!/usr/bin/env python3
"""Bring-up smoke run on the TPU: trains and serves stablelm-1.6b at its
published widths through the entry points a user calls (``RunSpec`` ->
``TrainEngine`` / ``ServeEngine``), with the Pallas kernels compiled for
the chip. Weights are random, made from a seed.

    python chip_smoke.py                # one chip: train, then serve
    python chip_smoke.py --four-chips   # four chips: the plans' ring only

Phases (one process; every check raises, nothing falls back):

* train — depth cut to ``TRAIN_LAYERS`` at full widths, plan cdp_v2 on a
  1x1 mesh, every kernel Pallas, batch 4 x seq 2048 for a few steps. The
  step fits in ``HEADROOM`` of the chip's memory, every loss is finite and
  the compiled step holds Mosaic kernels (``tpu_custom_call``). A
  ``kernels="jnp"`` engine runs the same steps: the loss trajectories agree
  within ``LOSS_ATOL``, and on the first batch every attention leaf's
  gradient agrees layer by layer within ``GRAD_RTOL`` (relative L2).
* serve — full depth, paged KV cache, chunked prefill over 4 slots, Pallas
  for prefill_attn / decode_attn / paged_attn. Eight greedy requests of
  mixed prompt lengths, four sharing a prefix, 16 tokens each, all must end
  ``ok``; a jnp paged engine serves the same requests. The first-token
  logits of a dense prefill agree within ``LOGITS_RTOL``. Every served
  token is the argmax of a teacher-forced jnp forward over the prompt and
  the Pallas stream wherever that forward's top-2 margin exceeds twice the
  largest first-token logit gap ("decided" steps), and the Pallas and jnp
  token streams are equal up to each row's first undecided step.
* four chips (``--four-chips``, only this phase) — mesh data=4 x model=1,
  the same batches through ``dp``, ``zero_cdp`` with ``rule="dp"`` (its
  parameters must match dp's) and ``zero_cdp`` (its loss must stay within
  the one-step delay band of dp's); the zero_cdp step's HLO moves
  parameters with collective-permutes only — no all-gather.

Every line but the last is set-up information (depths, bytes, losses,
seconds), not a measurement. The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
with N the chips the phases used (1, or 4 with ``--four-chips``), printed
only when every phase passed. Without a TPU the script exits
non-zero before any phase and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "stablelm-1.6b"
# Largest depth whose compiled Pallas train step keeps ``1 - HEADROOM`` of
# one v5e chip's memory free (checked on the chip), and whose jnp reference
# engine still fits. Compiled for a described v5e (batch 4 x seq 2048,
# cdp_v2: bf16 params + params_prev, f32 momentum): 16 layers hold 13.10 GiB
# with Pallas kernels and 14.32 GiB with jnp attention; 17 layers push the
# jnp engine to 14.87 GiB.
TRAIN_LAYERS = 16
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 2048, 5
HEADROOM = 0.9          # the Pallas step's share of the chip's bytes_limit
# Pallas-vs-jnp limits, about twice to five times what one v5e read
# (bf16): loss gap over the 5 steps 4.04e-4 nats, worst attention gradient
# 0.0238 (wq), first-token logits 0.0137. A wrong kernel reads ~1.
LOSS_ATOL = 2e-3        # nats: bf16 Pallas vs jnp loss, every step
GRAD_RTOL = 0.05        # worst attention leaf x layer, relative L2
SERVE_SLOTS, SERVE_CHUNK, SERVE_GEN = 4, 64, 16
SERVE_PROMPT_LEN = 256  # longest prompt the engine accepts
LOGITS_RTOL = 0.02      # relative L2 gap of bf16 Pallas vs jnp logits
# four-chip phase: full widths, depth 2, f32 so parity is tight
RING_LAYERS, RING_BATCH, RING_SEQ, RING_STEPS = 2, 8, 512, 6
PARAM_ATOL = 1e-3       # zero_cdp(rule=dp) vs dp parameters after the run
DELAY_BAND = 0.15       # |zero_cdp loss[t+1] - dp loss[t]| (one-step delay)


def check(cond, msg):
    if not cond:
        raise RuntimeError(msg)


def device_check(count):
    """The devices JAX found; exits non-zero unless they are TPUs (JAX
    falls back to the CPU when the TPU does not initialise)."""
    import jax
    from repro.kernels import ops
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {d.platform}); nothing run")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} chips, found {len(devs)}")
    check(not ops.default_interpret(),
          "Pallas kernels would run in interpret mode on a TPU")
    return devs


def _log(msg):
    print(msg, flush=True)


def attn_grad_gaps(spec, *, batch, seq, data_tokens):
    """Per-layer relative L2 gap, Pallas vs jnp kernels, of every attention
    leaf's gradient at the engines' initial params on the first batch of
    their data stream. Reads the flash backward (dq, dk/dv) directly."""
    import jax
    import jax.numpy as jnp
    from repro.data import lm_batch_iterator, make_lm_data
    from repro.models import init_params
    from repro.models.model import loss_fn
    cfg_p = spec.with_(kernels="pallas").resolve_config()
    cfg_j = spec.with_(kernels="jnp").resolve_config()
    tokens = make_lm_data(cfg_p.vocab_size, data_tokens, seed=spec.seed)
    hb = next(lm_batch_iterator(tokens, batch, seq, seed=spec.seed))
    b = {"tokens": jnp.asarray(hb["tokens"]),
         "targets": jnp.asarray(hb["targets"])}
    params = init_params(cfg_p, jax.random.PRNGKey(spec.seed))

    def attn_grads(cfg):
        g = jax.jit(jax.grad(lambda p, b: loss_fn(cfg, p, b)[0]))
        return g(params, b)["blocks"]["dense"]["attn"]

    gp = attn_grads(cfg_p)
    gj = attn_grads(cfg_j)

    @jax.jit
    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        ax = tuple(range(1, a.ndim))
        return (jnp.sqrt(jnp.sum((a - b) ** 2, ax)) /
                jnp.sqrt(jnp.sum(b ** 2, ax)))

    return {k: [float(x) for x in rel(gp[k], gj[k])] for k in sorted(gp)}


def train_phase(cfg, *, batch, seq, steps, loss_atol, grad_rtol,
                devices=None):
    """cdp_v2 on a 1x1 mesh with every kernel Pallas, against a jnp-kernel
    engine of the same seed: the whole loss trajectory (steps after the
    first depend on the backward pass) and, on the first batch, every
    attention leaf's gradient layer by layer."""
    import math

    import jax
    from repro.engine import RunSpec, TrainEngine
    spec = RunSpec(config=cfg, mesh_data=1, mesh_model=1, plan="cdp_v2")
    dev = (devices or jax.devices())[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    losses, fitted = {}, {}
    for kernels in ("pallas", "jnp"):
        t0 = time.time()
        eng = TrainEngine(spec.with_(kernels=kernels), steps=steps,
                          batch=batch, seq=seq, log_every=1, verbose=False)
        m = eng.compile().memory_analysis()
        fitted[kernels] = (m.argument_size_in_bytes +
                           m.output_size_in_bytes - m.alias_size_in_bytes +
                           m.temp_size_in_bytes)
        _log(f"train[{kernels}]: layers={cfg.num_layers} "
             f"fitted_bytes={fitted[kernels]} bytes_limit={limit}")
        if kernels == "pallas":
            check("tpu_custom_call" in eng.hlo_text(),
                  "the Pallas train step holds no Mosaic kernel")
        compiled_s = time.time() - t0
        eng.run()
        losses[kernels] = [h["loss"] for h in eng.history]
        data_tokens = eng.data_tokens
        _log(f"train[{kernels}]: losses={losses[kernels]} "
             f"(build+compile {compiled_s:.1f}s, "
             f"total {time.time() - t0:.1f}s)")
        eng.close()
        del eng, m
        gc.collect()
    check(limit is not None and fitted["pallas"] <= HEADROOM * limit,
          f"train step holds {fitted['pallas']} bytes, more than "
          f"{HEADROOM} of {limit}: cut TRAIN_LAYERS")
    for k, ls in losses.items():
        check(len(ls) == steps and all(math.isfinite(x) for x in ls),
              f"non-finite or missing {k} train losses: {ls}")
    gap = max(abs(a - b) for a, b in zip(losses["pallas"], losses["jnp"]))
    _log(f"train: max loss gap pallas-jnp over {steps} steps={gap} "
         f"(limit {loss_atol})")
    check(gap <= loss_atol,
          f"loss trajectories differ by {gap} > {loss_atol}: {losses}")

    t0 = time.time()
    gaps = attn_grad_gaps(spec, batch=batch, seq=seq,
                          data_tokens=data_tokens)
    worst = max(max(v) for v in gaps.values())
    _log(f"train: first-batch attention grad rel_l2 pallas-jnp, worst "
         f"layer per leaf: { {k: max(v) for k, v in gaps.items()} } "
         f"(limit {grad_rtol}, {time.time() - t0:.1f}s)")
    check(worst <= grad_rtol,
          f"attention gradients differ: rel L2 {worst} > {grad_rtol}: "
          f"{gaps}")
    return losses


def serve_requests(vocab, *, prompt_len, gen, seed=0):
    """Eight greedy requests, alternating: four share a 128-token prefix
    with tails of different lengths (the later two can hit the prefix
    blocks the earlier two wrote), four have prompts of their own
    (23..prompt_len tokens)."""
    import numpy as np
    from repro.engine.batching import Request
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, 128, dtype=np.int32)
    shared = [np.concatenate([prefix, rng.integers(0, vocab, t,
                                                   dtype=np.int32)])
              for t in (9, 40, 71, 100)]
    own = [rng.integers(0, vocab, n, dtype=np.int32)
           for n in (23, 64, 150, prompt_len)]
    prompts = [p for pair in zip(shared, own) for p in pair]
    return [Request(rid=i, prompt=p, max_gen=gen)
            for i, p in enumerate(prompts)]


def serve_once(name, spec, cfg, *, prompt_len, gen, slots, chunk):
    """The eight requests through a paged ServeEngine; returns the engine
    and the finished requests in rid order."""
    from repro.engine import ServeEngine, ServePolicy
    t0 = time.time()
    eng = ServeEngine(spec, prompt_len=prompt_len, gen=gen, paged=True,
                      verbose=False)
    reqs = serve_requests(cfg.vocab_size, prompt_len=prompt_len, gen=gen)
    res = eng.serve(reqs, policy=ServePolicy(max_slots=slots,
                                             prefill_chunk=chunk))
    done = sorted(res["requests"], key=lambda r: r.rid)
    m = res["metrics"]
    _log(f"serve[{name}]: layers={cfg.num_layers} "
         f"status={m['status_counts']} decode_steps={m['decode_steps']} "
         f"prefix_hit_rate={m['paging']['prefix_hit_rate']} "
         f"(build+compile+serve {time.time() - t0:.1f}s)")
    check(all(r.status == "ok" for r in done),
          f"requests not ok: {[(r.rid, r.status, r.error) for r in done]}")
    check(all(len(r.tokens) == gen for r in done),
          f"token counts {[len(r.tokens) for r in done]} != {gen}")
    return eng, done


def serve_phase(cfg, *, prompt_len, gen, slots, chunk, logits_rtol):
    """The eight requests through the Pallas paged engine and through a
    jnp paged engine. The first-token logits of a dense prefill are
    compared; every served token is compared with a teacher-forced jnp
    forward over the prompt and the Pallas stream."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.engine import RunSpec
    from repro.models.model import forward
    kernels = "prefill_attn=pallas,decode_attn=pallas,paged_attn=pallas"
    spec = RunSpec(config=cfg, mesh_data=1, mesh_model=1, kernels=kernels)
    V = cfg.vocab_size

    def first_token_logits(eng, done):
        lengths = np.array([len(r.prompt) for r in done], np.int32)
        prompts = np.zeros((len(done), prompt_len), np.int32)
        for i, r in enumerate(done):
            prompts[i, :len(r.prompt)] = r.prompt
        return np.asarray(eng.prefill(
            jnp.asarray(prompts),
            extras={"lengths": jnp.asarray(lengths)}), np.float32)[:, :V]

    eng, done = serve_once("pallas", spec, cfg, prompt_len=prompt_len,
                           gen=gen, slots=slots, chunk=chunk)
    lp = first_token_logits(eng, done)
    served = np.array([r.tokens for r in done], np.int64)      # [n, gen]
    del eng
    gc.collect()

    ref, done_j = serve_once("jnp", spec.with_(kernels="jnp"), cfg,
                             prompt_len=prompt_len, gen=gen, slots=slots,
                             chunk=chunk)
    lj = first_token_logits(ref, done_j)
    served_j = np.array([r.tokens for r in done_j], np.int64)
    # teacher-forced jnp logits at every served position of the Pallas
    # stream: row i, step t reads position len(prompt_i) - 1 + t
    n = len(done)
    seqs = np.zeros((n, prompt_len + gen), np.int32)
    pos = np.zeros((n, gen), np.int32)
    for i, r in enumerate(done):
        L = len(r.prompt)
        seqs[i, :L] = r.prompt
        seqs[i, L:L + gen] = served[i]
        pos[i] = L - 1 + np.arange(gen)
    rows = jnp.arange(n)[:, None]
    tf = jax.jit(lambda p, s, q: forward(ref.cfg, p, {"tokens": s})[0][
        rows, q].astype(jnp.float32))
    lt = np.asarray(tf(ref.params, jnp.asarray(seqs), jnp.asarray(pos)))
    lt = lt[..., :V]                                            # [n, gen, V]
    del ref
    gc.collect()

    check(np.isfinite(lp).all(), "non-finite Pallas prefill logits")
    rel = float(np.linalg.norm(lp - lj) / np.linalg.norm(lj))
    gap = float(np.abs(lp - lj).max())
    top2 = np.sort(lt, axis=-1)[..., -2:]
    margin = top2[..., 1] - top2[..., 0]                        # [n, gen]
    decided = margin > 2 * gap
    agree = served == lt.argmax(-1)
    # a row's streams may part only once a step is undecided
    first_open = np.where(decided.all(1), gen, (~decided).argmax(1))
    same = [bool((served[i, :u] == served_j[i, :u]).all())
            for i, u in enumerate(first_open)]
    _log(f"serve: first-token logits rel_l2 pallas-jnp={rel} "
         f"(limit {logits_rtol}), max_gap={gap}")
    _log(f"serve: served tokens = teacher-forced jnp argmax on "
         f"{int((agree & decided).sum())}/{int(decided.sum())} decided steps "
         f"({int(agree.sum())}/{agree.size} steps in all; decided = jnp "
         f"top-2 margin > 2*max_gap); Pallas and jnp streams equal on "
         f"{sum(same)}/{n} rows up to the first undecided step "
         f"{first_open.tolist()}, on {int((served == served_j).all(1).sum())}"
         f"/{n} rows in full")
    check(rel <= logits_rtol,
          f"first-token logits differ: rel L2 {rel} > {logits_rtol}")
    check(agree[decided].all(),
          f"served tokens differ from the teacher-forced jnp argmax at "
          f"decided (row, step) {np.argwhere(decided & ~agree).tolist()}")
    check(all(same), f"Pallas and jnp streams part before an undecided "
          f"step: rows {[i for i, ok in enumerate(same) if not ok]}")


def four_chip_phase(cfg, *, batch, seq, steps, param_atol, delay_band):
    """dp vs zero_cdp(rule=dp) vs zero_cdp on a data=4 ring, same batches
    (the engine's data stream is a pure function of the seed)."""
    import jax
    import numpy as np
    from repro.engine import RunSpec, TrainEngine
    from repro.launch.roofline import parse_collectives
    from repro.parallel import get_plan
    from repro.parallel.zero_cdp import params_from_state
    n = 4
    spec = RunSpec(config=cfg, mesh_data=n, mesh_model=1, kernels="pallas")
    plans = {"dp": "dp",
             "zero_cdp/dp": get_plan("zero_cdp").with_(rule="dp"),
             "zero_cdp": "zero_cdp"}
    losses, params = {}, {}
    for name, plan in plans.items():
        t0 = time.time()
        eng = TrainEngine(spec, plan=plan, steps=steps, batch=batch,
                          seq=seq, log_every=1, verbose=False)
        hlo = eng.hlo_text()
        check("tpu_custom_call" in hlo, f"{name}: no Mosaic kernel")
        if name == "zero_cdp":
            stats = parse_collectives(hlo)
            chunk_bytes = 4 * eng.state["params"]["stages"].shape[1]
            _log(f"ring[{name}]: collectives={stats.op_counts} "
                 f"max_all_reduce_bytes={stats.max_by_type['all-reduce']} "
                 f"chunk_bytes={chunk_bytes}")
            check(stats.op_counts["collective-permute"] >= 2 * (n - 1),
                  "zero_cdp moves stages with too few permutes")
            check(stats.op_counts["all-gather"] == 0,
                  "zero_cdp step holds an all-gather")
            check(stats.max_by_type["all-reduce"] < chunk_bytes // 100,
                  "zero_cdp step all-reduces gradient-sized buffers")
        state = eng.run()
        losses[name] = [h["loss"] for h in eng.history]
        p = (params_from_state(cfg, state, n) if name.startswith("zero")
             else state["params"])
        params[name] = [np.asarray(x, np.float32) for x in
                        jax.tree.leaves(p)]
        _log(f"ring[{name}]: losses={losses[name]} "
             f"({time.time() - t0:.1f}s)")
        eng.close()
        del eng, state, p
        gc.collect()
    diff = max(float(np.abs(a - b).max())
               for a, b in zip(params["dp"], params["zero_cdp/dp"]))
    shifted = float(np.abs(np.asarray(losses["zero_cdp"][1:]) -
                           np.asarray(losses["dp"][:-1])).max())
    _log(f"ring: max |param dp - zero_cdp(rule=dp)|={diff:.3e} "
         f"(limit {param_atol}); max |zero_cdp loss[t+1] - dp loss[t]|="
         f"{shifted:.4f} (band {delay_band})")
    check(diff <= param_atol, f"zero_cdp(rule=dp) params off dp by {diff}")
    check(shifted < delay_band,
          f"zero_cdp loss leaves the delay band: {losses}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip ring phase")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    devs = device_check(4 if args.four_chips else 1)

    from repro.configs import get_config
    full = get_config(ARCH)
    t0 = time.time()
    if args.four_chips:
        four_chip_phase(full.with_(num_layers=RING_LAYERS, dtype="float32"),
                        batch=RING_BATCH, seq=RING_SEQ, steps=RING_STEPS,
                        param_atol=PARAM_ATOL, delay_band=DELAY_BAND)
    else:
        train_phase(full.with_(num_layers=TRAIN_LAYERS), batch=TRAIN_BATCH,
                    seq=TRAIN_SEQ, steps=TRAIN_STEPS, loss_atol=LOSS_ATOL,
                    grad_rtol=GRAD_RTOL, devices=devs)
        serve_phase(full, prompt_len=SERVE_PROMPT_LEN, gen=SERVE_GEN,
                    slots=SERVE_SLOTS, chunk=SERVE_CHUNK,
                    logits_rtol=LOGITS_RTOL)
    _log(f"all phases passed in {time.time() - t0:.1f}s")
    d = devs[0]
    used = 4 if args.four_chips else 1
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": used}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
