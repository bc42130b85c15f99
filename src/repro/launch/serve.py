"""Serving launcher — a thin argparse shim over ``repro.engine.ServeEngine``.

Static batch (the original path — one fixed batch from prefill to last
token):

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
        --reduced --batch 4 --prompt-len 64 --gen 32 --host-devices 4 \
        [--kernels decode_attn=pallas]

Continuous batching (``--max-slots`` switches to the iteration-level
scheduler: ragged prompts prefill with per-row cache lengths and a queued
request is admitted the moment a decode slot frees up; ``--arrival
poisson`` replays a deterministic Poisson arrival trace):

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
        --reduced --max-slots 4 --arrival poisson --rate 0.5 \
        --num-requests 8

Paged KV cache (with --max-slots): ``--paged`` serves from a block pool
with prefix sharing and host-RAM offload (``--kv-block-size``,
``--kv-pool-blocks``, ``--no-prefix-cache``, ``--sleep-level``); the
paging metrics line reports peak pool occupancy and the prefix hit rate.

Prefill runs as ONE fused ``prefill_with_cache`` pass (prefill tok/s is
reported alongside decode tok/s); enc-dec archs go through the public
``models.encode``.

Wall-clock serving knobs (all built into one explicit ``ServePolicy``):
``--prefill-chunk N`` interleaves chunked prompt prefill with decode,
``--clock {step,wall,virtual}`` picks the scheduler clock, ``--admission
slo`` (or the ``--policy slo`` shorthand) enables deadline-aware
admission, and ``--stream`` prints each token live via
``serve_stream()``.
"""
from __future__ import annotations

import argparse
import sys


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--kernels", default=None,
                    help="per-op kernel backends (see launch.train --help)")
    ap.add_argument("--attn-backend", default=None,
                    choices=["jnp", "pallas"],
                    help="DEPRECATED alias: sets train_attn+prefill_attn")
    ap.add_argument("--mesh-data", type=int, default=2)
    ap.add_argument("--mesh-model", type=int, default=2)
    ap.add_argument("--host-devices", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    # continuous batching
    ap.add_argument("--max-slots", type=int, default=0,
                    help="serve with continuous batching over N decode "
                         "slots (0 = static batch via --batch)")
    ap.add_argument("--arrival", default="none",
                    choices=["none", "poisson"],
                    help="request arrival trace: all at step 0, or a "
                         "deterministic Poisson replay (--rate)")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="poisson arrival rate in requests per decode step")
    ap.add_argument("--num-requests", type=int, default=8,
                    help="synthetic staggered workload size (continuous)")
    ap.add_argument("--policy", default="continuous",
                    choices=["continuous", "static", "slo"],
                    help="scheduler policy for --max-slots serving (static "
                         "= fixed-batch baseline on the same jitted fns; "
                         "slo = continuous scheduling with deadline-aware "
                         "admission, shorthand for --admission slo)")
    ap.add_argument("--admission", default=None, choices=["fcfs", "slo"],
                    help="queue-ordering policy: fcfs (default) or "
                         "earliest-deadline-first with feasibility culling")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="cut admitted prompts into chunks of N tokens, "
                         "prefilled one chunk per scheduler iteration "
                         "interleaved with decode (0 = whole-prompt)")
    ap.add_argument("--clock", default="step",
                    choices=["step", "wall", "virtual"],
                    help="scheduler clock: step units (default), the "
                         "monotonic wall clock, or a deterministic virtual "
                         "clock advancing --step-dt seconds per step")
    ap.add_argument("--step-dt", type=float, default=1.0,
                    help="virtual seconds per decode step (--clock virtual)")
    ap.add_argument("--stream", action="store_true",
                    help="serve via serve_stream() and print each token "
                         "the moment its decode step syncs to host")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="optional early-stop token id (costs one host "
                         "sync per decode step)")
    ap.add_argument("--deadline-steps", type=int, default=None,
                    help="engine-wide per-request step budget (queue wait "
                         "+ decode); expired requests return "
                         "status='timeout' with partial tokens")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="bound the admission queue: arrivals beyond the "
                         "limit are rejected with a per-request error "
                         "instead of waiting forever")
    ap.add_argument("--resilience", default=None,
                    help="arm the resilience layer: 'on' enables the "
                         "health/quarantine pass only, or a fault spec "
                         "('poison_request@3') to poison request rid 3's "
                         "cache rows deterministically")
    # paged KV cache (continuous batching only)
    ap.add_argument("--paged", action="store_true",
                    help="serve from a paged KV block pool (prefix "
                         "sharing + host-RAM offload) instead of the "
                         "dense per-slot cache")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="tokens per KV block (--paged)")
    ap.add_argument("--kv-pool-blocks", type=int, default=None,
                    help="total pool blocks shared by all slots (--paged; "
                         "default: slots x cache blocks, the dense "
                         "equivalent)")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=True,
                    help="share full prompt-prefix blocks across requests "
                         "(--paged; default on)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false")
    ap.add_argument("--sleep-level", type=int, default=1, choices=[1, 2],
                    help="preemption mode under pool pressure (--paged): "
                         "1 = offload blocks to host RAM and restore "
                         "bitwise on wake, 2 = discard and re-prefill")
    args = ap.parse_args(argv)

    from repro.engine import RunSpec
    spec = RunSpec(arch=args.arch, reduced=args.reduced,
                   kernels=args.kernels, attn_backend=args.attn_backend,
                   mesh_data=args.mesh_data, mesh_model=args.mesh_model,
                   host_devices=args.host_devices, seed=args.seed)
    spec = spec.auto_host_devices()     # CPU container: default to mesh size
    spec.ensure_host_devices()          # before anything imports jax state
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.paged and not args.max_slots:
        print("--paged requires --max-slots (continuous batching)",
              file=sys.stderr)
        return 2

    from repro.engine import ServeEngine
    engine = ServeEngine(spec, batch=args.batch, prompt_len=args.prompt_len,
                         gen=args.gen, temperature=args.temperature,
                         resilience=args.resilience, paged=args.paged,
                         kv_block_size=args.kv_block_size,
                         kv_pool_blocks=args.kv_pool_blocks,
                         prefix_cache=args.prefix_cache,
                         sleep_level=args.sleep_level)

    if args.max_slots:
        from repro.engine import ServePolicy
        sched = "continuous" if args.policy == "slo" else args.policy
        admission = args.admission or \
            ("slo" if args.policy == "slo" else "fcfs")
        sp = ServePolicy(max_slots=args.max_slots,
                         num_requests=args.num_requests,
                         arrival=args.arrival, rate=args.rate,
                         policy=sched, admission=admission,
                         eos_id=args.eos_id,
                         deadline_steps=args.deadline_steps,
                         queue_limit=args.queue_limit,
                         prefill_chunk=args.prefill_chunk,
                         clock=args.clock, step_dt=args.step_dt)
        if args.stream:
            gen = engine.serve_stream(policy=sp)
            n_streamed = 0
            while True:
                try:
                    rid, tok = next(gen)
                except StopIteration as fin:
                    res = fin.value
                    break
                print(f"  [stream] rid {rid} token {tok}")
                n_streamed += 1
            print(f"  streamed {n_streamed} tokens live")
        else:
            res = engine.serve(policy=sp)
        for r in res["requests"][:2]:
            print(f"  request {r.rid} (arrival step {r.arrival_step}, "
                  f"{len(r.prompt)}-token prompt, status {r.status}): "
                  f"{r.tokens[:16].tolist()}")
        m = res["metrics"]
        print(f"  admitted mid-decode: {m['admitted_mid_decode']} / "
              f"{m['n_requests']}")
        print(f"  status counts: {m['status_counts']}")
        print(f"  clock {m['clock']} admission {m['admission']} "
              f"goodput {m['goodput']} ttft p50/p99 "
              f"{m['ttft']['p50']}/{m['ttft']['p99']}")
        if "paging" in m:
            pg = m["paging"]
            print(f"  paging: {pg['blocks_in_use_peak']}/"
                  f"{pg['pool_blocks']} blocks peak, prefix hit rate "
                  f"{pg['prefix_hit_rate']}, "
                  f"{pg['marginal_prefill_tokens']}/"
                  f"{pg['prefill_tokens_requested']} prefill tokens "
                  f"computed, {pg['preemptions']} preemptions")
        return 0

    result = engine.generate()
    for b in range(min(args.batch, 2)):
        print(f"  sample {b}: {result['tokens'][b][:16].tolist()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
