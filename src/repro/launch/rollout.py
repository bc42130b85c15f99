"""RL rollout launcher — a thin argparse shim over
``repro.engine.RolloutEngine``.

    PYTHONPATH=src python -m repro.launch.rollout --arch stablelm-1.6b \
        --reduced --iters 3 [--plan dp|zero_cdp|...] \
        [--groups 2 --group-size 4 --prompt-len 8 --gen 8] \
        [--mesh-data 2 --host-devices 2] [--events-jsonl rollout.jsonl]

One process runs the whole loop: generate (continuous batching over the
paged KV cache, per-request sampling seeds), score (steerable synthetic
reward + behaviour logprobs), train (REINFORCE through TrainEngine's
jitted step under the chosen plan, serve pool asleep at level 2), push
(device-side weight hand-off under a transfer guard). Mean group reward
on the synthetic task must RISE across iterations — the printed reward
curve is the acceptance signal.
"""
from __future__ import annotations

import argparse
import sys


def main(argv=None):
    from repro.parallel import available_plans, plan_help

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--plan", default=None, choices=available_plans(),
                    help="parallelism strategy for the TRAIN step "
                         "(repro.parallel registry). " + plan_help())
    ap.add_argument("--groups", type=int, default=2,
                    help="trajectory groups per iteration (one prompt each)")
    ap.add_argument("--group-size", type=int, default=4,
                    help="samples per group (the group-relative baseline)")
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the k most likely tokens "
                         "(0 = full vocab)")
    ap.add_argument("--lr", type=float, default=0.5)
    ap.add_argument("--kv-block-size", type=int, default=4)
    ap.add_argument("--reward-target", type=int, default=None,
                    help="first token id of the rewarded band "
                         "(default vocab//2)")
    ap.add_argument("--reward-width", type=int, default=None,
                    help="width of the rewarded token band "
                         "(default vocab//8)")
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N host CPU devices (0 = auto: the mesh size "
                         "when >1; inert when an accelerator is the default "
                         "jax backend)")
    ap.add_argument("--events-jsonl", default=None,
                    help="export the engine event log (phase boundaries, "
                         "pool sleeps) to this JSONL path on exit")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.engine import RunSpec
    spec = RunSpec(arch=args.arch, reduced=args.reduced,
                   plan=args.plan, mesh_data=args.mesh_data,
                   mesh_model=args.mesh_model,
                   host_devices=args.host_devices, seed=args.seed)
    spec = spec.auto_host_devices()     # CPU container: default to mesh size
    spec.ensure_host_devices()          # before anything imports jax state
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.engine import RolloutEngine
    engine = RolloutEngine(spec, plan=args.plan,
                           groups=args.groups, group_size=args.group_size,
                           prompt_len=args.prompt_len, gen=args.gen,
                           iters=args.iters, temperature=args.temperature,
                           top_k=args.top_k, lr=args.lr,
                           kv_block_size=args.kv_block_size,
                           reward_target=args.reward_target,
                           reward_width=args.reward_width)
    history = engine.run()
    curve = [h["mean_reward"] for h in history]
    print(f"reward curve: {[round(r, 3) for r in curve]}")
    if args.events_jsonl:
        n = engine.events.to_jsonl(args.events_jsonl)
        print(f"wrote {n} events to {args.events_jsonl}")
    improved = len(curve) >= 2 and curve[-1] > curve[0]
    print("reward improved." if improved else
          "WARNING: reward did not improve.")
    print("done.")
    return 0 if improved or len(curve) < 2 else 1


if __name__ == "__main__":
    sys.exit(main())
