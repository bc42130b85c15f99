"""Training launcher — a thin argparse shim over ``repro.engine.TrainEngine``.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --reduced --plan cdp_v2 --steps 100 --batch 8 --seq 128 \
        --mesh-data 2 --mesh-model 2 [--host-devices 4] [--ckpt-dir ckpts/] \
        [--kernels pallas | --kernels decode_attn=pallas,ssm_scan=pallas]

``--plan`` selects the parallelism strategy from the ``repro.parallel``
registry (dp | cdp_v1 | cdp_v2 | cdp_random | zero1_ring | zero_cdp);
``--rule`` survives as a deprecated alias for the plan of the same name,
exactly as ``--attn-backend`` aliases ``--kernels``.

On the CPU container use --reduced (+ --host-devices, auto-defaulted to the
mesh size when the host platform is the default backend); on a real TPU
slice the same flags drive the production mesh (mesh sizes = the slice
topology; the host-device flag only multiplies CPU devices and is inert).
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
                    help="parallelism strategy (repro.parallel registry). "
                         + plan_help())
    ap.add_argument("--rule", default=None,
                    choices=["dp", "cdp_v1", "cdp_v2", "cdp_random"],
                    help="DEPRECATED alias: selects the plan of the same "
                         "name (use --plan)")
    ap.add_argument("--kernels", default=None,
                    help="per-op kernel backends: one backend for all ops "
                         "('pallas') or a comma list of op=backend pairs "
                         "('decode_attn=pallas,ssm_scan=jnp'); ops: "
                         "train_attn, prefill_attn, decode_attn, ssm_scan")
    ap.add_argument("--attn-backend", default=None,
                    choices=["jnp", "pallas"],
                    help="DEPRECATED alias: sets train_attn+prefill_attn in "
                         "the kernel registry")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--weight-decay", type=float, default=1e-4)
    ap.add_argument("--mesh-data", type=int, default=2)
    ap.add_argument("--mesh-model", type=int, default=2)
    ap.add_argument("--mesh-pod", type=int, default=0)
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N host CPU devices (0 = auto: the mesh size "
                         "when >1; inert when an accelerator is the default "
                         "jax backend)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--keep-last", type=int, default=None,
                    help="retain only the newest N checkpoints (GC runs "
                         "after each successful save)")
    ap.add_argument("--resilience", default=None,
                    help="arm the resilience layer: 'on' enables the "
                         "health guard only, or a comma fault spec "
                         "('nan_loss@7,loader%%0.01,slow_step@3:0.2') for "
                         "deterministic chaos injection (sites: "
                         "loader nan_loss loss_spike slow_step "
                         "ckpt_truncate ckpt_io rank_down step_hang)")
    ap.add_argument("--elastic", action="store_true",
                    help="survive a data-rank loss: re-form the ring at N-1 "
                         "from the newest buddy snapshot (disk checkpoint "
                         "as fallback) and keep training")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="buddy-replicated host-RAM snapshot interval in "
                         "steps (0 = off; recovery then needs --ckpt-dir)")
    ap.add_argument("--watchdog-timeout", type=float, default=0.0,
                    help="per-step wall-clock deadline in seconds (0 = off); "
                         "an overrun counts as a hung collective and "
                         "triggers elastic recovery")
    ap.add_argument("--rejoin-after", type=int, default=0,
                    help="scale back up to the full mesh N steps after a "
                         "recovery (simulates the failed rank returning)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.plan and args.rule:
        ap.error("pass --plan or --rule (deprecated alias), not both")
    if args.rule:
        import warnings
        warnings.warn(f"--rule is deprecated; use --plan {args.rule}",
                      DeprecationWarning, stacklevel=2)

    from repro.engine import RunSpec
    spec = RunSpec(arch=args.arch, reduced=args.reduced,
                   kernels=args.kernels, attn_backend=args.attn_backend,
                   plan=args.plan or args.rule,
                   mesh_data=args.mesh_data, mesh_model=args.mesh_model,
                   mesh_pod=args.mesh_pod, host_devices=args.host_devices,
                   seed=args.seed)
    spec = spec.auto_host_devices()     # CPU container: default to mesh size
    spec.ensure_host_devices()          # before anything imports jax state
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.engine import TrainEngine
    engine = TrainEngine(spec, steps=args.steps,
                         batch=args.batch, seq=args.seq, lr=args.lr,
                         momentum=args.momentum,
                         weight_decay=args.weight_decay,
                         ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                         keep_last=args.keep_last,
                         resilience=args.resilience,
                         elastic=args.elastic,
                         snapshot_every=args.snapshot_every,
                         watchdog_timeout=args.watchdog_timeout,
                         rejoin_after=args.rejoin_after,
                         log_every=args.log_every)
    engine.run()
    print("done.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
