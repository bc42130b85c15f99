import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
os.environ["JAX_PLATFORMS"] = "cpu"     # the 512 devices are host devices

"""§Perf hillclimb runner: evaluate named optimisation variants of one
(arch x shape) pair and report the roofline-term deltas vs the
paper-faithful baseline.

    PYTHONPATH=src python -m repro.launch.perf --arch qwen2.5-14b \
        --shape train_4k --variants baseline,zero1,zero1_bf16,seqpar,combo
"""
import argparse
import json
import sys
import traceback

VARIANTS = {
    # train-step variants
    "baseline": {},
    "zero1": dict(zero1_ring=True),
    "zero1_bf16": dict(zero1_ring=True, grad_comm_dtype="bfloat16"),
    "seqpar": dict(seq_parallel=True),
    "combo": dict(zero1_ring=True, grad_comm_dtype="bfloat16",
                  seq_parallel=True),
    # decode-step variants
    "donate": dict(donate_cache=True),
    "cache_tp": dict(cache_model_shard=True),
    "serve_combo": dict(donate_cache=True, cache_model_shard=True),
    # f32 emulation (structurally clean CPU numbers; halve bytes for bf16)
    "f32_emu": dict(force_dtype="float32"),
    "f32_serve_combo": dict(force_dtype="float32", donate_cache=True,
                            cache_model_shard=True),
    "f32_combo": dict(force_dtype="float32", zero1_ring=True,
                      grad_comm_dtype="bfloat16", seq_parallel=True),
    "f32_zero1": dict(force_dtype="float32", zero1_ring=True),
    "f32_seqpar": dict(force_dtype="float32", seq_parallel=True),
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variants", default="baseline")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--rule", default="cdp_v2")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from repro.launch.dryrun import lower_pair

    records = []
    base = None
    for name in args.variants.split(","):
        kw = VARIANTS[name]
        try:
            rec = lower_pair(args.arch, args.shape, multi_pod=args.multi_pod,
                             rule=args.rule, extra={"variant": name}, **kw)
            rl = rec["roofline"]
            bpd = rec["bytes_per_device"]
            if name == "baseline":
                base = rec
            line = (f"[{name:12s}] compute={rl['compute_s']*1e3:8.2f}ms "
                    f"memory={rl['memory_s']*1e3:8.2f}ms "
                    f"collective={rl['collective_s']*1e3:8.2f}ms "
                    f"peak={bpd['peak_est']/2**30:7.2f}GiB "
                    f"(corr {bpd['peak_tpu_corrected']/2**30:7.2f}) "
                    f"burst={rl['coll_max_burst']/2**20:6.1f}MiB")
            if base is not None and name != "baseline":
                b = base["roofline"]
                dom = b["bottleneck"]
                key = {"compute": "compute_s", "memory": "memory_s",
                       "collective": "collective_s"}[dom]
                delta = (rl[key] - b[key]) / max(b[key], 1e-12) * 100
                line += f"  [{dom} {delta:+.1f}%]"
            print(line, flush=True)
            records.append(rec)
        except Exception as e:
            traceback.print_exc()
            print(f"[{name}] FAILED: {e}", flush=True)
            records.append({"variant": name, "ok": False,
                            "error": str(e)[:300]})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
