"""CLI: run BASELINE configs or ad-hoc sweeps, write CSVs, aggregate.

    python -m benchmarks --config 2 --out bench_out/
    python -m benchmarks --sweep allreduce --algorithm ring
    python -m benchmarks --elaborate bench_out/
    python -m benchmarks --tune --tuning-cache bench_out/tuning.json
"""

import argparse
import os


def main():
    ap = argparse.ArgumentParser(description="accl_tpu benchmark harness")
    ap.add_argument("--config", type=int, choices=range(1, 6),
                    help="run a BASELINE config (1-5)")
    ap.add_argument("--chip-sweep", action="store_true",
                    help="single-device combine-dataplane size sweep "
                         "(Pallas vs raw XLA; the curve behind bench.py)")
    ap.add_argument("--chip-attention", action="store_true",
                    help="single-device fused-attention sequence sweep "
                         "(flash_attention Pallas kernel vs score-"
                         "materializing XLA attention)")
    ap.add_argument("--chip-compression", action="store_true",
                    help="single-device wire-compression lane sweep "
                         "(fp16/bf16 cast lanes + scaled-fp8 codec, "
                         "Pallas vs raw XLA)")
    ap.add_argument("--chip-decode", action="store_true",
                    help="single-device KV-cache decode sweep "
                         "(flash_decode fused kernel vs max_len-"
                         "oblivious XLA einsum; GB/s of filled-prefix "
                         "reads + tokens/s)")
    ap.add_argument("--chip-llama", action="store_true",
                    help="single-device Llama train-step + KV-cache "
                         "decode throughput (tokens/s)")
    ap.add_argument("--tag", type=str, default=None,
                    help="suffix for the output CSV NAME only — elaborate "
                         "aggregates by CSV columns (collective/algorithm/"
                         "...), so variants must differ in those columns "
                         "to stay separate cells")
    ap.add_argument("--tune", action="store_true",
                    help="measure every (collective, algorithm) across a "
                         "size ladder on the emulator tier and persist a "
                         "tuning table (accl_tpu/tuner cache JSON)")
    ap.add_argument("--tune-world", type=int, default=4,
                    help="emulator world size for --tune")
    ap.add_argument("--tuning-cache", type=str, default=None,
                    help="tuning-table path for --tune (default "
                         "$ACCL_TPU_TUNING_CACHE, else OUT/tuning.json)")
    ap.add_argument("--sweep", type=str,
                    help="ad-hoc sweep of one collective")
    ap.add_argument("--algorithm", type=str, default="xla",
                    choices=["xla", "ring", "tree"])
    ap.add_argument("--backend", type=str, default=None,
                    choices=["emu", "daemon", "native"],
                    help="config-1 tier: in-process emulator (default), "
                         "Python rank daemons, or the C++ daemons")
    ap.add_argument("--stack", type=str, default=None,
                    choices=["tcp", "udp"],
                    help="config-1 daemon eth fabric (default tcp)")
    ap.add_argument("--sizes", type=str,
                    help="comma-separated payload bytes (sequence "
                         "lengths for --chip-attention)")
    ap.add_argument("--wire-dtype", type=str, default=None)
    ap.add_argument("--out", type=str, default="bench_out")
    ap.add_argument("--elaborate", type=str, metavar="DIR",
                    help="aggregate CSVs in DIR and print the table")
    ap.add_argument("--platform", type=str, default=None,
                    help="force a jax platform (e.g. cpu; pair with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8"
                         " for a virtual mesh)")
    args = ap.parse_args()

    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)

    if args.elaborate:
        from .elaborate import elaborate, format_table
        print(format_table(elaborate(args.elaborate)))
        return

    sizes = ([int(s) for s in args.sizes.split(",")] if args.sizes
             else None)

    if args.tune:
        if args.algorithm != "xla" or args.wire_dtype or args.config:
            ap.error("--tune measures every legal algorithm itself; "
                     "--algorithm/--wire-dtype/--config do not apply")
        from accl_tpu.tuner import cache as tcache
        from .tune import (format_capacity, format_rows, run_capacity,
                           run_tune, write_rows)
        cache_path = (args.tuning_cache or tcache.default_cache_path()
                      or os.path.join(args.out, "tuning.json"))
        out = run_tune(world=args.tune_world, sizes=sizes,
                       cache_path=cache_path)
        rows_path = write_rows(out["rows"], args.out)
        print(format_rows(out["rows"]))
        print(out["tuner"].describe())
        # capacity planning: predicted-vs-measured hierarchical
        # crossover over the N-tier topology grid (tune.py)
        cap = run_capacity(sizes=sizes)
        cap_path = write_rows(cap["rows"] + cap["summary"], args.out,
                              name="capacity.json")
        print(format_capacity(cap))
        print(f"wrote {rows_path}")
        print(f"wrote {cap_path}")
        print(f"wrote tuning table {out['cache_path']}")
        return

    if args.backend and args.config != 1:
        ap.error("--backend only applies to config 1 (the CPU-tier "
                 "ping-pong); configs 2-5 run on the mesh")
    if args.stack and (args.config != 1
                       or args.backend not in ("daemon", "native")):
        ap.error("--stack only applies to config 1 with a daemon backend")

    if args.config:
        from .configs import CONFIGS
        kwargs = {}
        if args.backend:
            kwargs["backend"] = args.backend
        if args.stack:
            kwargs["stack"] = args.stack
        if sizes:
            if args.config == 5:
                ap.error("--sizes does not apply to config 5 "
                         "(fixed Llama-shaped gradients)")
            kwargs["sizes"] = sizes
        if args.algorithm != "xla":
            if args.config != 2:
                ap.error("--algorithm only applies to config 2; configs "
                         "3-5 fix their algorithm per BASELINE")
            kwargs["algorithm"] = args.algorithm
        if args.wire_dtype:
            ap.error("--wire-dtype only applies to --sweep; config 3 "
                     "sweeps both bf16 and fp16 lanes itself")
        result = CONFIGS[args.config](**kwargs)
        name = f"config{args.config}.csv"
    elif args.chip_sweep:
        if args.algorithm != "xla" or args.wire_dtype:
            ap.error("--chip-sweep measures the fixed pallas-vs-xla fp32 "
                     "pair; --algorithm/--wire-dtype do not apply")
        from .configs import chip_combine_sweep
        result = chip_combine_sweep(sizes)
        name = "chip_combine.csv"
    elif args.chip_attention:
        if args.algorithm != "xla" or args.wire_dtype:
            ap.error("--chip-attention measures the fixed pallas-vs-xla "
                     "bf16 pair; --algorithm/--wire-dtype do not apply")
        from .configs import chip_attention_sweep
        result = chip_attention_sweep(sizes)  # sizes = sequence lengths
        name = "chip_attention.csv"
    elif args.chip_compression:
        if args.algorithm != "xla" or args.wire_dtype:
            ap.error("--chip-compression sweeps all three lanes itself; "
                     "--algorithm/--wire-dtype do not apply")
        from .configs import chip_compression_sweep
        result = chip_compression_sweep(sizes)
        name = "chip_compression.csv"
    elif args.chip_decode:
        if args.algorithm != "xla" or args.wire_dtype:
            ap.error("--chip-decode measures the fixed pallas-vs-xla "
                     "bf16 pair; --algorithm/--wire-dtype do not apply")
        from .configs import chip_decode_sweep
        result = chip_decode_sweep(sizes)  # sizes = fill lengths
        name = "chip_decode.csv"
    elif args.chip_llama:
        if args.algorithm != "xla" or args.wire_dtype or sizes:
            ap.error("--chip-llama uses a fixed model geometry; "
                     "--algorithm/--wire-dtype/--sizes do not apply")
        from .configs import chip_llama_sweep
        result = chip_llama_sweep()
        name = "chip_llama.csv"
    elif args.sweep:
        from accl_tpu.parallel import make_mesh
        from .sweep import sweep_collective
        mesh = make_mesh()
        result = sweep_collective(
            mesh, args.sweep, sizes or [1 << 12, 1 << 16, 1 << 20],
            algorithm=args.algorithm, wire_dtype=args.wire_dtype)
        name = f"sweep_{args.sweep}_{args.algorithm}.csv"
    else:
        ap.error("pass --config, --sweep or --elaborate")
        return

    os.makedirs(args.out, exist_ok=True)
    if args.tag:
        name = name.replace(".csv", f"_{args.tag}.csv")
    path = os.path.join(args.out, name)
    result.to_csv(path)
    if args.sweep:
        # self-describing JSON twin: each row carries algorithm +
        # algorithm_source for tuned-vs-default comparisons
        result.to_json(path.replace(".csv", ".json"))
    print(result.table())
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
