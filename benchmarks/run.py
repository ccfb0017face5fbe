# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
#   table1_metrics   — paper Table 1 (original vs quantized error metrics)
#   table_eq3_timing — paper Eq. 3 training-time model (FPGA/CPU/TPU)
#   table_resources  — paper §3 FPGA resource estimates
#   mrf_serve_bench  — recon serving stack: sync vs pipelined voxels/s on
#                      autotuned buckets + latency-from-enqueue percentiles,
#                      pipelined_speedup_vs_sync, int8_vs_float_speedup,
#                      per-bucket breakdown and the before/after int8 curve
#                      (writes BENCH_mrf_serve.json)
#   serve_autotune   — measured bucket-set + fused block-shape autotune with
#                      the roofline/hlo_cost cross-check
#                      (writes BENCH_serve_autotune.json)
from __future__ import annotations

import argparse
import traceback


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: table1,eq3,resources,mrf_serve,"
                         "serve_autotune")
    ap.add_argument("--steps", type=int, default=800,
                    help="training steps for table1 (scaled schedule)")
    ap.add_argument("--serve-waves", type=int, default=5,
                    help="timed request waves per backend for mrf_serve")
    ap.add_argument("--serve-reps", type=int, default=5,
                    help="interleaved timing repetitions for the serving "
                         "suites' per-bucket medians")
    args = ap.parse_args()
    want = set(args.only.split(",")) if args.only else None

    from repro.launch import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (mrf_serve_bench, serve_autotune, table1_metrics,
                            table_eq3_timing, table_resources)

    suites = [
        ("eq3", table_eq3_timing.run, {}),
        ("resources", table_resources.run, {}),
        ("serve_autotune", serve_autotune.run, {"reps": args.serve_reps}),
        ("mrf_serve", mrf_serve_bench.run, {"waves": args.serve_waves,
                                            "reps": args.serve_reps}),
        ("table1", table1_metrics.run, {"steps": args.steps}),
    ]
    print("name,us_per_call,derived")
    failed = []
    for key, fn, kw in suites:
        if want and key not in want:
            continue
        try:
            for name, us, derived in fn(**kw):
                print(f'{name},{us:.2f},"{derived}"', flush=True)
        except Exception as e:  # run the other suites, then fail the run
            traceback.print_exc()
            print(f'{key}/ERROR,0,"{type(e).__name__}: {e}"', flush=True)
            failed.append(key)
    if failed:
        print(f"FAILED suites: {','.join(failed)}", flush=True)
        return 1
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
