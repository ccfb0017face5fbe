"""The readings that a cell's limits are set from, on the accelerator this
process finds, many seeds in one process:

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 2
    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --control

Without ``--control`` each seed is one whole run of the cell (set-up, a
window of ``--seconds``, the comparison), printing every number the kind
computes: the lower readings.  With ``--control`` the reference computed
one step below the configuration's precision, each planted fault and, for
training, the program's own path at JAX's default matmul precision stand
in the program's place against the reference, at the cell's own sizes:
the upper readings.  The benchmark's own runs do neither.
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
if sys.path and pathlib.Path(sys.path[0] or ".").resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from bench import harness, spec
    if jax.devices()[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_cache()
    cell = spec.load(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if args.control:
            c = harness.kind(cell.traffic["kind"])(cell, seed)
            out = c.control()
            if hasattr(c, "program_control"):
                out["program_default"] = c.program_control()
                try:
                    out["program_high"] = c.program_control("high")
                except Exception as e:      # a control that fails has failed
                    out["program_high"] = repr(e)[:300]
        else:
            result, _, out = harness.run(cell, seed, args.seconds, False, t0)
            out["correct"] = result["correct"]
        print(json.dumps({"seed": seed, **out,
                          "s": round(time.perf_counter() - t0, 1)}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
