"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

from the root of a checkout.  Loads, warms up every shape the cell's
traffic uses (all of it counted as ``setup_s``), measures for ``--seconds``
and prints one JSON line as the last line of standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics from a profiler trace of the
window), ``device`` and, last, ``checks``: each number compared with the
reference beside its limit, also printed as the last lines of standard
error.  Exits non-zero, printing no result, when JAX finds no TPU, fewer
chips than the cell asks for, or no program to run.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# run as a script, this file's own directory heads sys.path and its
# modules would shadow the standard library's (trace, ...)
if sys.path and pathlib.Path(sys.path[0] or ".").resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import spec
    cell = spec.load(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    try:
        import repro.launch  # noqa: F401
    except ImportError as e:
        print(f"bench: the program is missing ({e})", file=sys.stderr)
        return 3

    from bench import harness
    harness.enable_cache()
    result, lowered, _ = harness.run(cell, args.seed, args.seconds,
                                     bool(args.trace), T_START)
    print(f"bench: {lowered} program(s) lowered inside the window",
          file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
