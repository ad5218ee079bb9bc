"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload vecadd.solo --seed 7 --seconds 10 --trace 0

The cell comes from ``BENCHMARK.json``.  The run makes its inputs from
``--seed``, compiles and warms up every shape it will use (set-up),
measures for ``--seconds``, compares what the window's launches wrote
with the numpy reference, and prints one JSON line last on standard
output.  ``--trace 1`` records a profiler trace of the window and
reports the cell's per-layer metrics in place of its end-to-end ones.
Without the accelerator the cell asks for it exits non-zero before
measuring.  ``--rehearse`` runs the same path on the CPU at the
rehearsal sizes and reports no metric.
"""
from time import perf_counter

T0 = perf_counter()     # set-up is counted from the start of the process

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at the rehearsal sizes")
    args = ap.parse_args(argv)
    from chipbench import bench
    bench.prepare_env()
    result = bench.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), rehearse=args.rehearse, t0=T0)
    bench.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
