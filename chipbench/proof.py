"""The readings a cell's limits are set from, in one process.

    python3 chipbench/proof.py --workload vecadd.solo --seconds 10 \\
        --seeds 1 2 3 --control-seeds 4 5 6 --fault-seeds 7 8 9

Sets the cell up once, then runs one window per seed: the program as it
is (the lower readings), the control in the program's place (the upper
readings), and each planted fault (``faults.KINDS``).  Prints one line
per window and a JSON summary last; with ``--out`` writes it there too.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seconds", type=float, default=None)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from chipbench import bench, drivers, faults
    bench.prepare_env()
    cell, _devs, driver = bench.setup(args.workload, args.seeds[0])
    limits = cell.config["limits"]
    rows = []

    def window(kind, seed, seconds):
        driver.make_inputs(seed)
        if kind == "program":
            out = driver.window(seconds)
        else:
            with faults.planted(kind, cell):
                out = driver.window(seconds)
        got = drivers.compare(driver, out)
        correct = bool(out.kept) and all(got[k] <= limits[k]
                                         for k in limits)
        rows.append({"kind": kind, "seed": seed, "correct": correct,
                     "attempted": out.attempted, **got})
        print(json.dumps(rows[-1]), flush=True)

    for s in args.seeds:
        window("program", s, args.seconds)
    for s in args.control_seeds:
        window("control", s, args.seconds)
    for kind in faults.KINDS[1:]:
        for s in args.fault_seeds:
            window(kind, s, args.fault_seconds or args.seconds)

    def reading(kind, pick):
        vals = [r for r in rows if r["kind"] == kind]
        return {k: pick(r[k] for r in vals) for k in limits} if vals \
            else None

    summary = {"workload": args.workload, "limits": limits,
               "lower": reading("program", max),
               "upper": reading("control", min),
               "faults": {k: reading(k, min) for k in faults.KINDS[1:]},
               "all_correct_where_sound": all(
                   r["correct"] for r in rows if r["kind"] == "program"),
               "none_correct_where_planted": not any(
                   r["correct"] for r in rows if r["kind"] != "program"),
               "router": {str(k): v for k, v in driver.routes.items()}}
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"summary": summary,
                                              "rows": rows}, indent=1))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
