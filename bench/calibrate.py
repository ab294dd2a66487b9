#!/usr/bin/env python3
"""Readings for a cell's limit and rate, many seeds in one process (one
set-up of the process, one compile cache), each seed a whole run at the
cell's own load.

    python bench/calibrate.py --workload <cell> --seconds <s> --seeds 1,2,3
        [--control] [--rate R --no-check]

One ``CALIBRATE`` JSON line per seed: the program's widest and mean
logit gap against the reference and, with ``--control``, the control's
on the same positions, judged as the run's check judges the program.
``--rate`` offers an open-loop cell another rate (the knee sweep), and
``--no-check`` skips the check. The benchmark's own runs never run the
control.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--no-check", action="store_true")
    args = ap.parse_args(argv)
    import harness

    for seed in (int(s) for s in args.seeds.split(",")):
        spec = harness.cell_spec(args.workload)
        if args.rate is not None:
            spec["mix"] = dict(spec["mix"], rate_per_s=args.rate)
        t0 = time.perf_counter()
        r = harness.run_cell(args.workload, seed, args.seconds, False,
                             spec=spec, control=args.control,
                             check=not args.no_check)
        print("CALIBRATE " + json.dumps({
            "workload": args.workload, "seed": seed, "rate": args.rate,
            "correct": r.get("correct"), "readings": r.get("readings"),
            "metrics": {k: v["value"] for k, v in r["metrics"].items()},
            "window": r["window"], "wall_s": time.perf_counter() - t0}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
