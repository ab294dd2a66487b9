#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip(s) this machine holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Earlier lines of stdout report the device, the pool, the traffic, the
window and the check; the last line is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and, with
``--trace 1``, ``breakdown``), then ``check``: each number compared with
its limit, which also ends stderr. With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.
Exits non-zero, printing no result, without a TPU or with fewer chips
than the cell needs.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced window's .xplane.pb here")
    args = ap.parse_args(argv)
    import harness

    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START,
                              trace_dir=args.keep_trace)
    for name, c in result["check"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
