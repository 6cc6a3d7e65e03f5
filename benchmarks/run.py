#!/usr/bin/env python3
"""The benchmark's command: one cell, once, in this process.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Prints, last, one JSON object with the keys ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device`` (and ``breakdown`` when traced),
then ``compared``: each number ``correct`` rests on beside its limit and
whether it holds, as on the last lines of standard error; everything else
worth reading is on earlier lines.  With ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result: it never falls back to the CPU.
``--rehearse`` is for the CPU rehearsals at a tiny size: it reports
``device.platform = "cpu"`` and no device metric.
"""
import time

T0 = time.perf_counter()     # set-up is timed from here

import argparse              # noqa: E402
import functools             # noqa: E402
import json                  # noqa: E402
import os                    # noqa: E402
import sys                   # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.harness import measure
    say = functools.partial(print, flush=True)
    try:
        result = measure.measure(args.workload, args.seed, args.seconds,
                                 bool(args.trace), args.rehearse, T0, say)
    except measure.Refused as e:
        print(f"benchmarks/run.py: refused: {e}", file=sys.stderr)
        return 1
    for name, pair in result["compared"].items():
        print(f"compared: {name} {pair['value']!r} limit {pair['limit']!r}",
              file=sys.stderr, flush=True)
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
