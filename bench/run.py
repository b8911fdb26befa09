"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on and needs the chips the cell asks
for: it exits 1, printing no result, when JAX's first device is not a TPU
or its ``device_kind`` is not in ``bench/peaks.json``, and 2 when the
repository's sources are not beside it.  Set-up parts, window sample
counts and the check go to earlier lines; the compared numbers with their
limits are the last lines on standard error; the last line of standard
output is the result as one JSON object.  With ``--trace 0`` its metrics
are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics read from a profiler trace of the window.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-events", default=None,
                    help="also write the trace's extracted events to this "
                         "JSON file (with --trace 1)")
    ap.add_argument("--control", action="store_true",
                    help="judge the float8 control's first-choice tokens "
                         "in place of the served ones, by the same limit: "
                         "correct comes out false (for setting and proving "
                         "the limit; not part of a benchmark run)")
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    try:
        import repro  # noqa: F401
        from bench import harness, spec
    except ImportError as e:
        print(f"bench: the repository's sources are not beside the "
              f"benchmark ({e})", file=sys.stderr)
        return 2
    try:
        cell = spec.load_cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), t_proc=T_PROC,
                               dump_events=args.dump_events,
                               control=args.control)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, d in out["checks"].items():
        print(f"check: {name}={d['value']!r} limit={d['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
