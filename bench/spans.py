"""The program's own spans in a traced window: the idle split by thread,
and five per-layer numbers read from them.

The program opens named host spans at its layer boundaries
(``repro.core.spans``: ``zipmoe.*``), on the decode thread and on the
fetch engine's worker threads.  ``bench/trace.py`` keeps only the
benchmark's ``bench.*`` spans and charges an idle gap to the innermost
span open at its midpoint whatever thread opened it; this module keeps
both kinds with the host line (thread) of each, charges a gap only to a
span of the decode thread (the line that holds ``bench.window``), and
sums each span name's time in the window.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> [--control]

runs one traced run of the cell as ``bench/run.py --trace 1`` does and
prints its result line with ``spans`` added: the five numbers, the idle
split under the decode thread's spans, each span name's seconds, and the
spans opened per step.  The benchmark's own runs do not call it.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":            # as a script: the ``bench`` package
    sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

PREFIXES = ("bench.", "zipmoe.")
STEP = "zipmoe.step"
DECOMPRESS = "zipmoe.dec.decompress"
OUTSIDE = "outside decode_rows"
_extract_bench = trace.extract


def extract(trace_dir: str) -> dict:
    """``bench.trace.extract``'s extraction with ``spans``: every
    ``bench.*`` and ``zipmoe.*`` host event as [name, start_ns,
    duration_ns, line], ``line`` numbering the host lines (threads) in
    the order the trace lists them; and ``decode_line``, the line that
    holds ``bench.window`` (None without one)."""
    from jax.profiler import ProfileData

    ex = _extract_bench(trace_dir)
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    spans, decode, n = [], None, 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    spans.append([e.name, e.start_ns, e.duration_ns, n])
                    if e.name == trace.WINDOW:
                        decode = n
            n += 1
    ex["spans"], ex["decode_line"] = spans, decode
    return ex


def _seconds(intervals) -> float:
    return sum(b - a for a, b in trace._union(intervals)) * 1e-9


def split(ex: dict, top: int = 10) -> Optional[dict]:
    """From an ``extract`` extraction: the window's idle seconds by the
    innermost decode-thread span open at each gap's midpoint (top
    ``top``, as ``bench.trace.reduce``'s ``idle_gaps``), the idle seconds
    under a ``zipmoe.*`` span other than ``zipmoe.step``, each span
    name's seconds in the window (decode thread: the union of its
    intervals; workers: per line, summed over lines), the union of the
    decode thread's fetch collect and drain, the decompression workers'
    union of ``zipmoe.dec.*`` (per line, summed), and the spans opened in
    the window on each side.
    ``None`` without a window on a decode line."""
    win, line = trace.window_of(ex), ex.get("decode_line")
    if win is None or line is None:
        return None
    w0, w1 = win
    busy = trace._union([(max(s, w0), min(s + d, w1))
                         for _, _, s, d in ex["device"]
                         if min(s + d, w1) > max(s, w0)])
    decode: Dict[str, list] = defaultdict(list)      # name -> [(s, e)]
    worker: Dict[str, Dict[int, list]] = defaultdict(lambda: defaultdict(list))
    for name, s, d, ln in ex["spans"]:
        if name == trace.WINDOW or not (s < w1 and s + d > w0):
            continue
        if ln == line:
            decode[name].append((s, s + d))
        else:
            worker[name][ln].append((s, s + d))
    clip = lambda ivs: [(max(a, w0), min(b, w1)) for a, b in ivs]
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    mids = np.array([(a + b) / 2 for a, b in gaps])
    names = sorted(decode)
    best = np.full(len(gaps), np.inf)
    owner = np.full(len(gaps), -1)
    for k, name in enumerate(names):  # the shortest open span is innermost
        for a, b in decode[name]:
            i, j = np.searchsorted(mids, [a, b])
            inner = (b - a) < best[i:j]
            best[i:j][inner] = b - a
            owner[i:j][inner] = k
    idle: Dict[str, float] = defaultdict(float)
    for (a, b), k in zip(gaps, owner):
        idle[names[k] if k >= 0 else OUTSIDE] += (b - a) * 1e-9
    rank = sorted(idle.items(), key=lambda kv: -kv[1])
    # the decompression workers: the lines that decompress (an upload also
    # runs on the I/O thread when it finishes a tensor's last read)
    dec_lines = {ln: [] for ln in worker.get(DECOMPRESS, {})}
    for name, lines in worker.items():
        if name.startswith("zipmoe.dec."):
            for ln, ivs in lines.items():
                if ln in dec_lines:
                    dec_lines[ln] += clip(ivs)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "idle_s": sum(idle.values()),
        "idle_gaps": [[k, v] for k, v in rank[:top]],
        "program_idle_s": sum(v for k, v in idle.items()
                              if k.startswith("zipmoe.") and k != STEP),
        "decode_s": {n: _seconds(clip(ivs)) for n, ivs in decode.items()},
        "worker_s": {n: sum(_seconds(clip(ivs)) for ivs in lines.values())
                     for n, lines in worker.items()},
        "collect_s": _seconds(clip(decode.get("zipmoe.fetch.collect", [])
                                   + decode.get("zipmoe.fetch.drain", []))),
        "dec_busy_s": sum(_seconds(ivs) for ivs in dec_lines.values()),
        "dec_lines": len(dec_lines),
        "n_decode": sum(len(v) for k, v in decode.items()
                        if k.startswith("zipmoe.")),
        "n_worker": sum(len(ivs) for lines in worker.values()
                        for ivs in lines.values()),
    }


def metrics(sp: Optional[dict], steps: int, workers: int) -> Dict[str, float]:
    """Four of the five numbers, each left out where its spans are
    absent: decode-thread milliseconds a step in the router
    (``router_sync_ms_per_step``), in staging the expert FFN's inputs
    (``expert_stage_ms_per_step``) and in fetch collect or drain
    (``fetch_collect_ms_per_step``); and the decompression workers' busy
    share of ``workers`` threads over the window
    (``dec_worker_busy_frac``).  The fifth, ``kv_gather_mb_per_step``,
    is a program counter's (``traced_run``)."""
    out: Dict[str, float] = {}
    if sp is None or steps <= 0:
        return out
    ms = lambda s: 1e3 * s / steps
    for name, span in (("router_sync_ms_per_step", "zipmoe.route"),
                       ("expert_stage_ms_per_step", "zipmoe.ffn.stage")):
        if span in sp["decode_s"]:
            out[name] = ms(sp["decode_s"][span])
    if "zipmoe.fetch.collect" in sp["decode_s"] \
            or "zipmoe.fetch.drain" in sp["decode_s"]:
        out["fetch_collect_ms_per_step"] = ms(sp["collect_s"])
    if sp["dec_lines"]:
        out["dec_worker_busy_frac"] = sp["dec_busy_s"] / (workers
                                                          * sp["window_s"])
    return out


def traced_run(cell, seed: int, seconds: float, **run_kw) -> dict:
    """``harness.run_cell`` traced (``run_kw`` passed on), with this
    module's extraction in place of ``bench.trace.extract`` and the KV
    pool's ``gather_bytes`` among the window's counters; the result line
    gains ``spans``."""
    from bench import harness
    got: dict = {}
    servers: List = []
    build, counters, per_layer = (harness._build_server, harness._counters,
                                  harness._per_layer)

    def build_server(*a, **k):
        zs, srv = build(*a, **k)
        servers.append(srv)
        return zs, srv

    def window_counters(zs):
        c = counters(zs)
        c["kv_gather_bytes"] = servers[-1].pool.gather_bytes
        return c

    def read_per_layer(cell_, run, ex, out, log):
        per_layer(cell_, run, ex, out, log)
        got["run"], got["ex"] = run, ex

    patches = {"_build_server": build_server, "_counters": window_counters,
               "_per_layer": read_per_layer}
    saved = {k: getattr(harness, k) for k in patches}
    try:
        for k, v in patches.items():
            setattr(harness, k, v)
        trace.extract = extract
        out = harness.run_cell(cell, seed, seconds, True,
                               **{"t_proc": T_PROC, **run_kw})
    finally:
        trace.extract = _extract_bench
        for k, v in saved.items():
            setattr(harness, k, v)
    run = got["run"]
    sp = split(got["ex"])
    nums = metrics(sp, run.n_steps, int(cell.cell["workers"]))
    # c0 is read between two steps and c1 after the closing step's gather:
    # the window holds one gather (one a step) more than it holds steps
    gathered = run.c1["kv_gather_bytes"] - run.c0["kv_gather_bytes"]
    nums["kv_gather_mb_per_step"] = gathered / (run.n_steps + 1) / 1e6
    out["spans"] = {"metrics": nums, "steps": run.n_steps, "split": sp}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true",
                    help="as bench/run.py --control")
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from bench import harness, spec
    cell = spec.load_cell(args.workload)
    try:
        out = traced_run(cell, args.seed, args.seconds,
                         control=args.control)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
