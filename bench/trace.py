"""From a profiler trace to busy time, kernel time and the breakdown.

``extract`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps what
the reduction needs: the first TPU's operations (name, module, start,
duration) and the benchmark's own host spans (``bench.*``, one of them
``bench.window``).  ``reduce`` works on that extraction alone, so a small
extraction recorded on the chip checks it on the CPU.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence

WINDOW = "bench.window"


def extract(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    device, host, modules = [], [], []
    dev_plane = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and (
                dev_plane is None or plane.name < dev_plane):
            dev_plane = plane.name
    for plane in pd.planes:
        if plane.name == dev_plane:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [(e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events]
                elif line.name == "XLA Ops":
                    device += [[op_name(e.name), "", e.start_ns,
                                e.duration_ns] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, e.start_ns, e.duration_ns])
    modules.sort()
    starts = [m[0] for m in modules]
    for op in device:                    # the module each operation ran in
        i = bisect.bisect_right(starts, op[2]) - 1
        if i >= 0 and op[2] < modules[i][1]:
            op[1] = modules[i][2].split("(")[0]
    return {"device_plane": dev_plane, "device": device, "host": host}


def op_name(hlo: str) -> str:
    """``%custom-call.12 = bf16[...] ...`` -> ``custom-call``."""
    name = hlo.split(" = ")[0].lstrip("%")
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def trim(ex: dict, seconds: float) -> dict:
    """The first ``seconds`` of the window, with the events in it."""
    w0, w1 = window_of(ex)
    w1 = min(w1, w0 + int(seconds * 1e9))
    keep = lambda s, d: s < w1 and s + d > w0
    return {"device_plane": ex["device_plane"],
            "device": [e for e in ex["device"] if keep(e[2], e[3])],
            "host": [[WINDOW, w0, w1 - w0]]
            + [e for e in ex["host"] if e[0] != WINDOW and keep(e[1], e[2])]}


def _union(intervals: Sequence[tuple]) -> List[List[float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def window_of(ex: dict) -> Optional[tuple]:
    spans = [(s, s + d) for n, s, d in ex["host"] if n == WINDOW]
    return spans[0] if spans else None


def reduce(ex: dict, kernels: Dict[str, Sequence[str]], top: int = 10
           ) -> Optional[dict]:
    """Busy and window seconds, per-kernel device seconds (an operation
    belongs to a kernel when its name or module holds one of the kernel's
    substrings), and the ``breakdown``: the device operations by total
    time and the idle time by the innermost benchmark span open in it.
    ``None`` when the trace holds no window."""
    win = window_of(ex)
    if win is None:
        return None
    w0, w1 = win
    ops = []
    for name, module, s, d in ex["device"]:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            ops.append((name, module, a, b))
    busy = _union([(a, b) for _, _, a, b in ops])
    busy_ns = sum(b - a for a, b in busy)
    kernel_s = {k: 0.0 for k in kernels}
    kernel_n = {k: 0 for k in kernels}
    by_op: Dict[str, float] = defaultdict(float)
    for name, module, a, b in ops:
        by_op[module or name] += (b - a) * 1e-9
        for k, subs in kernels.items():
            if any(sub in name or sub in module for sub in subs):
                kernel_s[k] += (b - a) * 1e-9
                kernel_n[k] += 1
    spans = [(n, s, s + d) for n, s, d in ex["host"] if n != WINDOW]
    idle: Dict[str, float] = defaultdict(float)
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        open_ = [(e - s, n) for n, s, e in spans if s <= mid < e]
        idle[min(open_)[1] if open_ else "outside decode_rows"] += (b - a) * 1e-9
    rank = lambda d: [[k, v] for k, v in sorted(d.items(),
                                                key=lambda kv: -kv[1])[:top]]
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy_ns * 1e-9,
            "kernel_s": kernel_s, "kernel_calls": kernel_n,
            "breakdown": {"device_ops": rank(by_op), "idle_gaps": rank(idle)}}
