"""Shared benchmark plumbing: paper-model specs, traces, CSV emission."""
from __future__ import annotations

import csv
import io
import sys
import time
from typing import Dict, List


from repro.core.simulator import HW, MoESpec, ZipMoESim, make_layer_trace
from repro.core.baselines import BASELINES

# The paper's evaluation models (§5), expert-offload view.
PAPER_SPECS: Dict[str, MoESpec] = {
    # DeepSeekV2-Lite: 26 MoE layers (first dense), 64 routed top-6, d2048 f1408
    "deepseekv2-lite": MoESpec(n_layers=26, n_experts=64, top_k=6,
                               d_model=2048, d_expert=1408),
    # Qwen1.5-MoE-A2.7B: 24 layers, 60 routed top-4
    "qwen1.5-moe": MoESpec(n_layers=24, n_experts=60, top_k=4,
                           d_model=2048, d_expert=1408),
    # Switch-Large-128: 24 MoE layers (enc+dec alternating), 128 experts top-1
    "switch-large-128": MoESpec(n_layers=24, n_experts=128, top_k=1,
                                d_model=1024, d_expert=2816, n_tensors=2),
}

# Edge testbed (§5): Jetson AGX Orin 64G + Samsung 970 EVO (3.5 GB/s)
HW1 = HW(storage_bw=3.5e9, dec_bw=1.2e9, L=6, flop_rate=30e12)   # Orin 64G


def expert_store_bytes(spec: MoESpec) -> int:
    return spec.n_layers * spec.n_experts * spec.expert_bytes_full


def warm_trace(spec: MoESpec, *, alpha=1.15, steps=400, seed=7, batch=1):
    return [s[0] for s in make_layer_trace(1, spec.n_experts, spec.top_k,
                                           steps, alpha=alpha, seed=seed,
                                           batch=batch)]


def eval_trace(spec: MoESpec, *, steps=48, alpha=1.15, seed=1, batch=1):
    return make_layer_trace(spec.n_layers, spec.n_experts, spec.top_k, steps,
                            alpha=alpha, seed=seed, batch=batch)


def make_system(name: str, spec: MoESpec, hw: HW, budget: float, *,
                batch=1, **kw):
    if name == "zipmoe":
        return ZipMoESim(spec, hw, budget,
                         warm_trace=warm_trace(spec, batch=batch),
                         plan=True, **kw)
    if name == "zipmoe-noplan":
        return ZipMoESim(spec, hw, budget, plan=False, **kw)
    return BASELINES[name](spec, hw, budget, **kw)


class Rows:
    """CSV row collector: ``name,us_per_call,derived``."""

    def __init__(self):
        self.rows: List[tuple] = []

    def add(self, name: str, us_per_call: float, derived: str = ""):
        self.rows.append((name, f"{us_per_call:.3f}", derived))

    def emit(self, fh=None):
        w = csv.writer(fh or sys.stdout)
        w.writerow(["name", "us_per_call", "derived"])
        for r in self.rows:
            w.writerow(r)


def timed(fn, *args, reps=1, **kw):
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    return out, (time.perf_counter() - t0) / reps
