"""The trace reduction: busy and idle time, kernel device time and the
breakdown, on a hand-made extraction and on a trimmed one recorded on a
TPU v5e."""
import json
import os

import pytest

from bench import trace as T
from conftest import DATA

MS = 1_000_000


def hand_made():
    # window 0..100 ms; ops at 10-30 (kernel A), 20-40 (B), 60-70 (A)
    return {"device_plane": "/device:TPU:0",
            "device": [["custom-call.1", "jit__slab_gemm_tpu", 10 * MS, 20 * MS],
                       ["fusion.2", "jit_add", 20 * MS, 20 * MS],
                       ["custom-call.1", "jit__slab_gemm_tpu", 60 * MS, 10 * MS],
                       ["fusion.9", "jit_mul", 150 * MS, 10 * MS]],
            "host": [["bench.window", 0, 100 * MS],
                     ["bench.decode_rows", 5 * MS, 80 * MS],
                     ["bench.acquire_experts", 42 * MS, 15 * MS]]}


def test_busy_idle_and_kernel_time():
    r = T.reduce(hand_made(), {"gemm": ["_slab_gemm_tpu"]})
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.040)          # 10-40 and 60-70
    assert r["kernel_s"]["gemm"] == pytest.approx(0.030)
    assert r["kernel_calls"]["gemm"] == 2
    ops = dict(r["breakdown"]["device_ops"])
    assert ops == pytest.approx({"jit__slab_gemm_tpu": 0.030, "jit_add": 0.020})
    idle = dict(r["breakdown"]["idle_gaps"])
    # gaps 0-10 (decode_rows open from 5: midpoint 5 -> decode_rows),
    # 40-60 (midpoint 50 inside acquire_experts), 70-100 (midpoint 85:
    # nothing open)
    assert idle == pytest.approx({"bench.decode_rows": 0.010,
                                  "bench.acquire_experts": 0.020,
                                  "outside decode_rows": 0.030})


def test_no_window_reads_nothing():
    ex = hand_made()
    ex["host"] = ex["host"][1:]
    assert T.reduce(ex, {}) is None


CHIP = os.path.join(DATA, "trace-dsv2lite-v5e.json")


def test_recorded_chip_trace():
    """A second of a dsv2lite.chat8.slab25 window traced on a TPU v5 lite."""
    with open(CHIP) as f:
        ex = json.load(f)
    from bench.metrics import (ragged_gemm_roofline, recover_bf16_roofline,
                               splice_admit_roofline)
    kernels = {"ragged": ragged_gemm_roofline.KERNEL,
               "splice": splice_admit_roofline.KERNEL,
               "recover": recover_bf16_roofline.KERNEL}
    r = T.reduce(ex, kernels)
    w0, w1 = T.window_of(ex)
    assert r["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    for k, subs in kernels.items():
        want = sum(min(s + d, w1) - max(s, w0) for n, m, s, d in ex["device"]
                   if any(x in n or x in m for x in subs)
                   and min(s + d, w1) > max(s, w0)) * 1e-9
        assert r["kernel_s"][k] == pytest.approx(want)
        assert r["kernel_calls"][k] > 0
    for key in ("device_ops", "idle_gaps"):
        vals = [v for _, v in r["breakdown"][key]]
        assert 0 < len(vals) <= 10 and vals == sorted(vals, reverse=True)
    assert sum(v for _, v in r["breakdown"]["idle_gaps"]) <= \
        r["window_s"] - r["busy_s"] + 1e-9
