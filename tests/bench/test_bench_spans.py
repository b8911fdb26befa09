"""The program's spans in a trace (``bench/spans.py``): idle gaps charged
only to the decode thread's spans, each span name's time, and the numbers
read from them; on hand-made extractions, on the recorded v5e trace and
on a tiny traced run on the CPU."""
import json
import os
import tempfile

import pytest

from bench import spans as S
from bench import trace as T
from conftest import DATA, tiny_cell, tiny_widths

MS = 1_000_000


def hand_made():
    # window 0..100 ms; the device busy 10-30 and 60-70, so idle 0-10,
    # 30-60 and 70-100.  Line 0 is the decode thread, 1 a decompression
    # worker, 2 the I/O thread.
    ex = {"device_plane": "/device:TPU:0",
          "device": [["fusion.1", "jit_add", 10 * MS, 20 * MS],
                     ["fusion.2", "jit_mul", 60 * MS, 10 * MS]],
          "host": [["bench.window", 0, 100 * MS]]}
    ex["spans"] = [["bench.window", 0, 100 * MS, 0],
                   ["bench.decode_rows", 5 * MS, 90 * MS, 0],
                   ["zipmoe.step", 6 * MS, 88 * MS, 0],
                   ["zipmoe.fetch.collect", 32 * MS, 13 * MS, 0],
                   ["zipmoe.fetch.drain", 40 * MS, 18 * MS, 0],
                   ["zipmoe.route", 72 * MS, 2 * MS, 0],
                   ["zipmoe.dec.decompress", 30 * MS, 30 * MS, 1],
                   ["zipmoe.dec.upload", 55 * MS, 10 * MS, 1],
                   # the shortest span open over 30-60's midpoint, on a
                   # worker: it must not take the gap
                   ["zipmoe.io.read", 44 * MS, 2 * MS, 2],
                   ["zipmoe.dec.upload", 50 * MS, 2 * MS, 2]]
    ex["decode_line"] = 0
    return ex


def test_a_gap_goes_to_the_decode_thread_not_a_worker():
    sp = S.split(hand_made())
    # 0-10: midpoint 5, decode_rows just open; 30-60: midpoint 45, the
    # drain (collect closed at 45); 70-100: midpoint 85, only the step
    assert dict(sp["idle_gaps"]) == pytest.approx(
        {"bench.decode_rows": 0.010, "zipmoe.fetch.drain": 0.030,
         "zipmoe.step": 0.030})
    assert sp["idle_s"] == pytest.approx(0.070)
    assert sp["program_idle_s"] == pytest.approx(0.030)


def test_span_time_is_a_union_per_thread():
    sp = S.split(hand_made())
    assert sp["decode_s"]["zipmoe.fetch.collect"] == pytest.approx(0.013)
    assert sp["decode_s"]["zipmoe.fetch.drain"] == pytest.approx(0.018)
    assert sp["collect_s"] == pytest.approx(0.026)    # 32-58, once
    assert sp["worker_s"]["zipmoe.dec.upload"] == pytest.approx(0.012)
    # the decompression worker's line: 30-65; the I/O line's upload is
    # not a decompression worker's
    assert sp["dec_busy_s"] == pytest.approx(0.035) and sp["dec_lines"] == 1
    assert sp["n_decode"] == 4 and sp["n_worker"] == 4


def test_numbers_read_from_the_spans():
    m = S.metrics(S.split(hand_made()), steps=2, workers=2)
    assert m == pytest.approx({"router_sync_ms_per_step": 1.0,
                               "fetch_collect_ms_per_step": 13.0,
                               "dec_worker_busy_frac": 0.175})


def test_nothing_to_read_without_the_program_spans():
    ex = hand_made()
    ex["spans"] = [s for s in ex["spans"] if not s[0].startswith("zipmoe.")]
    assert S.metrics(S.split(ex), steps=2, workers=2) == {}
    ex["decode_line"] = None
    assert S.split(ex) is None
    assert S.metrics(None, steps=2, workers=2) == {}


def test_recorded_chip_trace_splits_as_the_benchmark_does():
    """On the recorded v5e extraction, which holds the benchmark's spans
    alone (all on the decode thread), the idle split equals
    ``bench.trace.reduce``'s."""
    with open(os.path.join(DATA, "trace-dsv2lite-v5e.json")) as f:
        ex = json.load(f)
    ex["spans"] = [h + [0] for h in ex["host"]]
    ex["decode_line"] = 0
    sp = S.split(ex)
    want = T.reduce(ex, {})["breakdown"]["idle_gaps"]
    assert [n for n, _ in sp["idle_gaps"]] == [n for n, _ in want]
    assert [v for _, v in sp["idle_gaps"]] == pytest.approx(
        [v for _, v in want])
    assert sp["program_idle_s"] == 0


@pytest.fixture(scope="module")
def tiny_traced():
    cell = tiny_cell()
    with tempfile.TemporaryDirectory() as cache, tiny_widths(cell.config):
        return S.traced_run(cell, 2**33 + 11, 2.0, require_tpu=False,
                            log=lambda *a, **k: None, cache=cache)


def test_tiny_traced_run_reads_all_five(tiny_traced):
    out = tiny_traced
    assert out["correct"] is True
    m = out["spans"]["metrics"]
    assert set(m) == {"router_sync_ms_per_step", "expert_stage_ms_per_step",
                      "fetch_collect_ms_per_step", "dec_worker_busy_frac",
                      "kv_gather_mb_per_step"}
    assert all(v > 0 for v in m.values())
    assert m["dec_worker_busy_frac"] <= 1.0
    # the result line of bench/run.py --trace 1 is there as it was
    assert "device_idle_frac" in out["metrics"]


def test_tiny_traced_run_charges_no_gap_to_a_worker(tiny_traced):
    sp = tiny_traced["spans"]["split"]
    assert sp["n_worker"] > 0 and sp["n_decode"] > 0
    names = {n for n, _ in sp["idle_gaps"]}
    assert names <= set(sp["decode_s"]) | {S.OUTSIDE}
    assert not names & set(sp["worker_s"])
