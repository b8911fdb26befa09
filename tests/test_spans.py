"""Host spans inside the serving path (``repro.core.spans``): a tiny
DeepSeek-V2 (a dense layer, then an MoE layer with a shared expert)
served through ``BatchServer`` over ``ZipServer(device_cache=True,
ffn_impl="ragged")`` for two steps under the JAX profiler.  Every
decode-thread span lands on one host line, nested inside ``zipmoe.step``
where it belongs; the fetch engine's spans land on other lines; per-layer
spans carry ``layer=`` and fetch spans ``job=``."""
import glob
import os
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.spans import DECODE, SPANS, WORKER
from repro.models import init_params
from repro.serving.server import BatchServer
from repro.serving.zipserve import ZipServer


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Host events by line: {line: [(name, start, end, stats)]}, and the
    number of MoE layers."""
    from jax.profiler import ProfileData
    from repro.core.store import build_store
    cfg = get_smoke_config("deepseekv2-lite", n_layers=2)
    assert cfg.first_dense == 1 and cfg.n_shared_experts > 0
    params = init_params(jax.random.PRNGKey(0), cfg)
    tmp = tmp_path_factory.mktemp("spans")
    build_store(params, cfg, str(tmp / "store"), k_shards=2)
    zs = ZipServer(params, cfg, str(tmp / "store"), L=2,
                   pool_sizes={"F": 2, "C": 1, "S": 1, "E": 1},
                   device_cache=True, ffn_impl="ragged")
    srv = BatchServer(None, cfg, max_batch=2, max_len=8, zip_server=zs)
    for t in (3, 5):                  # one prompt token, two sampled: 2 steps
        srv.submit(np.asarray([t], np.int32), 2)
    jax.profiler.start_trace(str(tmp / "trace"))
    try:
        done = srv.run()
    finally:
        jax.profiler.stop_trace()
        zs.close()
    assert all(len(r.output) == 2 for r in done)
    path = glob.glob(os.path.join(str(tmp / "trace"), "**", "*.xplane.pb"),
                     recursive=True)[-1]
    lines = defaultdict(list)
    n = 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                lines[n] = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                             {k: v for k, v in e.stats})
                            for e in line.events
                            if e.name.startswith("zipmoe.")]
                n += 1
    return lines, len(zs._moe_layers)


def _decode_line(lines):
    (line,) = [ln for ln, ev in lines.items()
               if any(n == "zipmoe.step" for n, *_ in ev)]
    return line


def test_every_decode_span_is_on_the_decode_thread(traced):
    lines, moe = traced
    line = _decode_line(lines)
    names = [n for n, *_ in lines[line]]
    assert set(names) == set(DECODE)
    # one span a step, not a row, on the loop's per-step work
    for name in ("zipmoe.step", "zipmoe.batch.sample", "zipmoe.kv.gather",
                 "zipmoe.kv.commit", "zipmoe.lm_head"):
        assert names.count(name) == 2, name
    assert names.count("zipmoe.route") == 2 * moe


def _inside(ev, name, outer):
    """Intervals of ``name`` that do / do not lie inside one of ``outer``'s."""
    spans = [(s, e) for n, s, e, _ in ev if n in outer]
    inner = [(s, e) for n, s, e, _ in ev if n == name]
    yes = [iv for iv in inner if any(a <= iv[0] and iv[1] <= b
                                     for a, b in spans)]
    return yes, [iv for iv in inner if iv not in yes]


@pytest.mark.parametrize("name", ["zipmoe.route", "zipmoe.ffn.stage",
                                  "zipmoe.ffn.gemm", "zipmoe.attn",
                                  "zipmoe.dense_ffn", "zipmoe.shared_ffn",
                                  "zipmoe.req_accounting", "zipmoe.lm_head"])
def test_layer_spans_nest_inside_the_step(traced, name):
    lines, _ = traced
    yes, no = _inside(lines[_decode_line(lines)], name, {"zipmoe.step"})
    assert yes and not no


@pytest.mark.parametrize("name", ["zipmoe.fetch.wait",
                                  "zipmoe.fetch.collect"])
def test_fetch_spans_nest_inside_the_step_or_a_drain(traced, name):
    """A step waits on and collects its fetches; once the last request
    retires, ``drain_pending`` finishes the predictions still in flight,
    between steps."""
    lines, _ = traced
    ev = lines[_decode_line(lines)]
    yes, no = _inside(ev, name, {"zipmoe.step"})
    assert yes
    assert not _inside(ev, name, {"zipmoe.step", "zipmoe.fetch.drain"})[1]


def test_reconcile_nests_inside_collect(traced):
    lines, _ = traced
    yes, no = _inside(lines[_decode_line(lines)], "zipmoe.fetch.reconcile",
                      {"zipmoe.fetch.collect"})
    assert yes and not no


def test_worker_spans_are_on_other_threads(traced):
    lines, _ = traced
    line = _decode_line(lines)
    assert not any(n in WORKER for n, *_ in lines[line])
    names = {n for ln, ev in lines.items() if ln != line for n, *_ in ev}
    assert names <= set(WORKER)
    # the device-cache miss path reads, decompresses and uploads; the
    # standalone splice runs only without a device cache
    assert {"zipmoe.io.read", "zipmoe.dec.decompress",
            "zipmoe.dec.upload"} <= names
    assert set(SPANS) == set(DECODE) | set(WORKER)


def test_spans_carry_layer_and_job(traced):
    lines, _ = traced
    ev = [x for v in lines.values() for x in v]
    for n, _, _, stats in ev:
        assert set(stats) <= {"layer", "job"}, n
        if n in ("zipmoe.route", "zipmoe.attn", "zipmoe.fetch.drain",
                 "zipmoe.io.read", "zipmoe.dec.decompress"):
            assert "layer" in stats, n
        if n in ("zipmoe.fetch.wait", "zipmoe.fetch.collect",
                 "zipmoe.io.read", "zipmoe.dec.decompress"):
            assert "job" in stats, n
