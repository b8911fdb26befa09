"""The benchmark end to end on a tiny configuration, on the CPU, through
its internals; the entry point's refusals."""
import contextlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT, run_tiny, tiny_cell


SEED = 2**33 + 7


@pytest.fixture(scope="module")
def tiny_run():
    return run_tiny(seed=SEED)


@pytest.fixture(scope="module")
def traced_run():
    return run_tiny(seed=SEED + 1, trace=True)


def test_tiny_run_is_correct_with_every_end_to_end_metric(tiny_run):
    out = tiny_run
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"]: m["unit"] for m in tiny_cell().metrics(False)}
    assert {k: v["unit"] for k, v in out["metrics"].items()} == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    gap = out["checks"]["logit_gap_max"]
    assert gap["value"] <= gap["limit"]


def test_control_fails_the_limit_the_program_meets(tiny_run):
    """A run of the same seed whose compared tokens are the float8
    control's first choices, at each position of the same prompts and
    served tokens, is judged by the same comparison and limit: it comes
    out not correct, where the served program's run is correct."""
    out = run_tiny(seed=SEED, control=True)
    assert tiny_run["correct"] is True
    assert out["correct"] is False
    gap = out["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"] == \
        tiny_run["checks"]["logit_gap_max"]["limit"]


def test_nothing_compiles_in_the_window(traced_run):
    """Set-up warms every shape the window reaches: the KV view lengths and
    the expert FFN at every count of selected experts, in place and
    stacked."""
    assert traced_run["correct"] is True
    assert traced_run["metrics"]["window_compiles"]["value"] == 0


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_entry_point_refuses_a_host_without_a_tpu():
    p = _run(["--workload", "qwen15moe.chat8.slab25", "--seed", "1",
              "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode == 1, p.stderr
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_entry_point_needs_the_sources_beside_it(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in paths:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    p = _run(["--workload", "qwen15moe.chat8.slab25", "--seed", "1",
              "--seconds", "1", "--trace", "0"], str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax
    from bench import harness

    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(harness.NoDevice, match="not in bench/peaks.json"):
        harness.device_info(1)


def test_every_cell_finds_its_files():
    from bench import spec
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.cell["pools"]["F"] > 0
        assert "logit_gap_max" in cell.cell["limits"]
        spec.reference_module(cell.config)
        for m in cell.metrics(True):
            assert callable(spec.metric_module(m["name"]).read)
        assert {m["name"] for m in cell.metrics(False)} >= {"setup_s"}


@pytest.mark.parametrize("name", ["qwen1.5-moe-a2.7b.l4", "deepseek-v2-lite.l5",
                                  "qwen1.5-moe-a2.7b.l4.resident"])
def test_configuration_matches_the_program_at_published_widths(name):
    from bench import harness
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        conf = json.load(f)
    cfg = harness.program_config(conf)
    assert cfg.n_layers == conf["num_hidden_layers"]


def _conf(name):
    from conftest import _load
    if name.endswith(".json"):
        return _load(name)
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["tiny-qwen2-moe.json", "tiny-deepseek-v2.json",
                                  "qwen1.5-moe-a2.7b.l4", "deepseek-v2-lite.l5"])
def test_program_config_equals_the_parents(name):
    """The fields each reference module fixes give the program config that
    the harness's own checks gave before they moved (``parent-pins.json``)."""
    import dataclasses
    from bench import harness
    from conftest import _load, tiny_widths
    conf = _conf(name)
    with tiny_widths(conf) if "test_program_widths" in conf else \
            contextlib.nullcontext():
        cfg = harness.program_config(conf)
    assert dataclasses.asdict(cfg) == \
        _load("parent-pins.json")["program_config"][name]


@pytest.mark.parametrize("change,layer", [({"attn_layer_offset": 4}, 3),
                                          ({"expert_layer_offset": 0}, 0)],
                         ids=["attention-at-4", "experts-on-even-layers"])
def test_program_config_refuses_layer_kinds_that_disagree(change, layer):
    """The tiny hybrid with attention where the published Jamba has it
    (offset 4; the program's is 3) or with experts on the even layers,
    against the program's layout: refused at set-up, naming the first
    layer whose kind differs."""
    import tiny_hybrid
    from bench import harness
    from conftest import tiny_widths
    conf = _conf("tiny-jamba.json")
    with tiny_widths(conf):
        harness.program_config(conf, tiny_hybrid)
        with pytest.raises(ValueError, match=f"layer {layer}: the program"):
            harness.program_config(dict(conf, **change), tiny_hybrid)
