"""Shared set-up of the benchmark's CPU tests: the repository root on the
path (the ``bench`` package) and a tiny test-only cell."""
import contextlib
import dataclasses
import json
import os
import sys
import tempfile
import time
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


@contextlib.contextmanager
def tiny_widths(conf):
    """The program's registered config at a test file's tiny widths
    (``test_program_widths``), in place of its published ones."""
    from repro.launch import serve as serve_lib
    published = serve_lib.model_config

    def at_tiny_widths(arch, layers=None):
        return dataclasses.replace(published(arch, layers),
                                   **conf["test_program_widths"])

    with mock.patch.object(serve_lib, "model_config", at_tiny_widths):
        yield


def tiny_cell():
    """Qwen2-MoE's layer at tiny widths under a three-client chat loop,
    with every per-layer metric of the benchmark."""
    from bench import spec
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    strip = lambda ms: [{k: v for k, v in m.items() if k != "workloads"}
                        for m in ms]
    return spec.Cell(name="tiny", chips=1, config_name="tiny",
                     config=_load("tiny-qwen2-moe.json"), traffic_name="tiny",
                     traffic=_load("tiny-chat.json"), cell=_load("tiny-cell.json"),
                     end_to_end=bench["end_to_end"],
                     per_layer=strip(bench["per_layer"]))


def run_tiny(trace=False, tamper=None, control=False, seed=2**33 + 7,
             seconds=2.0):
    """One CPU run of the tiny cell through the harness's internals (the
    look for a chip skipped), with a store of its own."""
    from bench import harness
    cell = tiny_cell()
    with tempfile.TemporaryDirectory() as cache, tiny_widths(cell.config):
        return harness.run_cell(cell, seed, seconds, trace,
                                t_proc=time.perf_counter(), require_tpu=False,
                                tamper=tamper, control=control,
                                log=lambda *a, **k: None, cache=cache)
