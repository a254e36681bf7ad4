"""A configuration, a traffic mix and a per-layer metric enter the
benchmark as new files plus entries in BENCHMARK.json, with no edit to a
file that is there: the harness finds each by its name."""
from __future__ import annotations

import json
import time

import bench_testkit as K
import jax
import pytest

from bench.lib import harness as H
from bench.lib import spec as S

READER = '''"""Output tokens counted in the window (tokens)."""


def read(run):
    return run.window_tokens
'''


@pytest.fixture(autouse=True)
def no_persistent_cache():
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


@pytest.fixture
def root(tmp_path):
    root = K.tiny_root(tmp_path)
    b = root / "bench"
    (b / "configs" / "newmodel.json").write_text(
        json.dumps(dict(K.TINY, name="newmodel", num_hidden_layers=1)))
    (b / "traffic" / "newmix.json").write_text(json.dumps(dict(
        K.TINY_MIX, arrival={"law": "poisson", "rate_per_s": 3.0},
        prompt={"law": "uniform", "min": 4, "max": 40})))
    (b / "metrics" / "tokens_in_window.new.py").write_text(READER)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "newmodel", "source": "test",
                             "file": "bench/configs/newmodel.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "newmodel.newmix", "config": "newmodel",
                               "traffic": "newmix", "chips": 1, "why": "test"})
    bench["per_layer"] = [{"name": "tokens_in_window.new", "unit": "tokens",
                           "better": "higher", "source": "host_clock",
                           "layer": "service", "moves": "out_tok_s",
                           "workloads": ["newmodel.newmix"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_parts_found_by_name(root):
    bench = S.benchmark(root)
    assert S.config(root, bench, "newmodel")["num_hidden_layers"] == 1
    assert S.mix(root, "newmix")["prompt"]["law"] == "uniform"
    assert S.metric_reader(root, "tokens_in_window.new").__doc__ is None
    assert [m["name"] for m in S.cell_metrics(bench, "per_layer",
                                              "newmodel.newmix")] == \
        ["tokens_in_window.new"]
    assert S.cell_metrics(bench, "per_layer", "tiny.chat") == []


def test_new_cell_runs_and_reports_the_new_metric(root):
    res = H.run_cell(root, "newmodel.newmix", 2_147_483_647, 2.0, True,
                     jax.devices(), K.PEAK, time.perf_counter())
    assert res["correct"] is True and res["attempted"] == 6
    assert res["metrics"]["tokens_in_window.new"]["value"] > 0
    assert res["metrics"]["tokens_in_window.new"]["unit"] == "tokens"
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
