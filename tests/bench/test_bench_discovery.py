"""A configuration, a traffic mix, a per-layer metric and a whole new
architecture enter the benchmark as new files plus entries in
BENCHMARK.json, with no edit to a file that is there: the harness finds
each by its name."""
from __future__ import annotations

import hashlib
import json
import math
import time

import bench_testkit as K
import jax
import pytest

from bench.lib import harness as H
from bench.lib import spec as S
from bench.lib import trace as TR

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


# -- a second architecture: its own system, reference and counts, which
# -- wrap the Llama ones and count a second kernel of their own ---------------

WRAP = '''"""The {part} of the test architecture: Llama's."""
from pathlib import Path

from bench.lib import spec as S

_llama = S.module(Path(__file__).with_name("llama.py"))
{name} = _llama.{name}
'''
TWIN_COUNTS = '''"""Counts of the test architecture: Llama's, and a second kernel,
``dot``, charged per layer with the step's matmuls over its weights."""
from pathlib import Path

from bench.lib import spec as S

_llama = S.module(Path(__file__).with_name("llama.py"))


class Counts(_llama.Counts):
    def decode_kernels(self, contexts):
        ctx = list(contexts)
        out = super().decode_kernels(ctx)
        weights = self.layer_flops          # bf16: 2 bytes a multiply-add
        out["dot"] = (len(ctx) * self.layer_flops, weights, self.layers)
        return out
'''
TWIN_READERS = {
    "dot_roofline.twin": '''"""The test kernel's share of its roofline (%)."""


def read(run):
    t = run.kernel_s("dot")
    if t <= 0 or not run.traced_kernel_calls.get("dot"):
        return None
    return 100.0 * run.traced_kernel_ideal_s["dot"] / t
''',
    "kv_pages_per_step.twin": '''"""Pages walked a decode step (pages)."""


def read(run):
    steps = run.delta("decode_steps")
    return run.delta("kv_pages_walked") / steps if steps > 0 else None
''',
    "live_slots.twin": '''"""Slots decoding in a decode block of the traced slice, mean over
the engine's ``serve.decode_block`` spans (slots)."""


def read(run):
    lo, hi = run.trace_window
    live = [st["live_slots"] for s, e, n, st in run.engine_trace.spans
            if n == "serve.decode_block" and lo <= s and e <= hi]
    return sum(live) / len(live) if live else None
''',
}


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*.py"))
            if "__pycache__" not in p.parts}


@pytest.fixture
def cpu_ops_as_a_device(monkeypatch):
    """The CPU has no device plane; read the op line of the CPU runtime's
    thread as device 0's, so a kernel's time can be measured."""
    load = TR.load

    def with_cpu_ops(path):
        from jax.profiler import ProfileData
        tr = load(path)
        for plane in ProfileData.from_file(path).planes:
            if plane.name == "/host:CPU":
                tr.devices[0] = sorted(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                    for ln in plane.lines if ln.name.startswith("tf_XLA")
                    for e in ln.events)
        return tr

    monkeypatch.setattr(TR, "load", with_cpu_ops)


def test_new_architecture_needs_only_new_files(tmp_path, cpu_ops_as_a_device):
    root = K.tiny_root(tmp_path)
    b = root / "bench"
    kept = _digests(root)
    (b / "configs" / "twin.json").write_text(
        json.dumps(dict(K.TINY, name="twin", arch="twin")))
    (b / "systems" / "twin.py").write_text(
        WRAP.format(part="served system", name="model_config"))
    (b / "reference" / "twin.py").write_text(
        WRAP.format(part="plain reference", name="gaps"))
    (b / "counts" / "twin.py").write_text(TWIN_COUNTS)
    for name, text in TWIN_READERS.items():
        (b / "metrics" / f"{name}.py").write_text(text)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "twin", "source": "test",
                             "file": "bench/configs/twin.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "twin.chat", "config": "twin",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    bench["per_layer"] = [
        {"name": name, "unit": "x", "better": "higher",
         "source": "program_counter", "layer": "test",
         "moves": "tpot_p90_ms", "workloads": ["twin.chat"]}
        for name in TWIN_READERS]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    now = _digests(root)
    assert {p: d for p, d in now.items() if p in kept} == kept

    res = H.run_cell(root, "twin.chat", 2_147_483_659, 2.0, True,
                     jax.devices(), K.PEAK, time.perf_counter())
    assert res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) == set(TWIN_READERS)
    assert all(math.isfinite(v) and v > 0 for v in got.values()), got
    assert got["live_slots.twin"] <= K.TINY["engine"]["num_slots"]
