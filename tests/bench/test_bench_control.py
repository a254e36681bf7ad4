"""The comparison that decides ``correct`` fails its control: at a size a
CPU test run can hold, served requests read a widest gap below the limit,
and the control (the float32 reference with fp8 projections, put in the
program's place on the same prompts and tokens) reads above it."""
from __future__ import annotations

import gc
from pathlib import Path

import bench_testkit as K
import jax
import pytest

from bench.lib import harness as H

LIMIT = 0.02       # tiny model: sound runs read <= 0.005, the control >= 0.05


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    jax.config.update("jax_enable_compilation_cache", False)
    root = K.tiny_root(Path(tmp_path_factory.mktemp("ctrl")))
    c = H.Cell.load(root, "tiny.chat")
    c.mix = dict(c.mix, output={"law": "uniform", "min": 24, "max": 40})
    yield c
    jax.config.update("jax_enable_compilation_cache", True)


@pytest.mark.parametrize("seed", [1, 2, 2_147_483_659])
def test_program_within_limit_and_control_beyond(cell, seed):
    reqs = cell.requests(seed, 3.0)
    eng = cell.engine(seed, jax.devices())
    H.serve_window(cell, eng, reqs, 3.0, K.PEAK, False)
    del eng
    gc.collect()
    pick = H.sample([r for r in reqs if r.tokens], seed, 1, 8,
                    int(cell.spec["engine"]["chunk_prefill"]))
    assert sum(len(r.tokens) for r in pick) >= 150
    program = H.widest_gap(cell.root, cell.spec, seed, pick)
    control = H.widest_gap(cell.root, cell.spec, seed, pick, "fp8")
    assert program <= LIMIT < control
