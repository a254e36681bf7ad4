"""The benchmark runs only on a chip it knows the peaks of."""
from __future__ import annotations

import os
import subprocess
import sys
from types import SimpleNamespace

import bench_testkit as K
import pytest

from bench.lib import harness as H
from bench.lib import peaks as P


def _dev(platform="tpu", kind="TPU v5 lite"):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_refuses_a_cpu():
    with pytest.raises(H.NoChip):
        H.check_devices([_dev("cpu", "cpu")], 1)


def test_refuses_too_few_chips():
    with pytest.raises(H.NoChip):
        H.check_devices([_dev()], 4)


def test_refuses_an_unknown_device_kind():
    with pytest.raises(P.UnknownDevice):
        H.check_devices([_dev(kind="TPU v9 imaginary")], 1)


def test_known_chip_gives_its_peaks():
    peak = H.check_devices([_dev()] * 4, 4)
    assert peak["bf16_flops"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9


def test_run_exits_nonzero_without_a_result_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(K.REPO / "bench" / "run.py"), "--workload",
         "yi-9b.24L.chat", "--seed", "2147483653", "--seconds", "10",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=K.REPO, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "refused" in p.stderr
