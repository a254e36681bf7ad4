"""Finding a cell's parts by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own under ``bench/``, found by the
name ``BENCHMARK.json`` gives it:

  bench/configs/<config>.json     sizes, engine settings, limits
  bench/systems/<arch>.py         the program's model built from a config
  bench/reference/<arch>.py       the plain float32 reference
  bench/counts/<arch>.py          ``Counts(spec)``: the operations and bytes
                                  of a token, a prompt range and each
                                  kernel of a decode step
  bench/traffic/<mix>.json        a traffic mix, read by lib/traffic.py
  bench/metrics/<metric>.py       the reader of one per-layer metric
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Dict

ROOT = Path(__file__).resolve().parents[2]


def benchmark(root: Path = ROOT) -> Dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _named(entries, name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def cell(bench: Dict, name: str) -> Dict:
    return _named(bench["workloads"], name, "workload")


def config(root: Path, bench: Dict, name: str) -> Dict:
    entry = _named(bench["configs"], name, "configuration")
    return json.loads((Path(root) / entry["file"]).read_text())


def mix(root: Path, name: str) -> Dict:
    return json.loads((Path(root) / "bench" / "traffic"
                       / f"{name}.json").read_text())


def module(path: Path):
    """Import one file by path: its name may hold dots, as metric names do."""
    path = Path(path)
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_name = "bench_part_" + "".join(
        c if c.isalnum() else "_" for c in str(path))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def system(root: Path, arch: str):
    return module(Path(root) / "bench" / "systems" / f"{arch}.py")


def reference(root: Path, arch: str):
    return module(Path(root) / "bench" / "reference" / f"{arch}.py")


def counts(root: Path, arch: str):
    return module(Path(root) / "bench" / "counts" / f"{arch}.py")


def metric_reader(root: Path, name: str):
    return module(Path(root) / "bench" / "metrics" / f"{name}.py").read


def cell_metrics(bench: Dict, kind: str, cell_name: str):
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]
