"""A benchmark root at a size a CPU test run can hold: the repository's
``bench/`` code with one tiny configuration, mix and cell beside it."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "name": "tiny", "arch": "llama", "source": "test",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "max_position_embeddings": 128, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "hidden_act": "silu", "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "engine": {"num_slots": 4, "max_len": 128, "page_size": 16, "k_block": 4,
               "chunk_prefill": 32, "bucket_quantum": 16},
    "check": {"requests": 4, "widest_gap": 0.25},
}
TINY_MIX = {
    "arrival": {"law": "poisson", "rate_per_s": 4.0},
    "prompt": {"law": "lognormal", "median": 24, "sigma": 0.5, "min": 4,
               "max": 80},
    "output": {"law": "lognormal", "median": 8, "sigma": 0.5, "min": 4,
               "max": 16},
    "drain_s": 60,
}
PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def recorded_trace():
    """A slice of a real trace: the first 1,500 device events of one engine
    tick of yi-9b.24L serving three requests on a TPU v5e, op names only
    (read by ``trace.load``), with the host spans trimmed to end with them."""
    from bench.lib import trace as TR
    raw = json.loads((REPO / "tests" / "bench" / "data"
                      / "trace_v5e_tick.json").read_text())
    tr = TR.Trace()
    tr.devices = {int(k): [tuple(e) for e in v]
                  for k, v in raw["devices"].items()}
    tr.host = [tuple(e) for e in raw["host"]]
    return tr


def tiny_root(tmp: Path, chips: int = 1) -> Path:
    """A root holding ``bench/`` and a BENCHMARK.json with one tiny cell."""
    root = Path(tmp) / "root"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(TINY))
    mix = dict(TINY_MIX, shards=chips) if chips > 1 else TINY_MIX
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(mix))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": "tiny.chat", "config": "tiny",
                           "traffic": "tiny", "chips": chips, "why": "test"}]
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
