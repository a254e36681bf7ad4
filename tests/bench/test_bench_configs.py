"""The configuration files keep the published widths and list every key
they change, and BENCHMARK.json names each part that exists."""
from __future__ import annotations

import json

import bench_testkit as K
import pytest

from bench.lib import spec as S

# the public config.json of each source, the keys that describe its shape
PUBLISHED = {
    "yi-9b.24L": {"hidden_size": 4096, "intermediate_size": 11008,
                  "num_attention_heads": 32, "num_key_value_heads": 4,
                  "num_hidden_layers": 48, "vocab_size": 64000,
                  "max_position_embeddings": 4096, "hidden_act": "silu",
                  "tie_word_embeddings": False, "torch_dtype": "bfloat16"},
}
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "head_dim", "vocab_size")

BENCH = S.benchmark(K.REPO)
FILES = sorted((K.REPO / "bench" / "configs").glob("*.json"))


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_published_widths_kept_and_cuts_listed(path):
    spec = json.loads(path.read_text())
    pub = PUBLISHED[spec["name"]]
    changed = sorted(k for k, v in pub.items() if spec[k] != v)
    assert changed == sorted(spec["reduced"])
    assert not set(spec["reduced"]) & set(WIDTHS)
    assert spec["head_dim"] == spec["hidden_size"] // \
        spec["num_attention_heads"]
    for entry in BENCH["configs"]:
        if entry["file"] == f"bench/configs/{path.name}":
            assert sorted(entry["reduced"]) == changed
            assert entry["source"] == spec["source"]


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_program_model_has_the_config_widths(path):
    spec = json.loads(path.read_text())
    cfg = S.system(K.REPO, spec["arch"]).model_config(spec)
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.d_ff,
            cfg.vocab_size, cfg.resolved_head_dim, cfg.num_layers) == (
        spec["hidden_size"], spec["num_attention_heads"],
        spec["num_key_value_heads"], spec["intermediate_size"],
        spec["vocab_size"], spec["head_dim"], spec["num_hidden_layers"])
    assert cfg.attn.rope_base == spec["rope_theta"]
    assert cfg.dtype == "bfloat16" and not cfg.tie_embeddings


def test_every_named_part_exists():
    names = {c["name"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert w["config"] in names
        assert S.mix(K.REPO, w["traffic"])["arrival"]["rate_per_s"] > 0
    for m in BENCH["per_layer"]:
        assert callable(S.metric_reader(K.REPO, m["name"]))
    cells = {w["name"] for w in BENCH["workloads"]}
    for kind in ("end_to_end", "per_layer"):
        for m in BENCH[kind]:
            assert set(m.get("workloads", cells)) <= cells
