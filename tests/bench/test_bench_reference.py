"""The plain float32 reference against the model's own forward pass.

At a small width, in float32, the program's blocks and the reference
agree to rounding; with the reference's matmuls lowered to one bf16 pass
(what the TPU's default precision does to float32) they do not.
"""
from __future__ import annotations

import bench_testkit as K
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import spec as S
from repro.models import model as M
from repro.models.layers import rms_norm

F32 = dict(K.TINY, torch_dtype="float32")
TOL = 1e-4          # float32 logits of magnitude ~1, summed in another order


def _program_logits(spec, seed, tokens):
    cfg = S.system(K.REPO, "llama").model_config(spec)
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    x = jnp.take(params["embed"]["table"], jnp.asarray(tokens)[None], axis=0)
    pos = jnp.arange(len(tokens), dtype=jnp.int32)
    x, _, _ = M.run_blocks(params, x, pos, cfg, M.LOCAL, None, "train")
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    out = jnp.einsum("bsd,vd->bsv", x, params["head"]["w_head"],
                     precision=jax.lax.Precision.HIGHEST)
    return np.asarray(out[0, :, : cfg.vocab_size])


@pytest.fixture(scope="module")
def case():
    tokens = np.random.default_rng(3).integers(0, 256, 40).tolist()
    with jax.default_matmul_precision("highest"):
        want = _program_logits(F32, 11, tokens)
    return tokens, want


def test_reference_matches_the_model_forward(case):
    tokens, want = case
    ref = S.reference(K.REPO, "llama")
    got = ref.logits(F32, 11, tokens)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL


def test_reference_fails_with_lowered_matmul_precision(case, monkeypatch):
    tokens, want = case
    ref = S.reference(K.REPO, "llama")
    mm = ref._mm

    def one_bf16_pass(eq, x, w, fp8):
        return mm(eq, x.astype(jnp.bfloat16).astype(jnp.float32),
                  w.astype(jnp.bfloat16).astype(jnp.float32), fp8)

    monkeypatch.setattr(ref, "_mm", one_bf16_pass)
    got = ref.logits(F32, 11, tokens)
    assert np.abs(got - want).max() > TOL
