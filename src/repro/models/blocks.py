"""Per-family transformer block assembly.

Block kinds (see ModelConfig.block_pattern):
  attn     full-attention + swiglu MLP          (yi, starcoder2, llama3, ...)
  local    sliding-window attention + MLP       (gemma3 local layers, hymba)
  moe      attention + routed MoE (+ shared)    (llama4-scout)
  mla_moe  MLA attention + routed MoE (+shared) (deepseek-v2)
  hybrid   parallel attention & mamba heads     (hymba)
  mlstm / slstm                                 (xlstm)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import KeyGen, dense_init, rms_norm, swiglu


def mlp_params(cfg: ModelConfig, kg: KeyGen, dtype, d_ff: Optional[int] = None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    return {
        "w_gate": dense_init(kg(), (d, f), dtype),
        "w_up": dense_init(kg(), (d, f), dtype),
        "w_down": dense_init(kg(), (f, d), dtype),
    }


def block_params(cfg: ModelConfig, kind: str, key, dtype) -> Dict[str, Any]:
    kg = KeyGen(key)
    d = cfg.d_model
    p: Dict[str, Any] = {"ln1": jnp.zeros((d,), dtype)}
    if kind in ("attn", "local"):
        p["attn"] = attn_mod.gqa_params(cfg, kg, dtype)
        p["ln2"] = jnp.zeros((d,), dtype)
        p["mlp"] = mlp_params(cfg, kg, dtype)
    elif kind == "moe":
        p["attn"] = attn_mod.gqa_params(cfg, kg, dtype)
        p["ln2"] = jnp.zeros((d,), dtype)
        p["moe"] = moe_mod.moe_params(cfg, kg, dtype)
    elif kind == "mla_moe":
        p["attn"] = attn_mod.mla_params(cfg, kg, dtype)
        p["ln2"] = jnp.zeros((d,), dtype)
        p["moe"] = moe_mod.moe_params(cfg, kg, dtype)
    elif kind == "hybrid":
        p["attn"] = attn_mod.gqa_params(cfg, kg, dtype)
        p["ssm"] = ssm_mod.mamba_params(cfg, kg, dtype)
        p["ln2"] = jnp.zeros((d,), dtype)
        p["mlp"] = mlp_params(cfg, kg, dtype)
    elif kind == "mlstm":
        p["core"] = ssm_mod.mlstm_params(cfg, kg, dtype)
    elif kind == "slstm":
        p["core"] = ssm_mod.slstm_params(cfg, kg, dtype)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return p


# ---------------------------------------------------------------------------
# MoE wiring (EP shard_map when available)
# ---------------------------------------------------------------------------


def _moe_specs(plan, fs):
    tp = plan.model_axis
    return {
        "router": P(),
        "we_gate": P(tp, fs, None),
        "we_up": P(tp, fs, None),
        "we_down": P(tp, fs, None),
    }


def apply_moe(params, x, cfg: ModelConfig, plan, mode: str):
    """Routed experts (+ shared experts).  Returns (y, aux_loss)."""
    m = cfg.moe
    routed = {k: params[k] for k in ("router", "we_gate", "we_up", "we_down")}
    tp_size = plan.plan.axis_size(plan.model_axis) if plan.mesh is not None else 1
    use_ep = (plan.mesh is not None and plan.ep and plan.model_axis is not None
              and m.num_experts % tp_size == 0 and tp_size > 1)

    B, S, D = x.shape
    if not use_ep:
        y, aux = moe_mod.dense_moe(routed, x, cfg)
    elif mode == "decode" or S % tp_size:
        b_axes = plan.batch_axes or None

        def local(p_l, x_l):
            if plan.fsdp_axis:
                p_l = _ep_gather(p_l, plan.fsdp_axis)
            t = x_l.reshape(-1, D)
            y = moe_mod.ep_moe_decode_local(p_l, t, cfg, plan.model_axis)
            return y.reshape(x_l.shape)

        specs = _moe_specs(plan, plan.fsdp_axis)
        fn = jax.shard_map(local, mesh=plan.mesh,
                           in_specs=(specs, P(b_axes, None, None)),
                           out_specs=P(b_axes, None, None), check_vma=False)
        y = fn(routed, x)
        aux = jnp.float32(0.0)       # decode: no aux loss needed
    else:
        b_axes = plan.batch_axes or None
        tp = plan.model_axis
        axes_all = plan.all_axes

        def local(p_l, x_l):
            if plan.fsdp_axis:
                p_l = _ep_gather(p_l, plan.fsdp_axis)
            t = x_l.reshape(-1, D)
            y, aux = moe_mod.ep_moe_local(p_l, t, cfg, tp)
            for ax in axes_all:
                if ax != tp:
                    aux = jax.lax.pmean(aux, ax)
            return y.reshape(x_l.shape), aux

        specs = _moe_specs(plan, plan.fsdp_axis)
        fn = jax.shard_map(local, mesh=plan.mesh,
                           in_specs=(specs, P(b_axes, tp, None)),
                           out_specs=(P(b_axes, tp, None), P()), check_vma=False)
        y, aux = fn(routed, x)

    if m.num_shared_experts:
        # shared experts as a plain TP MLP (outside the EP region) so their
        # d_ff shards over the model axis instead of replicating; SP gather/
        # scatter keeps both terms S-sharded
        shared = swiglu(sp_gather(x, plan, mode, cfg), params["ws_gate"],
                        params["ws_up"], params["ws_down"])
        y = y + sp_scatter(shared, plan, mode, cfg)
    return y, aux


def _ep_gather(p_l, fs):
    """FSDP all-gather of expert weights at use time (storage stays sharded)."""
    return {
        "router": p_l["router"],
        "we_gate": jax.lax.all_gather(p_l["we_gate"], fs, axis=1, tiled=True),
        "we_up": jax.lax.all_gather(p_l["we_up"], fs, axis=1, tiled=True),
        "we_down": jax.lax.all_gather(p_l["we_down"], fs, axis=1, tiled=True),
    }


# ---------------------------------------------------------------------------
# Sequence parallelism plumbing (Megatron-SP)
# ---------------------------------------------------------------------------


def sp_enabled(cfg: ModelConfig, plan, seq_len: int, mode: str = "train") -> bool:
    """Whether the residual stream runs sequence-sharded for this cell —
    the single source of truth shared by blocks, embedding and the loss
    head (mismatched producers/consumers cause per-layer gather storms —
    measured on hymba, EXPERIMENTS §Perf)."""
    if not (plan is not None and plan.mesh is not None
            and plan.model_axis is not None and mode in ("train", "prefill")):
        return False
    tp = plan.plan.axis_size(plan.model_axis)
    if tp <= 1 or seq_len % tp:
        return False
    if cfg.num_heads % tp != 0:
        return False
    return cfg.param_count() >= 1_000_000_000


def _sp_on(x, plan, mode, cfg: Optional[ModelConfig] = None) -> bool:
    if not (plan is not None and plan.mesh is not None
            and plan.model_axis is not None and mode in ("train", "prefill")
            and x.ndim == 3
            and plan.plan.axis_size(plan.model_axis) > 1
            and x.shape[1] % plan.plan.axis_size(plan.model_axis) == 0):
        return False
    if cfg is not None:
        tp = plan.plan.axis_size(plan.model_axis)
        # SP only pays when the mixers actually shard over the model axis:
        # measured regressions on hymba (25 heads % 16), llama4 (40 % 16)
        # and sub-1B models (xlstm) — see EXPERIMENTS §Perf.
        if cfg.num_heads % tp != 0:
            return False
        if cfg.param_count() < 1_000_000_000:
            return False
    return True


def sp_gather(x, plan, mode, cfg: Optional[ModelConfig] = None):
    """S-sharded residual -> full sequence at a mixer input (all-gather)."""
    if not _sp_on(x, plan, mode, cfg):
        return x
    from jax.sharding import NamedSharding
    b = plan.batch_axes or None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(plan.mesh, P(b, None, None)))


def sp_scatter(x, plan, mode, cfg: Optional[ModelConfig] = None):
    """Mixer output (partial-sum over TP) -> S-sharded residual.  Turns the
    TP all-reduce into a reduce-scatter: same wire bytes, 1/TP the HBM
    writes, and the remat'd scan carry shrinks by TP."""
    if not _sp_on(x, plan, mode, cfg):
        return x
    from jax.sharding import NamedSharding
    b = plan.batch_axes or None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(plan.mesh, P(b, plan.model_axis, None)))


# ---------------------------------------------------------------------------
# Block apply
# ---------------------------------------------------------------------------


def apply_block(params, x, positions, cfg: ModelConfig, kind: str, plan,
                cache: Optional[Dict], mode: str, write_mask=None):
    """Returns (x, new_cache, aux_loss).  The residual stream enters and
    leaves S-sharded (SP); each mixer gathers the sequence at its input and
    scatters its output.  ``write_mask`` (B,) gates decode-step attention
    cache writes per slot (the fused K-step block freezes finished slots;
    recurrent states need no mask — a dead slot only corrupts itself and is
    reset wholesale at refill)."""
    aux = jnp.float32(0.0)
    eps = cfg.norm_eps
    if kind in ("attn", "local", "moe", "mla_moe"):
        h = sp_gather(rms_norm(x, params["ln1"], eps), plan, mode, cfg)
        if kind == "mla_moe":
            a, new_cache = attn_mod.mla_apply(params["attn"], h, positions, cfg,
                                              plan, cache, mode,
                                              write_mask=write_mask)
        else:
            a, new_cache = attn_mod.gqa_apply(
                params["attn"], h, positions, cfg,
                "local" if kind == "local" else "full", plan, cache, mode,
                write_mask=write_mask)
        x = x + sp_scatter(a, plan, mode, cfg)
        h = rms_norm(x, params["ln2"], eps)
        if kind in ("moe", "mla_moe"):
            # EP consumes S-sharded tokens directly — no gather needed
            f, aux = apply_moe(params["moe"], h, cfg, plan, mode)
            x = x + sp_scatter(f, plan, mode, cfg)
        else:
            f = swiglu(sp_gather(h, plan, mode, cfg), params["mlp"]["w_gate"],
                       params["mlp"]["w_up"], params["mlp"]["w_down"])
            x = x + sp_scatter(f, plan, mode, cfg)
    elif kind == "hybrid":
        h = sp_gather(rms_norm(x, params["ln1"], eps), plan, mode, cfg)
        a, attn_cache = attn_mod.gqa_apply(params["attn"], h, positions, cfg,
                                           "local", plan,
                                           cache.get("attn") if cache else None,
                                           mode, write_mask=write_mask)
        s, ssm_cache = ssm_mod.mamba_apply(params["ssm"], h, cfg, plan,
                                           cache.get("ssm") if cache else None, mode)
        x = x + sp_scatter(0.5 * (a + s), plan, mode, cfg)
        h = sp_gather(rms_norm(x, params["ln2"], eps), plan, mode, cfg)
        f = swiglu(h, params["mlp"]["w_gate"], params["mlp"]["w_up"],
                   params["mlp"]["w_down"])
        x = x + sp_scatter(f, plan, mode, cfg)
        new_cache = None
        if attn_cache is not None or ssm_cache is not None:
            new_cache = {"attn": attn_cache, "ssm": ssm_cache}
    elif kind == "mlstm":
        h = sp_gather(rms_norm(x, params["ln1"], eps), plan, mode, cfg)
        y, new_cache = ssm_mod.mlstm_apply(params["core"], h, cfg, plan, cache, mode)
        x = x + sp_scatter(y, plan, mode, cfg)
    elif kind == "slstm":
        h = sp_gather(rms_norm(x, params["ln1"], eps), plan, mode, cfg)
        y, new_cache = ssm_mod.slstm_apply(params["core"], h, cfg, plan, cache, mode)
        x = x + sp_scatter(y, plan, mode, cfg)
    else:
        raise ValueError(kind)
    return x, new_cache, aux


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int, dtype,
                     paged: bool = False, num_pages: int = 0,
                     page_size: int = 16):
    """Decode cache for one block (None for cacheless kinds in train).

    ``paged=True`` gives full-attention GQA layers the paged-pool layout
    (``attn.init_paged_gqa_cache``); window/ring and recurrent layers keep
    their dense layout — their state is already bounded (window / constant)
    so paging buys nothing there.
    """
    if kind in ("attn", "moe"):
        if paged:
            return attn_mod.init_paged_gqa_cache(cfg, batch, num_pages,
                                                 page_size, max_len, dtype)
        return attn_mod.init_gqa_cache(cfg, "full", batch, max_len, dtype)
    if kind == "local":
        return attn_mod.init_gqa_cache(cfg, "local", batch, max_len, dtype)
    if kind == "mla_moe":
        return attn_mod.init_mla_cache(cfg, batch, max_len, dtype)
    if kind == "hybrid":
        return {"attn": attn_mod.init_gqa_cache(cfg, "local", batch, max_len, dtype),
                "ssm": ssm_mod.init_mamba_cache(cfg, batch, dtype)}
    if kind == "mlstm":
        return ssm_mod.init_mlstm_cache(cfg, batch)
    if kind == "slstm":
        return ssm_mod.init_slstm_cache(cfg, batch)
    raise ValueError(kind)
