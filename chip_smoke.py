#!/usr/bin/env python3
"""On-chip smoke check: serve yi-9b at its published widths on a TPU.

    python chip_smoke.py            # one chip: the served path + kernel checks
    python chip_smoke.py --chips 4  # four drives on four chips vs one drive

One chip: builds ``ServeEngine`` on yi-9b with its depth cut to 24 of 48
layers (``config.one_chip_config``, random weights from ``--seed``), checks
that its compiled decode block holds the Pallas paged kernel, checks the
paged-decode and flash-attention kernels against their jnp references at
the served shapes, then serves 16 seeded requests (prompts of 16-1024
tokens, 32 new tokens each; prompts above 256 tokens take chunked
prefill).  It fails unless every request ends ``ok`` with all its tokens,
the request counts add up, the KV page free-list is balanced, and nothing
compiles inside the served window.

``--chips 4`` runs only the cluster phase: four drives, each with its own
copy of the weights, caches and decode state on its own chip, behind
``data_local`` routing; then the same requests through one drive.  It fails
unless the drives sit on four distinct devices and the tokens are
identical.

The last line of standard output is one JSON object naming the device.
Where JAX finds no TPU the script exits non-zero and prints no result.
Times printed here are smoke readings, not metrics.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import monitoring  # noqa: E402

from repro.config import get_config, one_chip_config  # noqa: E402
from repro.kernels import flash_attention, paged_decode, ref  # noqa: E402
from repro.launch.compiles import (count_compiles,  # noqa: E402
                                   has_kernel, use_persistent_cache)
from repro.models import model as M  # noqa: E402
from repro.train.cluster_loop import ClusterEngine  # noqa: E402
from repro.train.serve_loop import ServeEngine  # noqa: E402

ENGINE_KW = dict(kv_layout="paged", k_block=8, num_slots=8, max_len=2048,
                 page_size=16, chunk_prefill=256, prewarm=True,
                 # one-shot prompts (<= 256 tokens) pad to 4 bucket lengths:
                 # 4 prefill compiles in prewarm, not 32
                 bucket_quantum=64)
N_REQUESTS, MAX_NEW = 16, 32
SHORT, LONG = (16, 256), (257, 1024)     # prompt lengths: one-shot, chunked

# Kernel-vs-reference tolerances.  Both sides read bf16 inputs and
# accumulate in float32; they differ in summation order (the kernel's
# online softmax runs page by page or block by block) and in how the
# float32 probabilities meet the bf16 values in the second matmul: the TPU's
# default matmul precision rounds a float32 operand to bf16 (relative
# 2^-8).  The flash kernel also writes its output in bf16.
PAGED_TOL = dict(atol=2e-2, rtol=2e-2)        # on acc / l, the attention out
PAGED_M_TOL = dict(atol=1e-3, rtol=1e-3)      # row max of q.k: no softmax
FLASH_TOL = dict(atol=3e-2, rtol=3e-2)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def make_requests(vocab: int, seed: int, n: int = N_REQUESTS):
    """Half one-shot prompts, half chunked, interleaved; seeded."""
    rng = np.random.default_rng(seed)
    lengths = np.empty(n, np.int64)
    lengths[0::2] = rng.integers(SHORT[0], SHORT[1] + 1, (n + 1) // 2)
    lengths[1::2] = rng.integers(LONG[0], LONG[1] + 1, n // 2)
    return [rng.integers(0, vocab, int(k)).tolist() for k in lengths]


def _ragged_pool(rng, num_slots, maxp, page_size, hkv, dh):
    """A full pool of random bf16 pages, each slot owning a random ragged
    prefix of pages (table entries -1 past it) at a random position."""
    pool_pages = num_slots * maxp + 1                   # + scratch page
    pool = lambda: jnp.asarray(rng.normal(size=(pool_pages, page_size, hkv,
                                                dh)), jnp.bfloat16)
    perm = rng.permutation(num_slots * maxp)
    table = np.full((num_slots, maxp), -1, np.int32)
    cur = np.zeros(num_slots, np.int32)
    for b in range(num_slots):
        n = int(rng.integers(1, maxp + 1))
        table[b, :n] = perm[b * maxp: b * maxp + n]
        cur[b] = int(rng.integers((n - 1) * page_size, n * page_size))
    return pool(), pool(), jnp.asarray(table), jnp.asarray(cur)


def check_paged_kernel(cfg, seed: int, *, num_slots: int, max_len: int,
                       page_size: int) -> float:
    """The Pallas paged decode partial against its jnp reference at the
    served decode shape.  Returns the largest error of the output."""
    rng = np.random.default_rng(seed)
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    kp, vp, table, cur = _ragged_pool(rng, num_slots, max_len // page_size,
                                      page_size, hkv, dh)
    q = jnp.asarray(rng.normal(size=(num_slots, h, dh)), jnp.bfloat16)
    acc, l, m = jax.jit(paged_decode.paged_decode_partial)(
        q, kp, vp, table, cur)
    racc, rl, rm = jax.jit(paged_decode.paged_decode_partial_ref)(
        q, kp, vp, table, cur)
    out = np.asarray(acc / l[..., None])
    want = np.asarray(racc / rl[..., None])
    assert np.isfinite(out).all(), "paged kernel: non-finite output"
    np.testing.assert_allclose(out, want, **PAGED_TOL)
    np.testing.assert_allclose(np.asarray(m), np.asarray(rm), **PAGED_M_TOL)
    return float(np.abs(out - want).max())


def check_flash_kernel(cfg, seed: int, *, batch: int, seq: int) -> float:
    """The Pallas flash attention forward against ``ref.chunked_attention``
    at the largest one-shot prefill shape.  Returns the largest error."""
    rng = np.random.default_rng(seed)
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    t = lambda *s: jnp.asarray(rng.normal(size=s), jnp.bfloat16)
    q, k, v = t(batch, seq, h, dh), t(batch, seq, hkv, dh), t(batch, seq,
                                                              hkv, dh)
    got = jax.jit(lambda *a: flash_attention.flash_attention(
        *a, causal=True))(q, k, v)
    want = jax.jit(lambda *a: ref.chunked_attention(*a, causal=True))(q, k, v)
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert np.isfinite(got).all(), "flash kernel: non-finite output"
    np.testing.assert_allclose(got, want, **FLASH_TOL)
    return float(np.abs(got - want).max())


def serve(engine, requests, max_new: int = MAX_NEW, shards: int = 0):
    """Submit every request, run to completion inside a compile counter.
    Returns (results by rid, programs lowered in the window, wall s)."""
    with count_compiles() as lowered:
        t0 = time.perf_counter()
        for i, prompt in enumerate(requests):
            if shards:
                engine.submit(prompt, max_new=max_new, shard_id=i % shards)
            else:
                engine.submit(prompt, max_new=max_new)
        results = engine.run_until_complete()
        wall = time.perf_counter() - t0
    return sorted(results, key=lambda r: r.rid), lowered, wall


def check_served(results, submitted: int, lowered, max_new: int = MAX_NEW):
    counts = {s: sum(r.status == s for r in results)
              for s in ("ok", "shed", "failed")}
    assert submitted == sum(counts.values()), (submitted, counts)
    assert counts["ok"] == submitted, counts
    short = [r.rid for r in results if len(r.tokens) != max_new]
    assert not short, f"requests without all {max_new} tokens: {short}"
    assert not lowered, f"compiled inside the served window: {lowered}"
    return counts


def one_chip(seed: int) -> None:
    cfg = one_chip_config()
    t0 = time.perf_counter()
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"params: {n_bytes} bytes, initialised under jit in "
        f"{time.perf_counter() - t0:.3f} s (compile included)")

    t0 = time.perf_counter()
    engine = ServeEngine(cfg, params, **ENGINE_KW)
    log(f"engine built and prewarmed in {time.perf_counter() - t0:.3f} s; "
        f"compile_s {engine.stats.compile_s:.3f}")
    hlo = engine._decode_block.lower(
        engine.params, engine.caches, engine._tok_dev, engine._pos_dev,
        engine._alive_dev, engine._rem_dev).compile().as_text()
    assert has_kernel(hlo, "paged_decode"), \
        "the compiled decode block holds no Pallas paged_decode kernel"
    log("compiled decode block holds the Pallas paged_decode kernel "
        f"({hlo.count('tpu_custom_call')} tpu_custom_call)")

    err = check_paged_kernel(cfg, seed, num_slots=ENGINE_KW["num_slots"],
                             max_len=ENGINE_KW["max_len"],
                             page_size=ENGINE_KW["page_size"])
    log(f"paged_decode_partial vs paged_decode_partial_ref: max |err| "
        f"{err:.3e} (atol {PAGED_TOL['atol']}, rtol {PAGED_TOL['rtol']})")
    err = check_flash_kernel(cfg, seed, batch=ENGINE_KW["num_slots"],
                             seq=ENGINE_KW["chunk_prefill"])
    log(f"flash_attention vs ref.chunked_attention: max |err| {err:.3e} "
        f"(atol {FLASH_TOL['atol']}, rtol {FLASH_TOL['rtol']})")

    requests = make_requests(cfg.vocab_size, seed)
    n_chunked = sum(len(p) > ENGINE_KW["chunk_prefill"] for p in requests)
    log(f"serving {len(requests)} requests: prompts "
        f"{min(map(len, requests))}-{max(map(len, requests))} tokens, "
        f"{n_chunked} chunked, max_new {MAX_NEW}")
    results, lowered, wall = serve(engine, requests)
    counts = check_served(results, len(requests), lowered)
    engine.pager.check_balanced()
    n_tok = sum(len(r.tokens) for r in results)
    log(f"served: {counts}; {n_tok} tokens in {wall:.3f} s wall "
        f"(smoke reading, not a metric); 0 programs compiled in the "
        f"served window; page free-list balanced")
    log(f"first tokens of request 0: {results[0].tokens[:8]}")


def four_chips(seed: int) -> None:
    devices = jax.devices()
    assert len(devices) >= 4, f"--chips 4 needs four devices, got {devices}"
    cfg = one_chip_config()
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    requests = make_requests(cfg.vocab_size, seed)

    one = ServeEngine(cfg, params, **ENGINE_KW)
    want, lowered, wall = serve(one, requests)
    check_served(want, len(requests), lowered)
    log(f"one drive: {len(want)} requests in {wall:.3f} s wall "
        "(smoke reading)")

    t0 = time.perf_counter()
    cluster = ClusterEngine(cfg, params, n_drives=4, routing="data_local",
                            jit_donor=one, **ENGINE_KW)
    log(f"four drives built and prewarmed in "
        f"{time.perf_counter() - t0:.3f} s")
    del one
    got, lowered, wall = serve(cluster, requests, shards=4)
    check_served(got, len(requests), lowered)
    placed = []
    for drive in cluster.drives:
        eng = drive.engine
        on = {d for x in jax.tree.leaves((eng.params, eng.caches,
                                          eng._tok_dev, eng._pages_dev))
              for d in x.devices()}
        assert len(on) == 1, f"drive {drive.drive_id} spans {on}"
        placed.append(on.pop())
        eng.pager.check_balanced()
    assert len(set(placed)) == 4, f"drives share devices: {placed}"
    served_by = sorted({r.drive for r in got})
    assert served_by == [0, 1, 2, 3], served_by
    mismatched = [r.rid for r, w in zip(got, want) if r.tokens != w.tokens]
    assert not mismatched, f"tokens differ from one drive: {mismatched}"
    log(f"four drives on {[str(d) for d in placed]}: {len(got)} requests "
        f"in {wall:.3f} s wall (smoke reading); token-identical to one "
        "drive")
    cluster.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-drive cluster phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, inputs and requests")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    log(f"compile cache: {use_persistent_cache()}")
    cache = {"hits": 0, "misses": 0}

    def tally(event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    monitoring.register_event_listener(tally)
    full = get_config("yi-9b")
    cfg = one_chip_config()
    log(f"config: yi-9b at published widths (d_model {cfg.d_model}, "
        f"{cfg.num_heads} query heads over {cfg.num_kv_heads} KV heads, "
        f"head_dim {cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}, {cfg.dtype}); reduced: num_layers "
        f"{full.num_layers} -> {cfg.num_layers}")
    log("chunked-prefill attention has no Pallas kernel: chunks run the "
        "jnp reference (kernels/ops.py chunk_prefill_attention)")

    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    used = jax.devices()[:args.chips]       # the devices the phase ran on
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in used]
    log(f"peak_bytes_in_use per device: {peaks}")
    log(f"persistent compile cache: {cache['hits']} hits, "
        f"{cache['misses']} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(used)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
