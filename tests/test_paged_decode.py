"""Paged KV decode: the fused Pallas ragged kernel (interpret mode) vs the
jnp reference, and the paged serve engine vs the dense-strip engine —
token-identical across random prompt lengths, evictions and refills, with
a balanced free-list and a live-token (not num_slots*max_len) footprint."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config import reduced_config
from repro.core import kv_pages
from repro.kernels import ops as kops
from repro.kernels import paged_decode, ref
from repro.models import model as M
from repro.train.serve_loop import AdmissionController, ServeEngine

MAX_LEN = 64


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(reduced_config("yi-9b"), dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return M.init_params(cfg, jax.random.PRNGKey(0))


def make_engine(cfg, params, num_slots=2, **kw):
    kw.setdefault("admission",
                  AdmissionController(num_slots, host_rate=3.0, csd_rate=1.0))
    return ServeEngine(cfg, params, max_len=MAX_LEN, num_slots=num_slots, **kw)


# ---------------------------------------------------------------------------
# Kernel vs reference
# ---------------------------------------------------------------------------


def _random_pool(rng, B, Hkv, dh, P, ps, maxp, dtype=jnp.float32):
    t = lambda *s: jnp.asarray(rng.normal(size=s), dtype)
    kpool, vpool = t(P + 1, ps, Hkv, dh), t(P + 1, ps, Hkv, dh)
    # random non-overlapping page tables with ragged fill levels
    perm = rng.permutation(P)
    tables, cur, used = [], [], 0
    for b in range(B):
        n_alloc = int(rng.integers(0, min(maxp, P - used) + 1))
        row = np.full(maxp, -1, np.int32)
        row[:n_alloc] = perm[used: used + n_alloc]
        used += n_alloc
        tables.append(row)
        hi = n_alloc * ps - 1
        cur.append(int(rng.integers(0, hi + 1)) if hi >= 0 else 0)
    return kpool, vpool, jnp.asarray(np.stack(tables)), \
        jnp.asarray(cur, jnp.int32)


@pytest.mark.fast
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 11])
@pytest.mark.parametrize("H,Hkv", [(8, 4), (32, 4)])   # (32, 4): yi-9b's g=8
def test_pallas_paged_decode_matches_ref(rng, dtype, window, H, Hkv):
    B, dh, ps, P, maxp = 3, 16, 8, 12, 5
    q = jnp.asarray(rng.normal(size=(B, H, dh)), dtype)
    kpool, vpool, pages, cur = _random_pool(rng, B, Hkv, dh, P, ps, maxp,
                                            dtype)
    want = paged_decode.paged_decode_partial_ref(q, kpool, vpool, pages, cur,
                                                 window=window)
    got = paged_decode.paged_decode_partial(q, kpool, vpool, pages, cur,
                                            window=window, interpret=True)
    tol = dict(atol=5e-6, rtol=5e-6) if dtype == jnp.float32 \
        else dict(atol=2e-2, rtol=2e-2)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)


@pytest.mark.fast
def test_paged_ref_equals_strip_path(rng):
    """The jnp paged reference must equal the strip-path reference on the
    gathered view — bit-exact (same oracle, same masking)."""
    B, H, Hkv, dh, ps, P, maxp = 2, 4, 2, 16, 4, 8, 4
    q = jnp.asarray(rng.normal(size=(B, H, dh)), jnp.float32)
    kpool, vpool, pages, cur = _random_pool(rng, B, Hkv, dh, P, ps, maxp)
    acc, l, m = paged_decode.paged_decode_partial_ref(q, kpool, vpool, pages,
                                                      cur)
    k, v, kpos = kv_pages.pages_to_strips((kpool, vpool), pages, ps)
    acc2, l2, m2 = ref.decode_partial_masked(q, k, v, kpos, cur)
    for a, b in zip((acc, l, m), (acc2, l2, m2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.fast
def test_ops_dispatch_paged(rng):
    B, H, Hkv, dh, ps, P, maxp = 2, 4, 2, 16, 4, 8, 4
    q = jnp.asarray(rng.normal(size=(B, H, dh)), jnp.float32)
    kpool, vpool, pages, cur = _random_pool(rng, B, Hkv, dh, P, ps, maxp)
    jn = kops.paged_decode_partial(q, kpool, vpool, pages, cur, impl="jnp")
    pk = kops.paged_decode_partial(q, kpool, vpool, pages, cur, impl="pallas")
    for a, b in zip(jn, pk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-6, rtol=5e-6)


# ---------------------------------------------------------------------------
# Engine: paged == strip, end to end
# ---------------------------------------------------------------------------


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_paged_engine_token_identical_to_strip(cfg, params, seed):
    """Random mixed-length workloads with eviction + refill: the paged
    engine — running the fused K-block loop AND chunked prefill — must emit
    exactly the K=1 strip host-reference loop's tokens, finish with a
    balanced free-list, and peak below the dense worst case."""
    rng = np.random.default_rng(seed)
    n_req = int(rng.integers(4, 7))
    prompts = [rng.integers(0, cfg.vocab_size, int(rng.integers(3, 25))).tolist()
               for _ in range(n_req)]
    max_news = [int(rng.integers(1, 7)) for _ in range(n_req)]

    strip = make_engine(cfg, params, kv_layout="strip", k_block=1)
    paged = make_engine(cfg, params, kv_layout="paged", page_size=8,
                        k_block=8, chunk_prefill=8)
    for p, m in zip(prompts, max_news):
        strip.submit(p, max_new=m)
        paged.submit(p, max_new=m)
    want = {r.rid: r.tokens for r in strip.run_until_complete()}
    got = {r.rid: r.tokens for r in paged.run_until_complete()}
    assert got == want

    paged.pager.check_balanced()                      # eager frees leaked 0
    assert paged.pager.peak_pages <= paged.pager.num_pages
    st_ = paged.stats
    assert st_.kv_bytes_touched < st_.baseline.kv_bytes
    assert 0.0 < st_.kv_reduction <= 1.0
    assert paged.kv_stats()["peak_kv_bytes"] < paged.kv_stats()["dense_kv_bytes"]


def test_paged_engine_eos_eviction_frees_same_step(cfg, params, rng):
    """EOS must return the slot's pages to the pool in the same engine step
    (not at refill): run until the EOS request finishes, then check the
    free-list regained its pages while other slots still decode."""
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (8, 10)]
    reference = make_engine(cfg, params).generate(prompts, max_new=6)
    eos = reference[0].tokens[2]
    # k_block=1: per-step ticks, so the EOS tick is observable while the
    # other slot is still mid-decode (the fused-block analogue — pages
    # freed in the same tick the block reports EOS — is in
    # test_decode_block.py)
    engine = make_engine(cfg, params, eos_id=eos, page_size=8, k_block=1)
    for p in prompts:
        engine.submit(p, max_new=6)
    done = []
    while (engine.queue or engine.num_active) and not done:
        done = engine.step()
    assert done and done[0].tokens[-1] == eos
    assert engine.num_active == 1                      # other slot still live
    # only the surviving request's pages remain in use: req 1 holds at most
    # pages_for(10 prompt + 6 new) = 2 pages; lazy eviction would retain
    # req 0's 2 pages as well
    assert engine.pager.num_in_use <= kv_pages.pages_for(
        len(prompts[1]) + 6, engine.page_size)
    assert (engine.page_table >= 0).sum() == engine.pager.num_in_use
    engine.run_until_complete()
    engine.pager.check_balanced()


def test_paged_engine_backpressure_tiny_pool(cfg, params, rng):
    """A pool sized for a single request must serialize admission through
    reservation backpressure — every request still completes, exactly."""
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (6, 11, 7, 13)]
    max_news = [2, 5, 3, 4]
    want = {}
    strip = make_engine(cfg, params, kv_layout="strip")
    for p, m in zip(prompts, max_news):
        strip.submit(p, max_new=m)
    want = {r.rid: r.tokens for r in strip.run_until_complete()}

    ps = 8
    biggest = max(kv_pages.pages_for(len(p) + m, ps)
                  for p, m in zip(prompts, max_news))
    engine = make_engine(cfg, params, kv_layout="paged", page_size=ps,
                         num_pages=biggest)
    for p, m in zip(prompts, max_news):
        engine.submit(p, max_new=m)
    got = {r.rid: r.tokens for r in engine.run_until_complete()}
    assert got == want
    engine.pager.check_balanced()
    assert engine.pager.peak_pages <= biggest


def test_paged_refill_resets_page_table(cfg, params, rng):
    """Refilling a slot must leave no pages from the old occupant mapped
    (the paged analogue of the strip kpos-reset test)."""
    engine = make_engine(cfg, params, page_size=8)
    long_p = rng.integers(0, cfg.vocab_size, 20).tolist()
    engine.generate([long_p], max_new=4)          # 24 tokens -> 3 pages peak
    assert (engine.page_table == -1).all()        # eager free on completion
    engine.pager.check_balanced()
    assert engine.pager.peak_pages == 3
    short_p = rng.integers(0, cfg.vocab_size, 5).tolist()
    engine.generate([short_p], max_new=1)         # refill needs only 1 page
    assert engine.pager.peak_pages == 3           # no stale pages retained
    engine.pager.check_balanced()


def test_submit_rejects_request_larger_than_pool(cfg, params, rng):
    engine = make_engine(cfg, params, page_size=8, num_pages=1)
    with pytest.raises(ValueError):
        engine.submit(rng.integers(0, cfg.vocab_size, 20).tolist(),
                      max_new=4)


def test_paged_engine_pallas_interpret_token_identical(cfg, params, rng,
                                                       monkeypatch):
    """Force the fused Pallas kernel (interpret mode on CPU) through the
    engine's decode path — INSIDE the fused K-block loop and with chunked
    prefill — and require exactly the K=1 strip host loop's tokens."""
    import functools
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (5, 9, 13)]
    want = [r.tokens for r in
            make_engine(cfg, params, kv_layout="strip", k_block=1).generate(
                prompts, max_new=3)]
    monkeypatch.setattr(kops, "paged_decode_partial", functools.partial(
        kops.paged_decode_partial, impl="pallas"))
    got = [r.tokens for r in
           make_engine(cfg, params, kv_layout="paged", page_size=8,
                       k_block=8, chunk_prefill=4)
           .generate(prompts, max_new=3)]
    assert got == want
