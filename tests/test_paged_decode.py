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


def _pool_with(rng, rows, Hkv, dh, ps, maxp, P, dtype=jnp.float32,
               order="shuffled"):
    """Pools and page tables for explicit slots: ``rows`` holds one
    ``(cur, allocated pages)`` per slot; ``(cur, 0)`` is a slot that owns
    no page (its row all -1).  Physical pages are handed out in a shuffled
    (or descending) order, and never page 0."""
    t = lambda *s: jnp.asarray(rng.normal(size=s), dtype)
    kpool, vpool = t(P + 1, ps, Hkv, dh), t(P + 1, ps, Hkv, dh)
    free = rng.permutation(np.arange(1, P)) if order == "shuffled" \
        else np.arange(P - 1, 0, -1)
    tables, used = [], 0
    for _, n_alloc in rows:
        row = np.full(maxp, -1, np.int32)
        row[:n_alloc] = free[used: used + n_alloc]
        used += n_alloc
        tables.append(row)
    cur = jnp.asarray([c for c, _ in rows], jnp.int32)
    return kpool, vpool, jnp.asarray(np.stack(tables)), cur


# ragged-edge geometry: dh 16, pages of 8 over 40 logical pages, so the
# walk takes blocks of 16 pages (128 tokens) and a last block of 8
EDGE_PS, EDGE_MAXP, EDGE_TPB = 8, 40, 128
_full = lambda cur: -(-(cur + 1) // EDGE_PS)          # pages holding 0..cur
EDGE_ROWS = {
    "page-edge": [(EDGE_PS - 1, 1), (EDGE_PS, 2)],
    "block-edge": [(EDGE_TPB - 1, _full(EDGE_TPB - 1)),
                   (EDGE_TPB, _full(EDGE_TPB)),
                   (EDGE_TPB + 1, _full(EDGE_TPB + 1))],
    "full-slot": [(EDGE_MAXP * EDGE_PS - 1, EDGE_MAXP), (3, 1)],
    "inactive-slot": [(200, 0), (50, _full(50)), (0, 0)],
    "descending-pages": [(300, _full(300)), (100, _full(100))],
    "past-cur": [(20, 10), (EDGE_TPB + 3, EDGE_MAXP)],
}


def _edge_case(rng, case, H, Hkv, dtype):
    dh = 16
    if case == "yi-batch":                     # B=16, g=8 as in yi-9b
        B = 16
        kpool, vpool, pages, cur = _random_pool(
            rng, B, Hkv, dh, B * EDGE_MAXP, EDGE_PS, EDGE_MAXP, dtype)
    else:
        rows = EDGE_ROWS[case]
        B = len(rows)
        kpool, vpool, pages, cur = _pool_with(
            rng, rows, Hkv, dh, EDGE_PS, EDGE_MAXP,
            sum(n for _, n in rows) + 2, dtype,
            "descending" if case == "descending-pages" else "shuffled")
    q = jnp.asarray(rng.normal(size=(B, H, dh)), dtype)
    return q, kpool, vpool, pages, cur


_RANDOM_CASES = [
    pytest.param(dtype, window, H, Hkv, "random", marks=pytest.mark.fast,
                 id=f"{H}-{Hkv}-{window}-{dtype.__name__}")
    for dtype in (jnp.float32, jnp.bfloat16) for window in (None, 11)
    for H, Hkv in ((8, 4), (32, 4))]     # (32, 4): yi-9b's g=8
_EDGE_CASES = [
    pytest.param(jnp.float32, window, 32, 4, case, id=f"{case}-{window}")
    for case in (*EDGE_ROWS, "yi-batch") for window in (None, 11)]


@pytest.mark.parametrize("dtype,window,H,Hkv,case",
                         _RANDOM_CASES + _EDGE_CASES)
def test_pallas_paged_decode_matches_ref(rng, dtype, window, H, Hkv, case):
    if case == "random":
        B, dh, ps, P, maxp = 3, 16, 8, 12, 5
        q = jnp.asarray(rng.normal(size=(B, H, dh)), dtype)
        kpool, vpool, pages, cur = _random_pool(rng, B, Hkv, dh, P, ps,
                                                maxp, dtype)
    else:
        q, kpool, vpool, pages, cur = _edge_case(rng, case, H, Hkv, dtype)
        assert paged_decode.pages_per_block(EDGE_PS, Hkv, q.shape[2],
                                            EDGE_MAXP) * EDGE_PS == EDGE_TPB
    want = paged_decode.paged_decode_partial_ref(q, kpool, vpool, pages, cur,
                                                 window=window)
    got = paged_decode.paged_decode_partial(q, kpool, vpool, pages, cur,
                                            window=window, interpret=True)
    tol = dict(atol=5e-6, rtol=5e-6) if dtype == jnp.float32 \
        else dict(atol=2e-2, rtol=2e-2)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)


@pytest.mark.parametrize("window", [None, 11])
def test_pallas_paged_decode_never_reads_dead_pages(rng, window):
    """Every pool page outside all slots' live prefixes (pages past
    ``cur``, unallocated ones — page 0 among them — and the scratch page)
    is NaN: the kernel's partials stay finite and equal the reference's on
    the clean pool, so a dead page never enters the math."""
    rows = [(5, 3), (EDGE_TPB + 2, EDGE_MAXP), (300, _full(300) + 1),
            (77, 0)]
    H, Hkv, dh = 32, 4, 16
    P = sum(n for _, n in rows) + 4
    kpool, vpool, pages, cur = _pool_with(rng, rows, Hkv, dh, EDGE_PS,
                                          EDGE_MAXP, P)
    q = jnp.asarray(rng.normal(size=(len(rows), H, dh)), jnp.float32)
    live = np.zeros(P + 1, bool)                   # the scratch page is P
    for row, c in zip(np.asarray(pages), np.asarray(cur)):
        held = row[: _full(int(c))]
        live[held[held >= 0]] = True
    assert not live[0] and not live[P]
    dead = jnp.asarray(~live)[:, None, None, None]
    poisoned = [jnp.where(dead, jnp.nan, p) for p in (kpool, vpool)]
    want = paged_decode.paged_decode_partial_ref(q, kpool, vpool, pages, cur,
                                                 window=window)
    got = paged_decode.paged_decode_partial(q, *poisoned, pages, cur,
                                            window=window, interpret=True)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-6, rtol=5e-6)


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


@pytest.mark.parametrize("k_block", [1, 4])
def test_kv_pages_walked_counts_the_kernels_trip_counts(cfg, params, rng,
                                                        k_block):
    """``ServeStats.kv_pages_walked`` and the decode spans' stat equal the
    pages the paged kernel's trip counts cover — ``block_range`` on the
    page table and positions each decode call hands the kernel, step by
    step, times the layers that run it — and stay at most a quarter of the
    old walk over every page of every slot each step."""
    from repro.core.telemetry import TelemetryHub
    hub = TelemetryHub()
    slots, max_len, ps = 4, 512, 8
    eng = ServeEngine(cfg, params, max_len=max_len, num_slots=slots,
                      kv_layout="paged", page_size=ps, k_block=k_block,
                      telemetry=hub)
    maxp = max_len // ps
    ppb = paged_decode.pages_per_block(ps, cfg.num_kv_heads,
                                       cfg.resolved_head_dim, maxp)
    layers = sum(k in ("attn", "moe") for k in cfg.layer_pattern)

    def table_of(caches):
        return next(np.asarray(c["pages"])[0] for c in caches.values()
                    if isinstance(c, dict) and "pages" in c)

    calls = []                     # (page table, (steps, B) positions)
    if k_block > 1:
        block_fn = eng._decode_block

        def spy(p, caches, tok, pos, alive, rem):
            table, pos0 = table_of(caches), np.asarray(pos)
            out = block_fn(p, caches, tok, pos, alive, rem)
            emitted = np.asarray(out[0])[: int(out[1])] >= 0
            before = np.cumsum(emitted, axis=0) - emitted
            calls.append((table, pos0 + before))
            return out
        eng._decode_block = spy
    else:
        step_fn = eng._decode

        def spy(p, caches, tokens, positions):
            calls.append((table_of(caches), np.asarray(positions)[None]))
            return step_fn(p, caches, tokens, positions)
        eng._decode = spy

    # budgets that end mid-block, and a fifth request that refills a slot
    for n, m in ((5, 6), (17, 3), (30, 9), (9, 5), (24, 7)):
        eng.submit(rng.integers(0, cfg.vocab_size, n).tolist(), max_new=m)
    eng.run_until_complete()

    want = 0
    for table, cur in calls:
        first, end = paged_decode.block_range(table, cur, ps, ppb)
        want += int((end - first).sum()) * ppb * layers
    name = "serve.decode_block" if k_block > 1 else "serve.decode"
    spans = [e["attrs"]["kv_pages_walked"] for e in hub.events()
             if e["ev"] == "phase" and e["name"] == name]
    assert want > 0 and len(spans) == len(calls)
    assert eng.stats.kv_pages_walked == sum(spans) == want
    assert eng.stats.metrics()["kv_pages_walked"] == want
    old_walk = eng.stats.decode_steps * slots * maxp * layers
    assert 4 * want <= old_walk
