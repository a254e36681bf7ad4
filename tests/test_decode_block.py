"""Device-resident fused K-block decode loop + chunked prefill: early-exit
semantics, same-tick page release, chunked-vs-one-shot prefill equivalence
(caches and sampled tokens), jit pre-warm accounting, a compile-free served
window after it, and the bench guard's payload invariants."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.config import reduced_config
from repro.core import kv_pages
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
# K-block early exit
# ---------------------------------------------------------------------------


def test_kblock_early_exit_no_extra_tokens_pages_freed(cfg, params, rng):
    """All slots finishing mid-block must end the block early (no wasted
    device steps), emit exactly max_new tokens, and return every page to
    the pool in the same engine tick."""
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (6, 9)]
    engine = make_engine(cfg, params, kv_layout="paged", page_size=8,
                         k_block=8)
    for p in prompts:
        engine.submit(p, max_new=3)
    done = engine.step()                 # admit + prefill + ONE fused block
    # max_new=3 = prefill token + 2 decode steps — both slots die at inner
    # step 2 of an 8-step block, so the while_loop must exit early
    assert [len(r.tokens) for r in done] == [3, 3]
    assert engine.stats.decode_steps == 2
    assert engine.num_active == 0 and engine.pending == 0
    engine.pager.check_balanced()        # pages freed in the SAME tick


def test_kblock_matches_host_loop_with_eos(cfg, params, rng):
    """EOS firing inside a block must stop that slot exactly where the K=1
    host loop stops it, while other slots keep decoding to their budget."""
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (8, 10)]
    reference = make_engine(cfg, params, k_block=1).generate(prompts,
                                                            max_new=6)
    eos = reference[0].tokens[2]
    want = [r.tokens[: r.tokens.index(eos) + 1] if eos in r.tokens
            else r.tokens for r in reference]
    got = make_engine(cfg, params, eos_id=eos, k_block=8).generate(
        prompts, max_new=6)
    assert [r.tokens for r in got] == want
    assert len(got[0].tokens) == 3 and got[0].tokens[-1] == eos


def test_kblock_device_state_survives_refill(cfg, params, rng):
    """More requests than slots with k_block > 1: mid-workload refills must
    resync the persistent device token/position/alive arrays correctly."""
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (6, 11, 7, 13, 9)]
    max_news = [2, 6, 3, 5, 4]
    ref = make_engine(cfg, params, k_block=1, kv_layout="strip")
    fused = make_engine(cfg, params, k_block=3)   # K not dividing budgets
    for p, m in zip(prompts, max_news):
        ref.submit(p, max_new=m)
        fused.submit(p, max_new=m)
    want = {r.rid: r.tokens for r in ref.run_until_complete()}
    got = {r.rid: r.tokens for r in fused.run_until_complete()}
    assert got == want
    fused.pager.check_balanced()


# ---------------------------------------------------------------------------
# Chunked prefill
# ---------------------------------------------------------------------------


def _prompt_rows(engine, group, n_tokens):
    """Gather a slot-0 KV strip view (k, v) for the first n_tokens rows."""
    cache = engine.caches[group]
    pages = np.asarray(engine.page_table[0])[None]
    k = kv_pages.gather_pages(cache["kp"][0], pages)[0, :n_tokens]
    v = kv_pages.gather_pages(cache["vp"][0], pages)[0, :n_tokens]
    return np.asarray(k), np.asarray(v)


def test_chunked_prefill_equivalent_to_one_shot(cfg, params, rng):
    """Chunked prefill must leave the paged pool holding the same KV rows
    as the one-shot prefill (same physical pages, allclose values) and
    sample the same next token."""
    prompt = rng.integers(0, cfg.vocab_size, 21).tolist()

    oneshot = make_engine(cfg, params, page_size=8, k_block=1)
    chunked = make_engine(cfg, params, page_size=8, k_block=1,
                          chunk_prefill=8)
    r1 = oneshot.submit(prompt, max_new=1)
    r2 = chunked.submit(prompt, max_new=1)
    while oneshot.num_active or oneshot.pending:
        oneshot.step()
    ticks = 0
    while chunked.num_active or chunked.pending:
        chunked.step()
        ticks += 1
    assert ticks >= 3                          # 21 tokens / 8 = 3 chunks
    want = {r.rid: r.tokens for r in oneshot._finished}
    got = {r.rid: r.tokens for r in chunked._finished}
    assert got[r2] == want[r1]                 # same sampled token

    # engines are drained, so re-prefill once more and inspect the pool
    # before decode: submit + single admission/prefill tick each
    oneshot.submit(prompt, max_new=4)
    chunked.submit(prompt, max_new=4)
    oneshot._admit()
    chunked._admit()
    for _ in range(3):
        chunked._chunk_prefill_tick()
    assert np.array_equal(oneshot.page_table, chunked.page_table)
    for g in oneshot.caches:
        k1, v1 = _prompt_rows(oneshot, g, len(prompt))
        k2, v2 = _prompt_rows(chunked, g, len(prompt))
        np.testing.assert_allclose(k1, k2, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(v1, v2, atol=1e-5, rtol=1e-5)


def test_chunked_prefill_interleaves_decode(cfg, params, rng):
    """A long admission must not stall in-flight decodes: the short request
    keeps emitting (and can finish) while the long prompt is still
    splicing chunk by chunk."""
    short = rng.integers(0, cfg.vocab_size, 5).tolist()
    long_p = rng.integers(0, cfg.vocab_size, 48).tolist()
    engine = make_engine(cfg, params, num_slots=2, page_size=8,
                         k_block=1, chunk_prefill=4)      # 12 chunk ticks
    engine.submit(short, max_new=3)
    engine.submit(long_p, max_new=2)
    finished = []
    while (engine.num_active or engine.pending) and not finished:
        finished = engine.step()
    # the short request finished while the long one was still prefilling
    assert finished and finished[0].tokens and len(finished[0].tokens) == 3
    assert any(s.active and s.prefilling for s in engine.slots)
    engine.run_until_complete()
    engine.pager.check_balanced()


def test_chunk_prefill_gated_to_paged_full_attention(cfg, params):
    """Strip layouts (and stacks with window/recurrent layers) must fall
    back to one-shot prefill instead of mis-splicing chunks."""
    strip = make_engine(cfg, params, kv_layout="strip", chunk_prefill=8)
    assert strip.chunk_prefill is None
    g3 = dataclasses.replace(reduced_config("gemma3-12b"), dtype="float32")
    g3_engine = ServeEngine(g3, M.init_params(g3, jax.random.PRNGKey(0)),
                            max_len=MAX_LEN, num_slots=2, chunk_prefill=8)
    assert g3_engine.chunk_prefill is None     # window layers in the stack


# ---------------------------------------------------------------------------
# Pre-warm
# ---------------------------------------------------------------------------


def test_prewarm_reports_compile_time_and_stays_identical(cfg, params, rng):
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (5, 12)]
    cold = make_engine(cfg, params, k_block=8)
    warm = make_engine(cfg, params, k_block=8, chunk_prefill=8, prewarm=True)
    assert warm.stats.compile_s > 0
    want = [r.tokens for r in cold.generate(prompts, max_new=4)]
    got = [r.tokens for r in warm.generate(prompts, max_new=4)]
    assert got == want
    # the cold engine's lazy first-shape calls are booked as compile, not
    # serving: neither engine's decode_s contains the XLA compile anymore,
    # and the compile the cold engine paid is visible in compile_s
    assert cold.stats.compile_s > 0
    assert cold.stats.decode_s < cold.stats.compile_s
    assert warm.stats.decode_s < warm.stats.compile_s


def test_prewarmed_engine_compiles_nothing_while_serving(cfg, params, rng):
    """After prewarm, a served window — one-shot and chunked prompts, more
    requests than slots, refills, page growth and frees — lowers no
    program at all: every shape it runs was compiled before it began."""
    from repro.launch.compiles import count_compiles
    engine = make_engine(cfg, params, num_slots=3, page_size=8, k_block=4,
                         chunk_prefill=16, prewarm=True)
    lengths = (3, 16, 17, 40, 9, 25, 60)
    with count_compiles() as lowered:
        for n in lengths:
            engine.submit(rng.integers(0, cfg.vocab_size, n).tolist(),
                          max_new=3)
        results = engine.run_until_complete()
    assert lowered == []
    assert [len(r.tokens) for r in results] == [3] * len(lengths)
    engine.pager.check_balanced()
