"""Continuous-batching serve engine with scheduler-driven admission.

The paper's serving story (§IV-A) is a *pull* pipeline: resident state stays
on the storage side, the scheduler decides who pulls the next batch, and
only queries/results cross the link.  This engine is that story applied to
LM serving:

  request queue ──▶ admission (PullScheduler.tick + rebalance_shares)
               ──▶ slot pool (per-slot position/length tracks)
               ──▶ plan chooser (choose_embedding_plan / choose_decode_plan)
               ──▶ TransferLedger ("bytes that never crossed the link")

Mechanics:
  * the decode inner loop is device-resident (``k_block`` > 1, default):
    one jitted ``lax.while_loop`` runs up to ``k_block`` greedy steps per
    engine tick — on-device sampling, per-slot position increments,
    EOS/max-new/cache-full termination masks and KV writes — and returns a
    single (K, num_slots) token block to the host.  Cache pools are
    donated (in-place on accelerators), and tokens/positions/page-table
    live as persistent device arrays, uploaded whole from the host's view
    only when admission, growth or a finish changes them (one fixed shape
    each, so serving never compiles).  ``k_block=1`` keeps the per-step
    host loop as the reference the fused path is property-tested against;
  * KV lives in a paged pool by default (``core.kv_pages``): prefill
    allocates ``ceil(len/page_size)`` fixed-size pages per slot, decode
    pre-reserves the pages a whole K-block can touch (a host-side lookup
    before the dispatch — growth inside the scan is a pure page-table
    read), and EOS/eviction frees the slot's pages back to the free list
    in the same tick — peak KV memory and decode reads track live tokens,
    not ``num_slots * max_len``.  Admission reserves each request's
    worst-case page count, so a full pool backpressures the queue instead
    of failing mid-decode (``kv_layout="strip"`` keeps the dense per-slot
    reference layout);
  * chunked prefill (``chunk_prefill=N``): prompts longer than N are
    spliced into the paged pool one fixed-size chunk per tick, interleaved
    with decode blocks, so a long admission never stalls in-flight
    requests and the scheduler observes bounded per-tick service times;
  * variable-length prompts are admitted into a fixed pool of batch slots;
  * prefill is length-bucketed — prompts padded to a common bucket length
    batch together; pad positions are masked out of the per-slot kpos track
    afterwards, so the padded prefill is numerically exact (padding is only
    used for architectures where that holds: pure-attention stacks, window
    not exceeded — recurrent stacks fall back to exact-length buckets);
  * decode steps run the whole pool with per-slot positions — the paged
    layout walks each slot's page table in one fused pass
    (``kernels.paged_decode``: Pallas on TPU, jnp reference elsewhere);
    the strip layout uses per-slot kpos (B,S) masking (see
    ``models.attention``).  EOS / max-len finishes free the slot (and its
    pages), which is refilled from the queue on the next step, mid-decode;
  * every prefill/decode step consults the host-vs-ISP plan chooser and
    records both the chosen and the host-baseline link bytes, so
    ``stats().link_reduction`` reproduces the paper's Fig. 5 accounting
    live.
"""
from __future__ import annotations

import math
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.core.isp import choose_decode_plan, choose_embedding_plan
from repro.core.kv_pages import PageAllocator, pages_for
from repro.core.latency import NAN, LatencyRecord, LatencyStats
from repro.core.scheduler import (PullScheduler, SchedulerState, make_cluster,
                                  optimal_batch_ratio, rebalance_shares,
                                  split_block_service)
from repro.core.telemetry import NULL_HUB
from repro.core.transfer import TransferLedger
from repro.models import model as M


@dataclass
class GenResult:
    tokens: List[int]
    prefill_s: float
    decode_s: float
    rid: int = 0
    tier: str = "host"
    drive: int = 0               # cluster serving: which replica served it
    status: str = "ok"           # "ok" | "shed" (deadline-expired, dropped)
                                 # | "failed" (retry budget exhausted /
                                 #   the last drive died under it)
    priority: int = 0
    # per-request latency on the serving clock (NaN until measurable):
    # queue wait (submit -> slot), TTFT (submit -> first token), TPOT
    # (inter-token cadence after the first), end-to-end (submit -> done)
    queue_wait_s: float = NAN
    ttft_s: float = NAN
    tpot_s: float = NAN
    e2e_s: float = NAN


@dataclass
class ServeStats:
    requests: int = 0
    tokens: int = 0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    decode_steps: int = 0        # inner decode steps actually executed
    compile_s: float = 0.0       # jit pre-warm time (kept out of decode_s)
    tier_tokens: Dict[str, int] = field(default_factory=dict)
    tier_requests: Dict[str, int] = field(default_factory=dict)
    ledger: TransferLedger = field(default_factory=TransferLedger)     # chosen
    baseline: TransferLedger = field(default_factory=TransferLedger)  # host-only
    # SLO accounting: per-request latency records (serving clock) plus the
    # load-shedding tally — shed_wasted_s is serving time already spent on
    # requests that were then dropped (the energy the shed cost anyway)
    latency: LatencyStats = field(default_factory=LatencyStats)
    shed_requests: int = 0
    shed_wasted_s: float = 0.0

    @property
    def link_bytes(self) -> float:
        return self.ledger.link_bytes

    @property
    def host_link_bytes(self) -> float:
        return self.baseline.link_bytes

    @property
    def bytes_never_crossed(self) -> float:
        """Link bytes the ISP plans kept resident vs the host baseline."""
        return max(self.host_link_bytes - self.link_bytes, 0.0)

    @property
    def link_reduction(self) -> float:
        if self.host_link_bytes <= 0:
            return 0.0
        return self.bytes_never_crossed / self.host_link_bytes

    @property
    def kv_bytes_touched(self) -> float:
        """KV rows the decode kernel actually walked (paged: live pages)."""
        return self.ledger.kv_bytes

    @property
    def kv_reduction(self) -> float:
        """Fractional KV-traffic reduction vs the dense per-slot strips the
        baseline decode reads every step (0.0 for the strip layout)."""
        if self.baseline.kv_bytes <= 0:
            return 0.0
        return max(1.0 - self.ledger.kv_bytes / self.baseline.kv_bytes, 0.0)

    def tier_throughput(self, tier: str) -> float:
        dt = max(self.decode_s + self.prefill_s, 1e-9)
        return self.tier_tokens.get(tier, 0) / dt

    @property
    def steps_per_s(self) -> float:
        return self.decode_steps / max(self.decode_s, 1e-9)

    def metrics(self) -> Dict[str, float]:
        """Flat metric dict — the single source ``summary()`` renders from
        and ``launch/serve.py --metrics-out`` exports, so the printed and
        the exported numbers can never disagree."""
        m = {
            "requests": self.requests,
            "tokens": self.tokens,
            "prefill_s": self.prefill_s,
            "decode_s": self.decode_s,
            "decode_steps": self.decode_steps,
            "steps_per_s": self.steps_per_s,
            "compile_s": self.compile_s,
            "link_bytes": self.link_bytes,
            "host_link_bytes": self.host_link_bytes,
            "link_reduction": self.link_reduction,
            "kv_bytes": self.kv_bytes_touched,
            "kv_dense_bytes": self.baseline.kv_bytes,
            "kv_reduction": self.kv_reduction,
            "shed_requests": self.shed_requests,
            "shed_wasted_s": self.shed_wasted_s,
        }
        for tier in sorted(self.tier_tokens):
            m[f"tier.{tier}.requests"] = self.tier_requests.get(tier, 0)
            m[f"tier.{tier}.tokens"] = self.tier_tokens[tier]
            m[f"tier.{tier}.tok_per_s"] = self.tier_throughput(tier)
        return m

    def summary(self) -> str:
        m = self.metrics()
        lines = [f"requests={m['requests']} tokens={m['tokens']} "
                 f"prefill={m['prefill_s']:.2f}s "
                 f"decode={m['decode_s']:.2f}s "
                 f"({m['decode_steps']} steps, {m['steps_per_s']:.1f} "
                 f"steps/s; compile {m['compile_s']:.2f}s separate)"]
        for tier in sorted(self.tier_tokens):
            lines.append(
                f"tier[{tier}]: {m[f'tier.{tier}.requests']} reqs, "
                f"{m[f'tier.{tier}.tokens']} tok, "
                f"{m[f'tier.{tier}.tok_per_s']:.1f} tok/s")
        lines.append(
            f"link bytes: {m['link_bytes'] / 1e6:.2f} MB vs host-only "
            f"{m['host_link_bytes'] / 1e6:.2f} MB "
            f"({m['link_reduction']:.0%} never crossed the link)")
        if m["kv_dense_bytes"] > 0:
            lines.append(
                f"KV bytes touched: {m['kv_bytes'] / 1e6:.2f} MB vs "
                f"dense {m['kv_dense_bytes'] / 1e6:.2f} MB "
                f"({m['kv_reduction']:.0%} fewer KV reads)")
        if self.latency.records:
            lines.append(self.latency.summary())
        if m["shed_requests"]:
            lines.append(f"shed: {m['shed_requests']} requests "
                         f"({m['shed_wasted_s']:.3f}s serving time wasted)")
        return "\n".join(lines)


@dataclass
class TickObservation:
    """What one ``ServeEngine.step()`` actually did — the per-tick signal
    the cluster pull scheduler (``core.scheduler.ClusterAdmission``) and the
    cluster wall-clock/energy accounting consume.

    ``busy_s`` is serving wall time only; ``compile_s`` is the lazy-XLA
    share of the tick (first call at a new shape), reported separately so
    callers timing the whole tick can subtract it — compile happens once
    per process, not once per replica drive, and must not pollute the
    cluster's parallel wall-clock model or the energy integral.
    """
    busy_s: float = 0.0          # serving wall time this tick
    compile_s: float = 0.0       # lazy jit/eager-shape compile time
    tokens: int = 0              # tokens emitted this tick
    steps: int = 0               # inner decode steps executed
    per_step_items: List[int] = field(default_factory=list)
    admitted_rids: List[int] = field(default_factory=list)
    first_token_rids: List[int] = field(default_factory=list)


@dataclass
class _Request:
    rid: int
    prompt: List[int]
    max_new: int
    priority: int = 0
    deadline_s: Optional[float] = None   # absolute TTFT deadline (engine clock)


@dataclass
class _Slot:
    index: int
    active: bool = False
    rid: int = -1
    tier: str = "host"
    pos: int = 0                 # next cache position to write
    cur_token: int = 0           # input token of the next decode step
    max_new: int = 0
    out: List[int] = field(default_factory=list)
    prefill_s: float = 0.0
    decode_s: float = 0.0
    reserved_pages: int = 0      # paged layout: admission-time reservation
    prefilling: bool = False     # chunked prefill still in flight
    prefill_done_tokens: int = 0  # prompt tokens already spliced

    @property
    def decoding(self) -> bool:
        return self.active and not self.prefilling


class AdmissionController:
    """Scheduler-driven admission: which tier pulls the next requests.

    The paper's pull protocol decides, per ack, whether the host or a CSD
    gets the next batch; here each admitted request is tagged with the tier
    whose pull it rode in on (the tag drives the ledger/throughput split).
    ``rebalance_shares`` periodically refits the host:CSD batch ratio from
    observed per-tier service times — the batch-ratio rule applied online.
    In-process serving runs both tiers in one jitted batch, so observed
    per-token times are equal and the configured ratio is kept; the refit
    engages when genuinely different per-tier timings are fed to
    ``observe`` (separate devices / real CSD workers).
    """

    def __init__(self, num_slots: int, host_rate: float = 20.0,
                 csd_rate: float = 1.0, n_csds: int = 1, batch_size: int = 1,
                 poll_interval: float = 0.0, rebalance_every: int = 16):
        self.num_slots = max(num_slots, 2)
        nodes = make_cluster(host_rate, csd_rate, max(n_csds, 1),
                             host_overhead=0.0, csd_overhead=0.0)
        ratio = optimal_batch_ratio(host_rate, csd_rate)
        self.sched = PullScheduler(nodes, batch_size, ratio,
                                   poll_interval=poll_interval)
        self.state: Optional[SchedulerState] = None
        self._pending: Deque[str] = deque()
        self.shares = {"host": max(self.num_slots - 1, 1), "csd": 1}
        self._busy = {"host": 0.0, "csd": 0.0}
        self._tok = {"host": 0, "csd": 0}
        self._since_rebalance = 0
        self.rebalance_every = rebalance_every

    def tiers_for(self, n: int, queued: int) -> List[str]:
        """Tier tags for the next ``n`` admissions, in scheduler pull order."""
        out: List[str] = []
        while len(out) < n:
            if self._pending:
                out.append(self._pending.popleft())
                continue
            if self.state is None or self.state.done:
                self.state = self.sched.start(max(queued, n, 1))
            a = self.sched.tick(self.state)
            if a is None:                      # stream outlived this window
                self.state = None
                continue
            tier = "host" if a.node.is_host else "csd"
            self._pending.extend([tier] * a.n_items)
        return out

    def observe(self, tier: str, busy_s: float, tokens: int) -> None:
        """Feed measured service back; refit the batch ratio periodically.

        Negative / non-finite intervals are dropped whole: even with
        monotonic timers a caller bug (or a restored checkpoint replaying
        stale observations) must not poison the EWMA-style busy windows —
        one negative sample can flip a refit's host:CSD ratio.
        """
        if busy_s < 0.0 or not math.isfinite(busy_s):
            return
        self._busy[tier] += busy_s
        self._tok[tier] += tokens
        self._since_rebalance += 1
        if self._since_rebalance < self.rebalance_every:
            return
        if min(self._tok.values()) == 0:
            return
        self._since_rebalance = 0
        step_times = {t: self._busy[t] / self._tok[t] for t in self._tok}
        tput = {t: self._tok[t] / max(self._busy[t], 1e-9) for t in self._tok}
        # fresh window per rebalance so the refit tracks *recent* service
        # times instead of a lifetime average
        self._busy = {t: 0.0 for t in self._busy}
        self._tok = {t: 0 for t in self._tok}
        if max(step_times.values()) <= 1.10 * min(step_times.values()):
            return       # no observable tier difference: keep configured ratio
        self.shares = rebalance_shares(step_times, self.shares,
                                       self.num_slots)
        # the paper's rule, online: ratio = measured host/CSD throughput
        self.sched.batch_ratio = max(tput["host"] / max(tput["csd"], 1e-9),
                                     1e-3)


class ServeEngine:
    """Continuous-batching greedy-decode engine over a fixed slot pool."""

    def __init__(self, cfg: ModelConfig, params, recipe=None,
                 max_len: int = 256, eos_id: Optional[int] = None,
                 num_slots: int = 8, bucket_quantum: int = 8,
                 shards: int = 16,
                 admission: Optional[AdmissionController] = None,
                 kv_layout: str = "paged", page_size: int = 16,
                 num_pages: Optional[int] = None, k_block: int = 8,
                 chunk_prefill: Optional[int] = None, prewarm: bool = False,
                 jit_donor: Optional["ServeEngine"] = None,
                 admission_order: str = "fifo", chunk_budget: int = 1,
                 shed_expired: bool = True, telemetry=None):
        if kv_layout not in ("paged", "strip"):
            raise ValueError(f"kv_layout must be 'paged' or 'strip', "
                             f"got {kv_layout!r}")
        if admission_order not in ("fifo", "edf"):
            raise ValueError(f"admission_order must be 'fifo' or 'edf', "
                             f"got {admission_order!r}")
        self.cfg = cfg
        # device: the one device the params sit on.  The caches and decode
        # state go there too, so every jitted call runs there, because its
        # committed arguments do.  Params spread over several devices (a
        # sharded recipe) leave it None: JAX's default device.
        on = {d for x in jax.tree.leaves(params) for d in x.devices()}
        self.device = on.pop() if len(on) == 1 else None
        self.params = params
        self.recipe = recipe if recipe is not None else M.LOCAL
        self.max_len = max_len
        self.eos_id = eos_id
        self.num_slots = num_slots
        self.bucket_quantum = max(bucket_quantum, 1)
        self.shards = shards
        self.admission = admission if admission is not None else \
            AdmissionController(num_slots)
        # k_block: decode steps per engine tick that run device-resident in
        # ONE jitted dispatch (lax.while_loop with on-device sampling and
        # termination masks).  k_block=1 is the per-step host reference loop
        # every fused configuration is property-tested against.
        self.k_block = max(int(k_block), 1)
        if jit_donor is not None:
            # Cluster replicas share one set of jitted callables: the
            # closures only capture static wiring (cfg/recipe/k_block/
            # eos/max_len) and every mutable piece is an argument, so N
            # drives cost one XLA compile instead of N — but only if the
            # wiring is byte-identical.
            same = (jit_donor.cfg == cfg and jit_donor.recipe is self.recipe
                    and jit_donor.k_block == self.k_block
                    and jit_donor.eos_id == eos_id
                    and jit_donor.max_len == max_len)
            if not same:
                raise ValueError(
                    "jit_donor wiring (cfg/recipe/k_block/eos_id/max_len) "
                    "differs from this engine; replicas must be identical")
            self._decode = jit_donor._decode
            self._prefill = jit_donor._prefill
            self._decode_block = jit_donor._decode_block
            self._prefill_chunk = jit_donor._prefill_chunk
            self._splice_pages = jit_donor._splice_pages
        else:
            self._decode = jax.jit(
                lambda p, c, t, pos: M.decode_fn(p, c, t, pos, cfg,
                                                 self.recipe))
            self._prefill = jax.jit(
                lambda p, b: M.prefill_fn(p, b, cfg, self.recipe))
            # Donate the cache pools (and the per-slot decode state) to the
            # fused block so strips/pages update in place instead of being
            # copied every call; CPU has no donation support, so skip the
            # warning noise there.
            donate = (1, 2, 3, 4, 5) if jax.default_backend() != "cpu" else ()
            self._decode_block = jax.jit(
                lambda p, c, t, pos, alive, rem: M.decode_block_fn(
                    p, c, t, pos, alive, rem, cfg, self.recipe,
                    k_steps=self.k_block, eos_id=eos_id, max_len=max_len),
                donate_argnums=donate)
            self._prefill_chunk = jax.jit(
                lambda p, c, t, qpos, last: M.prefill_chunk_fn(
                    p, c, t, qpos, last, cfg, self.recipe),
                donate_argnums=(1,) if donate else ())
            self._splice_pages = jax.jit(
                _splice_paged_group, donate_argnums=(0, 1) if donate else ())
        # KV layout: "paged" (default) keeps full-attention KV in fixed-size
        # pages handed out by a free-list allocator — memory and decode
        # reads track live tokens; "strip" is the dense per-slot reference
        # layout (one max_len strip per slot).
        self.kv_layout = kv_layout if self._has_paged_layers() else "strip"
        self.page_size = max(page_size, 1)
        self._maxp = pages_for(max_len, self.page_size)
        # chunk_prefill: split prompts longer than this into chunk-sized
        # pieces spliced into the paged pool one chunk per engine tick, so a
        # long admission never stalls in-flight decodes for more than one
        # chunk's worth of work.  Incremental splice needs the paged layout
        # and a pure full-attention stack (window rings and recurrent state
        # would have to carry chunk-crossing state).
        self.chunk_prefill: Optional[int] = None
        if chunk_prefill and self.kv_layout == "paged" and \
                all(k in ("attn", "moe") for k in cfg.layer_pattern):
            self.chunk_prefill = max(int(chunk_prefill), 1)
        paged = self.kv_layout == "paged"
        if paged:
            if num_pages is None:
                num_pages = num_slots * self._maxp        # dense worst case
            self.pager: Optional[PageAllocator] = PageAllocator(
                num_pages, self.page_size)
            self.page_table = np.full((num_slots, self._maxp), -1, np.int32)
        else:
            self.pager = None
            self.page_table = None
        # built on the drive's device (not staged through the default one),
        # then committed to it
        with jax.default_device(self.device):
            self.caches = self._put(M.init_caches(
                cfg, num_slots, max_len, per_slot=True, paged=paged,
                page_size=self.page_size, num_pages=num_pages))
        # per-slot decode state for the fused block, and the device page
        # table: persistent device arrays round-tripped through the block,
        # uploaded whole from the host's view only when admission, page
        # growth or a finish changes them (_upload_slot_state /
        # _upload_pages) — never per step
        self.slots = [_Slot(index=i) for i in range(num_slots)]
        self._upload_slot_state()
        self._pages_dev = None
        if paged:
            self._upload_pages()
        self.queue: Deque[_Request] = deque()
        self.stats = ServeStats()
        self.ledger = self.stats.ledger          # chosen-plan link bytes
        self.baseline = self.stats.baseline      # everything-to-host baseline
        self._next_rid = 0
        self._finished: List[GenResult] = []
        # SLO-aware admission: "edf" stable-sorts the queue by absolute
        # deadline (earliest first; no-deadline requests last, FIFO within
        # each (deadline, priority) class); chunk_budget is the number of
        # prefill chunks one tick may run — >1 accelerates admission at the
        # cost of decode TTFT/TPOT in the same tick; shed_expired drops
        # requests whose deadline already passed (queued ones for free,
        # mid-prefill ones counting their spent serving time as waste)
        self.admission_order = admission_order
        self.chunk_budget = max(int(chunk_budget), 1)
        self.shed_expired = shed_expired
        # fault injection (page_pool_clamp): only this fraction of the KV
        # page pool is admissible — NEW admissions backpressure against the
        # clamped capacity, while in-flight requests keep their full
        # worst-case reservation (a clamp degrades, it never fails a
        # flying batch).  1.0 = unclamped; the cluster tier sets it per
        # tick from the active fault schedule.
        self.pool_clamp_frac = 1.0
        # virtual serving clock: advances by measured serving time (compile
        # excluded) and fast-forwards across idle via advance_clock() — all
        # LatencyRecord timestamps live on it
        self.clock = 0.0
        self.records: Dict[int, LatencyRecord] = {}
        # telemetry: events stamp this engine's virtual clock on
        # ``tele_track``; the cluster re-points the track per drive and
        # turns ``tele_requests`` off (drive-local rids would collide with
        # cluster-global ones — the coordinator owns request spans there)
        self.tele = telemetry if telemetry is not None else NULL_HUB
        self.tele_track = "engine"
        self.tele_requests = True
        # lazy-compile attribution: the first call at a new (site, shape)
        # key is XLA compile, not serving — its wall time goes to
        # stats.compile_s (and the tick observation) instead of
        # prefill_s/decode_s.  prewarm() registers its keys here so a
        # pre-warmed engine's first real calls count as serving, and
        # replicas SHARE their donor's live set (jit executables are
        # cached per shared callable and eager ones process-wide, so a
        # shape any replica has run is warm for all of them).
        # A replica on another device compiles its own executables, so it
        # keeps its own set.
        self._warm_keys: set = jit_donor._warm_keys \
            if jit_donor is not None and jit_donor.device == self.device \
            else set()
        self._tick_compile_s = 0.0
        self.last_tick = TickObservation()
        if prewarm:
            self.prewarm()

    # -- paged KV bookkeeping ------------------------------------------------

    def _has_paged_layers(self) -> bool:
        """Paged pools exist only for full-attention GQA layers; a model with
        none (pure window/recurrent/MLA stacks) serves on the strip layout."""
        return any(k in ("attn", "moe") for k in self.cfg.layer_pattern)

    def _put(self, x):
        """Place a host value (or pytree) on this drive's device."""
        return jax.device_put(x, self.device)

    def _upload_pages(self) -> None:
        """Upload the device page table from the host's and point every
        group's ``pages`` cache leaf at it.  Mid-prefill slots keep -1 rows
        there, so decode writes route to the scratch page until their last
        chunk is spliced.  A fixed-shape upload: it never compiles."""
        table = self.page_table.copy()
        table[[s.index for s in self.slots if s.prefilling]] = -1
        self._pages_dev = self._put(table)
        for g, cache in self.caches.items():
            if isinstance(cache, dict) and "pages" in cache:
                ng = cache["pages"].shape[0]
                self.caches[g] = dict(cache, pages=jnp.broadcast_to(
                    self._pages_dev[None], (ng,) + self._pages_dev.shape))

    def _upload_slot_state(self) -> None:
        """Upload the fused block's per-slot decode state from the host's
        slots (after a prefill, a finish or a cancel).  Between blocks the
        host's replay matches the device's state for every live slot;
        dead slots only need ``alive`` false."""
        self._tok_dev = self._put(np.asarray(
            [s.cur_token for s in self.slots], np.int32))
        self._pos_dev = self._put(np.asarray(
            [s.pos for s in self.slots], np.int32))
        self._alive_dev = self._put(np.asarray(
            [s.decoding for s in self.slots], bool))
        self._rem_dev = self._put(np.asarray(
            [max(s.max_new - len(s.out), 0) for s in self.slots], np.int32))

    def _reservation(self, prompt_len: int, max_new: int) -> int:
        """Pages a request can ever need: prompt + generated tokens, capped
        at max_len.  Reserving (not allocating) this at admission makes
        mid-decode allocation infallible — the pool backpressures at
        admission instead of failing a flying batch."""
        return pages_for(min(prompt_len + max_new, self.max_len),
                         self.page_size)

    def _reservable_pages(self) -> int:
        """Free pages not spoken for by active slots' unallocated tail.

        Under a ``pool_clamp_frac`` fault only that fraction of the pool
        is admissible: the clamp shrinks what NEW admissions may reserve
        (possibly below what is already live — then nothing is admissible
        until the clamp lifts or slots free), but never touches in-flight
        reservations, so mid-decode allocation stays infallible."""
        outstanding = sum(
            s.reserved_pages - int((self.page_table[s.index] >= 0).sum())
            for s in self.slots if s.active)
        free = self.pager.num_free
        if self.pool_clamp_frac < 1.0:
            cap = int(self.pager.num_pages * self.pool_clamp_frac)
            free = min(free, cap - self.pager.num_in_use)
        return free - outstanding

    def _kv_bytes_per_token(self) -> int:
        """K+V bytes one token row costs across all paged-eligible (full
        GQA) layers — the single source for kv_stats and the step ledger."""
        n_kv_layers = sum(k in ("attn", "moe") for k in self.cfg.layer_pattern)
        return 2 * self.cfg.num_kv_heads * self.cfg.resolved_head_dim \
            * jnp.dtype(self.cfg.dtype).itemsize * n_kv_layers

    def kv_stats(self) -> Dict[str, float]:
        """Live/peak KV footprint vs the dense per-slot baseline (bytes)."""
        per_token = self._kv_bytes_per_token()
        dense_tokens = self.num_slots * self.max_len
        if self.kv_layout == "paged":
            live = self.pager.num_in_use * self.page_size
            peak = self.pager.peak_pages * self.page_size
            pool = self.pager.num_pages * self.page_size
        else:
            live = peak = pool = dense_tokens
        return {"layout": self.kv_layout, "page_size": self.page_size,
                "live_kv_bytes": live * per_token,
                "peak_kv_bytes": peak * per_token,
                "pool_kv_bytes": pool * per_token,
                "dense_kv_bytes": dense_tokens * per_token}

    # -- compile attribution -------------------------------------------------

    def _serving_time(self, key, dt: float) -> float:
        """Split a measured call between serving and lazy compile.

        The first call at a new (site, shape) key triggers an XLA compile
        that dwarfs the actual run (seconds vs milliseconds), so the whole
        first-call wall time is booked as ``compile_s`` and the call
        contributes zero serving time — undercounting one warm run per
        shape, which is noise next to attributing a compile to serving.
        Returns the serving time to account (``dt`` once the key is warm).
        """
        if key in self._warm_keys:
            return dt
        self._warm_keys.add(key)
        self.stats.compile_s += dt
        self._tick_compile_s += dt
        return 0.0

    # -- jit pre-warm --------------------------------------------------------

    def prewarm(self) -> float:
        """Compile every jitted entry point this engine can hit before the
        first request: the decode block (or the K=1 step), every prefill
        bucket shape a one-shot prompt can take — up to ``max_len``, or up
        to ``chunk_prefill`` where longer prompts are chunked — with its
        paged splice (the batch dimension is fixed at ``num_slots``, so
        each bucket length is exactly one compile), and the chunk-prefill
        shape.  First-request latency and ``decode_s`` then
        measure serving, not compilation; the compile time is reported
        separately as ``ServeStats.compile_s``.  Returns total compile_s.
        """
        t0 = time.perf_counter()
        if self.k_block > 1:
            # all slots start dead, so the while_loop compiles fully but
            # executes zero steps — caches stay untouched
            self._warm_keys.add(("decode_block",))
            out = self._decode_block(self.params, self.caches, self._tok_dev,
                                     self._pos_dev, self._alive_dev,
                                     self._rem_dev)
            jax.block_until_ready(out)
            (_, _, self._tok_dev, self._pos_dev, self._alive_dev,
             self._rem_dev, self.caches) = out
        else:
            # an all-inactive step: paged writes land in the scratch page;
            # strip writes stamp position 0, which every admission splice
            # resets before it is ever read
            self._warm_keys.add(("decode",))
            nxt, caches = self._decode(
                self.params, self.caches,
                self._put(np.zeros((self.num_slots, 1), np.int32)),
                self._put(np.zeros((self.num_slots,), np.int32)))
            jax.block_until_ready(nxt)
            self.caches = caches
        longest = min(self.chunk_prefill or self.max_len, self.max_len - 1)
        buckets = sorted({self._bucket_len(n) for n in range(1, longest + 1)})
        if len(buckets) <= self.max_len // self.bucket_quantum + 2:
            # bounded bucket set (padding-safe archs); exact-length
            # bucketing (recurrent stacks) would mean max_len compiles —
            # those engines warm lazily per length instead
            for padded in buckets:
                batch = {"tokens": self._put(np.zeros(
                             (self.num_slots, padded), np.int32)),
                         "lengths": self._put(np.ones(
                             (self.num_slots,), np.int32))}
                self._warm_keys.add(("prefill", padded))
                _, pre = self._prefill(self.params, batch)
                if self.kv_layout == "paged":
                    # no real rows: every write lands in the scratch page
                    self._warm_keys.add(("splice", padded))
                    self._splice(pre, [], [], padded)
                jax.block_until_ready(pre)
        if self.chunk_prefill is not None:
            # an all-pad chunk against an empty page row: every write routes
            # to the scratch page.  The pool view is donated, so keep the
            # returned kp/vp leaves (only scratch rows changed).
            self._warm_keys.add(("chunk",))
            view = self._chunk_view(np.full((self._maxp,), -1, np.int32))
            tokens = self._put(np.zeros((1, self.chunk_prefill), np.int32))
            qpos = self._put(np.full((1, self.chunk_prefill), -1, np.int32))
            nxt, new_view = self._prefill_chunk(
                self.params, view, tokens, qpos,
                self._put(np.zeros((1,), np.int32)))
            jax.block_until_ready(nxt)
            for g, cache in new_view.items():
                self.caches[g] = dict(self.caches[g], kp=cache["kp"],
                                      vp=cache["vp"])
        dt = time.perf_counter() - t0
        self.stats.compile_s += dt
        return dt

    # -- request intake ------------------------------------------------------

    def validate_request(self, prompt: Sequence[int],
                         max_new: int = 32) -> None:
        """Raise ValueError if this engine can never serve the request —
        shared by ``submit`` and the cluster dispatcher (which must reject
        a bad request at enqueue time, not mid-dispatch)."""
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) >= self.max_len:
            raise ValueError(f"prompt ({len(prompt)}) must fit below "
                             f"max_len ({self.max_len})")
        if self.kv_layout == "paged" and \
                self._reservation(len(prompt), max_new) > self.pager.num_pages:
            raise ValueError(
                f"request needs {self._reservation(len(prompt), max_new)} KV "
                f"pages but the pool only has {self.pager.num_pages}")

    def submit(self, prompt: Sequence[int], max_new: int = 32,
               priority: int = 0,
               deadline_s: Optional[float] = None) -> int:
        """Enqueue a request; ``deadline_s`` is an ABSOLUTE first-token
        deadline on the engine's serving clock (None = best-effort)."""
        prompt = list(prompt)
        self.validate_request(prompt, max_new)
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(_Request(rid, prompt, max_new, priority,
                                   deadline_s))
        self.records[rid] = LatencyRecord(rid=rid, priority=priority,
                                          deadline_s=deadline_s,
                                          submit_t=self.clock)
        if self.tele.enabled and self.tele_requests:
            self.tele.open_request(rid, self.clock, priority=priority,
                                   prompt_len=len(prompt), max_new=max_new)
        return rid

    def cancel(self, rid: int) -> Optional[float]:
        """Abort a request WITHOUT producing a result — the cluster's
        hedged dispatch uses this to retire the losing copy once the other
        drive finished first.  Returns the serving seconds already burned
        on the copy (0.0 if it was still queued), or None if the rid is
        unknown (already finished — the caller lost the race).  The
        latency record is dropped too: the surviving copy owns it."""
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                del self.queue[i]
                self.records.pop(rid, None)
                if self.tele.enabled and self.tele_requests:
                    self.tele.close_request(rid, self.clock, "canceled",
                                            wasted_s=0.0)
                return 0.0
        for s in self.slots:
            if s.active and s.rid == rid:
                wasted = s.prefill_s + s.decode_s
                was_decoding = s.decoding
                self.records.pop(rid, None)
                if self.tele.enabled and self.tele_requests:
                    self.tele.close_request(rid, self.clock, "canceled",
                                            wasted_s=wasted)
                self._release_slot(s)
                if was_decoding and self.k_block > 1:
                    # the fused block keeps liveness on device; a released
                    # slot must be marked dead there or the next block
                    # would keep decoding into freed (re-allocatable) pages
                    self._upload_slot_state()
                return wasted
        self.records.pop(rid, None)
        return None

    # -- serving clock + shedding --------------------------------------------

    def advance_clock(self, to_t: float) -> None:
        """Fast-forward the serving clock across an idle gap (open-loop
        replay: wall time passes even when no work is in flight).  The
        clock never moves backwards."""
        self.clock = max(self.clock, to_t)

    def _shed_expired(self) -> None:
        """Drop requests whose deadline already passed — they cannot make
        their SLO even if served right now, so serving them only burns
        capacity others need.  Queued requests shed for free; a mid-prefill
        slot sheds with its spent serving time booked as waste."""
        if not self.shed_expired:
            return
        if any(r.deadline_s is not None and r.deadline_s < self.clock
               for r in self.queue):
            keep: Deque[_Request] = deque()
            for req in self.queue:
                if req.deadline_s is not None and req.deadline_s < self.clock:
                    self._shed(req.rid, req.priority, wasted_s=0.0)
                else:
                    keep.append(req)
            self.queue = keep
        for s in self.slots:
            if not (s.active and s.prefilling):
                continue
            rec = self.records.get(s.rid)
            if rec is not None and rec.deadline_s is not None \
                    and rec.deadline_s < self.clock:
                self._shed(s.rid, rec.priority, wasted_s=s.prefill_s,
                           prefill_s=s.prefill_s)
                self._release_slot(s)

    def _shed(self, rid: int, priority: int, wasted_s: float,
              prefill_s: float = 0.0) -> None:
        """Record one shed request: a 'shed' GenResult for the caller, its
        latency record closed out, and the waste tallied."""
        self.stats.shed_requests += 1
        self.stats.shed_wasted_s += wasted_s
        rec = self.records.pop(rid, None)
        res = GenResult(tokens=[], prefill_s=prefill_s, decode_s=0.0,
                        rid=rid, status="shed", priority=priority)
        if rec is not None:
            rec.finish_t = self.clock
            rec.status = "shed"
            self.stats.latency.add(rec)
            res.e2e_s = rec.e2e_s
            res.queue_wait_s = rec.queue_wait_s
        if self.tele.enabled:
            self.tele.counter("engine.shed")
            if self.tele_requests:
                self.tele.close_request(rid, self.clock, "shed",
                                        wasted_s=wasted_s)
        self._finished.append(res)

    # -- bucketing -----------------------------------------------------------

    def _padding_safe(self, padded_len: int) -> bool:
        """Padded prefill is exact iff no recurrent state integrates pad
        tokens and no sliding-window ring evicts real prompt positions."""
        kinds = set(self.cfg.layer_pattern)
        if kinds & {"hybrid", "mlstm", "slstm"}:
            return False
        if "local" in kinds and self.cfg.attn.window is not None \
                and padded_len > self.cfg.attn.window:
            return False
        return True

    def _bucket_len(self, n: int) -> int:
        q = self.bucket_quantum
        padded = min(-(-n // q) * q, self.max_len - 1)
        return padded if padded > n and self._padding_safe(padded) else n

    # -- engine steps --------------------------------------------------------

    @property
    def num_active(self) -> int:
        return sum(s.active for s in self.slots)

    @property
    def pending(self) -> int:
        return len(self.queue)

    @property
    def bytes_never_crossed(self) -> float:
        """Live counter: link bytes kept resident so far (paper Fig. 5)."""
        return self.stats.bytes_never_crossed

    def step(self) -> List[GenResult]:
        """One engine tick: admit into free slots, advance one chunk of any
        in-flight chunked prefill, then run one decode block (``k_block``
        fused steps on device; ``k_block=1`` is the per-step host reference
        loop).  Returns the requests that finished during this tick;
        ``last_tick`` describes the tick for the cluster scheduler."""
        n_before = len(self._finished)
        self.last_tick = obs = TickObservation()
        self._tick_compile_s = 0.0
        tok0, steps0 = self.stats.tokens, self.stats.decode_steps
        busy0 = self.stats.prefill_s + self.stats.decode_s
        self._shed_expired()
        self._admit()
        if self.chunk_prefill is not None:
            self._chunk_prefill_tick()
        if any(s.decoding for s in self.slots):
            if self.k_block > 1:
                self._decode_block_step()
            else:
                self._decode_step()
        obs.compile_s = self._tick_compile_s
        obs.tokens = self.stats.tokens - tok0
        obs.steps = self.stats.decode_steps - steps0
        obs.busy_s = self.stats.prefill_s + self.stats.decode_s - busy0
        if not obs.per_step_items and obs.tokens:
            # prefill-only / K=1 ticks: one aggregate sample
            obs.per_step_items = [obs.tokens]
        if self.tele.enabled:
            self.tele.counter(f"{self.tele_track}.ticks")
            self.tele.counter(f"{self.tele_track}.tokens", obs.tokens)
            self.tele.gauge(f"{self.tele_track}.clock_s", self.clock)
            self.tele.counter_sample(self.tele_track, "queue_depth",
                                     self.clock, len(self.queue))
            if obs.busy_s > 0:
                self.tele.observe("tick_busy_s", obs.busy_s)
        return self._finished[n_before:]

    def run_until_complete(self) -> List[GenResult]:
        while self.queue or self.num_active:
            self.step()
        out, self._finished = self._finished, []
        return sorted(out, key=lambda r: r.rid)

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new: int = 32) -> List[GenResult]:
        """Greedy generation for a batch of (possibly mixed-length) prompts.

        Drains the whole queue; results of requests queued earlier via
        ``submit()`` are kept for their caller, not discarded.
        """
        rids = [self.submit(p, max_new) for p in prompts]
        return collect_results(self, rids)

    # -- admission + prefill -------------------------------------------------

    def _admit(self) -> None:
        free = [s for s in self.slots if not s.active]
        n = min(len(free), len(self.queue))
        if n == 0:
            return
        if self.admission_order == "edf" and len(self.queue) > 1:
            # earliest deadline first; no-deadline requests last.  The sort
            # is stable and ties break on rid, so FIFO order is preserved
            # within a (deadline, priority) class.
            self.queue = deque(sorted(
                self.queue,
                key=lambda r: (r.deadline_s if r.deadline_s is not None
                               else math.inf, r.priority, r.rid)))
        if self.kv_layout == "paged":
            # Backpressure at the pool: admit (FIFO) only while the pool can
            # still reserve each request's worst case — a request that does
            # not fit waits queued, it never fails mid-flight.
            budget = self._reservable_pages()
            fits = 0
            for req in list(self.queue)[:n]:
                need = self._reservation(len(req.prompt), req.max_new)
                if need > budget:
                    break
                budget -= need
                fits += 1
            n = fits
            if n == 0:
                return
        tiers = self.admission.tiers_for(n, queued=len(self.queue))
        admitted: List[_Slot] = []
        for slot, tier in zip(free, tiers):
            req = self.queue.popleft()
            slot.active = True
            slot.rid = req.rid
            slot.tier = tier
            slot.pos = len(req.prompt)
            slot.max_new = req.max_new
            slot.out = []
            slot.prefill_s = 0.0
            slot.decode_s = 0.0
            slot.prefilling = self.chunk_prefill is not None and \
                len(req.prompt) > self.chunk_prefill
            slot.prefill_done_tokens = 0
            slot._prompt = req.prompt          # consumed by the bucket pass
            if self.kv_layout == "paged":
                slot.reserved_pages = self._reservation(len(req.prompt),
                                                        req.max_new)
                pages = self.pager.alloc(pages_for(len(req.prompt),
                                                   self.page_size))
                self.page_table[slot.index, :] = -1
                self.page_table[slot.index, : len(pages)] = pages
            admitted.append(slot)
            self.last_tick.admitted_rids.append(req.rid)
            rec = self.records.get(req.rid)
            if rec is not None:
                rec.admit_t = self.clock
            if self.tele.enabled and self.tele_requests:
                self.tele.request_point(req.rid, "admit", self.clock,
                                        tier=tier)
            self.stats.requests += 1
            self.stats.tier_requests[tier] = \
                self.stats.tier_requests.get(tier, 0) + 1
        oneshot = [s for s in admitted if not s.prefilling]
        if self.kv_layout == "paged" and oneshot:
            # mid-prefill slots keep their device row -1 (decode writes hit
            # the scratch page) until their last chunk is spliced
            self._upload_pages()

        buckets: Dict[int, List[_Slot]] = {}
        for slot in oneshot:
            buckets.setdefault(self._bucket_len(len(slot._prompt)),
                               []).append(slot)
        for padded, group in sorted(buckets.items()):
            self._prefill_bucket(group, padded)

    def _prefill_bucket(self, group: List[_Slot], padded: int) -> None:
        b = len(group)
        lengths = [len(s._prompt) for s in group]
        # fixed batch dimension: pad the bucket with dummy length-1 rows so
        # each bucket length compiles exactly once (pre-warmable) instead of
        # once per admission group size; rows are independent, so the pads
        # cost compute but never touch the real rows' math
        tokens = np.zeros((self.num_slots, padded), np.int32)
        lens = np.ones((self.num_slots,), np.int32)
        for i, s in enumerate(group):
            tokens[i, : lengths[i]] = s._prompt
            lens[i] = lengths[i]
        t0 = time.perf_counter()
        batch = {"tokens": self._put(tokens), "lengths": self._put(lens)}
        nxt, pre_caches = self._prefill(self.params, batch)
        nxt = np.asarray(nxt)
        t1 = time.perf_counter()
        # prefill jit is keyed by the bucket length, and so is the paged
        # splice (its index arrays are padded to the bucket's rows); the
        # strip splice runs eager executables keyed by the group size too.
        # Each compiles lazily on first sight, and that wall time is XLA,
        # not serving (see _serving_time)
        dt = self._serving_time(("prefill", padded), t1 - t0)
        self._splice(pre_caches, [s.index for s in group], lengths, padded)
        splice_key = ("splice", padded) if self.kv_layout == "paged" \
            else ("splice", b, padded)
        dt += self._serving_time(splice_key, time.perf_counter() - t1)
        self._account_prefill(sum(lengths))
        self.clock += dt               # first tokens are stamped post-prefill
        if self.tele.enabled:
            self.tele.phase(self.tele_track, "prefill", self.clock - dt, dt,
                            batch=b, padded=padded)
        for i, s in enumerate(group):
            s.prefill_s = dt
            s.cur_token = int(nxt[i])
            self.stats.prefill_s += dt / b
            del s._prompt
            # the prefill-sampled token is the first generated token
            self._push_token(s, s.cur_token)
        if self.k_block > 1:
            self._upload_slot_state()

    def _splice(self, pre_caches, slot_ids: List[int], lengths: List[int],
                padded: int) -> None:
        """Write a prefill bucket's dense caches into the slot pool.
        Paged groups scatter through one jitted, pool-donating program per
        bucket length; strip groups keep the dense per-slot splice."""
        index = None
        if self.kv_layout == "paged":
            # the pool's last page (index num_pages) is the scratch page
            index = self._put(_paged_splice_index(
                slot_ids, lengths, self.page_table, self.page_size,
                rows=self.num_slots * padded, scratch=self.pager.num_pages))
        out = {}
        for g, dst in self.caches.items():
            src = pre_caches[g]
            if isinstance(dst, dict) and "pages" in dst:
                kp, vp = self._splice_pages(dst["kp"], dst["vp"], src["k"],
                                            src["v"], *index)
                out[g] = dict(dst, kp=kp, vp=vp)
            elif slot_ids:
                out[g] = _splice_strip_group(dst, src, slot_ids, lengths)
            else:
                out[g] = dst
        jax.block_until_ready(out)
        self.caches = out

    def _chunk_prefill_tick(self) -> None:
        """Advance up to ``chunk_budget`` prefill chunks this tick.

        Long prompts no longer monopolize a tick: each tick splices a
        bounded number of fixed-size chunks into the paged pool and then
        still runs a decode block for everyone else, so the scheduler's
        ``observe()`` samples stay bounded by the budget + one block
        instead of one whole prompt.  ``chunk_budget=1`` (default) is the
        decode-protecting setting: in-flight TPOT/TTFT see at most one
        chunk of prefill interference per tick; larger budgets admit long
        prompts faster at the decode tail's expense.
        """
        for _ in range(self.chunk_budget):
            slot = next((s for s in self.slots if s.active and s.prefilling),
                        None)
            if slot is None:
                return
            self._advance_chunk(slot)

    def _advance_chunk(self, slot: _Slot) -> None:
        chunk = self.chunk_prefill
        prompt = slot._prompt
        c0 = slot.prefill_done_tokens
        real = min(chunk, len(prompt) - c0)
        tokens = np.zeros((1, chunk), np.int32)
        tokens[0, :real] = prompt[c0: c0 + real]
        qpos = np.full((1, chunk), -1, np.int32)
        qpos[0, :real] = np.arange(c0, c0 + real, dtype=np.int32)
        view = self._chunk_view(self.page_table[slot.index])
        t0 = time.perf_counter()
        nxt, new_view = self._prefill_chunk(
            self.params, view, self._put(tokens), self._put(qpos),
            self._put(np.asarray([real - 1], np.int32)))
        nxt = np.asarray(nxt)
        dt = self._serving_time(("chunk",), time.perf_counter() - t0)
        self.clock += dt
        if self.tele.enabled:
            self.tele.phase(self.tele_track, "prefill_chunk",
                            self.clock - dt, dt, rid=slot.rid, tokens=real)
        for g, cache in new_view.items():
            if isinstance(cache, dict) and "kp" in cache:
                self.caches[g] = dict(self.caches[g], kp=cache["kp"],
                                      vp=cache["vp"])
        slot.prefill_done_tokens = c0 + real
        slot.prefill_s += dt
        self.stats.prefill_s += dt
        self._account_prefill(real)
        if slot.prefill_done_tokens == len(prompt):
            slot.prefilling = False
            slot.cur_token = int(nxt[0])
            del slot._prompt
            self._upload_pages()
            self._push_token(slot, slot.cur_token)
            if self.k_block > 1:
                self._upload_slot_state()

    def _chunk_view(self, table_row: np.ndarray):
        """B=1 view of the paged caches for one slot: the shared kp/vp
        pools under the slot's own page-table row — the chunk splices into
        the pool without the other slots' batch dimension in the program."""
        row = self._put(table_row[None])              # (1, maxp)
        view = {}
        for g, cache in self.caches.items():
            ng = cache["pages"].shape[0]
            view[g] = dict(cache, pages=jnp.broadcast_to(
                row[None], (ng,) + row.shape))
        return view

    # -- decode --------------------------------------------------------------

    def _decode_step(self) -> None:
        """K=1 host reference loop: one decode step, one token readback per
        slot.  The fused block (``_decode_block_step``) must stay
        token-identical to this path."""
        tokens = np.zeros((self.num_slots, 1), np.int32)
        positions = np.zeros((self.num_slots,), np.int32)
        for s in self.slots:
            if s.decoding:
                tokens[s.index, 0] = s.cur_token
                positions[s.index] = s.pos
        if self.kv_layout == "paged":
            self._grow_pages(1)
        t0 = time.perf_counter()
        nxt, self.caches = self._decode(self.params, self.caches,
                                        self._put(tokens),
                                        self._put(positions))
        nxt = np.asarray(nxt)
        dt = self._serving_time(("decode",), time.perf_counter() - t0)
        self.stats.decode_s += dt
        self.stats.decode_steps += 1
        self.clock += dt
        if self.tele.enabled:
            self.tele.phase(self.tele_track, "decode", self.clock - dt, dt,
                            steps=1)

        active = [s for s in self.slots if s.decoding]
        self._observe_step(active, dt)
        for s in active:
            s.decode_s += dt
            s.pos += 1
            s.cur_token = int(nxt[s.index])
            self._push_token(s, s.cur_token)

    def _observe_step(self, live: List[_Slot], step_s: float) -> None:
        """Per-decode-step ledger + scheduler bookkeeping — the single
        accounting path shared by the K=1 loop and the fused block's
        replay, so stats/rebalance behavior cannot drift between them."""
        self._account_decode(len(live), int(max(s.pos for s in live)) + 1)
        tier_counts: Dict[str, int] = {}
        for s in live:
            tier_counts[s.tier] = tier_counts.get(s.tier, 0) + 1
        for tier, cnt in tier_counts.items():
            self.admission.observe(tier, step_s * cnt / len(live), cnt)

    def _decode_block_step(self) -> None:
        """Fused device-resident tick: up to ``k_block`` decode steps in one
        jitted dispatch.  The only per-block host↔device traffic is the
        (K, num_slots) token block coming back; sampling, positions and
        termination masks live on device, and the cache pools are donated so
        they update in place.  The host then *replays* the block's per-step
        bookkeeping (stats, ledger, scheduler observations, page frees)
        from the token block alone."""
        if self.kv_layout == "paged":
            # pre-reserve the whole block's pages so growth inside the scan
            # is a pure page-table lookup (reservation makes this infallible)
            self._grow_pages(self.k_block)
        t0 = time.perf_counter()
        out = self._decode_block(self.params, self.caches, self._tok_dev,
                                 self._pos_dev, self._alive_dev,
                                 self._rem_dev)
        block, n_steps, tok, pos, alive, rem, caches = out
        self.caches = caches
        self._tok_dev, self._pos_dev = tok, pos
        self._alive_dev, self._rem_dev = alive, rem
        block = np.asarray(block)                 # ONE readback per block
        n_steps = int(n_steps)
        dt = self._serving_time(("decode_block",), time.perf_counter() - t0)
        self.stats.decode_s += dt
        self.stats.decode_steps += n_steps

        active = [s for s in self.slots if s.decoding]
        # a slot emitted at step i iff its token row is >= 0 — the live
        # counts drive the proportional split of the block's wall time
        emitted = block[:n_steps, [s.index for s in active]] >= 0
        self.last_tick.per_step_items = emitted.sum(axis=1).tolist()
        per_step = split_block_service(dt, self.last_tick.per_step_items)
        clock_end = self.clock + dt
        for i in range(n_steps):
            live = [s for s in active if s.decoding]
            if not live:
                break
            # the clock advances per replayed step so first-token /
            # completion stamps land at the step's share of the block, not
            # all at the block boundary
            self.clock += per_step[i]
            self._observe_step(live, per_step[i])
            for s in live:
                t = int(block[i, s.index])
                assert t >= 0, "device/host liveness diverged"
                s.decode_s += per_step[i]
                s.pos += 1
                s.cur_token = t
                self._push_token(s, t)
        # per_step sums to dt; pin the block end exactly (fp drift, early
        # break when every slot finished mid-block)
        self.clock = max(self.clock, clock_end)
        if self.tele.enabled:
            self.tele.phase(self.tele_track, "decode_block", clock_end - dt,
                            dt, steps=n_steps)

    def _push_token(self, slot: _Slot, tok: int) -> None:
        """Record a generated token and finish/evict the slot if done."""
        if slot.max_new <= 0:
            self._finish(slot)
            return
        slot.out.append(tok)
        if len(slot.out) == 1:
            rec = self.records.get(slot.rid)
            if rec is not None and not math.isfinite(rec.first_token_t):
                rec.first_token_t = self.clock
            self.last_tick.first_token_rids.append(slot.rid)
            if self.tele.enabled and self.tele_requests:
                self.tele.request_point(slot.rid, "first_token", self.clock)
        self.stats.tokens += 1
        self.stats.tier_tokens[slot.tier] = \
            self.stats.tier_tokens.get(slot.tier, 0) + 1
        eos = self.eos_id is not None and tok == self.eos_id
        full = slot.pos >= self.max_len - 1
        if eos or full or len(slot.out) >= slot.max_new:
            self._finish(slot)

    def _grow_pages(self, steps: int = 1) -> None:
        """Allocate every page the next ``steps`` decode writes can touch —
        at most ``min(steps, tokens left)`` positions per slot, so a K-block
        never reserves past a slot's own max-new budget.  Admission reserved
        the worst case, so this never exhausts the pool
        (``_reservable_pages`` accounts for the unallocated tail)."""
        grew = False
        ps = self.page_size
        for s in self.slots:
            if not s.decoding:
                continue
            e = min(steps, max(s.max_new - len(s.out), 1))
            last = min(s.pos + e - 1, self.max_len - 1)
            for lp in range(s.pos // ps, last // ps + 1):
                if self.page_table[s.index, lp] < 0:
                    self.page_table[s.index, lp] = self.pager.alloc(1)[0]
                    grew = True
        if grew:
            self._upload_pages()

    def _finish(self, slot: _Slot) -> None:
        res = GenResult(tokens=slot.out, rid=slot.rid, tier=slot.tier,
                        prefill_s=slot.prefill_s, decode_s=slot.decode_s)
        rec = self.records.pop(slot.rid, None)
        if rec is not None:
            rec.finish_t = self.clock
            rec.n_tokens = len(slot.out)
            rec.status = "ok"
            self.stats.latency.add(rec)
            res.priority = rec.priority
            res.queue_wait_s = rec.queue_wait_s
            res.ttft_s = rec.ttft_s
            res.tpot_s = rec.tpot_s
            res.e2e_s = rec.e2e_s
        if self.tele.enabled and self.tele_requests:
            self.tele.close_request(slot.rid, self.clock, "ok",
                                    tokens=len(slot.out))
        self._finished.append(res)
        self._release_slot(slot)

    def _release_slot(self, slot: _Slot) -> None:
        """Return a slot (and its pages) to the pool — shared by normal
        completion and mid-prefill shedding."""
        slot.active = False
        slot.prefilling = False
        slot.out = []
        slot.rid = -1
        if hasattr(slot, "_prompt"):          # shed mid-prefill
            del slot._prompt
        if self.kv_layout == "paged":
            # eager release: the pages (and the reservation tail) return to
            # the pool in the same step EOS/max-len fired, so a queued
            # request can be admitted at the very next tick
            row = self.page_table[slot.index]
            live = [int(p) for p in row[row >= 0]]
            if live:
                self.pager.free(live)
            self.page_table[slot.index, :] = -1
            slot.reserved_pages = 0
            self._upload_pages()

    # -- transfer accounting -------------------------------------------------

    def _account_prefill(self, n_tokens: int) -> None:
        """Embedding lookups for the prompt tokens: host plan ships table
        shards, ISP plan ships indexes (the paper's protocol)."""
        c = choose_embedding_plan(n_tokens, self.cfg.vocab_size,
                                  self.cfg.d_model, tp=self.shards)
        chosen = c.isp_link_bytes if c.plan == "isp" else c.host_link_bytes
        self.ledger.add("link", chosen, "prefill")
        self.baseline.add("link", c.host_link_bytes, "prefill")

    def _account_decode(self, batch: int, seq: int) -> None:
        """One decode step: embedding lookup of the step tokens plus the
        per-layer decode attention over the resident KV span."""
        e = choose_embedding_plan(batch, self.cfg.vocab_size,
                                  self.cfg.d_model, tp=self.shards)
        d = choose_decode_plan(batch, self.cfg.num_heads,
                               self.cfg.resolved_head_dim, seq,
                               self.cfg.num_kv_heads, shards=self.shards)
        layers = self.cfg.num_layers
        chosen = (e.isp_link_bytes if e.plan == "isp" else e.host_link_bytes) \
            + layers * (d.isp_link_bytes if d.plan == "isp"
                        else d.host_link_bytes)
        base = e.host_link_bytes + layers * d.host_link_bytes
        self.ledger.add("link", chosen, "decode")
        self.baseline.add("link", base, "decode")
        self._account_kv_step()

    def _account_kv_step(self) -> None:
        """KV rows this decode step walks, chosen layout vs the dense
        baseline (the strip path reads every slot's full strip every step;
        the paged kernel reads only live pages)."""
        per_token = self._kv_bytes_per_token()
        if per_token == 0:
            return
        dense = self.num_slots * self.max_len * per_token
        if self.kv_layout == "paged":
            touched = self.pager.num_in_use * self.page_size * per_token
        else:
            touched = dense
        self.ledger.add("kv", touched, "decode KV rows")
        self.baseline.add("kv", dense, "decode KV rows")


def collect_results(engine, rids: List[int]) -> List[GenResult]:
    """Drain ``engine`` and return ``rids``'s results in submission order,
    re-appending other submitters' finished results for *their* caller —
    the generate() contract shared by ServeEngine and ClusterEngine."""
    mine = set(rids)
    by_rid = {}
    for r in engine.run_until_complete():
        if r.rid in mine:
            by_rid[r.rid] = r
        else:                             # someone else's submit(): keep it
            engine._finished.append(r)
    return [by_rid[r] for r in rids]


def _paged_splice_index(slot_ids: List[int], lengths: List[int],
                        page_table, page_size: int, rows: int, scratch: int):
    """Index arrays (src batch, src position, dst page, dst offset) that
    scatter a prefill bucket's real prompt rows into their pages.

    Only the first ``lengths[i]`` rows of each sequence are real — pad rows
    are never scattered, so the pool only ever holds live tokens.  The
    arrays are padded to ``rows`` entries (the bucket's whole batch), and
    the pad entries copy source row (0, 0) to the ``scratch`` page, which
    is never read back: each bucket length is one program shape.
    """
    sb = np.zeros(rows, np.int32)
    sp = np.zeros(rows, np.int32)
    dp = np.full(rows, scratch, np.int32)
    do = np.zeros(rows, np.int32)
    i = 0
    for b, (sid, n) in enumerate(zip(slot_ids, lengths)):
        p = np.arange(n)
        sb[i: i + n] = b
        sp[i: i + n] = p
        dp[i: i + n] = page_table[sid, p // page_size]
        do[i: i + n] = p % page_size
        i += n
    assert (dp >= 0).all(), "prefill splice into unallocated page"
    return sb, sp, dp, do


def _splice_paged_group(kp, vp, k, v, sb, sp, dp, do):
    """Scatter prefill rows ``k/v[:, sb, sp]`` (dense (ng, b, padded, ...))
    into pool rows ``kp/vp[:, dp, do]`` — jitted with the pools donated."""
    return (kp.at[:, dp, do].set(k[:, sb, sp].astype(kp.dtype)),
            vp.at[:, dp, do].set(v[:, sb, sp].astype(vp.dtype)))


def _splice_strip_group(pool, pre, slot_ids: List[int], lengths: List[int]):
    """Dense per-slot splice: ``pool`` leaves are (num_groups, num_slots,
    ...); ``pre`` leaves are (num_groups, bpad, ...) for the prefill batch
    (the bucket's ``b`` real sequences first, dummy pad rows after — see
    ``_prefill_bucket``'s fixed batch).  kpos rows become per-slot tracks:
    prefill positions >= the true prompt length (padding) are masked to -1,
    everything past the copied span stays -1.
    """
    b = len(slot_ids)
    slots = jnp.asarray(slot_ids)
    lens = jnp.asarray(lengths)

    def splice(path, dst, src):
        names = [str(p.key) for p in path if hasattr(p, "key")]
        name = names[-1] if names else ""
        if name == "kpos":
            # src (ng, n) shared track -> per-slot rows (ng, b, n)
            n = min(src.shape[1], dst.shape[2])
            row = jnp.broadcast_to(src[:, None, :n],
                                   (src.shape[0], b, n))
            row = jnp.where((row >= 0) & (row < lens[None, :, None]), row, -1)
            dst = dst.at[:, slots, :].set(-1)
            return dst.at[:, slots, :n].set(row)
        if name in ("k", "v", "ckv", "krope"):
            n = min(src.shape[2], dst.shape[2])
            return dst.at[:, slots, :n].set(src[:, :b, :n].astype(dst.dtype))
        # recurrent / stateful leaves: whole per-sequence rows
        return dst.at[:, slots].set(src[:, :b].astype(dst.dtype))

    return jax.tree_util.tree_map_with_path(splice, pool, pre)
