"""One run of one cell: set-up, an open-loop window on the wall clock, the
metrics, and the comparison with the plain reference that decides
``correct``.

The loop runs in one process and one thread.  Each request is submitted at
the first loop turn at or after the time it is due, then the engine takes
one ``step()``.  The loop sleeps to the next due time only when nothing is
in flight.  Every latency is measured on ``time.perf_counter`` from the
request's due time to the end of the tick in which the event showed.  After
the window closes no request arrives; the run serves on until every
request due in the window has finished, or the mix's ``drain_s`` is spent.

What happened in each tick is read from the engine's slots before and
after it (prompt rows spliced, tokens emitted, per request), which gives
the live context of every decode step for the FLOP and byte counts; the
configuration's architecture counts them (``bench/counts/<arch>.py``).
A traced run also keeps the engine's own spans and module executions
(``spans.load``), and ``Run.delta`` reads any counter of ``ServeStats``.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench.lib import flops as F
from bench.lib import peaks as P
from bench.lib import spec as S
from bench.lib import traffic as T

TRACE_AT = 0.3        # the traced slice starts this share into the window
TRACE_S = 4.0         # and lasts this long (whole ticks)


class NoChip(RuntimeError):
    pass


def check_devices(devices, chips: int) -> Dict[str, float]:
    """The cell's devices must be TPUs of a kind the peak table knows."""
    if not devices or devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform "
                     f"{devices[0].platform if devices else None!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(devices)}")
    return P.peaks(devices[0].device_kind)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclass(eq=False)
class Tracked:
    req: T.Request
    key: int = -1
    submit_t: float = math.nan
    admit_t: float = math.nan
    first_t: float = math.nan
    finish_t: float = math.nan
    tokens: Optional[List[int]] = None
    drive: int = 0


@dataclass
class Run:
    """What a run measured; per-layer metric readers take it."""
    spec: Dict
    chips: int
    seconds: float
    peak: Dict[str, float]
    reqs: List[Tracked]
    end_t: float = 0.0
    window_tokens: float = 0.0
    stats0: Dict[str, float] = field(default_factory=dict)
    stats1: Dict[str, float] = field(default_factory=dict)
    counts: Optional[object] = None     # the cell's ``Counts``
    # traced slice (``--trace 1``)
    trace: Optional[object] = None
    trace_window: Optional[tuple] = None
    engine_trace: Optional[object] = None   # ``spans.EngineTrace``
    traced_decode_flops: float = 0.0
    traced_prefill_flops: float = 0.0
    # per kernel the count module names: the least time its calls in the
    # slice could take, and how many calls there were
    traced_kernel_ideal_s: Dict[str, float] = field(default_factory=dict)
    traced_kernel_calls: Dict[str, int] = field(default_factory=dict)

    def delta(self, key: str) -> float:
        return self.stats1.get(key, 0.0) - self.stats0.get(key, 0.0)

    def ttft_s(self) -> List[float]:
        """Due to first token, for every request due in the window; one that
        never got a first token counts as late as the run's end."""
        return [(r.first_t if math.isfinite(r.first_t) else self.end_t)
                - r.req.due_s for r in self.reqs]

    def tpot_s(self) -> List[float]:
        return [(r.finish_t - r.first_t) / (len(r.tokens) - 1)
                for r in self.reqs
                if r.tokens is not None and len(r.tokens) > 1]

    def queue_wait_s(self) -> List[float]:
        return [(r.admit_t if math.isfinite(r.admit_t) else self.end_t)
                - r.req.due_s for r in self.reqs]

    def traced_s(self) -> float:
        lo, hi = self.trace_window
        return (hi - lo) / 1e9

    def device_busy_s(self) -> float:
        from bench.lib import trace as TR
        lo, hi = self.trace_window
        per = [TR.busy_ns(ev, lo, hi) for ev in self.trace.devices.values()]
        return sum(per) / max(len(per), 1) / 1e9

    def kernel_s(self, name: str) -> float:
        from bench.lib import trace as TR
        lo, hi = self.trace_window
        return sum(TR.kernel_ns(ev, name, lo, hi)
                   for ev in self.trace.devices.values()) / 1e9


def pct(values: List[float], q: float) -> float:
    """The q-th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, float), q))


# -- the system under test ---------------------------------------------------


class Engine:
    """The served path (``ServeEngine`` or ``ClusterEngine``) seen by the
    loop: submit, step, and what each drive's slots hold."""

    def __init__(self, spec: Dict, cfg, params, chips: int):
        from repro.train.cluster_loop import ClusterEngine
        from repro.train.serve_loop import ServeEngine
        kw = dict(spec["engine"], kv_layout="paged", eos_id=None,
                  prewarm=True)
        if chips == 1:
            self.sys = ServeEngine(cfg, params, **kw)
            self.engines = [self.sys]
        else:
            self.sys = ClusterEngine(cfg, params, n_drives=chips,
                                     routing="data_local", **kw)
            self.engines = [d.engine for d in self.sys.drives]
        self.cluster = chips > 1

    def submit(self, r: T.Request) -> int:
        if self.cluster:
            return self.sys.submit(r.prompt.tolist(), max_new=r.max_new,
                                   shard_id=r.shard)
        return self.sys.submit(r.prompt.tolist(), max_new=r.max_new)

    def busy(self) -> bool:
        return bool(self.sys.pending or self.sys.num_active or
                    (self.cluster and self.sys.in_flight))

    def slots(self) -> List[Dict[int, tuple]]:
        """Per drive: {request key: (prompt rows spliced or None when the
        whole prompt is in, tokens emitted)} over its active slots."""
        out = []
        for d, eng in enumerate(self.engines):
            rid_map = self.sys.drives[d].rid_map if self.cluster else None
            cur = {}
            for s in eng.slots:
                if not s.active:
                    continue
                key = rid_map.get(s.rid, -1) if rid_map is not None else s.rid
                cur[key] = (s.prefill_done_tokens if s.prefilling else None,
                            len(s.out))
            out.append(cur)
        return out

    def stats(self) -> Dict[str, float]:
        """Every int and float field of the drives' ``ServeStats``, summed
        over the drives."""
        tot: Dict[str, float] = {}
        for eng in self.engines:
            for f in dataclasses.fields(eng.stats):
                v = getattr(eng.stats, f.name)
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    tot[f.name] = tot.get(f.name, 0.0) + float(v)
        return tot


# -- one run -----------------------------------------------------------------


@dataclass
class Cell:
    """A cell's parts, found by name."""
    root: Path
    bench: Dict
    name: str
    spec: Dict
    mix: Dict
    chips: int
    counts: object
    cfg: object

    @classmethod
    def load(cls, root: Path, workload: str) -> "Cell":
        bench = S.benchmark(root)
        cell = S.cell(bench, workload)
        spec = S.config(root, bench, cell["config"])
        return cls(root, bench, workload, spec, S.mix(root, cell["traffic"]),
                   int(cell["chips"]),
                   S.counts(root, spec["arch"]).Counts(spec),
                   S.system(root, spec["arch"]).model_config(spec))

    def requests(self, seed: int, seconds: float, mix=None) -> List[Tracked]:
        return [Tracked(r) for r in T.generate(
            mix or self.mix, seed, seconds, int(self.spec["vocab_size"]),
            int(self.spec["engine"]["max_len"]))]

    def engine(self, seed: int, devices) -> Engine:
        """Weights from the seed, made on the device in one jitted call, and
        the served path with every shape of the cell compiled."""
        import jax
        from repro.models import model as M
        with jax.default_device(devices[0]):
            params = M.init_params(self.cfg, jax.random.PRNGKey(seed))
            jax.block_until_ready(params)
        return Engine(self.spec, self.cfg, params, self.chips)


def serve_window(cell: Cell, eng: Engine, reqs: List[Tracked],
                 seconds: float, peak: Dict[str, float],
                 trace: bool) -> Run:
    """Offer ``reqs`` open loop for ``seconds``, then drain."""
    import jax
    from repro.launch.compiles import count_compiles

    run = Run(spec=cell.spec, chips=cell.chips, seconds=seconds, peak=peak,
              reqs=reqs, counts=cell.counts)
    by_key: Dict[int, Tracked] = {}
    prof_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    lag: List[float] = []
    tracing = traced = False
    trace_lo = math.inf
    window_span = None
    nxt = 0
    with count_compiles() as compiled:
        t0 = time.perf_counter()
        close = t0 + seconds
        run.stats0 = eng.stats()
        stats_closed = False
        before = eng.slots()
        while True:
            now = time.perf_counter()
            if trace and not tracing and not traced and \
                    now >= t0 + TRACE_AT * seconds:
                jax.profiler.start_trace(prof_dir)
                window_span = jax.profiler.TraceAnnotation("bench.window")
                window_span.__enter__()
                tracing, trace_lo = True, time.perf_counter()
            with _span(tracing, "bench.submit"):
                while nxt < len(reqs) and t0 + reqs[nxt].req.due_s <= now:
                    r = reqs[nxt]
                    r.submit_t = now - t0
                    lag.append(r.submit_t - r.req.due_s)
                    r.key = eng.submit(r.req)
                    by_key[r.key] = r
                    nxt += 1
            if now >= close and nxt == len(reqs):
                break
            if eng.busy():
                tb = time.perf_counter()
                with _span(tracing, "bench.step"):
                    done = eng.sys.step()
                te = time.perf_counter()
                with _span(tracing, "bench.account"):
                    after = eng.slots()
                    _account(run, eng, by_key, before, after, done, tb - t0,
                             te - t0, tracing)
                    before = after
                if not stats_closed and te >= close:
                    run.stats1, stats_closed = eng.stats(), True
                if tracing and te >= trace_lo + TRACE_S:
                    window_span.__exit__(None, None, None)
                    jax.profiler.stop_trace()
                    tracing, traced = False, True
                continue
            wake = t0 + reqs[nxt].req.due_s if nxt < len(reqs) else close
            with _span(tracing, "bench.sleep"):
                time.sleep(max(wake - time.perf_counter(), 0.0))
        if tracing:
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        _drain(run, eng, by_key, before, t0,
               close + float(cell.mix.get("drain_s", 60.0)), stats_closed)
    if compiled:
        raise RuntimeError(f"{len(compiled)} programs compiled inside the "
                           f"window: {sorted(set(compiled))[:8]}")
    lag_ms = np.asarray(lag) * 1e3
    log(f"generator lateness ms: p50 {pct(lag_ms, 50):.3f} "
        f"p99 {pct(lag_ms, 99):.3f} max {lag_ms.max():.3f}")
    if trace:
        from bench.lib import spans as SP
        from bench.lib import trace as TR
        files = sorted(Path(prof_dir).rglob("*.xplane.pb"))
        run.trace = TR.load(str(files[-1]))
        run.engine_trace = SP.load(str(files[-1]))
        shutil.rmtree(prof_dir, ignore_errors=True)
        run.trace_window = run.trace.window()
    return run


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, devices, peak: Dict[str, float],
             t_start: float) -> Dict:
    cell = Cell.load(root, workload)
    reqs = cell.requests(seed, seconds)
    log(f"{workload}: {len(reqs)} requests due in {seconds} s, seed {seed}")
    eng = cell.engine(seed, devices)
    setup_s = time.perf_counter() - t_start
    run = serve_window(cell, eng, reqs, seconds, peak, trace)

    used = list(devices[:cell.chips])
    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in used)
    dev = {"platform": used[0].platform, "kind": used[0].device_kind,
           "count": cell.chips, "memory_peak_bytes": mem}
    result: Dict = {"attempted": len(reqs)}
    kinds = "per_layer" if trace else "end_to_end"
    if trace:
        from bench.lib import trace as TR
        dev["busy_s"] = run.device_busy_s()
        dev["window_s"] = run.traced_s()
        lo, hi = run.trace_window
        for k, calls in sorted(run.traced_kernel_calls.items()):
            seen = sum(TR.kernel_count(e, k, lo, hi)
                       for e in run.trace.devices.values())
            log(f"traced {run.traced_s():.3f} s: {k} calls counted {calls}, "
                f"in the trace {seen}, kernel {run.kernel_s(k):.6f} s, "
                f"roofline {run.traced_kernel_ideal_s[k]:.6f} s")
        result["breakdown"] = {"device_ops": TR.top_ops(run.trace, lo, hi),
                               "idle_gaps": TR.idle_gaps(run.trace, lo, hi)}
    metrics = {}
    for m in S.cell_metrics(cell.bench, kinds, workload):
        value = (_end_to_end(run, m["name"], setup_s) if kinds == "end_to_end"
                 else S.metric_reader(root, m["name"])(run))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # -- correctness, once the program's state is freed ---------------------
    del eng
    gc.collect()
    finished = [r for r in reqs if r.tokens is not None]
    pick = sample(finished, seed, cell.chips,
                  int(cell.spec["check"]["requests"]),
                  int(cell.spec["engine"]["chunk_prefill"]))
    compared = {"widest_gap": {
        "value": widest_gap(root, cell.spec, seed, pick),
        "limit": float(cell.spec["check"]["widest_gap"])}}
    mismatches = sum(1 for r in finished if len(r.tokens) != r.req.max_new)
    compared["token_count_mismatches"] = {"value": mismatches, "limit": 0}
    # due in the window and never answered, not even after the drain
    compared["unfinished"] = {"value": len(reqs) - len(finished), "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    failed = mismatches + compared["unfinished"]["value"]
    for k, c in compared.items():
        log(f"compared {k} {c['value']!r} limit {c['limit']!r}")
    result.update(correct=correct, failed=failed, metrics=metrics, device=dev)
    result["compared"] = compared
    return result


class _span:
    """A profiler span around harness work, only while the trace runs."""

    def __init__(self, on: bool, name: str):
        self.ann = None
        if on:
            import jax
            self.ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        if self.ann is not None:
            self.ann.__enter__()

    def __exit__(self, *exc):
        if self.ann is not None:
            self.ann.__exit__(*exc)


def _account(run: Run, eng: Engine, by_key, before, after, done, tb: float,
             te: float, tracing: bool) -> None:
    """Stamp the tick's events and count its work, drive by drive."""
    counts = run.counts
    finished = {}
    for res in done:
        finished[res.rid] = res
    emitted = 0
    decode_flops = prefill_flops = 0.0
    ideal: Dict[str, float] = {}
    calls: Dict[str, int] = {}
    for d in range(len(eng.engines)):
        b, a = before[d], after[d]
        keys = set(b) | set(a) | {k for k, res in finished.items()
                                  if getattr(res, "drive", 0) == d}
        steps: Dict[int, List[int]] = {}
        for k in keys:
            r = by_key.get(k)
            if r is None:
                continue
            plen = len(r.req.prompt)
            pb, nb = b.get(k, (0, 0))
            if k in a:
                pa, na = a[k]
            else:
                res = finished.get(k)
                if res is None:
                    continue
                pa, na = None, len(res.tokens)
                r.tokens, r.finish_t, r.drive = list(res.tokens), te, d
            pb = plen if pb is None else pb
            pa = plen if pa is None else pa
            if k not in b and (k in a or k in finished) \
                    and not math.isfinite(r.admit_t):
                r.admit_t = te
            if pa > pb:
                prefill_flops += counts.prefill_flops(pb, pa, pa == plen)
            first = nb == 0 and na >= 1
            if first:
                r.first_t = te
            m0 = nb + int(first)
            for i in range(na - nb - int(first)):
                ctx = plen + m0 + i
                steps.setdefault(i, []).append(ctx)
                decode_flops += counts.token_flops(ctx, logits=True)
            emitted += na - nb
        if tracing:
            for ctx in steps.values():
                for k, (fl, by, n) in counts.decode_kernels(ctx).items():
                    ideal[k] = ideal.get(k, 0.0) + \
                        n * F.roofline_s(fl, by, run.peak)
                    calls[k] = calls.get(k, 0) + n
    for res in done:
        r = by_key.get(res.rid)
        if r is not None and r.tokens is None:
            r.tokens, r.finish_t = list(res.tokens), te
    if tracing:
        run.traced_decode_flops += decode_flops
        run.traced_prefill_flops += prefill_flops
        for k, t in ideal.items():
            run.traced_kernel_ideal_s[k] = \
                run.traced_kernel_ideal_s.get(k, 0.0) + t
            run.traced_kernel_calls[k] = \
                run.traced_kernel_calls.get(k, 0) + calls[k]
    # tokens of the tick that straddles the close count by the share of the
    # tick inside the window
    if tb < run.seconds:
        share = 1.0 if te <= run.seconds else \
            (run.seconds - tb) / max(te - tb, 1e-12)
        run.window_tokens += emitted * share


def _drain(run: Run, eng: Engine, by_key, before, t0: float, limit: float,
           stats_closed: bool):
    """Serve on, with no new arrivals, until every request due in the window
    has finished or the drain time is spent."""
    while eng.busy() and time.perf_counter() < limit:
        tb = time.perf_counter() - t0
        done = eng.sys.step()
        te = time.perf_counter() - t0
        after = eng.slots()
        _account(run, eng, by_key, before, after, done, tb, te, False)
        before = after
        if not stats_closed and te >= run.seconds:
            run.stats1, stats_closed = eng.stats(), True
    if not stats_closed:
        run.stats1 = eng.stats()
    run.end_t = time.perf_counter() - t0
    return before


def _end_to_end(run: Run, name: str, setup_s: float) -> float:
    if name == "setup_s":
        return setup_s
    if name == "ttft_p50_s":
        return pct(run.ttft_s(), 50)
    if name == "ttft_p90_s":
        return pct(run.ttft_s(), 90)
    if name == "tpot_p90_ms":
        return pct(run.tpot_s(), 90) * 1e3
    if name == "out_tok_s":
        return run.window_tokens / run.seconds
    raise KeyError(f"no end-to-end metric named {name!r}")


def sample(finished: List[Tracked], seed: int, chips: int, n: int,
           chunk: int) -> List[Tracked]:
    """Finished requests to hold against the reference: the one that served
    the most tokens, the longest prompt above ``chunk`` (chunked prefill),
    one prompt of at most ``chunk`` (one-shot bucket prefill and its
    splice), then on several drives one from every drive, then others; the
    draws come from the seed."""
    rng = np.random.default_rng(seed + 1)
    pool = sorted(finished, key=lambda r: -len(r.tokens))
    pick: List[Tracked] = pool[:1]
    chunked = [r for r in pool if len(r.req.prompt) > chunk]
    if chunked:
        longest = max(chunked, key=lambda r: len(r.req.prompt))
        if longest not in pick:
            pick.append(longest)
    if not any(len(r.req.prompt) <= chunk for r in pick):
        oneshot = [r for r in pool if len(r.req.prompt) <= chunk]
        if oneshot:
            pick.append(oneshot[int(rng.integers(len(oneshot)))])
    if chips > 1:
        for d in range(chips):
            on = [r for r in pool if r.drive == d and r not in pick]
            if on:
                pick.append(on[int(rng.integers(len(on)))])
    rest = [r for r in pool if r not in pick]
    for i in rng.permutation(len(rest)):
        if len(pick) >= n:
            break
        pick.append(rest[int(i)])
    return pick


def widest_gap(root: Path, spec: Dict, seed: int, pick: List[Tracked],
               precision: str = "f32") -> float:
    """The widest gap by which a served token's logit lies below the plain
    reference's best, over the picked requests (``precision="fp8"``: the
    control's reading on the same positions)."""
    if not pick:
        return math.inf
    t = time.perf_counter()
    ref = S.reference(root, spec["arch"])
    rows = ref.gaps(spec, seed, [(r.req.prompt.tolist(), r.tokens)
                                 for r in pick], precision=precision)
    chunk = int(spec["engine"]["chunk_prefill"])
    paths = {"chunked": [r for r in pick if len(r.req.prompt) > chunk],
             "one-shot": [r for r in pick if len(r.req.prompt) <= chunk]}
    log(f"reference ({precision}) over {len(pick)} requests, "
        f"{sum(len(r.tokens) for r in pick)} served tokens ("
        + ", ".join(f"{k} prefill {len(v)} requests "
                    f"{sum(len(r.tokens) for r in v)} tokens"
                    for k, v in paths.items())
        + f"), drives {sorted({r.drive for r in pick})}: "
        f"{time.perf_counter() - t:.3f} s")
    return max(float(np.max(g)) for g in rows)


def cache_dir(root: Path) -> str:
    """JAX's persistent compilation cache, at a fixed path in the checkout."""
    import jax
    path = str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    os.makedirs(path, exist_ok=True)
    return path
