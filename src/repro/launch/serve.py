"""Serving driver: load (or init) a model and serve requests through the
continuous-batching engine.

Request sources (first match wins):
  --arrival M    open-loop SLO mode: generate a reproducible arrival trace
                 (poisson / bursty / diurnal at --rate req/s, mixed
                 priority classes with TTFT deadlines; --slo-ms overrides
                 every class budget) and replay it on the engine's serving
                 clock — requests arrive when the trace says, not when the
                 engine is ready.  --sched edf turns on deadline-aware
                 admission; expired requests are shed.  Prints the tail
                 latency + goodput-under-SLO summary;
  --trace FILE   one request per line: whitespace-separated token ids,
                 optionally ``ids... | max_new`` to override --max-new;
  --requests N   N random prompts with lengths uniform in
                 [--min-prompt, --prompt-len];
  (neither)      the legacy fixed batch: --batch equal-length prompts.

Always prints the engine's per-tier throughput and the ledger's link-byte
reduction (the paper's "data that never left the drive" counter).

With ``--replicas N`` (N > 1) the requests are served by a multi-drive
cluster instead: N replica engines behind one queue, routed per
``--routing`` (round_robin / least_loaded / data_local / rate_aware);
``--shards K`` tags request i with shard ``i % K`` so data_local has
locality to exploit, ``--speed-factor 1.0,0.5`` models heterogeneous
drives (the pull scheduler learns the skew, rate_aware routing exploits
it), and shard re-placement on drain/fail is on unless
``--no-shard-replacement``.  The cluster prints per-drive AND aggregate
stats — learned rates included — plus the live energy-per-query integral
(paper Table I).

Fault injection (implies the cluster path, even at --replicas 1):
``--mttf S`` draws a seeded fault schedule (stalls / slowdowns / pool
clamps / crashes) from exponential MTTF/MTTR distributions
(``--mttr S``, ``--fault-seed N``), or ``--fault-trace FILE`` replays a
saved schedule (``FaultSchedule.save`` jsonl, or the legacy JSON event
list of the ``from_spec`` form).  The
failure detector auto-fails drives it declares DEAD; restarted requests
spend their ``--max-retries`` budget and ``--hedge`` duplicates
SUSPECT-stranded requests onto healthy drives.  The summary then carries
the recovery story: faults injected, drive health, retries granted,
requests failed terminally, hedge wins/losses and the serving time the
lost copies burned.

``--concurrent`` swaps the cluster's serial drive loop for the worker
runtime: one thread per drive fed over command queues, tick time is the
measured wall-clock overlap, and DEAD verdicts come from the heartbeat
watchdog (missed beats + real dispatch timeouts) rather than virtual
clock thresholds — so a crashed or hung worker is detected by its
silence on the monitor channel, exactly as it would be in production.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.config import get_config, reduced_config
from repro.core.cluster import ROUTING_POLICIES
from repro.data.workload import (ARRIVAL_MODES, DEFAULT_CLASSES,
                                 PriorityClass, WorkloadConfig,
                                 generate_trace, replay_open_loop)
from repro.launch.compiles import use_persistent_cache
from repro.models import model as M
from repro.train.cluster_loop import ClusterEngine
from repro.train.serve_loop import AdmissionController, ServeEngine


def _load_trace(path: str, default_max_new: int):
    reqs = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            ids, _, tail = line.partition("|")
            prompt = [int(t) for t in ids.split()]
            max_new = int(tail) if tail.strip() else default_max_new
            reqs.append((prompt, max_new))
    return reqs


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--min-prompt", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--num-slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=0,
                    help="serve N random variable-length requests")
    ap.add_argument("--trace", type=str, default=None,
                    help="file of token-id prompts, one request per line")
    ap.add_argument("--host-rate", type=float, default=20.0)
    ap.add_argument("--csd-rate", type=float, default=1.0)
    ap.add_argument("--csds", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-layout", choices=("paged", "strip"),
                    default="paged",
                    help="paged: fixed-size KV pages, memory tracks live "
                         "tokens; strip: dense max_len strip per slot")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (smaller = tighter memory, "
                         "larger = fewer/bigger kernel blocks)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="KV pool size in pages (0 = dense worst case); "
                         "smaller pools backpressure admission")
    ap.add_argument("--k-block", type=int, default=8,
                    help="decode steps fused into one device-resident "
                         "dispatch per tick (1 = per-step host loop)")
    ap.add_argument("--chunk-prefill", type=int, default=0,
                    help="split prompts longer than this into per-tick "
                         "prefill chunks (0 = one-shot prefill)")
    ap.add_argument("--prewarm", action="store_true",
                    help="compile decode + prefill buckets before serving "
                         "(first-request latency excludes compile time)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replica drives; >1 serves through the cluster "
                         "engine (one queue, routed dispatch)")
    ap.add_argument("--routing", choices=ROUTING_POLICIES,
                    default="least_loaded",
                    help="cluster dispatch policy (with --replicas > 1)")
    ap.add_argument("--shards", type=int, default=0,
                    help="tag request i with shard i %% K for data_local "
                         "routing (0 = unsharded requests)")
    ap.add_argument("--speed-factor", type=str, default=None,
                    help="comma-separated per-drive speed factors (e.g. "
                         "'1.0,0.5' models one drive 2x slower); the "
                         "cluster pull scheduler learns the skew live and "
                         "rate_aware routing exploits it")
    ap.add_argument("--arrival", choices=ARRIVAL_MODES, default=None,
                    help="open-loop SLO mode: generate + replay an arrival "
                         "trace of --requests requests at --rate req/s")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="mean arrival rate (req/s) for --arrival")
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="override every class's TTFT SLO budget (ms); "
                         "0 keeps the per-class defaults")
    ap.add_argument("--sched", choices=("fifo", "edf"), default="fifo",
                    help="admission order under --arrival (edf = earliest "
                         "deadline first + shedding of expired requests)")
    ap.add_argument("--chunk-budget", type=int, default=1,
                    help="prefill chunks one tick may run (with "
                         "--chunk-prefill); 1 protects decode TTFT")
    ap.add_argument("--no-shard-replacement", action="store_true",
                    help="keep static shard placement on drain/fail "
                         "(every re-routed request re-pays the shard's "
                         "link bytes instead of one migration charge)")
    ap.add_argument("--mttf", type=float, default=0.0,
                    help="mean seconds between injected faults per drive "
                         "(0 = no fault injection); faults are drawn "
                         "seeded from exponential MTTF/MTTR distributions")
    ap.add_argument("--mttr", type=float, default=0.5,
                    help="mean repair window (s) of injected transient "
                         "faults (stall / slowdown / pool clamp)")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="seed for the drawn fault schedule "
                         "(default: --seed)")
    ap.add_argument("--fault-trace", type=str, default=None,
                    help="fault event file: jsonl from FaultSchedule.save "
                         "or a legacy JSON event list "
                         "(FaultSchedule.from_spec form); overrides --mttf")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="restarts a request may spend on drive failures "
                         "before finishing status='failed'")
    ap.add_argument("--hedge", action="store_true",
                    help="duplicate SUSPECT-stranded requests onto healthy "
                         "drives (first finisher wins; the loser's serving "
                         "time is booked as hedge waste)")
    ap.add_argument("--concurrent", action="store_true",
                    help="run drives on real worker threads (one per "
                         "drive); tick time is measured wall-clock overlap "
                         "and failures are detected from missed heartbeats "
                         "(implies the cluster path, even at --replicas 1)")
    ap.add_argument("--dispatch-timeout", type=float, default=0.25,
                    help="seconds the concurrent coordinator waits on the "
                         "heartbeat channel per join before charging the "
                         "silent drives a missed beat")
    ap.add_argument("--min-tick-ms", type=float, default=0.0,
                    help="per-drive service-time floor (ms) so tiny smoke "
                         "models still show real tick overlap under "
                         "--concurrent (applied in serial mode too, "
                         "keeping the two comparable)")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="write a Chrome-trace/Perfetto JSON timeline of "
                         "the run (one track per drive worker + the "
                         "coordinator + queue-depth counters); enables "
                         "the telemetry hub")
    ap.add_argument("--metrics-out", type=str, default=None,
                    help="write the telemetry metrics registry (counters/"
                         "gauges/histograms + detection latency + the "
                         "stats snapshots the summary prints from) as "
                         "JSON; enables the telemetry hub")
    ap.add_argument("--events-out", type=str, default=None,
                    help="write the raw telemetry event ring as jsonl; "
                         "enables the telemetry hub")
    args = ap.parse_args()

    print(f"[serve] compile cache: {use_persistent_cache()}")
    cfg = reduced_config(args.arch) if args.smoke else get_config(args.arch)
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    engine_kw = dict(max_len=args.max_len, num_slots=args.num_slots,
                     kv_layout=args.kv_layout, page_size=args.page_size,
                     num_pages=args.num_pages or None, k_block=args.k_block,
                     chunk_prefill=args.chunk_prefill or None,
                     prewarm=args.prewarm)
    # the cluster binds admission_order at its shared queue (ClusterEngine
    # kwarg); the single engine at its own; chunk_budget always reaches the
    # ServeEngine(s)
    engine_kw["admission_order"] = args.sched
    engine_kw["chunk_budget"] = args.chunk_budget
    def admission():
        return AdmissionController(args.num_slots, host_rate=args.host_rate,
                                   csd_rate=args.csd_rate, n_csds=args.csds)

    hub = None
    if args.trace_out or args.metrics_out or args.events_out:
        from repro.core.telemetry import TelemetryHub
        hub = TelemetryHub()

    faults = None
    if args.fault_trace:
        from repro.core.faults import FaultSchedule
        faults = FaultSchedule.load(args.fault_trace)
    elif args.mttf > 0:
        from repro.core.faults import FaultSchedule
        fault_seed = args.seed if args.fault_seed is None else args.fault_seed
        faults = FaultSchedule.from_rates(args.replicas, mttf_s=args.mttf,
                                          mttr_s=args.mttr, seed=fault_seed)

    if args.replicas > 1 or faults is not None or args.concurrent:
        # fault injection and the worker runtime need the cluster's
        # detector/retry machinery, so both route through ClusterEngine
        # even at --replicas 1
        speed = None
        if args.speed_factor:
            speed = [float(s) for s in args.speed_factor.split(",")]
        engine = ClusterEngine(cfg, params, n_drives=args.replicas,
                               routing=args.routing,
                               admission_factory=admission,
                               speed_factor=speed,
                               shard_replacement=not args.no_shard_replacement,
                               faults=faults, max_retries=args.max_retries,
                               hedge=args.hedge,
                               concurrent=args.concurrent,
                               dispatch_timeout_s=args.dispatch_timeout,
                               min_tick_s=args.min_tick_ms / 1e3,
                               telemetry=hub,
                               **engine_kw)
    else:
        engine = ServeEngine(cfg, params, admission=admission(),
                             telemetry=hub, **engine_kw)
    is_cluster = isinstance(engine, ClusterEngine)

    def export_telemetry(wall_s=None) -> None:
        """Dump the hub after the run: Perfetto trace, metrics JSON (with
        the same stats snapshots the summary printed from), raw events."""
        if hub is None:
            return
        stats_m = engine.stats.metrics()
        hub.publish("cluster" if is_cluster else "engine", stats_m)
        hub.publish("latency", engine.stats.latency.metrics(wall_s=wall_s))
        if args.trace_out:
            hub.write_chrome_trace(args.trace_out)
            print(f"[serve] trace written to {args.trace_out}")
        if args.metrics_out:
            hub.write_metrics(args.metrics_out)
            print(f"[serve] metrics written to {args.metrics_out}")
        if args.events_out:
            hub.write_jsonl(args.events_out)
            print(f"[serve] events written to {args.events_out}")

    if args.arrival:
        classes = DEFAULT_CLASSES
        if args.slo_ms > 0:
            classes = tuple(PriorityClass(
                c.name, priority=c.priority, weight=c.weight,
                slo_s=args.slo_ms / 1e3, prompt_range=c.prompt_range,
                max_new_range=c.max_new_range) for c in DEFAULT_CLASSES)
        wl = WorkloadConfig(n_requests=args.requests or 32,
                            vocab_size=cfg.vocab_size, arrival=args.arrival,
                            rate=args.rate, classes=classes, seed=args.seed)
        t0 = time.perf_counter()
        report = replay_open_loop(engine, generate_trace(wl))
        dt = time.perf_counter() - t0
        lat = engine.stats.latency
        # one source of truth: the goodput/attainment the export carries
        # are the SAME dict entries printed here (no inline recompute)
        lm = lat.metrics(wall_s=report.wall_s)
        n_tok = sum(len(r.tokens) for r in report.results)
        print(f"[serve] {args.arch}: open-loop {args.arrival}@{args.rate}/s "
              f"({args.sched}): {report.submitted} requests, {n_tok} tokens "
              f"in {dt:.2f}s wall / {report.wall_s:.2f}s serving clock")
        print(f"[serve] {lat.summary()}")
        print(f"[serve] goodput under SLO: "
              f"{lm['goodput_qps']:.2f} qps "
              f"(attainment {lm['slo_attainment']:.0%}, "
              f"{report.shed} shed)")
        summary = engine.summary() if is_cluster \
            else engine.stats.summary()
        for line in summary.splitlines():
            print(f"[serve] {line}")
        export_telemetry(wall_s=report.wall_s)
        if is_cluster:
            engine.close()      # joins worker threads (no-op if serial)
        return 0

    rng = np.random.default_rng(args.seed)
    if args.trace:
        requests = _load_trace(args.trace, args.max_new)
    elif args.requests:
        requests = [
            (rng.integers(0, cfg.vocab_size,
                          rng.integers(args.min_prompt,
                                       args.prompt_len + 1)).tolist(),
             args.max_new)
            for _ in range(args.requests)]
    else:
        requests = [(rng.integers(0, cfg.vocab_size,
                                  args.prompt_len).tolist(), args.max_new)
                    for _ in range(args.batch)]

    if not requests:
        print("[serve] no requests (empty --trace file?)")
        return 1

    t0 = time.perf_counter()
    for i, (prompt, max_new) in enumerate(requests):
        if is_cluster:
            shard = i % args.shards if args.shards else None
            engine.submit(prompt, max_new=max_new, shard_id=shard)
        else:
            engine.submit(prompt, max_new=max_new)
    results = engine.run_until_complete()
    dt = time.perf_counter() - t0

    # token count from the stats registry (the same number the metrics
    # export carries), not recomputed from the result list
    n_tok = engine.stats.metrics()["tokens"]
    print(f"[serve] {args.arch}: {len(results)} requests, {n_tok} tokens in "
          f"{dt:.2f}s ({n_tok / max(dt, 1e-9):.1f} tok/s); "
          f"first: {results[0].tokens[:8]}")
    summary = engine.summary() if is_cluster \
        else engine.stats.summary()
    for line in summary.splitlines():
        print(f"[serve] {line}")
    export_telemetry(wall_s=dt)
    kvs = engine.kv_stats()                 # cluster: one entry per drive
    for kv in kvs if isinstance(kvs, list) else [kvs]:
        print(f"[serve] KV[{kv['layout']}]: peak "
              f"{kv['peak_kv_bytes'] / 1e6:.3f} MB vs dense "
              f"{kv['dense_kv_bytes'] / 1e6:.3f} MB "
              f"(page_size={kv['page_size']})")
    if is_cluster:
        engine.close()          # joins worker threads (no-op if serial)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
