"""Micro-benchmarks of the framework's own moving parts: simulator
throughput, governor sink throughput (events/sec through the streaming
engine — the number the bounded-RSS refactor is held to), kernel
interpret-mode sanity, the instrumentation overhead of the artificial
barrier (paper §4.2 claim: negligible), and the theta sweep — adaptive
theta (cntd_adaptive) vs the paper's fixed 500 us across the three
co-scheduling workload families (compute-bound / comm-bound / bursty).

``python benchmarks/bench_runtime.py sink_throughput`` runs just the
governor hot-path benchmark; ``... telemetry_overhead [--check]`` runs the
obs-stack overhead guard (``--check`` exits non-zero past the 10% budget).
"""
from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np

from benchmarks.common import baseline_trace, emit, save_json, time_call
from repro.core.governor import Governor
from repro.core.policies import ALL_POLICIES, BASELINE, CNTD_ADAPTIVE, COUNTDOWN_SLACK
from repro.core.simulator import simulate
from repro.core.workloads import APPS, generate

THETA_GRID = (250e-6, 500e-6, 1e-3, 2e-3)
FAMILIES = ("compute_bound", "comm_bound", "bursty_serve")

# Table-3 predictive contrast: the ladder from prediction-only (Guermouche /
# Fermata-style whole-comm pre-arm, no fallback) through the paper's fixed
# 500 us and the adaptive tuner to the guarded hybrid
TABLE3_POLICIES = ("cntd_predict_only", "cntd_slack", "cntd_adaptive",
                   "cntd_predictive")
TABLE3_BUDGET_PCT = 1.0          # the paper's rho: 1% time-overhead budget
TABLE3_N_TASKS = 1000            # long enough that predictor warmup washes out


DEFAULT_CHUNK = 65536        # instrument.DEFAULT_BATCH_SIZE: the fold's sweet spot


def _stream_columns(n_calls: int, n_ranks: int, call_base: int = 0):
    """The sink benchmark's event stream as fixed-dtype columns.

    Exactly the sequence the per-event loop produces — per call: all
    barrier_enters (skewed 1 us/rank), then per rank barrier_exit +
    copy_exit — so the two arms fold the identical stream.  Call ids
    recur mod 50 (the rotation path); ``call_base`` offsets call index
    and time so windows chain into one long stream.
    """
    R = n_ranks
    ranks_blk = np.concatenate(
        [np.arange(R, dtype=np.int32), np.repeat(np.arange(R, dtype=np.int32), 2)])
    codes_blk = np.concatenate(
        [np.zeros(R, dtype=np.int8), np.tile(np.array([1, 2], dtype=np.int8), R)])
    t_blk = np.concatenate(
        [np.arange(R) * 1e-6, np.tile(np.array([1e-3, 1.2e-3]), R)])
    n_blk = 3 * R
    c = np.arange(call_base, call_base + n_calls, dtype=np.int64)
    ranks = np.tile(ranks_blk, n_calls)
    codes = np.tile(codes_blk, n_calls)
    cids = np.repeat(c % 50, n_blk)
    # per-call base times as the sequential fold the per-event loop's
    # ``t += 2e-3`` performs (np.add.accumulate is a strict left fold),
    # so the two arms' float streams are bitwise identical
    step = np.full(n_calls, 2e-3)
    step[0] = 0.0
    t_call = np.add.accumulate(step)
    if call_base:
        t_call += call_base * 2e-3
    ts = np.tile(t_blk, n_calls) + np.repeat(t_call, n_blk)
    return ranks, codes, cids, ts


def _stream_batches(cols, chunk: int = DEFAULT_CHUNK) -> list:
    from repro.core.events import EventBatch

    ranks, codes, cids, ts = cols
    n = ranks.shape[0]
    return [EventBatch(ranks[i:i + chunk], codes[i:i + chunk],
                       cids[i:i + chunk], ts[i:i + chunk], capacity=chunk)
            for i in range(0, n, chunk)]


def sink_throughput(n_calls: int = 12000, n_ranks: int = 16,
                    repeats: int = 9, chunk: int = DEFAULT_CHUNK) -> dict:
    """Events/sec through the full ingest pipeline (producer -> EventBus
    -> governor) on a downshift-heavy stream, A/B: per-event
    ``EventBus.publish`` vs ``EventBus.publish_batch`` over the identical
    stream (recurring call ids — every occurrence rotates through
    retirement + streaming accumulation; 1 ms slack over the 500 us
    default theta — every barrier_exit books an actuation pair).

    The arms are interleaved (A,B,A,B,...) and compared on per-arm
    medians so ambient load lands on both, and the per-event baseline the
    speedup is quoted against comes from the same run.  Also reported:
    bitwise equality of the two arms' ``GovernorReport``s (the batched
    fold's contract), finalize() wall time (must stay flat — an
    O(in-flight) read of the accumulators), and the retained-record count
    (bounded by the retention ring, not the stream length).

    Acceptance (CI ``--check``): batched median >= 5M ev/s and >= 8x the
    per-event median.
    """
    from repro.core.events import EventBus

    n_events = 3 * n_calls * n_ranks

    def stream_events(gov: Governor) -> float:
        bus = EventBus()
        bus.subscribe(gov)
        pub = bus.publish
        t0 = time.perf_counter()
        t = 0.0
        for c in range(n_calls):
            cid = c % 50                    # call ids recur: rotation path
            for r in range(n_ranks):
                pub(r, "barrier_enter", cid, t + r * 1e-6)
            for r in range(n_ranks):
                pub(r, "barrier_exit", cid, t + 1e-3)
                pub(r, "copy_exit", cid, t + 1.2e-3)
            t += 2e-3
        return n_events / (time.perf_counter() - t0)

    batches = _stream_batches(_stream_columns(n_calls, n_ranks), chunk)

    def stream_batched(gov: Governor) -> float:
        bus = EventBus()
        bus.subscribe(gov)
        pub = bus.publish_batch
        t0 = time.perf_counter()
        for b in batches:
            pub(b)
        return n_events / (time.perf_counter() - t0)

    rates_a, rates_b = [], []
    gov_a = gov_b = None
    for _ in range(repeats):
        gov_a = Governor()
        rates_a.append(stream_events(gov_a))
        gov_b = Governor()
        rates_b.append(stream_batched(gov_b))
    med_a = float(np.median(rates_a))
    med_b = float(np.median(rates_b))
    rep_a = gov_a.finalize()
    t0 = time.perf_counter()
    rep_b = gov_b.finalize()
    t_fin = time.perf_counter() - t0
    out = {
        "events_per_s": med_b,
        "per_event_events_per_s": med_a,
        "speedup": med_b / med_a,
        "batched_min_events_per_s": float(min(rates_b)),
        "n_events": n_events,
        "chunk": chunk,
        "reports_equal": rep_a.to_dict() == rep_b.to_dict(),
        "finalize_s": t_fin,
        "n_retained": len(gov_b.recent_records()),
        "n_calls": rep_b.n_calls,
    }
    emit("bench/sink_throughput", 1e6 / med_b,
         f"events_per_s={med_b:.0f};per_event={med_a:.0f};"
         f"speedup={out['speedup']:.2f};finalize_s={t_fin:.4f};"
         f"retained={out['n_retained']};equal={out['reports_equal']}")
    return out


def telemetry_overhead(n_calls: int = 2500, n_ranks: int = 16,
                       repeats: int = 7) -> dict:
    """The obs-stack overhead guard: ``sink_throughput``'s event stream
    through an :class:`~repro.core.events.EventBus` with (A) only the
    governor subscribed (the bare-bus baseline) vs (B) the full telemetry
    stack attached the way the launch drivers wire it — a
    :class:`~repro.obs.tracer.GovernorTap` in the governor's recorder slot
    forwarding retired occurrences and theta decisions to a
    :class:`~repro.obs.tracer.SpanTracer` and a
    :class:`~repro.obs.metrics.BusMetrics`, plus the cold-path costs the
    report cadence pays (a registry snapshot and the spine-log actuation
    pull).

    Both ingest paths are guarded: the per-event pair streams through
    ``EventBus.publish``, the batched pair streams the identical columns
    through ``EventBus.publish_batch`` (the tap advertises
    ``on_retired_batch``, so the governor keeps its vectorized fold while
    recording).  All four arms are interleaved (A,B,C,D,...) and compared
    on per-arm medians, so ambient load lands on every arm instead of
    whichever ran last.  The acceptance bar (CI ``--check``): attached
    within 10% of bare on *each* path (``ratio >= 0.9``).
    """
    from repro.core.events import EventBus
    from repro.obs.metrics import BusMetrics, MetricsRegistry
    from repro.obs.tracer import GovernorTap, SpanTracer

    n_events = 3 * n_calls * n_ranks
    batches = _stream_batches(_stream_columns(n_calls, n_ranks))

    def stream(bus: EventBus) -> float:
        t0 = time.perf_counter()
        t = 0.0
        for c in range(n_calls):
            cid = c % 50
            for r in range(n_ranks):
                bus.publish(r, "barrier_enter", cid, t + r * 1e-6)
            for r in range(n_ranks):
                bus.publish(r, "barrier_exit", cid, t + 1e-3)
                bus.publish(r, "copy_exit", cid, t + 1.2e-3)
            t += 2e-3
        return n_events / (time.perf_counter() - t0)

    def stream_batched(bus: EventBus) -> float:
        t0 = time.perf_counter()
        for b in batches:
            bus.publish_batch(b)
        return n_events / (time.perf_counter() - t0)

    def bare(streamer) -> float:
        bus = EventBus()
        bus.subscribe(Governor())
        return streamer(bus)

    def attached(streamer) -> float:
        registry = MetricsRegistry()
        tracer = SpanTracer()
        tap = GovernorTap(tracer, metrics=BusMetrics(registry))
        gov = Governor(recorder=tap)
        bus = EventBus()
        bus.subscribe(gov)
        rate = streamer(bus)
        registry.snapshot()             # include the collector-sync cost
        tracer.ingest_governor(gov)     # ... and the export-time spine pull
        return rate

    rates: dict = {"bare": [], "attached": [],
                   "bare_batched": [], "attached_batched": []}
    for _ in range(repeats):
        rates["bare"].append(bare(stream))
        rates["attached"].append(attached(stream))
        rates["bare_batched"].append(bare(stream_batched))
        rates["attached_batched"].append(attached(stream_batched))
    med = {k: float(np.median(v)) for k, v in rates.items()}
    out = {
        "bare_events_per_s": med["bare"],
        "telemetry_events_per_s": med["attached"],
        "ratio": med["attached"] / med["bare"],
        "overhead_pct": 100.0 * (1.0 - med["attached"] / med["bare"]),
        "batched_bare_events_per_s": med["bare_batched"],
        "batched_telemetry_events_per_s": med["attached_batched"],
        "batched_ratio": med["attached_batched"] / med["bare_batched"],
        "batched_overhead_pct":
            100.0 * (1.0 - med["attached_batched"] / med["bare_batched"]),
        "n_events": n_events,
        "repeats": repeats,
    }
    emit("bench/telemetry_overhead", 1e6 / med["attached"],
         f"bare={med['bare']:.0f};telemetry={med['attached']:.0f};"
         f"ratio={out['ratio']:.3f};batched_ratio={out['batched_ratio']:.3f}")
    return out


def ingest_soak(n_events: int = 10_000_000, n_ranks: int = 64,
                chunk: int = DEFAULT_CHUNK, window_calls: int = 2000,
                rss_budget_mb: float = 256.0) -> dict:
    """Long-horizon batched-ingest soak: a continuous 64-rank stream is
    generated window-by-window (so the producer itself is O(window), like
    a real run), published through ``EventBus.publish_batch`` into the
    production recorder wiring (GovernorTap -> BusMetrics), and held to a
    bounded-RSS contract: after the first window warms every pool (numpy
    buffers, retention ring, accumulators), the process high-water mark
    may grow by at most ``rss_budget_mb`` regardless of stream length —
    the week-long-trace property.  RSS is read from
    ``resource.getrusage`` (ru_maxrss), events/s over the whole soak, and
    the bus's own ingest counters cross-check delivery.
    """
    import resource

    from repro.core.events import EventBus
    from repro.obs.metrics import BusMetrics, IngestMetrics, MetricsRegistry
    from repro.obs.tracer import GovernorTap

    def rss_mb() -> float:
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0

    registry = MetricsRegistry()
    tap = GovernorTap(None, metrics=BusMetrics(registry))
    # log_retention bounds the raw actuation spine — without it the spine
    # is an unbounded debugging log and a week-long stream grows without
    # limit no matter how tight the rest of the pipeline is
    gov = Governor(recorder=tap, log_retention=1024)
    bus = EventBus()
    bus.subscribe(gov)
    ingest = IngestMetrics(registry, bus)

    ev_per_call = 3 * n_ranks
    n_calls = max(1, n_events // ev_per_call)
    published = 0
    call_base = 0
    rss_warm = None
    t0 = time.perf_counter()
    while call_base < n_calls:
        wc = min(window_calls, n_calls - call_base)
        for b in _stream_batches(_stream_columns(wc, n_ranks, call_base), chunk):
            bus.publish_batch(b)
            published += b.n
        call_base += wc
        if rss_warm is None:
            rss_warm = rss_mb()
    dt = time.perf_counter() - t0
    rep = gov.finalize()
    st = ingest.collect()
    rss_final = rss_mb()
    out = {
        "events_per_s": published / dt,
        "n_events": published,
        "wall_s": dt,
        "n_ranks": n_ranks,
        "rss_warm_mb": rss_warm,
        "rss_final_mb": rss_final,
        "rss_growth_mb": rss_final - rss_warm,
        "rss_budget_mb": rss_budget_mb,
        "rss_ok": rss_final - rss_warm <= rss_budget_mb,
        "delivered_ok": int(st["events_total"]) == published,
        "n_retained": len(gov.recent_records()),
        "n_calls": rep.n_calls,
        "mean_occupancy": st["mean_occupancy"],
    }
    emit("bench/ingest_soak", 1e6 * dt / max(published, 1),
         f"events_per_s={out['events_per_s']:.0f};n={published};"
         f"rss_growth_mb={out['rss_growth_mb']:.1f};"
         f"retained={out['n_retained']}")
    return out


def device_producer_smoke(n_iters: int = 4) -> dict:
    """64-emulated-rank stress of the jitted producer path: under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=64`` an
    instrumented ``cd_psum`` runs in a shard_map over every device with
    batched ingestion on, so each collective's 3-phase events cross the
    io_callback wire into the BatchAccumulator; ``flush_events`` then
    drains the partial chunk through the bus.  Verifies the full
    device->accumulator->bus->governor spine end to end (every event
    delivered, none dropped to the per-event fallback).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import instrument
    from repro.dist.sharding import make_mesh

    n_dev = len(jax.devices())
    gov = Governor()
    instrument.reset_instrumentation()
    instrument.set_mode("profile")
    instrument.enable_events(True)
    instrument.set_ingest_mode("batched")
    bus = instrument.get_event_bus()
    bus.subscribe(gov)
    try:
        mesh = make_mesh((n_dev,), ("r",))
        from repro.core.instrument import cd_psum

        def f(x):
            return cd_psum(x, "r")

        with jax.set_mesh(mesh):
            g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("r"),
                                      out_specs=P("r"), axis_names={"r"},
                                      check_vma=False))
            x = jnp.arange(float(n_dev))
            for _ in range(n_iters):
                jax.block_until_ready(g(x))
        instrument.flush_events()
        st = bus.ingest_stats()
        rep = gov.finalize()
    finally:
        instrument.reset_instrumentation()
    expected = 3 * n_dev * n_iters
    out = {
        "n_devices": n_dev,
        "n_events_expected": expected,
        "n_events_ingested": int(st["events_total"]),
        "fallback_events": int(st["fallback_events_total"]),
        "n_calls": rep.n_calls,
        "ok": int(st["events_total"]) == expected
              and int(st["fallback_events_total"]) == 0
              and rep.n_calls == n_iters,
    }
    emit("bench/device_producer", 0.0,
         f"devices={n_dev};events={out['n_events_ingested']}/{expected};"
         f"calls={rep.n_calls};ok={out['ok']}")
    return out


def theta_sweep(seed: int = 0, n_tasks: int = 400) -> dict:
    """Adaptive vs fixed theta on the three tenant families (DESIGN.md §8).

    For each family: baseline, fixed-theta cntd_slack across ``THETA_GRID``,
    and ``cntd_adaptive`` (online ThetaTuner).  Reports energy saving and
    time-to-completion overhead vs baseline, plus the two acceptance
    aggregates: adaptive beats (or matches) fixed-500us on >= 1 family, and
    adaptive overhead stays under 1% on every family.
    """
    from repro.cluster.coschedule import MIX_SPECS

    out: dict = {"families": {}}
    beats = False
    max_ovh = 0.0
    for fam in FAMILIES:
        spec = dataclasses.replace(MIX_SPECS[fam], n_tasks=n_tasks)
        wl = generate(spec, seed=seed)
        base, _ = simulate(wl, BASELINE)
        row: dict = {}
        for th in THETA_GRID:
            pol = dataclasses.replace(COUNTDOWN_SLACK, theta=th)
            res, _ = simulate(wl, pol)
            row[f"fixed_{th * 1e6:.0f}us"] = {
                "energy_saving_pct": res.energy_saving_vs(base),
                "overhead_pct": res.overhead_vs(base),
            }
        us, ad = time_call(lambda: simulate(wl, CNTD_ADAPTIVE)[0], repeats=1)
        row["adaptive"] = {
            "energy_saving_pct": ad.energy_saving_vs(base),
            "overhead_pct": ad.overhead_vs(base),
            "theta_eff_final_us": float(np.nanmean(ad.theta_series[-20:]) * 1e6),
        }
        out["families"][fam] = row
        fixed500 = row["fixed_500us"]["energy_saving_pct"]
        adaptive = row["adaptive"]["energy_saving_pct"]
        beats = beats or adaptive >= fixed500
        max_ovh = max(max_ovh, row["adaptive"]["overhead_pct"])
        emit(
            f"bench/theta_sweep/{fam}", us,
            f"esave_fixed500={fixed500:.2f};esave_adaptive={adaptive:.2f};"
            f"ovh_adaptive={row['adaptive']['overhead_pct']:.3f}",
        )
    out["adaptive_beats_fixed500"] = bool(beats)
    out["max_overhead_pct"] = float(max_ovh)
    save_json("theta_sweep", out)
    return out


def table3(seed: int = 0, n_tasks: int = TABLE3_N_TASKS) -> dict:
    """Paper Table 3 on the predictive axis (DESIGN.md §12): prediction-only
    vs fixed-500us vs cntd_adaptive vs the guarded hybrid on the three
    golden stream families.

    ``cntd_predict_only`` is the prediction-based strawman (Guermouche /
    Fermata lineage): it pre-arms the downshift at comm entry on ANY
    predicted slack and slows the whole call — slack *and* copy — with no
    reactive fallback and no guard.  ``cntd_predictive`` is the hybrid:
    pre-arm only past the residue-cost bar, reactive ThetaTuner fallback
    otherwise, per-site misprediction guard tripping back to the pure tuner.

    Reported per family: energy saving / wall overhead / DVFS busy-time cost
    (the quantity the 1% rho budget actually constrains), pre-arm, mispredict
    and guard-trip counts.  Acceptance aggregates (CI ``--check``):

    * ``prediction_only_exceeds_budget`` — the strawman's wall overhead
      blows the 1% budget on >= 1 family (it does on all three);
    * ``hybrid_within_budget`` — the hybrid stays <= 1% on every family;
    * ``hybrid_beats_adaptive_everywhere`` — hybrid energy saving >=
      cntd_adaptive on every family.
    """
    from repro.cluster.coschedule import MIX_SPECS

    out: dict = {
        "seed": seed, "n_tasks": n_tasks,
        "overhead_budget_pct": TABLE3_BUDGET_PCT, "families": {},
    }
    po_exceeds, hy_within, hy_beats = False, True, True
    for fam in FAMILIES:
        spec = dataclasses.replace(MIX_SPECS[fam], n_tasks=n_tasks)
        wl = generate(spec, seed=seed)
        base, _ = simulate(wl, BASELINE)
        row: dict = {}
        for name in TABLE3_POLICIES:
            us, res = time_call(
                lambda p=name: simulate(wl, ALL_POLICIES[p])[0], repeats=1)
            row[name] = {
                "energy_saving_pct": res.energy_saving_vs(base),
                "overhead_pct": res.overhead_vs(base),
                "dvfs_cost_pct": res.dvfs_cost_pct(),
                "n_prearm": res.n_prearm,
                "n_mispredict": res.n_mispredict,
                "n_guard_trips": res.n_guard_trips,
            }
            emit(
                f"bench/table3/{fam}/{name}", us,
                f"esave={row[name]['energy_saving_pct']:.2f};"
                f"ovh={row[name]['overhead_pct']:.3f};"
                f"dvfs={row[name]['dvfs_cost_pct']:.3f};"
                f"prearm={res.n_prearm};mis={res.n_mispredict};"
                f"trips={res.n_guard_trips}",
            )
        out["families"][fam] = row
        po, hy, ad = (row["cntd_predict_only"], row["cntd_predictive"],
                      row["cntd_adaptive"])
        po_exceeds = po_exceeds or po["overhead_pct"] > TABLE3_BUDGET_PCT
        hy_within = hy_within and hy["overhead_pct"] <= TABLE3_BUDGET_PCT
        hy_beats = hy_beats and (
            hy["energy_saving_pct"] >= ad["energy_saving_pct"])
    out["prediction_only_exceeds_budget"] = bool(po_exceeds)
    out["hybrid_within_budget"] = bool(hy_within)
    out["hybrid_beats_adaptive_everywhere"] = bool(hy_beats)
    emit("bench/table3/aggregates", 0.0,
         f"po_exceeds_budget={po_exceeds};hybrid_within={hy_within};"
         f"hybrid_beats_adaptive={hy_beats}")
    save_json("table3_predictive", out)
    return out


def run(full: bool = False) -> dict:
    out = {}

    # simulator throughput (rank-task events / s)
    wl, _, _ = baseline_trace("nas_is.D.128")
    us, _ = time_call(lambda: simulate(wl, COUNTDOWN_SLACK)[0], repeats=2)
    events = wl.n_tasks * wl.n_ranks
    out["sim_events_per_s"] = events / (us / 1e6)
    emit("bench/simulator", us, f"events_per_s={out['sim_events_per_s']:.0f}")

    # governor sink throughput (the streaming hot path)
    out["sink_throughput"] = sink_throughput()
    out["governor_events_per_s"] = out["sink_throughput"]["events_per_s"]

    # obs-stack cost on the same stream (acceptance: within 10% of bare)
    out["telemetry_overhead"] = telemetry_overhead()

    # artificial-barrier cost inside the simulator (paper: negligible)
    base, _ = simulate(wl, BASELINE)
    res, _ = simulate(wl, ALL_POLICIES["cntd_slack"])
    out["barrier_overhead_pct"] = res.overhead_vs(base)
    emit("bench/barrier_overhead", 0.0, out["barrier_overhead_pct"])

    # theta sweep: adaptive vs fixed across the workload families
    out["theta_sweep"] = theta_sweep()

    if full:
        import jax.numpy as jnp

        from repro.kernels import ops

        x = jnp.ones((64, 256), jnp.float32)
        sc = jnp.ones((256,), jnp.float32)
        ops.rmsnorm(x, sc).block_until_ready()
        us, _ = time_call(lambda: ops.rmsnorm(x, sc).block_until_ready(), repeats=3)
        emit("bench/rmsnorm_interpret", us, "interpret-mode (CPU)")
    return out


def _cli_arg(name: str, default, cast=float):
    if name in sys.argv:
        return cast(sys.argv[sys.argv.index(name) + 1])
    return default


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "sink_throughput":
        print("name,us_per_call,derived")
        res = sink_throughput()
        print(f"sink_throughput: {res['events_per_s']:,.0f} events/s batched "
              f"({res['speedup']:.1f}x the per-event "
              f"{res['per_event_events_per_s']:,.0f}), "
              f"finalize {res['finalize_s'] * 1e3:.2f} ms, "
              f"{res['n_retained']} records retained, "
              f"reports_equal={res['reports_equal']}")
        if "--check" in sys.argv:
            fails = []
            if res["events_per_s"] < 5e6:
                fails.append(f"batched {res['events_per_s']:,.0f} ev/s "
                             f"< 5M floor")
            if res["speedup"] < 8.0:
                fails.append(f"speedup {res['speedup']:.2f}x < 8x floor")
            if not res["reports_equal"]:
                fails.append("batched GovernorReport != per-event report")
            if fails:
                print("FAIL: " + "; ".join(fails))
                sys.exit(1)
    elif len(sys.argv) > 1 and sys.argv[1] == "telemetry_overhead":
        print("name,us_per_call,derived")
        res = telemetry_overhead()
        print(f"telemetry_overhead: {res['telemetry_events_per_s']:,.0f} "
              f"events/s with full obs stack vs {res['bare_events_per_s']:,.0f} "
              f"bare ({res['overhead_pct']:.1f}% overhead); batched "
              f"{res['batched_telemetry_events_per_s']:,.0f} vs "
              f"{res['batched_bare_events_per_s']:,.0f} "
              f"({res['batched_overhead_pct']:.1f}% overhead)")
        if "--check" in sys.argv:
            fails = []
            if res["ratio"] < 0.9:
                fails.append(f"per-event ratio {res['ratio']:.3f} < 0.9")
            if res["batched_ratio"] < 0.9:
                fails.append(f"batched ratio {res['batched_ratio']:.3f} < 0.9")
            if fails:
                print("FAIL: telemetry overhead exceeds the 10% budget "
                      "(" + "; ".join(fails) + ")")
                sys.exit(1)
    elif len(sys.argv) > 1 and sys.argv[1] == "table3":
        print("name,us_per_call,derived")
        res = table3(
            seed=_cli_arg("--seed", 0, int),
            n_tasks=_cli_arg("--tasks", TABLE3_N_TASKS, int),
        )
        for fam, row in res["families"].items():
            for pol, cell in row.items():
                print(f"table3 {fam:14s} {pol:18s} "
                      f"esave={cell['energy_saving_pct']:6.2f}% "
                      f"ovh={cell['overhead_pct']:6.3f}% "
                      f"dvfs={cell['dvfs_cost_pct']:6.3f}% "
                      f"prearm={cell['n_prearm']} mis={cell['n_mispredict']} "
                      f"trips={cell['n_guard_trips']}")
        print(f"table3: po_exceeds_budget={res['prediction_only_exceeds_budget']} "
              f"hybrid_within_budget={res['hybrid_within_budget']} "
              f"hybrid_beats_adaptive={res['hybrid_beats_adaptive_everywhere']}")
        if "--check" in sys.argv:
            fails = []
            if not res["prediction_only_exceeds_budget"]:
                fails.append("prediction-only stayed under the 1% budget "
                             "on every family (strawman should blow it)")
            if not res["hybrid_within_budget"]:
                fails.append("hybrid overhead exceeded the 1% budget")
            if not res["hybrid_beats_adaptive_everywhere"]:
                fails.append("hybrid energy saving fell below cntd_adaptive")
            if fails:
                print("FAIL: " + "; ".join(fails))
                sys.exit(1)
    elif len(sys.argv) > 1 and sys.argv[1] == "ingest_soak":
        print("name,us_per_call,derived")
        if "--device-producer" in sys.argv:
            dres = device_producer_smoke()
            print(f"device_producer: {dres['n_events_ingested']}/"
                  f"{dres['n_events_expected']} events across "
                  f"{dres['n_devices']} emulated ranks, "
                  f"calls={dres['n_calls']}, ok={dres['ok']}")
            if "--check" in sys.argv and not dres["ok"]:
                print("FAIL: device producer lost or fell back on events")
                sys.exit(1)
        res = ingest_soak(
            n_events=_cli_arg("--events", 10_000_000, int),
            n_ranks=_cli_arg("--ranks", 64, int),
            rss_budget_mb=_cli_arg("--rss-budget-mb", 256.0, float),
        )
        print(f"ingest_soak: {res['events_per_s']:,.0f} events/s over "
              f"{res['n_events']:,} events x {res['n_ranks']} ranks, "
              f"RSS {res['rss_warm_mb']:.0f} -> {res['rss_final_mb']:.0f} MB "
              f"(growth {res['rss_growth_mb']:.1f} MB / budget "
              f"{res['rss_budget_mb']:.0f} MB), "
              f"{res['n_retained']} records retained")
        if "--check" in sys.argv:
            fails = []
            if not res["rss_ok"]:
                fails.append(f"RSS grew {res['rss_growth_mb']:.1f} MB "
                             f"> {res['rss_budget_mb']:.0f} MB budget")
            if not res["delivered_ok"]:
                fails.append("bus ingest counter != published events")
            if fails:
                print("FAIL: " + "; ".join(fails))
                sys.exit(1)
    else:
        run(full=True)
