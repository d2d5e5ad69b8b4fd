"""Serving driver: static batch, continuous batching, or a replica fleet.

  # legacy static batch (TP partition rules on a multi-chip host)
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
      --batch 4 --prompt-len 32 --steps 16 [--kv-int8]

  # continuous batching: paged KV pool, Poisson arrivals, governor report
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
      --continuous --n-requests 8 --arrival-rate 40 --slots 4 --page-size 8

  # replica fleet: N real engines behind the prefix-aware router, watt
  # arbitration per epoch (wall clock); add --autoscale for the
  # deterministic virtual-clock fleet with SLO-driven membership
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
      --fleet 2 --fleet-trace flash-crowd --fleet-duration 3 \
      [--autoscale] [--metrics-out fleet.jsonl]

Timing excludes compilation: one warmup generate runs before the clock
starts and the compile time is printed separately.  On a multi-chip host
this applies ``serve_param_shardings`` (TP weights) and, in continuous
mode, ``page_pool_shardings`` for the paged KV pool; on one device it
runs unsharded.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.core.events import EventBus
from repro.core.governor import Governor
from repro.core.policies import policy_for_theta
from repro.dist import sharding as SH
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.models.hooks import install_constraint
from repro.models.inputs import make_batch
from repro.obs import log as obslog
from repro.serve import (
    ContinuousEngine,
    Request,
    ServeEngine,
    SLOTracker,
    poisson_arrivals,
)

log = obslog.get_logger("serve")


def _run_static(args, cfg, params) -> None:
    eng = ServeEngine(cfg, params, max_len=args.prompt_len + args.steps + 8,
                      temperature=args.temperature)
    batch = make_batch(cfg, batch=args.batch, seq_len=args.prompt_len,
                       kind="prefill")
    t0 = time.time()
    jax.block_until_ready(eng.generate(batch, n_steps=args.steps,
                                       key=jax.random.PRNGKey(1)))
    t_compile = time.time() - t0
    t0 = time.time()
    out = jax.block_until_ready(eng.generate(batch, n_steps=args.steps,
                                             key=jax.random.PRNGKey(1)))
    dt = time.time() - t0
    log.info("static_done", arch=args.arch, shape=str(out.shape), wall_s=dt,
             tok_per_s=args.batch * args.steps / dt, compile_s=t_compile,
             kv_int8=args.kv_int8)
    log.info("sample", tokens=out[0].tolist())


def _make_requests(args, cfg) -> list:
    rng = np.random.default_rng(args.seed)
    arrivals = poisson_arrivals(args.n_requests, args.arrival_rate, seed=args.seed,
                                burst_every=max(args.slots, 2), burst_gap=0.05)
    base_key = jax.random.PRNGKey(args.seed) if args.temperature > 0 else None
    reqs = []
    for i in range(args.n_requests):
        prompt = rng.integers(0, cfg.vocab, size=args.prompt_len).astype(np.int32)
        max_new = int(rng.integers(max(2, args.steps // 2), args.steps + 1))
        req = Request(prompt=prompt, max_new=max_new, arrival=float(arrivals[i]),
                      key=None if base_key is None else jax.random.fold_in(base_key, i))
        if cfg.n_prefix:
            req.prefix_embeds = rng.normal(
                0, 0.02, size=(cfg.n_prefix, cfg.d_model)
            ).astype(np.float32)
        reqs.append(req)
    return reqs


def _run_continuous(args, cfg, params, mesh, n_dev: int, mp: int) -> dict:
    """Serve ``args.n_requests`` Poisson arrivals through one engine.

    Returns what it logs: the engine, the finished requests, token count,
    wall and compile seconds, the governor report and the SLO summary.
    """
    max_len = args.prompt_len + args.steps + args.page_size
    max_len += (-max_len) % args.page_size
    eng = ContinuousEngine(cfg, params, n_slots=args.slots, max_len=max_len,
                           page=args.page_size, temperature=args.temperature,
                           attn_kernel=args.attn_kernel)
    if mp > 1 or n_dev > 1:
        eng.pool.blocks = jax.device_put(
            eng.pool.blocks, SH.page_pool_shardings(mesh, eng.pool.blocks)
        )
    # warmup: compile prefill bucket + join + decode before the clock starts
    warm = make_batch(cfg, batch=1, seq_len=args.prompt_len, kind="prefill")
    t0 = time.time()
    eng.generate(warm, n_steps=2)
    t_compile = time.time() - t0

    recorder = trace_rec = None
    if args.trace_out:
        from repro.cluster.trace import TraceRecorder

        recorder = trace_rec = TraceRecorder(
            meta={"driver": "serve", "arch": args.arch,
                  "n_requests": args.n_requests,
                  "theta": args.theta or "default"})

    registry = tracer = collector = busmetrics = writer = dash = None
    obs_on = bool(args.perfetto_out or args.metrics_out or args.dashboard)
    if obs_on:
        from repro.obs.export import ConsoleDashboard, MetricsJsonlWriter
        from repro.obs.metrics import BusMetrics, GovernorCollector, MetricsRegistry
        from repro.obs.tracer import GovernorTap, RecorderFanout, SpanTracer

        registry = MetricsRegistry()
        busmetrics = BusMetrics(registry)
        if args.perfetto_out:
            tracer = SpanTracer(meta={"driver": "serve", "arch": args.arch,
                                      "n_requests": args.n_requests})
            eng.tracer = tracer
        # production wiring: metrics + tracer ride the governor's recorder
        # slot (ingested phases, retired occurrences, theta decisions) —
        # exactly one phase source each, never a second bus subscription,
        # or every phase would double-count
        tap = GovernorTap(tracer, metrics=busmetrics)
        recorder = RecorderFanout([recorder, tap]) if recorder is not None \
            else tap

    gov = Governor(policy=policy_for_theta(args.theta), recorder=recorder)
    # the engine publishes decode phases onto a bus, not into a governor:
    # the governor is just the first subscriber (add probes beside it)
    bus = EventBus()
    bus.subscribe(gov)
    if args.ingest == "batched":
        from repro.core import instrument

        instrument.set_ingest_mode("batched")
    if registry is not None:
        collector = GovernorCollector(registry, gov)
        if args.ingest == "batched":
            from repro.obs.metrics import IngestMetrics

            IngestMetrics(registry, bus)
        if args.metrics_out:
            writer = MetricsJsonlWriter(args.metrics_out, registry, collector)
        if args.dashboard:
            dash = ConsoleDashboard(registry, title=f"serve {args.arch}")
    tenant = None
    if args.power_cap > 0:
        from repro.cluster.job import ServeJob

        tenant = ServeJob("serve", eng, gov, cap_w=args.power_cap, n_ranks=n_dev)
        if registry is not None:
            tenant.attach_obs(registry, tracer, clock=time.monotonic)
    slo = SLOTracker(tpot_target=args.tpot_target or None)
    if registry is not None:
        registry.add_collector(lambda: slo.export_metrics(registry))
    reqs = _make_requests(args, cfg)
    t0 = time.time()
    done = eng.serve(reqs, governor=bus, slo=slo)
    dt = time.time() - t0
    if args.ingest == "batched":
        from repro.core import instrument

        instrument.flush_events()
        instrument.set_ingest_mode("event")
    n_tok = sum(len(r.out) for r in done)
    rep = gov.finalize()
    meter = eng._last_meter
    log.info("continuous_done", arch=args.arch, requests=len(done),
             tokens=n_tok, wall_s=dt, tok_per_s=n_tok / dt,
             compile_s=t_compile, fill=meter.fill_fraction,
             kv_int8=args.kv_int8)
    log.info("slack", priced_ms=rep.total_slack * 1e3, phases=rep.n_calls,
             downshifts=rep.n_downshifts, actuations=len(gov.actuation_log),
             energy_saving_pct=rep.energy_saving_pct)
    if gov.tuner is not None:
        per_site = {s: f"{th * 1e6:.0f}us" for s, th in gov.tuner.summary().items()}
        log.info("theta_auto", decisions=rep.n_theta_decisions,
                 theta_per_site=per_site)
    s = slo.summary()
    log.info("slo", ttft_p95_ms=s["ttft"]["p95"] * 1e3,
             tpot_p95_ms=s["tpot"]["p95"] * 1e3, completed=s["completed"])
    if tracer is not None:
        tnow = time.monotonic()
        tracer.sample("slo", "ttft_p95_ms", tnow, s["ttft"]["p95"] * 1e3)
        tracer.sample("slo", "tpot_p95_ms", tnow, s["tpot"]["p95"] * 1e3)
    if tenant is not None:
        stats = collector.collect() if collector is not None else None
        er = tenant.run_epoch(args.power_cap, stats=stats)
        log.info("power", cap_w=er.cap_w, draw_w=er.power_w,
                 exploited_pct=100 * er.exploited_ratio,
                 fill=tenant.fill_fraction)
    if writer is not None:
        writer.write()
        writer.close()
        log.info("metrics_out", path=args.metrics_out, lines=writer.n_lines)
    if dash is not None:
        dash.tick()
    if tracer is not None:
        tracer.ingest_governor(gov)         # spine-log actuations, once
        path = tracer.save(args.perfetto_out)
        log.info("perfetto_out", path=path, events=tracer.n_seen,
                 dropped=tracer.n_dropped)
    if trace_rec is not None:
        trace_rec.meta["report"] = rep.to_dict()
        path = trace_rec.save(args.trace_out)
        log.info("trace_out", records=trace_rec.n_seen,
                 dropped=trace_rec.n_dropped, path=path)
    return {"engine": eng, "requests": done, "tokens": n_tok, "wall_s": dt,
            "tok_per_s": n_tok / dt, "compile_s": t_compile, "report": rep,
            "slo": s}


def _fleet_trace(args):
    from repro.serve.fleet import (
        diurnal_trace,
        flash_crowd_trace,
        session_reuse_trace,
    )

    if args.fleet_trace == "diurnal":
        return diurnal_trace(duration_s=args.fleet_duration, seed=args.seed)
    if args.fleet_trace == "session-reuse":
        return session_reuse_trace(seed=args.seed)
    return flash_crowd_trace(duration_s=args.fleet_duration, seed=args.seed)


def _fleet_metrics_out(args, fill_registry) -> None:
    """Export fleet metrics (and validate-able snapshots) if asked."""
    if not (args.metrics_out or args.dashboard):
        return
    from repro.obs.export import ConsoleDashboard, MetricsJsonlWriter
    from repro.obs.metrics import MetricsRegistry

    registry = MetricsRegistry()
    fill_registry(registry)
    if args.metrics_out:
        with MetricsJsonlWriter(args.metrics_out, registry) as writer:
            writer.write()
            log.info("metrics_out", path=args.metrics_out,
                     lines=writer.n_lines)
    if args.dashboard:
        ConsoleDashboard(registry, title=f"fleet {args.arch}").tick()


def _run_fleet_sim(args, cfg) -> None:
    """Deterministic virtual-clock fleet: membership changes allowed, so
    this is the ``--autoscale`` path (spawning a real engine mid-run would
    recompile; the sim replica warms up in ``warmup_s`` of virtual time)."""
    from repro.serve.fleet import FleetConfig, FleetSim

    cap_w = args.power_cap if args.power_cap > 0 else 40.0
    fc = FleetConfig(cfg=cfg, n_replicas=args.fleet,
                     autoscale=args.autoscale, min_replicas=1,
                     n_slots=args.slots, cap_w=cap_w, floor_w=4.0,
                     step_s=0.01, ttft_target=1.5)
    sim = FleetSim(fc)
    trace = _fleet_trace(args)
    res = sim.run(trace)
    log.info("fleet_done", trace=trace.name, autoscaled=args.autoscale,
             requests=res.n_completed, tokens=res.tokens_out,
             joules_per_token=res.joules_per_token,
             ttft_attainment=res.ttft_attainment,
             prefix_hit_rate=res.prefix_hit_rate,
             peak_replicas=res.n_replicas_peak,
             scale_ups=res.n_scale_ups, scale_downs=res.n_scale_downs,
             cap_w=res.cap_w, max_alloc_sum_w=res.max_alloc_sum_w)
    _fleet_metrics_out(args, sim.export_metrics)


def _run_fleet_real(args, cfg, params) -> None:
    """N real engines behind the router on the wall clock: fixed
    membership, per-epoch watt arbitration from each replica's governor."""
    from repro.serve import ContinuousEngine
    from repro.serve.fleet import run_engine_fleet

    trace = _fleet_trace(args)
    reqs = trace.fresh_requests()
    longest = max(len(r.prompt) + r.max_new for r in reqs)
    max_len = longest + args.page_size
    max_len += (-max_len) % args.page_size
    cap_w = args.power_cap if args.power_cap > 0 else 40.0

    if args.ingest == "batched":
        from repro.core import instrument

        instrument.set_ingest_mode("batched")
    engines, governors, slos = [], [], []
    t0 = time.time()
    for _ in range(args.fleet):
        eng = ContinuousEngine(cfg, params, n_slots=args.slots,
                               max_len=max_len, page=args.page_size,
                               temperature=args.temperature,
                               attn_kernel=args.attn_kernel)
        eng.enable_prefix_cache()
        warm = make_batch(cfg, batch=1, seq_len=len(reqs[0].prompt),
                          kind="prefill")
        eng.generate(warm, n_steps=2)
        engines.append(eng)
        governors.append(Governor(policy=policy_for_theta(args.theta)))
        slos.append(SLOTracker())
    t_compile = time.time() - t0

    t0 = time.time()
    finished, router, arbiter, _ = run_engine_fleet(
        engines, reqs, cap_w=cap_w, floor_w=4.0,
        governors=governors, slos=slos)
    dt = time.time() - t0
    if args.ingest == "batched":
        from repro.core import instrument

        instrument.flush_events()
        instrument.set_ingest_mode("event")
    n_tok = sum(len(r.out) for r in finished)
    hits = sum(e.prefix_cache.n_hits for e in engines)
    lookups = sum(e.prefix_cache.n_lookups for e in engines)
    log.info("fleet_done", trace=trace.name, replicas=args.fleet,
             requests=len(finished), tokens=n_tok, wall_s=dt,
             tok_per_s=n_tok / dt, compile_s=t_compile,
             routed=len(router.decisions),
             prefix_routed=router.n_prefix_routed,
             prefix_hits=hits, prefix_lookups=lookups, cap_w=cap_w)

    def fill(registry):
        router.export_metrics(registry)
        arbiter.export_metrics(registry)
        for k, (gov, slo) in enumerate(zip(governors, slos)):
            if k == 0:
                # one replica's SLO percentiles as the fleet sample; the
                # registry families are unlabelled per-run singletons
                slo.export_metrics(registry)
        registry.gauge("fleet_replicas", "live replicas").set(
            float(args.fleet))
        registry.gauge("fleet_prefix_hit_rate",
                       "prompt tokens served from resident pages").set(
                           sum(e.prefix_cache.tokens_matched
                               for e in engines)
                           / max(sum(e.prefix_cache.tokens_looked_up
                                     for e in engines), 1))

    _fleet_metrics_out(args, fill)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over the paged KV pool")
    ap.add_argument("--fleet", type=int, default=0,
                    help="run N serving replicas behind the prefix-aware "
                         "router with per-epoch watt arbitration (real "
                         "engines on the wall clock; N is the static size, "
                         "or the autoscale maximum with --autoscale)")
    ap.add_argument("--autoscale", action="store_true",
                    help="fleet mode: SLO-driven membership on the "
                         "deterministic virtual-clock fleet simulator "
                         "(scale-ups/-downs reprice every replica's watts)")
    ap.add_argument("--fleet-trace",
                    choices=["flash-crowd", "diurnal", "session-reuse"],
                    default="flash-crowd",
                    help="fleet mode arrival scenario")
    ap.add_argument("--fleet-duration", type=float, default=10.0,
                    help="fleet trace duration in seconds (wall-clock for "
                         "--fleet, virtual for --autoscale)")
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--arrival-rate", type=float, default=40.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--attn-kernel", choices=["xla", "pallas"], default="xla",
                    help="decode attention hot path: XLA gather/scatter "
                         "reference or the Pallas paged kernel (fused "
                         "dequant + scatter/sample epilogue)")
    ap.add_argument("--tpot-target", type=float, default=0.0,
                    help="TPOT SLO target (s); 0 disables throttling")
    ap.add_argument("--theta", default="",
                    help="governor timeout (continuous mode only): seconds, "
                         "'auto' for the online ThetaTuner (decode underfill/"
                         "idle feed its per-site histograms), or 'predictive' "
                         "for the guarded predictor+timeout hybrid "
                         "(cntd_predictive); empty = the policy default")
    ap.add_argument("--trace-out", default="",
                    help="record the governor's event stream to this JSONL file "
                         "(continuous mode; replayable via repro.cluster.trace)")
    ap.add_argument("--power-cap", type=float, default=0.0,
                    help="job power cap in watts: attach a cluster.ServeJob tenant "
                         "+ RAPL-style cap actuator and report draw vs cap")
    ap.add_argument("--perfetto-out", default="",
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "(continuous mode: decode phase track, governor "
                         "counters, serve join/evict instants)")
    ap.add_argument("--metrics-out", default="",
                    help="write metrics-registry snapshots (with the exact "
                         "cumulative GovernorReport) to this JSONL file "
                         "(continuous mode)")
    ap.add_argument("--dashboard", action="store_true",
                    help="render the telemetry dashboard after the run "
                         "(continuous mode)")
    ap.add_argument("--ingest", choices=["event", "batched"], default="event",
                    help="instrument-layer event ingestion: 'batched' "
                         "accumulates raw 5-phase events into fixed-dtype "
                         "EventBatch chunks and exports ingest-health metrics "
                         "(events/s, occupancy, queue depth); the continuous "
                         "engine's own phase stream is occurrence-granular "
                         "and unaffected")
    obslog.add_flags(ap)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def setup(args):
    """Config, weights and mesh for parsed ``args``: returns ``(cfg,
    params, mesh, n_devices, model_parallel)`` with the weights random
    from ``PRNGKey(0)`` and placed by the serving partition rules."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.kv_int8:
        cfg = dataclasses.replace(cfg, kv_quant=True)

    n = len(jax.devices())
    mp = max(g for g in range(1, args.model_parallel + 1) if n % g == 0)
    mesh = SH.make_mesh((n // mp, mp), ("data", "model"))
    install_constraint(SH.activation_constraint_fn(mesh))

    params = init_params(cfg, jax.random.PRNGKey(0))
    if mp > 1 or n > 1:
        psh = SH.serve_param_shardings(mesh, params)
        params = jax.tree.map(lambda a, s: jax.device_put(a, s), params, psh)
    return cfg, params, mesh, n, mp


def main(argv=None):
    enable_compile_cache()
    args = build_parser().parse_args(argv)
    obslog.configure_from_args(args)
    cfg, params, mesh, n, mp = setup(args)

    if not args.continuous and not args.fleet and (
            args.theta or args.trace_out or args.power_cap > 0
            or args.perfetto_out or args.metrics_out
            or args.dashboard):
        # static mode builds no governor: these flags would be silent no-ops
        log.warning("flags_ignored",
                    why="--theta/--trace-out/--power-cap/telemetry need the "
                        "continuous engine's governor (add --continuous)")

    with jax.set_mesh(mesh):
        if args.fleet:
            if args.autoscale:
                _run_fleet_sim(args, cfg)
            else:
                _run_fleet_real(args, cfg, params)
        elif args.continuous:
            return _run_continuous(args, cfg, params, mesh, n, mp)
        else:
            _run_static(args, cfg, params)


if __name__ == "__main__":
    main()
