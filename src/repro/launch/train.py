"""Production training driver: checkpoint/restart, simulated node failures,
elastic re-meshing, straggler telemetry, COUNTDOWN instrumentation.

Example (CPU host):
  PYTHONPATH=src python -m repro.launch.train --arch countdown-100m \
      --steps 20 --batch 8 --seq 128 --checkpoint-dir /tmp/ckpt \
      --save-every 5 --fail-at 12

On a real cluster the same driver runs under one process per host with
jax.distributed.initialize(); the mesh factory, sharding rules, checkpoint
protocol and failure path are identical.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.core import instrument
from repro.core.governor import Governor
from repro.core.policies import policy_for_theta
from repro.dist import sharding as SH
from repro.dist.checkpoint import CheckpointManager
from repro.dist.elastic import ElasticMesh, FailureInjector
from repro.launch.compile_cache import enable_compile_cache
from repro.models.hooks import install_constraint
from repro.obs import log as obslog
from repro.train.data import DataLoader
from repro.train.loop import TrainConfig, init_state, make_train_step
from repro.train.optimizer import OptConfig

log = obslog.get_logger("train")


def build(mesh, cfg, opt_cfg, state_host):
    install_constraint(SH.activation_constraint_fn(mesh))
    ps = SH.param_shardings(mesh, state_host["params"])
    osd = SH.opt_state_shardings(mesh, ps, state_host["opt"])
    sh = {"params": ps, "opt": osd}
    state = jax.tree.map(lambda a, s: jax.device_put(a, s), state_host, sh)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=(0,))
    return state, step_fn


def build_live(mesh, cfg, opt_cfg, state_host):
    """Fully-manual data-parallel step with instrumented collectives.

    The production ``build`` path partitions with jit + sharding rules; its
    collectives are XLA-inserted, so the host phase events (io_callback)
    that feed the governor/telemetry never fire.  ``--live-events`` swaps in
    this step: replicated params/opt, batch split over "data", and the
    gradient/loss all-reduce routed through ``cd_psum`` — the artificial
    barrier + 3-phase event sequence of the paper's PMPI layer, legal here
    because the whole region is manual over every mesh axis.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.instrument import cd_psum
    from repro.models.transformer import loss_fn
    from repro.train.optimizer import adamw_update

    n_data = int(mesh.shape["data"])
    # every mesh axis is manual in this region, where a sharding
    # constraint may name none of them: clear whatever hook an earlier
    # build (or a server in the same process) installed
    install_constraint(None)

    def per_device_step(state, batch):
        params, opt = state["params"], state["opt"]
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch)[0])(params)
        grads = cd_psum(grads, "data")
        grads = jax.tree.map(lambda g: g / n_data, grads)
        loss = cd_psum(loss, "data") / n_data
        params, opt, m = adamw_update(params, grads, opt, opt_cfg)
        return {"params": params, "opt": opt}, {**m, "loss": loss}

    repl = NamedSharding(mesh, P())
    dsh = NamedSharding(mesh, P("data"))
    state = jax.device_put(state_host,
                           jax.tree.map(lambda _: repl, state_host))
    # fully-specified jit shardings: the production idiom
    step_fn = jax.jit(
        jax.shard_map(
            per_device_step, mesh=mesh,
            in_specs=(P(), P("data")), out_specs=(P(), P()),
            axis_names=set(mesh.axis_names), check_vma=False,
        ),
        in_shardings=(
            jax.tree.map(lambda _: repl, state),
            {"tokens": dsh, "labels": dsh, "mask": dsh},
        ),
        out_shardings=(jax.tree.map(lambda _: repl, state),
                       {"grad_norm": repl, "lr": repl, "loss": repl}),
    )
    return state, step_fn


def main(argv=None) -> dict:
    """Run the driver; returns the last logged loss, the step reached, the
    device count and (under ``--instrument profile``) the governor report."""
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="countdown-100m")
    ap.add_argument("--reduced", action="store_true", help="smoke-size config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at", type=int, default=0,
                    help="simulate a node failure at this step (fault-tolerance demo)")
    ap.add_argument("--instrument", choices=["off", "barrier", "profile"],
                    default=None,
                    help="cd_* mode (default off; profile with --live-events "
                         "or the event-consuming flags below)")
    ap.add_argument("--live-events", action="store_true",
                    help="run the step as a fully-manual data-parallel shard_map "
                         "with cd_psum gradient reduction, so host phase events "
                         "actually fire (the jit path's XLA-inserted collectives "
                         "cannot emit them); data parallelism only.  Implies "
                         "--instrument profile unless --instrument is given: "
                         "'--instrument off' is the same step uninstrumented, "
                         "the baseline for the instrumentation's overhead")
    ap.add_argument("--theta", default="",
                    help="governor timeout: seconds (e.g. 500e-6), 'auto' for "
                         "the online ThetaTuner (cntd_adaptive policy), or "
                         "'predictive' for the guarded predictor+timeout "
                         "hybrid (cntd_predictive: pre-arms the downshift "
                         "when predicted slack clears the residue-cost bar); "
                         "empty = the policy default (500 us fixed)")
    ap.add_argument("--trace-out", default="",
                    help="record the governor's event stream to this JSONL file "
                         "(replayable via repro.cluster.trace; implies --instrument profile)")
    ap.add_argument("--power-cap", type=float, default=0.0,
                    help="job power cap in watts: attach a cluster.GovernorJob tenant "
                         "+ RAPL-style cap actuator and report per-interval power "
                         "(implies --instrument profile)")
    ap.add_argument("--perfetto-out", default="",
                    help="write a Chrome-trace/Perfetto JSON of the run (per-rank "
                         "phase tracks + governor/arbiter counter tracks; implies "
                         "--instrument profile)")
    ap.add_argument("--metrics-out", default="",
                    help="append one metrics-registry snapshot per report interval "
                         "to this JSONL file, each embedding the exact cumulative "
                         "GovernorReport (implies --instrument profile)")
    ap.add_argument("--dashboard", action="store_true",
                    help="render a console telemetry dashboard at the report "
                         "cadence (implies --instrument profile)")
    ap.add_argument("--ingest", choices=["event", "batched"], default="event",
                    help="event-bus ingestion: 'event' publishes each phase "
                         "event as it fires; 'batched' accumulates fixed-dtype "
                         "EventBatch columns (21 B/event) and delivers them "
                         "chunk-at-a-time to batch-capable subscribers — same "
                         "stream order, bit-identical governor report, ~8x the "
                         "sink throughput")
    obslog.add_flags(ap)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    obslog.configure_from_args(args)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    cfg = dataclasses.replace(cfg, remat=True)
    opt_cfg = OptConfig(lr=args.lr, warmup_steps=min(100, args.steps // 10 + 1),
                        total_steps=args.steps)

    recorder = None
    if args.trace_out:
        from repro.cluster.trace import TraceRecorder

        recorder = TraceRecorder(meta={"driver": "train", "arch": args.arch,
                                       "steps": args.steps,
                                       "theta": args.theta or "default"})
    obs_on = bool(args.perfetto_out or args.metrics_out or args.dashboard)
    if args.live_events and args.model_parallel != 1:
        log.warning("live_events_dp_only", model_parallel=args.model_parallel,
                    using=1)
        args.model_parallel = 1
    needs_events = bool(args.trace_out or args.power_cap > 0 or args.theta
                        or obs_on)
    if args.instrument is None:
        args.instrument = "profile" if needs_events or args.live_events else "off"
    elif needs_events and args.instrument != "profile":
        # the recorder records events, the tenant polls interval snapshots,
        # the telemetry stack consumes both, and the governor/tuner feeds
        # them all: everything is empty (a silent no-op) without the
        # profile-mode event stream
        log.info("instrument_upgrade", requested=args.instrument, using="profile",
                 why="--trace-out/--power-cap/--theta/telemetry need phase events")
        args.instrument = "profile"

    registry = tracer = collector = busmetrics = writer = dash = None
    if obs_on:
        from repro.obs.export import ConsoleDashboard, MetricsJsonlWriter
        from repro.obs.metrics import BusMetrics, GovernorCollector, MetricsRegistry
        from repro.obs.tracer import GovernorTap, RecorderFanout, SpanTracer

        registry = MetricsRegistry()
        busmetrics = BusMetrics(registry)
        if args.perfetto_out:
            tracer = SpanTracer(meta={"driver": "train", "arch": args.arch,
                                      "steps": args.steps})
        # production wiring: the whole obs stack rides the governor's
        # recorder slot (retired occurrences + theta decisions), never the
        # per-event bus — that is the 10% bench budget's contract
        tap = GovernorTap(tracer, metrics=busmetrics)
        recorder = RecorderFanout([recorder, tap]) if recorder is not None \
            else tap
    governor = Governor(policy=policy_for_theta(args.theta), recorder=recorder)
    if registry is not None:
        collector = GovernorCollector(registry, governor)
        if args.ingest == "batched":
            from repro.obs.metrics import IngestMetrics

            IngestMetrics(registry, instrument.get_event_bus())
        if args.metrics_out:
            writer = MetricsJsonlWriter(args.metrics_out, registry, collector)
        if args.dashboard:
            dash = ConsoleDashboard(registry, title=f"train {args.arch}")
    tenant = None
    if args.power_cap > 0:
        from repro.cluster.job import GovernorJob

        tenant = GovernorJob("train", governor, n_ranks=len(jax.devices()),
                             cap_w=args.power_cap)
        if registry is not None:
            tenant.attach_obs(registry, tracer, clock=time.monotonic)
    if args.instrument != "off":
        instrument.set_mode(args.instrument)
        if args.live_events:
            instrument.enable_events(True)   # fully-manual mesh: events legal
        if args.instrument == "profile":
            # the governor is one bus subscriber among N (probes attach
            # beside it without displacing anything); telemetry hangs off
            # the governor's recorder slot, not the bus
            bus = instrument.get_event_bus()
            bus.subscribe(governor)
        if args.ingest == "batched":
            instrument.set_ingest_mode("batched")

    em = ElasticMesh(axis_names=("data", "model"))
    mesh = em.build(model_parallel=args.model_parallel)
    injector = FailureInjector(
        fail_at_steps=[args.fail_at] if args.fail_at else [],
        device_ids=[jax.devices()[-1].id],
    )
    mgr = CheckpointManager(args.checkpoint_dir, keep=3) if args.checkpoint_dir else None

    state_host = init_state(cfg, opt_cfg, jax.random.PRNGKey(args.seed))
    start_step = 0
    if mgr and args.resume:
        latest = mgr.latest_step()
        if latest is not None:
            skel = jax.tree.map(lambda a: np.zeros(a.shape, a.dtype), state_host)
            start_step, state_host = latest, mgr.load(latest, skel)
            log.info("resumed", step=latest)

    builder = build_live if args.live_events else build
    state, step_fn = builder(mesh, cfg, opt_cfg, state_host)
    loader = DataLoader(cfg, batch=args.batch, seq_len=args.seq, seed=args.seed)

    t_start = time.time()
    step = start_step
    loss = float("nan")
    # mesh-epoch loop: each epoch runs under one mesh; a failure breaks out,
    # rebuilds the mesh from the surviving devices and restores the latest
    # checkpoint (the 1000-node recovery path, scaled down)
    while step < args.steps:
        failed_device = None
        with jax.set_mesh(mesh):
            while step < args.steps:
                failed_device = injector.check(step)
                if failed_device is not None:
                    break
                batch = next(loader)
                state, metrics = step_fn(state, batch)
                step += 1
                if mgr and step % args.save_every == 0:
                    mgr.save(step, jax.device_get(state))
                if step % max(1, args.steps // 20) == 0 or step == args.steps:
                    loss = float(metrics["loss"])
                    log.info(
                        "step", step=step, loss=loss,
                        grad_norm=float(metrics["grad_norm"]),
                        lr=float(metrics["lr"]),
                        s_per_step=(time.time() - t_start)
                        / max(step - start_step, 1),
                    )
                    stats = collector.collect() if collector is not None else None
                    if tenant is not None:
                        # hand the collector's poll over: the governor keeps
                        # one snapshot mark, so tenant + collector must share
                        # a single interval stream
                        er = tenant.run_epoch(args.power_cap, stats=stats)
                        log.info("power", cap_w=er.cap_w, draw_w=er.power_w,
                                 exploited_pct=100 * er.exploited_ratio,
                                 phases=er.n_calls)
                    if tracer is not None and stats is not None:
                        tnow = time.monotonic()
                        busy = max(stats.busy, 1e-30)
                        tracer.sample("governor", "slack_ratio_pct", tnow,
                                      100.0 * stats.slack / busy)
                        tracer.sample("governor", "overlap_ratio_pct", tnow,
                                      100.0 * stats.overlap / busy)
                        saving = registry.get_value("governor_energy_saving_pct")
                        tracer.sample("governor", "energy_saving_pct", tnow,
                                      saving or 0.0)
                    if writer is not None:
                        writer.write(step=step)
                    if dash is not None:
                        dash.tick(step=step)
        if failed_device is not None:
            log.warning("device_failed", step=step, device=failed_device,
                        action="re-meshing")
            jax.block_until_ready(state)            # drain in-flight work
            em.fail(failed_device)
            if mgr is None:
                raise RuntimeError("node failure without checkpointing enabled")
            latest = mgr.latest_step()
            host_state = jax.device_get(state)
            del state
            jax.clear_caches()                      # old-mesh executables out
            if latest is None:
                # failed before the first checkpoint: cold restart from init
                latest = 0
                state_host = init_state(cfg, opt_cfg, jax.random.PRNGKey(args.seed))
            else:
                skel = jax.tree.map(
                    lambda a: np.zeros(a.shape, a.dtype), host_state
                )
                state_host = mgr.load(latest, skel)
            mesh = em.build(model_parallel=args.model_parallel)
            state, step_fn = builder(mesh, cfg, opt_cfg, state_host)
            step = latest
            log.info("resumed", devices=len(em.healthy_devices()), step=latest)
    loader.close()
    if args.ingest == "batched":
        # drain the partial accumulator + any queued chunks while the
        # governor is still subscribed, then drop back to per-event mode
        instrument.flush_events()
        instrument.set_ingest_mode("event")
    rep = None
    if args.instrument == "profile":
        rep = governor.finalize()
        log.info("governor", calls=rep.n_calls, downshifts=rep.n_downshifts,
                 slack_s=rep.total_slack, exploited_s=rep.exploited_slack,
                 overlap_s=rep.total_overlap,
                 energy_saving_pct=rep.energy_saving_pct,
                 stragglers=rep.stragglers)
        if governor.tuner is not None:
            thetas = sorted(governor.tuner.summary().values())
            if thetas:
                log.info("theta_auto", decisions=rep.n_theta_decisions,
                         sites=len(thetas), theta_lo_us=thetas[0] * 1e6,
                         theta_hi_us=thetas[-1] * 1e6)
            else:
                log.info("theta_auto", sites=0)
    if tenant is not None:
        log.info("power_total", energy_j=tenant.total_energy_j,
                 wall_s=tenant.total_wall_s,
                 cap_commits=len(tenant.actuator.commits),
                 suppressed=tenant.actuator.n_suppressed)
    if writer is not None:
        # one terminal snapshot: the acceptance contract is that this
        # line's embedded report equals the run's final GovernorReport
        writer.write(step=step)
        writer.close()
        log.info("metrics_out", path=args.metrics_out, lines=writer.n_lines)
    if dash is not None:
        dash.tick(step=step)
    if tracer is not None:
        tracer.ingest_governor(governor)    # spine-log actuations, once
        path = tracer.save(args.perfetto_out)
        log.info("perfetto_out", path=path, events=tracer.n_seen,
                 dropped=tracer.n_dropped)
    if recorder is not None and args.trace_out:
        trace_rec = recorder.children[0] if hasattr(recorder, "children") \
            else recorder
        if args.instrument == "profile":
            trace_rec.meta["report"] = rep.to_dict()
        path = trace_rec.save(args.trace_out)
        log.info("trace_out", records=trace_rec.n_seen,
                 dropped=trace_rec.n_dropped, path=path)
    instrument.set_mode("off")
    if args.live_events:
        instrument.enable_events(False)
    bus = instrument.get_event_bus()
    bus.unsubscribe(governor)
    return {"loss": loss, "steps": step, "n_devices": len(em.healthy_devices()),
            "report": rep}


if __name__ == "__main__":
    main()
