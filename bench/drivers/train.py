"""Training window: the program's live-events data-parallel step
(``launch.train.build_live``: ``cd_psum`` on the gradient tree and the
loss, then AdamW), fed by ``train.data.DataLoader``, on a ``data`` mesh
over every chip.

Set-up makes the weights from the seed and the optimizer state on the
device, builds the step and drives it through its first three steps, with
the window's own call and feed; the first compiles.  Those three steps are
what the reference follows.  The window keeps the mix's ``ahead_s``
seconds of steps dispatched ahead of the one it waits for (at least one
step), so that the chip stays fed while the host stands still.  Once
``seconds`` have passed it sends nothing more, waits for every step it
sent, and reads the clock after that wait; ``train_tok_s`` is the tokens
of all those steps over all that time, on all chips.

``correct``: against the plain reference's three steps, the loss of each
step, the first gradient as the optimizer got it (read from its first
moment after step one), and each parameter's change after step three,
both norms taken per leaf (per layer of a stacked leaf).  In profile mode
every ``cd_psum`` call must reach the bus as exactly three phase events
per rank; with the instrumentation off, none.
"""
from __future__ import annotations

import collections
import gc
import math

import numpy as np

from bench.drivers.common import TracedWindow, memory_peak_bytes, now, span

SETUP_STEPS = 3
CD_PSUM_PER_STEP = 2           # the gradient tree and the loss
PHASES = ("barrier_enter", "barrier_exit", "copy_exit")


def opt_config(conf):
    from repro.train.optimizer import OptConfig

    o = conf["optimizer"]
    return OptConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                     weight_decay=o["weight_decay"], grad_clip=o["grad_clip"],
                     warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
                     min_lr_ratio=o["min_lr_ratio"])


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import instrument
    from repro.core.governor import Governor
    from repro.dist.sharding import make_mesh
    from repro.launch import train as program
    from repro.models.transformer import init_params
    from repro.train.data import DataLoader
    from repro.train.optimizer import init_opt_state

    from bench import model, weights

    conf, mix, seed, seconds = ctx.conf, ctx.mix, ctx.seed, ctx.seconds
    ref = model.reference(conf)
    cfg = model.model_config(conf)
    opt_cfg = opt_config(conf)
    devices = jax.devices()
    n = len(devices)
    mode = mix["instrument"]
    counts = collections.Counter()

    def count(rank, phase, call_id, t):
        counts[(int(rank), phase)] += 1

    bus = instrument.get_event_bus()
    if mode != "off":
        instrument.set_mode(mode)
        instrument.enable_events(True)
    instrument.set_ingest_mode(mix["ingest"])
    bus.subscribe(count)
    try:
        mesh = make_mesh((n, 1), ("data", "model"))
        repl = NamedSharding(mesh, P())
        shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        make_p0 = lambda: weights.make(shapes, seed, jax.tree.map(lambda _: repl, shapes))
        params = make_p0()
        opt = jax.jit(lambda p: init_opt_state(p, opt_cfg),
                      out_shardings=repl)(params)
        state, step_fn = program.build_live(mesh, cfg, opt_cfg,
                                            {"params": params, "opt": opt})
        del params, opt
        rows = mix["per_chip_batch"] * n
        loader = DataLoader(cfg, batch=rows, seq_len=mix["seq_len"], seed=seed)
        tokens_per_step = rows * mix["seq_len"]
        b1 = opt_cfg.b1
        losses, batches = [], []
        with jax.set_mesh(mesh):
            for s in range(1, SETUP_STEPS + 1):
                b = next(loader)
                batches.append((np.asarray(b["tokens"]), np.asarray(b["labels"])))
                t = now()
                state, m = step_fn(state, b)
                losses.append(float(m["loss"]))
                step_s = now() - t          # the last set-up step's, compiled
                if s == 1:          # m after one step is (1 - b1) x the gradient used
                    g_prog = {k: v / (1.0 - b1) for k, v in
                              ref.leaf_norms(state["opt"]["m"]).items()}
            p0 = make_p0()
            change_prog = ref.leaf_norms(jax.tree.map(jnp.subtract, state["params"], p0))
            del p0
            jax.block_until_ready(state)
            ctx.mark_setup_done()

            gov = Governor() if mode == "profile" else None
            if gov is not None:
                bus.subscribe(gov)
            trace = TracedWindow(ctx.trace)
            tr = ctx.trace
            depth = max(1, math.ceil(mix["ahead_s"] / step_s))
            ahead = collections.deque()
            trace.start()
            t0 = now()
            done = 0
            while now() - t0 < seconds:
                with span("next", tr):
                    b = next(loader)
                with span("dispatch", tr):
                    state, m = step_fn(state, b)
                done += 1
                ahead.append(m["loss"])
                if len(ahead) > depth:
                    with span("block", tr):
                        jax.block_until_ready(ahead.popleft())
            with span("block", tr):
                jax.block_until_ready((state, list(ahead)))
            t_end = now()
            trace.stop()
            ctx.mark_window_done()
        window = t_end - t0
        steps_run = SETUP_STEPS + done
        instrument.flush_events()
        report = gov.finalize() if gov is not None else None
        if gov is not None:
            bus.unsubscribe(gov)
        loader.close()
    finally:
        bus.unsubscribe(count)
        instrument.set_ingest_mode("event")
        instrument.set_mode("off")
        instrument.enable_events(False)
    peak = memory_peak_bytes(jax.local_devices())
    e2e = {"train_tok_s": done * tokens_per_step / window}
    ctx.log(f"steps in window {done} over {window:.4f} s, {depth} queued ahead, "
            f"setup losses {losses}")
    del state, m, step_fn
    gc.collect()
    summary = trace.reduce()

    # ---- phase events: 3 per cd_psum call per rank in profile mode, else none
    ranks = sorted({r for r, _ in counts})
    want = steps_run * CD_PSUM_PER_STEP if mode == "profile" else 0
    bad = 0
    for r in (range(n) if mode == "profile" else ranks):
        bad += sum(abs(counts[(r, ph)] - want) for ph in PHASES)
    bad += sum(v for (r, ph), v in counts.items() if ph not in PHASES)

    # ---- the reference's three steps, once the program's state is freed
    t = now()
    r_loss, r_grad, r_change = ref.train_reference(conf, seed, batches, "f32")
    ctx.log(f"reference {now() - t:.3f} s, losses {r_loss}")
    checks = compare(conf, losses, g_prog, change_prog, r_loss, r_grad, r_change)
    checks["phase_event_errors"] = (bad, 0)
    layer = {"window_s": window, "steps": done, "tokens_per_step": tokens_per_step,
             "chips": n, "rows": rows, "seq": mix["seq_len"]}
    if report is not None:
        layer["slack_s"] = report.total_slack / n        # per rank
        layer["governed_steps"] = done
    return {"attempted": done, "failed": 0, "e2e": e2e, "checks": checks,
            "memory_peak_bytes": peak, "summary": summary, "cfg": cfg, "layer": layer}


def compare(conf, losses, g_prog, change_prog, r_loss, g_ref, change_ref) -> dict:
    """The three numbers compared, each with the configuration's limit.

    A norm is compared per leaf as the gap between the program's and the
    reference's, over the larger of the reference's norm of that leaf and
    of the median leaf.  A leaf whose reference gradient is under a
    thousandth of the median leaf's moves by round-off alone and is left
    out of the change."""
    lim = conf["limits"]
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, r_loss))
    med_g = float(np.median(list(g_ref.values())))
    grad_gap = max(abs(g_prog[k] - v) / max(v, med_g) for k, v in g_ref.items())
    moving = [k for k, v in g_ref.items() if v >= 1e-3 * med_g]
    med_c = float(np.median([change_ref[k] for k in moving]))
    change_gap = max(abs(change_prog[k] - change_ref[k]) / max(change_ref[k], med_c)
                     for k in moving)
    return {"loss_gap": (loss_gap, lim["loss_gap"]),
            "grad_norm_gap": (grad_gap, lim["grad_norm_gap"]),
            "change_norm_gap": (change_gap, lim["change_norm_gap"])}
