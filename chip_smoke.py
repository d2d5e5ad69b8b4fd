"""Chip smoke: drive the serving and instrumented-training paths once on a TPU.

  python chip_smoke.py                # one chip (the default)
  python chip_smoke.py --four-chips   # the four-chip data-parallel step only

Default phases, one process:

* serve — ``internlm2-1.8b`` at its published widths (random weights from
  a seed) through the continuous path of ``repro.launch.serve`` with the
  compiled Pallas paged-decode kernel: 8 slots, page 16, 8 requests of
  256-token prompts, up to 32 greedy tokens each.  Every request must
  finish with its requested token count, and one decode step's logits
  from the Pallas kernel must match the XLA paged reference on the same
  weights, prompts and pool: the RMS of the difference within
  ``LOGIT_RTOL`` of the reference logits' RMS (bf16 compute through 24
  layers).
* train — ``repro.launch.train`` on ``countdown-100m``, live events,
  ``--instrument profile``, 5 steps of batch 8 x 1024 tokens.  The loss
  must be finite, and every ``cd_psum`` call must reach the governor as
  three phase events (barrier enter, barrier exit, copy exit) per rank.

``--four-chips`` runs only the same live-events step on a four-chip data
mesh, first with ``--instrument off`` and then with ``--instrument
profile``: the two losses must agree within ``LOSS_RTOL`` and the
governor must see four ranks.

Findings print on earlier lines; the tokens/s and seconds there are smoke
figures, not benchmark numbers.  The last line is one JSON object naming
the device.  Any failed check, or a first device that is not a TPU, exits
non-zero with no such line.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SERVE_ARCH = "internlm2-1.8b"
TRAIN_ARCH = "countdown-100m"
SERVE_ARGV = [
    "--arch", SERVE_ARCH, "--continuous", "--attn-kernel", "pallas",
    "--temperature", "0", "--slots", "8", "--page-size", "16",
    "--n-requests", "8", "--prompt-len", "256", "--steps", "32",
    "--seed", "0",
]
TRAIN_ARGV = [
    "--arch", TRAIN_ARCH, "--live-events", "--steps", "5", "--batch", "8",
    "--seq", "1024", "--seed", "0",
]
CD_PSUM_SITES = 2        # per live-events step: the gradient tree, the loss
# rms(logit_pallas - logit_xla) allowed, as a share of rms(logit_xla):
# sixteen bf16 roundings (2**-8 each) of a typical logit.  A wrong page,
# head or mask moves the logits by about their own RMS.  (The logits are
# bf16, so a max-abs bound this tight would sit below one bf16 step of
# the largest logits: 2**-4 at |logit| >= 8.)
LOGIT_RTOL = 1.0 / 16
# instrumented vs uninstrumented loss: the barrier adds a 1-element psum
# and optimization barriers, so only fusion-order rounding may differ
LOSS_RTOL = 1e-3
PHASES = ("barrier_enter", "barrier_exit", "copy_exit")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def say(**kv) -> None:
    print("chip_smoke " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def serve_phase(extra=()):
    """Serve through ``repro.launch.serve``'s continuous path, then compare
    the Pallas step with the XLA reference on the same weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.launch import serve
    from repro.serve import ContinuousEngine
    from repro.serve.engine import EngineSession, make_paged_decode_step
    from repro.serve.scheduler import Request

    args = serve.build_parser().parse_args([*SERVE_ARGV, *extra])
    cfg, params, mesh, n, mp = serve.setup(args)
    with jax.set_mesh(mesh):
        res = serve._run_continuous(args, cfg, params, mesh, n, mp)
        eng = res["engine"]
        done = sorted(res["requests"], key=lambda r: r.rid)
        check(len(done) == args.n_requests,
              f"{len(done)} of {args.n_requests} requests finished")
        for r in done:
            check(len(r.out) == r.max_new,
                  f"request {r.rid}: {len(r.out)} tokens, asked {r.max_new}")

        # the same prompts through the XLA paged engine: greedy agreement
        prompts = np.stack([np.asarray(r.prompt, np.int32) for r in done])
        ref = ContinuousEngine(cfg, params, n_slots=args.slots,
                               max_len=eng.max_len, page=args.page_size,
                               attn_kernel="xla")
        want = np.asarray(ref.generate({"tokens": prompts}, n_steps=args.steps))
        same = sum(int(np.sum(np.asarray(r.out) == want[i, :len(r.out)]))
                   for i, r in enumerate(done))
        agree = same / sum(len(r.out) for r in done)

        # one decode step, both kernels, on the XLA engine's joined pool
        sess = EngineSession(ref)
        for p in prompts:
            sess.submit(Request(prompt=p, max_new=2, arrival=0.0))
        sess.admit(now=0.0)
        for req in sess.sched.active.values():
            ref._grow_pages(req)                 # the page the step writes
        m_live = min(ref._table.shape[1],
                     int(ref._lengths.max()) // args.page_size + 1)
        step_args = (params, jnp.asarray(ref._tokens), jnp.asarray(ref._lengths),
                     jnp.asarray(ref._table[:, :m_live]), ref.pool.blocks)
        steps = {k: jax.jit(make_paged_decode_step(cfg, k))
                 for k in ("xla", "pallas")}
        hlo = steps["pallas"].lower(*step_args).as_text()
        logits = {k: np.asarray(f(*step_args)[0], np.float32)
                  for k, f in steps.items()}
    lx, lp = logits["xla"], logits["pallas"]
    rms = float(np.sqrt(np.mean(lx * lx)))
    rel = float(np.sqrt(np.mean((lp - lx) ** 2))) / rms
    stats = jax.devices()[0].memory_stats() or {}
    say(phase="serve", arch=cfg.name, requests=len(done), tokens=res["tokens"],
        greedy_agreement_vs_xla=agree, logits_rel_rms_diff=rel,
        logits_max_abs_diff=float(np.max(np.abs(lp - lx))), logits_rms=rms,
        compile_s=res["compile_s"],
        peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"),
        smoke_tok_per_s=res["tok_per_s"])
    check(not ops._interpret() and "tpu_custom_call" in hlo,
          "the Pallas decode step lowers to a Mosaic kernel")
    check(lp.shape == (args.slots, cfg.vocab) and np.isfinite(lp).all(),
          f"pallas logits finite, shape {lp.shape}")
    check(rel <= LOGIT_RTOL,
          f"pallas vs xla logits: rms diff {rel} of rms {rms} > {LOGIT_RTOL}")
    del res, eng, ref, sess, params, step_args
    gc.collect()
    return cfg


def train_phase(instrument: str, extra=()) -> dict:
    """One ``repro.launch.train`` run of the live-events step; counts the
    phase events every rank publishes onto the instrument bus."""
    from repro.core import instrument as cd
    from repro.launch import train

    counts = collections.Counter()

    def count(rank, phase, call_id, t):
        counts[(int(rank), phase)] += 1

    bus = cd.get_event_bus()
    bus.subscribe(count)
    try:
        out = train.main([*TRAIN_ARGV, "--instrument", instrument, *extra])
    finally:
        bus.unsubscribe(count)
    ranks = sorted({r for r, _ in counts})
    rep = out["report"]
    out["ranks"] = ranks
    say(phase="train", arch=TRAIN_ARCH, instrument=instrument,
        devices=out["n_devices"], steps=out["steps"], loss=out["loss"],
        event_ranks=len(ranks),
        events={ph: sum(counts[(r, ph)] for r in ranks) for ph in PHASES},
        cd_psum_calls=rep.n_calls if rep else "none",
        slack_s=rep.total_slack if rep else "none")
    check(math.isfinite(out["loss"]), f"train loss {out['loss']} is finite")
    if instrument == "profile":
        want = out["steps"] * CD_PSUM_SITES
        for r in ranks:
            got = [counts[(r, ph)] for ph in PHASES]
            check(got == [want] * 3,
                  f"rank {r}: {dict(zip(PHASES, got))} phase events, "
                  f"want {want} of each")
        check(len(ranks) == out["n_devices"],
              f"events from ranks {ranks} on {out['n_devices']} devices")
        check(rep is not None and rep.n_calls == want,
              f"governor counted {rep and rep.n_calls} cd_psum calls, "
              f"want {want}")
    else:
        check(not counts, f"instrument off published events: {dict(counts)}")
    return out


def one_chip() -> None:
    from repro.configs import get_config

    cfg = serve_phase()
    check(cfg == get_config(SERVE_ARCH), "serve ran at published widths")
    train_phase("profile")


def four_chips() -> None:
    import jax

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, want 4")
    off = train_phase("off")
    prof = train_phase("profile")
    diff = abs(prof["loss"] - off["loss"])
    say(phase="train_4chip", loss_off=off["loss"], loss_profile=prof["loss"],
        loss_abs_diff=diff, loss_rtol=LOSS_RTOL)
    check(prof["ranks"] == [0, 1, 2, 3], f"governor saw ranks {prof['ranks']}")
    check(diff <= LOSS_RTOL * abs(off["loss"]),
          f"loss off {off['loss']} vs profile {prof['loss']}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip live-events train step, "
                         "instrument off vs profile")
    args = ap.parse_args()
    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (first device is "
                         f"{dev.platform}); nothing was run")
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
