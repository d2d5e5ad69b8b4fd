"""Serving window: an open loop of seeded requests through
``ContinuousEngine`` and ``EngineSession`` (submit, admit, decode_step),
exactly as the program serves them.

Set-up makes the weights from the seed, builds the engine with the
configuration's ``serving`` options (``ContinuousEngine``'s own keyword
arguments, and ``prefix_cache``: the keyword arguments of
``enable_prefix_cache``), and warms up, through the session's public
calls, every program the window's traffic can reach: a prefill and a join
per distinct prompt length, and a decode step per page-table width.  The
window opens with all requests submitted at their scheduled arrivals and
closes after ``seconds``; the engine then drains what arrived, so every
request gets its first token.  Token times are read after each admit and
decode step, on the session's clock.

``correct``: a sample of finished requests, the longest among them, is
run through the plain reference; the widest gap by which a served
token's reference logit lies below the reference's best must stay within
the configuration's limit.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from bench import stats, workload
from bench.drivers.common import TracedWindow, memory_peak_bytes, span

DRAIN_S = 60.0


def build(conf, seed, log):
    """The model config and the engine, its weights made from the seed."""
    import jax
    from repro.models.transformer import init_params
    from repro.serve import ContinuousEngine

    from bench import model, weights

    cfg = model.model_config(conf)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    t = time.perf_counter()
    params = jax.block_until_ready(weights.make(shapes, seed))
    log(f"weights {time.perf_counter() - t:.3f} s")
    opts = dict(conf["serving"])
    prefix = opts.pop("prefix_cache", None)
    eng = ContinuousEngine(cfg, params, temperature=0.0, **opts)
    if prefix is not None:
        eng.enable_prefix_cache(**prefix)
    return cfg, eng


def warm(eng, mix, seconds, rng, vocab, log):
    """Every shape the window can reach, through public session calls: a
    request of each distinct prompt length (its prefill, its join and a
    decode step), then one request that decodes from the shortest prompt
    (with the prefix cache on, from a join from cached pages at position
    1) to the longest prompt and answer, through every table width."""
    from repro.serve.engine import EngineSession
    from repro.serve.scheduler import Request

    plens, pmax, omax, pmin = workload.serve_shapes(mix, seconds)
    prompt = lambda n: rng.integers(0, vocab, n).astype(np.int32)
    short = [Request(prompt=prompt(L), max_new=2, arrival=0.0) for L in plens]
    if eng.prefix_cache is None:
        long = Request(prompt=prompt(pmin), max_new=pmax + omax - pmin, arrival=0.0)
    else:
        # a join from cached pages starts decoding where the match ends: one
        # that shares a cached prompt's first token (a copy of its partly
        # filled page) decodes from position 1
        long = Request(prompt=short[0].prompt[:2].copy(), max_new=pmax + omax - 2,
                       arrival=0.0)
    batches = [short, [long]]
    steps = 0
    for reqs in batches:
        sess = EngineSession(eng)
        for r in reqs:
            sess.submit(r)
        while not sess.done:
            sess.admit()
            if sess.n_active:
                sess.decode_step()
                steps += 1
    log(f"warm-up: {len(plens)} prompt lengths {plens[0]}..{plens[-1]}, "
        f"{steps} decode steps up to position {pmax + omax - 2}")


def window(eng, schedule, seconds, traced):
    """Serve ``schedule`` from an open loop; the window closes after
    ``seconds`` and the engine drains what arrived."""
    from repro.serve.engine import EngineSession
    from repro.serve.scheduler import Request

    trace = TracedWindow(traced)
    reqs = [Request(prompt=r.prompt, max_new=r.max_new, arrival=r.arrival)
            for r in schedule]
    times = {r.rid: [] for r in reqs}
    pos = {}                              # rid -> position its next decode step writes
    inflight = []
    joins, steps = [], []                 # (t, prompt len), (t, contexts)
    trace.start()
    sess = EngineSession(eng)
    for r in reqs:
        sess.submit(r)
    tr, closed = traced, False
    while not sess.done:
        t = sess.now()
        if not closed and t >= seconds:
            closed = True
            trace.stop()
            tr = False
        if t >= seconds + DRAIN_S:
            break
        with span("admit", tr):
            new = sess.admit()
        t = sess.now()
        for q in new:
            joins.append((t, len(q.prompt)))
            # a prefill join wrote the prompt; a join from cached pages
            # replays the prompt from where the match ends
            pos[q.rid] = len(q.prompt) if q.out else q.prefix_match.n_tokens
            _record(times, q)
            inflight.append(q)
        if sess.n_active == 0:
            if sess.next_arrival() is None:
                break
            with span("sleep", tr):
                sess.sleep_until_next()
            continue
        active = list(sess.sched.active.values())
        with span("decode", tr):
            sess.decode_step()
        steps.append((sess.now(), [pos[q.rid] + 1 for q in active]))   # contexts
        for q in active:
            pos[q.rid] += 1
        for q in inflight:
            _record(times, q)
        inflight = [q for q in inflight if q.wants_more()]
    trace.stop()
    return {"reqs": reqs, "times": times, "joins": joins, "steps": steps,
            "t_drain": sess.now(), "trace": trace}


def end_to_end(reqs, times, seconds):
    """The window's end-to-end metrics over ``[0, seconds]``, with every
    request's TTFT, every inter-token gap and the tokens counted."""
    ttft = [times[r.rid][0] - r.arrival if times[r.rid] else math.inf for r in reqs]
    gaps, tok = [], 0
    for r in reqs:
        ts = times[r.rid]
        tok += sum(1 for t in ts if t <= seconds)
        gaps += [b - a for a, b in zip(ts, ts[1:]) if b <= seconds]
    horizon = 1e3 * (seconds + DRAIN_S)      # a tail past the drain reads as the drain
    e2e = {"serve_tok_s": tok / seconds,
           "ttft_p95_ms": min(1e3 * stats.percentile(ttft, 95), horizon),
           "tpot_p95_ms": min(1e3 * stats.percentile(gaps, 95), horizon)}
    return e2e, ttft, gaps, tok


def run(ctx) -> dict:
    import jax

    conf, mix, seed, seconds = ctx.conf, ctx.mix, ctx.seed, ctx.seconds
    cfg, eng = build(conf, seed, ctx.log)
    schedule = workload.serve_schedule(mix, seed, seconds, cfg.vocab)
    t = time.perf_counter()
    warm(eng, mix, seconds, workload.seed_rng(seed, 2), cfg.vocab, ctx.log)
    ctx.log(f"warm-up {time.perf_counter() - t:.3f} s")
    ctx.mark_setup_done()

    w = window(eng, schedule, seconds, ctx.trace)
    ctx.mark_window_done()
    peak = memory_peak_bytes(jax.local_devices())
    reqs, times, joins, steps = w["reqs"], w["times"], w["joins"], w["steps"]
    done = [r for r in reqs if len(r.out) == r.max_new]
    e2e, ttft, gaps, tok = end_to_end(reqs, times, seconds)
    ctx.log(f"requests {len(reqs)}, finished {len(done)}, tokens in window "
            f"{tok}, decode steps {len(steps)}, ttft p50 "
            f"{1e3 * stats.percentile(ttft, 50):.1f} ms, tpot p50 "
            f"{1e3 * stats.percentile(gaps, 50):.1f} ms, ttft p50 of the first and last "
            f"quarter's arrivals {quarters(reqs, ttft, seconds)} ms; drain ended "
            f"{w['t_drain']:.3f} s after the window opened")

    # ---- correctness: the reference over a sample, once the program is gone
    sample = _sample(done, mix["check_requests"], seed)
    trace, n_done = w["trace"], len(done)
    del eng, reqs, done, w
    gc.collect()
    summary = trace.reduce()
    t = time.perf_counter()
    gap = served_gap(conf, seed, sample, mix, seconds) if sample else math.inf
    ctx.log(f"reference {time.perf_counter() - t:.3f} s over {len(sample)} requests, "
            f"{sum(len(r.out) for r in sample)} served tokens")
    unfinished = len(schedule) - n_done
    checks = {"max_logit_gap": (gap, conf["limits"]["max_logit_gap"]),
              "unfinished": (unfinished, 0)}
    return {"attempted": len(schedule), "failed": unfinished,
            "e2e": e2e, "checks": checks, "memory_peak_bytes": peak,
            "summary": summary, "cfg": cfg,
            "layer": {"window_s": seconds, "page": conf["serving"]["page"],
                      "joins": [j for j in joins if j[0] <= seconds],
                      "steps": [s for s in steps if s[0] <= seconds]}}


def quarters(reqs, ttft, seconds):
    """Median TTFT of the requests that arrived in the window's first and
    last quarter: a backlog that grows through the window shows here."""
    first = [t for r, t in zip(reqs, ttft) if r.arrival < 0.25 * seconds]
    last = [t for r, t in zip(reqs, ttft) if r.arrival >= 0.75 * seconds]
    return [round(1e3 * stats.percentile(x, 50), 1) for x in (first, last)]


def _record(times, q):
    """Each token's time: the engine stamps ``t_prev`` as a token lands,
    and admit or a decode step adds at most one token per request."""
    while len(times[q.rid]) < len(q.out):
        times[q.rid].append(q.t_prev)


def _sample(done, n, seed):
    """The request with the most tokens, and ``n - 1`` more drawn from the seed."""
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.out))
    rest = [r for r in done if r is not longest]
    rng = workload.seed_rng(seed, 3)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False) if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def check_arrays(sample, mix, seconds):
    """Inputs, served targets and a validity mask, padded to one shape for
    every seed: position j predicts token j + 1; the served tokens are
    predicted from the prompt's last position on."""
    _, pmax, omax, _ = workload.serve_shapes(mix, seconds)
    L = -(-(pmax + omax) // 128) * 128
    n = len(sample)
    tokens = np.zeros((n, L), np.int32)
    targets = np.zeros((n, L), np.int32)
    valid = np.zeros((n, L), bool)
    for i, r in enumerate(sample):
        full = np.concatenate([np.asarray(r.prompt, np.int32), np.asarray(r.out, np.int32)])
        P, T = len(r.prompt), len(full)
        tokens[i, :T - 1] = full[:T - 1]
        targets[i, P - 1:T - 1] = full[P:T]
        valid[i, P - 1:T - 1] = True
    return tokens, targets, valid


def served_gap(conf, seed, sample, mix, seconds, control=False) -> float:
    """The widest gap over the sample's served tokens (or, with
    ``control``, over the tokens the fp8 control puts first)."""
    import jax.numpy as jnp

    from bench.model import reference

    ref = reference(conf)
    tokens, targets, valid = check_arrays(sample, mix, seconds)
    hc = ref.serve_hidden(conf, seed, jnp.asarray(tokens), "fp8") if control else None
    g = ref.served_gaps(conf, seed, jnp.asarray(tokens), jnp.asarray(targets),
                        jnp.asarray(valid), control_hidden=hc)
    return float(jnp.max(g))
