"""Serving engines: continuous batching over the paged KV pool + legacy API.

``ContinuousEngine`` is the subsystem's production path: a fixed-width
decode batch of ``n_slots`` whose slots are continuously refilled —
arrived requests **join on prefill** (prefill runs through the stock
``transformer.prefill`` and is scattered into pool pages), finished
requests **evict on EOS** freeing their slot and pages in the same step.
Each decode step runs one jitted paged step for all slots (idle slots
write into the scratch page), and reports filled-vs-capacity plus
inter-arrival idle gaps to the governor through
:class:`~repro.serve.slack.DecodeSlackMeter`, so serving underfill is
priced in joules exactly like MPI slack.

``ServeEngine`` is the original static-batch engine, kept as a thin
compatibility wrapper: one prefill, a fixed batch, ``n_steps`` decode
steps for everyone.  ``ContinuousEngine.generate`` reproduces its
output token-for-token for greedy decoding (tier-1 asserted).
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models import layers as L
from repro.models.transformer import _block_decode, _embed_inputs, logits_fn
from repro.models.transformer import decode_step as _decode
from repro.models.transformer import init_cache, stack_layout
from repro.models.transformer import prefill as _prefill
from repro.obs.tracer import NO_SPAN, recording, span
from repro.serve.kvcache import (
    SCRATCH_PAGE,
    PagedKVPool,
    make_clone_pages,
    paged_attention_decode,
    scatter_prefill_attn,
)
from repro.serve.scheduler import Request, Scheduler
from repro.serve.slack import DecodeSlackMeter


# --------------------------------------------------------------------------
# legacy static-batch engine (compatibility wrapper)
# --------------------------------------------------------------------------

def make_serve_steps(cfg) -> Tuple[Callable, Callable]:
    """Returns (prefill_step(params, batch, cache), decode_step(params, token, pos, cache))."""

    def prefill_step(params, batch, cache):
        return _prefill(cfg, params, batch, cache)

    def decode_step(params, token, pos, cache):
        return _decode(cfg, params, token, pos, cache)

    return prefill_step, decode_step


@dataclass
class ServeEngine:
    cfg: Any
    params: Any
    max_len: int
    temperature: float = 0.0

    def __post_init__(self):
        self._prefill, self._decode = make_serve_steps(self.cfg)
        self._prefill = jax.jit(self._prefill)
        self._decode = jax.jit(self._decode)

    def generate(
        self,
        batch: Dict[str, Any],
        n_steps: int,
        key: Optional[jax.Array] = None,
    ) -> jnp.ndarray:
        """Greedy/sampled continuation of ``batch['tokens']`` for n_steps."""
        b, s = batch["tokens"].shape
        prompt_len = s + self.cfg.n_prefix
        cache = init_cache(self.cfg, b, self.max_len)
        logits, cache = self._prefill(self.params, batch, cache)
        out = []
        tok = self._select(logits, key, 0)
        out.append(tok)
        for i in range(1, n_steps):
            logits, cache = self._decode(
                self.params, tok, jnp.int32(prompt_len + i - 1), cache
            )
            tok = self._select(logits, key, i)
            out.append(tok)
        return jnp.stack(out, axis=1)                          # (B, n_steps)

    def _select(self, logits, key, i):
        if self.temperature <= 0.0 or key is None:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sub = jax.random.fold_in(key, i)
        return jax.random.categorical(sub, logits / self.temperature).astype(jnp.int32)


# --------------------------------------------------------------------------
# paged step builders
# --------------------------------------------------------------------------

def make_paged_decode_step(cfg, attn_kernel: str = "xla",
                           fused_sample: bool = False) -> Callable:
    """decode(params, token (B,), pos (B,), table (B,M), blocks) -> (out, blocks).

    The paged pool is state that every step updates in place.  The step
    walks the scanned layer stack with the pool's stacked ``blocks["stack"]``
    entries and the layer index in the ``lax.scan`` carry (the weights are
    the scanned ``xs``): each layer's attention gets the whole stacked
    block and the index, and returns the stacked block with one row per
    slot written into that layer's pages (``kvcache.paged_attention_decode``,
    via the XLA reference path or the Pallas paged kernel, ``attn_kernel``);
    recurrent-state blocks (SSM / RG-LRU) are read and written at ``[l]``.
    So no layer of the pool is sliced out or stacked back, and a caller
    that donates ``blocks`` (``ContinuousEngine`` does) gets the same
    buffers back, written in place, and its input pool is invalidated.
    The unscanned remainder (``rem``) keeps its per-layer blocks.  The
    layer body is the stock ``transformer._block_decode``; the dense cache
    keeps ``transformer.decode_step``, whose per-call cache flows through
    the scan's ``xs``/``ys``.

    With ``fused_sample`` the greedy argmax runs inside the same jitted
    dispatch and ``out`` is the sampled ``(B,)`` int32 tokens — the step
    ships B words back to the host instead of a (B, vocab) logits block
    plus a second argmax dispatch.  Callers that need logits (sampling
    with temperature) keep the unfused step.
    """
    n_full, rem_kinds = stack_layout(cfg)

    def step(params, token, pos, table, blocks):
        def block(kind, p, x, bc, layer):
            if kind == "attn":
                def paged_attn(p_attn, h, bc):
                    return paged_attention_decode(cfg, p_attn, h, pos, table, bc,
                                                  kernel=attn_kernel, layer=layer)

                return _block_decode(cfg, kind, p, x, pos, bc, paged_attn)
            if layer is None:
                return _block_decode(cfg, kind, p, x, pos, bc)
            state = jax.tree.map(lambda a: a[layer], bc)
            x, state = _block_decode(cfg, kind, p, x, pos, state)
            return x, jax.tree.map(lambda a, s: a.at[layer].set(s), bc, state)

        def period(x, stack, pp, layer):
            stack = dict(stack)
            for j, kind in enumerate(cfg.pattern):
                x, stack[str(j)] = block(kind, pp[str(j)], x, stack[str(j)], layer)
            return x, stack

        x = _embed_inputs(cfg, params, {"tokens": token[:, None]})
        stack = blocks["stack"]
        if cfg.scan_layers:
            def body(carry, pp):
                x, stack, layer = carry
                x, stack = period(x, stack, pp, layer)
                return (x, stack, layer + 1), None

            (x, stack, _), _ = lax.scan(body, (x, stack, jnp.int32(0)), params["stack"])
        else:
            for i in range(n_full):
                pp = jax.tree.map(lambda a: a[i], params["stack"])
                x, stack = period(x, stack, pp, i)
        new_blocks = {"stack": stack}
        if rem_kinds:
            new_blocks["rem"] = {}
            for j, kind in enumerate(rem_kinds):
                x, new_blocks["rem"][str(j)] = block(
                    kind, params["rem"][str(j)], x, blocks["rem"][str(j)], None
                )
        x = L.apply_norm(cfg, params["final_norm"], x)
        logits = logits_fn(cfg, params, x)[:, 0]
        if fused_sample:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_blocks
        return logits, new_blocks

    return step


def make_join_step(cfg) -> Callable:
    """join(blocks, prefill_cache, page_ids (n_used,), slot) -> blocks.

    Scatters a batch-1 prefill cache into the pool: attention K/V into the
    slot's freshly allocated pages of every layer of the stacked pool,
    recurrent state into the slot's row.  ``ContinuousEngine`` donates
    ``blocks``, so the scatter lands in place and the caller's input pool
    is invalidated.
    """

    def join(blocks, cache, page_ids, slot):
        new_stack = {}
        for j, kind in enumerate(cfg.pattern):
            pb, cb = blocks["stack"][str(j)], cache["stack"][str(j)]
            if kind == "attn":
                new_stack[str(j)] = scatter_prefill_attn(pb, cb, page_ids, stacked=True)
            else:
                new_stack[str(j)] = jax.tree.map(
                    lambda big, small: big.at[:, slot].set(small[:, 0]), pb, cb
                )
        new_blocks = {"stack": new_stack}
        _, rem_kinds = stack_layout(cfg)
        if rem_kinds:
            new_blocks["rem"] = {}
            for j, kind in enumerate(rem_kinds):
                pb, cb = blocks["rem"][str(j)], cache["rem"][str(j)]
                if kind == "attn":
                    new_blocks["rem"][str(j)] = scatter_prefill_attn(
                        pb, cb, page_ids, stacked=False
                    )
                else:
                    new_blocks["rem"][str(j)] = jax.tree.map(
                        lambda big, small: big.at[slot].set(small[0]), pb, cb
                    )
        return new_blocks

    return join


# --------------------------------------------------------------------------
# continuous-batching engine
# --------------------------------------------------------------------------

@dataclass
class ContinuousEngine:
    """Continuous batching over a paged KV pool with governor-priced slack.

    ``n_slots`` is the decode batch width, ``max_len`` the per-request
    position budget (multiple of ``page``), ``num_pages`` optionally
    shrinks the pool below full occupancy to exercise admission control.
    For windowed archs prompts must fit inside the window (the pool
    stores positions linearly and masks by window at read).

    ``attn_kernel`` selects the decode attention hot path: ``"xla"`` (the
    gather/scatter reference) or ``"pallas"`` (the paged kernel with the
    fused scatter epilogue; greedy decoding additionally samples inside
    the decode dispatch).  Both are token-for-token identical to the
    dense engine (tier-1 asserted).

    Every program that writes the pool (decode, join, clone) donates it:
    ``pool.blocks`` is replaced by each call's output, written in place.
    ``pool_copied`` counts the compiled pool-writing programs (one per
    decode table width, join length, and the clone) whose donated pool
    survived their first call, i.e. that wrote a copy; it is checked on
    a program's first call only, and should read 0.
    """

    cfg: Any
    params: Any
    n_slots: int = 4
    max_len: int = 128
    page: int = 16
    num_pages: Optional[int] = None
    temperature: float = 0.0
    attn_kernel: str = "xla"
    # optional repro.obs.tracer.SpanTracer (duck-typed: .serve_event):
    # batch join/evict instants land on the trace's serve track
    tracer: Any = None
    # optional repro.serve.fleet.prefix.PrefixCache over this engine's pool
    # (attach via enable_prefix_cache(); attn-only archs, n_prefix == 0)
    prefix_cache: Any = None

    def __post_init__(self):
        if self.attn_kernel not in ("xla", "pallas"):
            raise ValueError(f"unknown attn_kernel {self.attn_kernel!r}")
        self.pool = PagedKVPool(
            self.cfg, self.n_slots, self.max_len, self.page, self.num_pages
        )
        # sampling with temperature needs host-side logits; greedy decode
        # on the pallas path samples inside the decode dispatch
        self._fused_sample = (
            self.attn_kernel == "pallas" and self.temperature <= 0.0
        )
        self._prefill = jax.jit(partial(_prefill, self.cfg))
        self._decode = jax.jit(make_paged_decode_step(
            self.cfg, self.attn_kernel, self._fused_sample
        ), donate_argnums=4)
        self._join = jax.jit(make_join_step(self.cfg), donate_argnums=0)
        self._clone = jax.jit(make_clone_pages(self.cfg), donate_argnums=0)
        self.pool_copied = 0
        self._pool_programs: set = set()     # programs whose donation was checked
        m = self.pool.max_pages_per_req
        self._table = np.full((self.n_slots, m), SCRATCH_PAGE, np.int32)
        self._lengths = np.zeros((self.n_slots,), np.int32)
        self._tokens = np.zeros((self.n_slots,), np.int32)
        # slot -> deque of prompt-suffix tokens still to force-decode after
        # a prefix-cache join (no sampling/appending until drained)
        self._forced: Dict[int, collections.deque] = {}

    def enable_prefix_cache(self, max_pages: Optional[int] = None):
        """Attach a :class:`~repro.serve.fleet.prefix.PrefixCache` to the pool.

        Only attention K/V lives in shareable pages: recurrent state
        (SSM/RG-LRU) is per-slot and position-dependent, and frontend
        prefixes occupy positions the trie cannot key — so prefix reuse is
        restricted to all-attention archs with ``n_prefix == 0``.
        """
        if self.cfg.n_prefix:
            raise ValueError("prefix cache requires n_prefix == 0 "
                             "(frontend prefixes are not token-addressable)")
        if any(k != "attn" for k in self.cfg.pattern):
            raise ValueError("prefix cache requires an all-attention arch "
                             "(recurrent state is per-slot, not paged)")
        from repro.serve.fleet.prefix import PrefixCache
        self.prefix_cache = PrefixCache(self.pool, max_pages=max_pages)
        return self.prefix_cache

    # ---- request lifecycle ----------------------------------------------
    def _join_request(self, req: Request) -> None:
        m = req.prefix_match
        if m is not None and m.n_tokens > 0:
            self._join_via_prefix(req)
            return
        cfg = self.cfg
        prompt = np.asarray(req.prompt, np.int32)
        total = len(prompt) + cfg.n_prefix
        n_used = self.pool.pages_needed(total)
        lpad = n_used * self.pool.page
        if cfg.attention in ("swa", "local") and cfg.window and lpad > cfg.window:
            raise ValueError(
                f"paged serving stores positions linearly: prompt pages {lpad} "
                f"must fit the attention window {cfg.window}"
            )
        batch: Dict[str, Any] = {"tokens": jnp.asarray(prompt[None])}
        if req.prefix_embeds is not None:
            batch["prefix_embeds"] = jnp.asarray(np.asarray(req.prefix_embeds)[None])
        cache = init_cache(cfg, 1, lpad)
        logits, cache = self._prefill(self.params, batch, cache)
        req.pages = self.pool.alloc(req.rid, n_used)
        slot = req.slot
        self._table[slot] = SCRATCH_PAGE
        self._table[slot, :n_used] = req.pages
        old = self.pool.blocks
        self.pool.blocks = self._join(
            old, cache, jnp.asarray(req.pages, jnp.int32), jnp.int32(slot)
        )
        self._check_donated(("join", n_used), old)
        tok = int(self._select_one(logits[0], req))
        req.out.append(tok)
        self._lengths[slot] = total
        self._tokens[slot] = tok

    def _join_via_prefix(self, req: Request) -> None:
        """Join without prefill: resident pages cover ``m.n_tokens`` prompt
        positions, the remaining suffix is replayed through the paged decode
        step as *forced* tokens (exact K/V, no sampling) — first sampled
        token only lands once the suffix drains."""
        m = req.prefix_match
        prompt = np.asarray(req.prompt, np.int32)
        pages = list(m.full_pages)
        if m.partial_page is not None:
            # copy-on-write: this request extends the half-filled page in
            # place, so it writes into a private clone while other referents
            # keep reading the shared original
            (pid,) = self.pool.alloc(req.rid, 1)
            old = self.pool.blocks
            self.pool.blocks = self._clone(
                old, jnp.int32(m.partial_page), jnp.int32(pid)
            )
            self._check_donated(("clone",), old)
            pages.append(pid)
        req.pages = pages
        slot = req.slot
        self._table[slot] = SCRATCH_PAGE
        self._table[slot, :len(pages)] = pages
        self._lengths[slot] = m.n_tokens              # next write position
        self._tokens[slot] = int(prompt[m.n_tokens])  # next input token
        rest = prompt[m.n_tokens + 1:]
        if len(rest):
            self._forced[slot] = collections.deque(int(t) for t in rest)

    def _check_donated(self, program, old_blocks) -> None:
        """On ``program``'s first call, count it in ``pool_copied`` if the
        pool it was handed is still alive: it was not donated, so the
        program wrote its output pool beside it."""
        if program in self._pool_programs:
            return
        self._pool_programs.add(program)
        if not all(leaf.is_deleted() for leaf in jax.tree.leaves(old_blocks)):
            self.pool_copied += 1

    def _select_one(self, logits, req: Request) -> int:
        if self.temperature <= 0.0 or req.key is None:
            return int(jnp.argmax(logits))
        sub = jax.random.fold_in(req.key, req.n_generated)
        return int(jax.random.categorical(sub, logits / self.temperature))

    def _grow_pages(self, req: Request) -> None:
        pos = int(self._lengths[req.slot])
        while pos // self.pool.page >= len(req.pages):
            (pid,) = self.pool.alloc(req.rid, 1)
            self._table[req.slot, len(req.pages)] = pid
            req.pages.append(pid)

    def _retire(self, req: Request, sched: Scheduler, slo, now: float,
                t_start: float) -> None:
        """``now`` is on the session's clock, which starts at ``t_start``
        (``time.monotonic()``)."""
        if slo is not None:
            slo.on_finish(req, now)
        else:
            req.t_done = now
        if self.tracer is not None:
            self.tracer.serve_event("evict", t_start + now, req.rid, req.slot)
        drained = self._forced.pop(req.slot, None) is None
        if self.prefix_cache is not None and req.pages and drained:
            # adopt this request's written pages into the resident trie:
            # positions 0..lengths-1 hold K/V of prompt + out[:-1] (the last
            # sampled token was never decoded, so its K/V was never written)
            n_written = int(self._lengths[req.slot])
            tokens = np.concatenate([
                np.asarray(req.prompt, np.int64), np.asarray(req.out, np.int64)
            ])[:n_written]
            self.prefix_cache.insert(tokens, req.pages)
        self._table[req.slot] = SCRATCH_PAGE
        self._tokens[req.slot] = 0
        self._lengths[req.slot] = 0
        sched.release(req)

    # ---- driving loop ----------------------------------------------------
    def serve(
        self,
        requests: List[Request],
        governor=None,
        slo=None,
        max_steps: int = 100_000,
    ) -> List[Request]:
        """Run all requests to completion; returns them with outputs filled.

        Arrival offsets are honored against a wall clock started at call
        time; idle waits and per-step underfill are published as
        :class:`~repro.core.events.PhaseRecord` phases to ``governor`` — a
        :class:`repro.core.governor.Governor` or an
        :class:`~repro.core.events.EventBus` with any subscriber set —
        when given.
        """
        sess = EngineSession(self, governor=governor, slo=slo)
        for r in requests:
            sess.submit(r)
        steps = 0
        while not sess.done:
            sess.admit()
            if sess.n_active == 0:
                if not sess.sleep_until_next():
                    break
                continue
            sess.decode_step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"serve() exceeded {max_steps} decode steps")
        return sess.finished

    # ---- ServeEngine-compatible entry point ------------------------------
    def generate(
        self,
        batch: Dict[str, Any],
        n_steps: int,
        key: Optional[jax.Array] = None,
    ) -> jnp.ndarray:
        """Static-batch compatibility: all rows arrive at t=0, run to n_steps.

        Greedy output matches ``ServeEngine.generate`` token for token.
        (Sampled output uses per-request keys — ``fold_in(key, row)`` —
        rather than the legacy shared per-step key.)
        """
        tokens = np.asarray(batch["tokens"])
        b = tokens.shape[0]
        if b > self.n_slots:
            raise ValueError(f"batch {b} exceeds n_slots {self.n_slots}")
        reqs = []
        for i in range(b):
            req = Request(
                prompt=tokens[i], max_new=n_steps, arrival=0.0,
                key=None if key is None else jax.random.fold_in(key, i),
            )
            if "prefix_embeds" in batch:
                req.prefix_embeds = np.asarray(batch["prefix_embeds"][i])
            reqs.append(req)
        order = {r.rid: i for i, r in enumerate(reqs)}
        done = sorted(self.serve(reqs), key=lambda r: order[r.rid])
        return jnp.asarray(
            np.stack([np.asarray(r.out[:n_steps], np.int32) for r in done])
        )


# --------------------------------------------------------------------------
# step-granular session (fleet driver entry point)
# --------------------------------------------------------------------------

class EngineSession:
    """One engine's serving loop, exposed a step at a time.

    ``ContinuousEngine.serve`` is this session driven to completion; the
    fleet driver instead interleaves N sessions — submit routed requests,
    ``admit()`` + ``decode_step()`` each replica in turn, and only
    ``sleep_until_next()`` when *every* replica is idle.  All timestamps
    are relative to ``t_start`` (shareable across a fleet so SLO clocks
    agree).
    """

    def __init__(self, engine: "ContinuousEngine", governor=None, slo=None,
                 t_start: Optional[float] = None):
        self.engine = engine
        self.slo = slo
        self.sched = Scheduler(
            engine.pool, engine.n_slots, n_prefix=engine.cfg.n_prefix,
            slo=slo, prefix_cache=engine.prefix_cache,
        )
        self.meter = DecodeSlackMeter(governor) if governor is not None else None
        engine._last_meter = self.meter
        self.finished: List[Request] = []
        self.t_start = time.monotonic() if t_start is None else t_start
        self.steps = 0

    # ---- clock -----------------------------------------------------------
    def now(self) -> float:
        return time.monotonic() - self.t_start

    # ---- queue state -----------------------------------------------------
    @property
    def done(self) -> bool:
        return self.sched.done

    @property
    def n_active(self) -> int:
        return self.sched.n_active

    @property
    def n_queued(self) -> int:
        return self.sched.n_queued

    def next_arrival(self) -> Optional[float]:
        return self.sched.next_arrival()

    def fill_fraction(self) -> float:
        return self.sched.n_active / max(self.engine.n_slots, 1)

    # ---- lifecycle -------------------------------------------------------
    def submit(self, req: Request) -> None:
        eng = self.engine
        if eng.cfg.n_prefix and req.prefix_embeds is None:
            # without the prefix, positions [S, S+n_prefix) would never be
            # written and the page mask (unlike the dense slot_pos mask)
            # would attend their zero K/V — refuse up front
            raise ValueError(
                f"arch {eng.cfg.name!r} has n_prefix={eng.cfg.n_prefix}: "
                f"request {req.rid} must carry prefix_embeds"
            )
        self.sched.submit(req)

    def admit(self, now: Optional[float] = None) -> List[Request]:
        """Join every arrived request that fits; returns the joins.

        Under a recording profiler: the span ``engine.admit`` with the
        engine's ``pool_copied``, and in it one ``engine.prefill`` per join
        with the request's ``rid``, ``prompt_len`` and ``wait_us`` (from
        its arrival to the join's start, on the session's clock)."""
        eng = self.engine
        with span("engine.admit", pool_copied=eng.pool_copied):
            joins = self.sched.admit(self.now() if now is None else now)
            for req in joins:
                with (span("engine.prefill", rid=req.rid, prompt_len=len(req.prompt),
                           wait_us=round(1e6 * (self.now() - req.arrival)))
                      if recording() else NO_SPAN):
                    eng._join_request(req)
                tnow = self.now()
                if eng.tracer is not None:
                    eng.tracer.serve_event("join", self.t_start + tnow, req.rid,
                                           req.slot)
                if req.out:
                    # prefill joins produce the first token immediately; prefix
                    # joins stay silent until the forced suffix drains
                    if self.slo is not None:
                        self.slo.on_first_token(req, tnow)
                    else:
                        req.t_first = req.t_prev = tnow
                if not req.wants_more():
                    eng._retire(req, self.sched, self.slo, tnow, self.t_start)
                    self.finished.append(req)
        return joins

    def sleep_until_next(self) -> bool:
        """Idle until the next arrival (metered); False when queue is empty."""
        nxt = self.sched.next_arrival()
        if nxt is None:
            return False
        t0 = time.monotonic()
        wait = (self.t_start + nxt) - t0
        if wait > 0:
            time.sleep(wait)
        t1 = time.monotonic()
        self.note_idle(t0, t1)
        return True

    def note_idle(self, t0: float, t1: float) -> None:
        if self.meter is not None and t1 > t0:
            self.meter.idle(t0, t1)

    def decode_step(self) -> None:
        """One batched decode step over all active slots.

        Under a recording profiler: the span ``engine.decode`` with four
        children, ``engine.prepare`` (pages grown, the table clamped, the
        tokens, lengths and table uploaded), ``engine.dispatch`` (the decode
        call until it returns), ``engine.wait`` (until its output is ready)
        and ``engine.commit`` (the readback and the per-slot bookkeeping,
        retirements included)."""
        eng = self.engine
        with span("engine.decode"):
            with span("engine.prepare"):
                for req in self.sched.active.values():
                    eng._grow_pages(req)
                # clamp the table to the live pages: no request's K/V extends
                # past ceil((max_pos + 1) / page) pages, so neither the XLA
                # gather nor the pallas grid should pay O(max_len) per token.
                # (Each distinct width is its own jit bucket — widths only
                # grow, and there are at most max_pages_per_req of them.)
                max_pos = int(eng._lengths.max())
                m_live = min(eng._table.shape[1], max_pos // eng.pool.page + 1)
                t0 = time.monotonic()
                inputs = (jnp.asarray(eng._tokens), jnp.asarray(eng._lengths),
                          jnp.asarray(eng._table[:, :m_live]))
            with span("engine.dispatch"):
                old = eng.pool.blocks
                out, eng.pool.blocks = eng._decode(eng.params, *inputs, old)
                eng._check_donated(("decode", m_live), old)
            with span("engine.wait"):
                out = jax.block_until_ready(out)
            t1 = time.monotonic()
            with span("engine.commit"):
                self._commit(out, t0, t1)
        self.steps += 1

    def _commit(self, out, t0: float, t1: float) -> None:
        """A finished decode step's tokens, one per active slot."""
        eng = self.engine
        sched = self.sched
        if self.meter is not None:
            self.meter.step(t0, t1, sched.n_active, eng.n_slots)
        if eng._fused_sample:
            logits, greedy = None, np.asarray(out, np.int32)
        else:
            logits = out
            greedy = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        tnow = self.now()
        for slot, req in list(sched.active.items()):
            eng._lengths[slot] += 1
            forced = eng._forced.get(slot)
            if forced:
                # prompt-suffix replay after a prefix join: exact K/V was
                # just written for the fed token, next one goes in verbatim
                eng._tokens[slot] = forced.popleft()
                if not forced:
                    del eng._forced[slot]
                continue
            if eng.temperature <= 0.0 or req.key is None:
                tok = int(greedy[slot])
            else:
                tok = eng._select_one(logits[slot], req)
            first = not req.out
            req.out.append(tok)
            eng._tokens[slot] = tok
            if self.slo is not None:
                if first:
                    self.slo.on_first_token(req, tnow)
                else:
                    self.slo.on_token(req, tnow)
            else:
                if first:
                    req.t_first = tnow
                req.t_prev = tnow
            if not req.wants_more():
                eng._retire(req, sched, self.slo, tnow, self.t_start)
                self.finished.append(req)
