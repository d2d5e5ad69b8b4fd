"""Collective instrumentation: artificial barriers + host phase events.

This is the JAX analogue of the paper's PMPI interception layer (§4.1-4.2):

* ``cd_psum`` / ``cd_all_gather`` / ``cd_ppermute`` wrap the real collective
  with (i) an *artificial barrier* — a 1-element ``psum`` over the same axes,
  the ``MPI_Barrier``/``Isend+Wait`` analogue — that contains exactly the
  slack, and (ii) ordered ``io_callback`` phase events (barrier-enter,
  barrier-exit = slack end, collective-exit = copy end) that drive the host
  :class:`~repro.core.governor.Governor`, which applies the timeout policy.

* ``cd_psum_async`` / ``cd_all_gather_async`` + ``cd_wait`` are the
  nonblocking-collective analogue (``MPI_Iallreduce`` + ``MPI_Wait``).  They
  extend the 3-phase barrier/copy taxonomy to 5 phases: ``dispatch_enter``
  at the async start and ``wait_enter`` when the caller blocks.  The window
  ``[dispatch_enter, wait_enter]`` is compute/communication *overlap* — the
  core is busy, so the governor accounts it as non-slack instead of letting
  it silently inflate the slack (and get mispriced at the min P-state while
  the rank is actually computing).  Slack for an async pair starts at the
  wait, exactly as the paper's P2P ``Isend + Wait`` barrier starts at the
  wait.

* The instrumentation mode is ambient (``set_mode``), mirroring the paper's
  LD_PRELOAD transparency: model / optimizer code always calls the wrappers
  and pays zero cost when the mode is "off".

* Host events fan out through one ambient :class:`~repro.core.events.
  EventBus` (``get_event_bus()``): the governor, a trace recorder, and any
  further consumer subscribe side by side.  The legacy single-slot
  ``set_event_sink``/``set_event_tee`` setters are kept as thin shims over
  two named bus slots, so existing call sites (and the golden event
  ordering they rely on) keep working.

Modes:
  off      — wrapper == real collective (baseline).
  barrier  — artificial barrier emitted (dry-run visible, no host events).
  profile  — barrier + host phase events (live runs; energy accounting).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.events import PHASE_NAMES, BatchAccumulator, EventBus
from repro.obs.tracer import recording

AxisNames = Union[str, Sequence[str]]

_MODE = "off"
_EVENTS_ENABLED = False
_BUS = EventBus()
_LOCK = threading.Lock()
_CALL_COUNTER = [0]
_INGEST_MODE = "event"
_ACC: Optional[BatchAccumulator] = None
DEFAULT_BATCH_SIZE = 65536      # 65536 events x 21 B/event ~= 1.4 MB buffer;
# the size where the governor's vectorized fold peaks (DESIGN.md §10)


def set_mode(mode: str) -> None:
    """Set ambient instrumentation mode: off | barrier | profile."""
    global _MODE
    if mode not in ("off", "barrier", "profile"):
        raise ValueError(mode)
    _MODE = mode


def enable_events(on: bool) -> None:
    """Host phase events need a *fully manual* shard_map region (io_callback
    limitation under partial auto-sharding); callers in such regions opt in.
    """
    global _EVENTS_ENABLED
    _EVENTS_ENABLED = on


def get_mode() -> str:
    return _MODE


def get_event_bus() -> EventBus:
    """The ambient bus the instrumented collectives publish onto.

    Subscribe consumers directly: ``get_event_bus().subscribe(governor)``
    attaches anything exposing ``on_event``/``on_phase`` (the canonical
    subscriber protocol — see :mod:`repro.core.events`).
    """
    return _BUS


def set_ingest_mode(mode: str, batch_size: int = DEFAULT_BATCH_SIZE) -> None:
    """Choose how host phase events reach the bus: ``"event"`` publishes
    each event as it happens (the legacy low-latency path), ``"batched"``
    buffers events in a fixed-dtype :class:`~repro.core.events.
    BatchAccumulator` and publishes full columnar chunks — the vectorized
    telemetry spine for week-long, thousand-rank traces (launch drivers:
    ``--ingest batched``).

    Switching modes flushes any buffered partial batch first, so no event
    is lost or reordered across the switch.
    """
    global _INGEST_MODE, _ACC
    if mode not in ("event", "batched"):
        raise ValueError(mode)
    flush_events()
    with _LOCK:
        _INGEST_MODE = mode
        _ACC = BatchAccumulator(batch_size) if mode == "batched" else None


def get_ingest_mode() -> str:
    return _INGEST_MODE


def flush_events() -> int:
    """Deliver everything the batched ingest mode is holding: the partial
    accumulator batch is enqueued behind any already-queued full chunks,
    then the bus queue is drained in FIFO order (so flushing never
    reorders events around chunks still in flight).  Drivers call this at
    loop boundaries and end-of-run so the governor sees every event
    before ``finalize``.  Returns events delivered; in ``"event"`` mode
    it still drains the queue (normally a no-op)."""
    with _LOCK:
        acc = _ACC
        batch = acc.flush() if acc is not None else None
    if batch is not None:
        _BUS.enqueue(batch)
    return _BUS.drain()


def set_event_sink(sink: Optional[Callable[[int, str, int, float], None]]) -> None:
    """Deprecated single-slot shim over :func:`get_event_bus`.

    Occupies the bus's ``"sink"`` named slot: installing replaces the
    previous sink, ``None`` vacates it, and any other subscribers are
    untouched.  New code should subscribe to the bus directly.
    """
    if sink is None:
        _BUS.unsubscribe("sink")
    else:
        _BUS.subscribe(sink, name="sink")


def set_event_tee(tee: Optional[Callable[[int, str, int, float], None]]) -> None:
    """Deprecated single-slot shim over :func:`get_event_bus` (slot
    ``"tee"``) — historically a secondary consumer fed the identical
    stream, e.g. a :class:`repro.cluster.trace.TraceRecorder` recording a
    run the governor is not attached to.  The bus made the distinction
    moot (any number of consumers subscribe side by side); the setter
    stays for sink-less recording call sites.  When the recorder hangs
    off a live :class:`~repro.core.governor.Governor` instead, prefer the
    governor's ``recorder`` hook (it also captures ingested phases and
    actuations).
    """
    if tee is None:
        _BUS.unsubscribe("tee")
    else:
        _BUS.subscribe(tee, name="tee")


def reset_instrumentation() -> None:
    """Restore every piece of ambient instrumentation state to its default:
    mode off, events disabled, empty bus, call counter at zero.

    Ambient state otherwise leaks across tests (a subscriber installed by
    one test keeps timestamping the next test's collectives); the tier-1
    ``conftest.py`` calls this around every test.
    """
    global _MODE, _EVENTS_ENABLED, _INGEST_MODE, _ACC
    _MODE = "off"
    _EVENTS_ENABLED = False
    _BUS.clear()
    with _LOCK:
        _CALL_COUNTER[0] = 0
        _INGEST_MODE = "event"
        _ACC = None


def _emit(rank, phase_code, call_id) -> None:
    """Host-side callback: timestamp and publish onto the event bus —
    directly per event, or via the ingest accumulator when the batched
    spine is on (full buffers are queued, not delivered inline: an
    ordered ``io_callback`` must not run consumer code).

    While a profiler session records, the whole of it is the span
    ``cd.emit`` with arguments ``rank``, ``phase``, ``call`` and ``t_ns``
    (the timestamp handed to the bus, in ns)."""
    if not _BUS:
        return
    if not recording():
        _publish(rank, phase_code, call_id, time.monotonic())
        return
    with jax.profiler.TraceAnnotation("cd.emit") as s:
        t = time.monotonic()
        s.set_metadata(rank=int(rank), phase=PHASE_NAMES[int(phase_code)],
                       call=int(call_id), t_ns=round(t * 1e9))
        _publish(rank, phase_code, call_id, t)


def _publish(rank, phase_code, call_id, t: float) -> None:
    acc = _ACC
    if acc is None:
        _BUS.publish(int(rank), PHASE_NAMES[int(phase_code)], int(call_id), t)
        return
    with _LOCK:
        batch = acc.flush() if acc.append(
            int(rank), int(phase_code), int(call_id), t) else None
    if batch is not None:
        _BUS.enqueue(batch)


def _host_event(rank: jnp.ndarray, phase_code: int, call_id: int) -> None:
    jax.experimental.io_callback(
        _emit, None, rank, jnp.int32(phase_code), jnp.int32(call_id), ordered=True
    )


def _next_call_id() -> int:
    with _LOCK:
        _CALL_COUNTER[0] += 1
        return _CALL_COUNTER[0]


def _probe(tree: Any) -> jnp.ndarray:
    """A 1-element probe derived from live data, so the partitioner cannot
    constant-fold the barrier built on it away."""
    leaf = jax.tree.leaves(tree)[0]
    return jnp.real(jnp.ravel(leaf)[0]).astype(jnp.float32) * 0.0 + 1.0


def _barrier_token(tree: Any, axes: AxisNames) -> jnp.ndarray:
    """The artificial barrier: a 1-element all-reduce over ``axes``."""
    return lax.psum(_probe(tree), axes)


def _instrumented(real_op: Callable[[Any], Any], tree: Any, axes: AxisNames) -> Any:
    mode = _MODE
    if mode == "off":
        return real_op(tree)
    call_id = _next_call_id()
    profile = mode == "profile" and _EVENTS_ENABLED
    if profile:
        rank = lax.axis_index(axes if isinstance(axes, str) else axes[0])
        _host_event(rank, 0, call_id)                 # barrier enter (slack start)
    token = _barrier_token(tree, axes)                # ---- artificial barrier ----
    # order: real collective strictly after the barrier completes
    tree, token = lax.optimization_barrier((tree, token))
    if profile:
        _host_event(rank, 1, call_id)                 # barrier exit (slack end)
    out = real_op(tree)
    if profile:
        out, token = lax.optimization_barrier((out, token))
        _host_event(rank, 2, call_id)                 # copy exit
    return out


# --------------------------------------------------------------------------
# public wrappers (the "PMPI interface")
# --------------------------------------------------------------------------

def cd_psum(tree: Any, axes: AxisNames) -> Any:
    """Instrumented ``lax.psum`` (collective COUNTDOWN Slack barrier §4.2.1)."""
    return _instrumented(lambda t: jax.tree.map(lambda a: lax.psum(a, axes), t), tree, axes)


def cd_pmean(tree: Any, axes: AxisNames) -> Any:
    return _instrumented(lambda t: jax.tree.map(lambda a: lax.pmean(a, axes), t), tree, axes)


def cd_all_gather(tree: Any, axes: AxisNames, *, axis: int = 0, tiled: bool = True) -> Any:
    return _instrumented(
        lambda t: jax.tree.map(lambda a: lax.all_gather(a, axes, axis=axis, tiled=tiled), t),
        tree, axes,
    )


class AsyncCollective(NamedTuple):
    """Handle returned by ``cd_*_async``: the dispatched result plus the
    bookkeeping ``cd_wait`` needs to close the 5-phase event sequence."""

    result: Any
    axes: Any                    # AxisNames; static within the traced region
    call_id: int                 # 0 when mode is off (no events were armed)
    profile: bool
    rank: Any                    # traced axis index, None unless profiling
    probe: Any                   # 1-element probe from the INPUT tree: the
    # wait-side barrier must resolve on rank arrival, independent of the
    # in-flight payload (else the transfer would be booked as slack)


def _async_start(real_op: Callable[[Any], Any], tree: Any, axes: AxisNames) -> AsyncCollective:
    """Dispatch an async collective: emit ``dispatch_enter`` and launch the
    real op.  Whatever the caller computes between start and ``cd_wait`` is
    the overlap window — accounted as non-slack by the governor."""
    mode = _MODE
    if mode == "off":
        return AsyncCollective(real_op(tree), axes, 0, False, None, None)
    call_id = _next_call_id()
    profile = mode == "profile" and _EVENTS_ENABLED
    rank = None
    if profile:
        rank = lax.axis_index(axes if isinstance(axes, str) else axes[0])
        _host_event(rank, 3, call_id)                 # dispatch enter (overlap start)
    return AsyncCollective(real_op(tree), axes, call_id, profile, rank,
                           _probe(tree))


def cd_wait(handle: AsyncCollective) -> Any:
    """Block on an async collective (the ``MPI_Wait`` analogue).

    Emits ``wait_enter`` (slack starts HERE, not at dispatch), runs the
    artificial barrier that isolates the remaining wait, then forces the
    dispatched result: ``barrier_exit`` closes the slack, ``copy_exit``
    closes the copy remainder — same tail as the blocking wrappers, so the
    governor reconstructs async and sync calls with one code path.

    The barrier token is a 1-element psum over the *input* probe carried on
    the handle — deliberately independent of the dispatched payload, so it
    resolves on rank arrival at the wait (the slack the paper isolates).
    Deriving it from the result would tie the barrier to the in-flight
    transfer: the wire time would be priced as exploitable slack and the
    copy remainder would collapse to zero, losing the copy-at-full-speed
    protection the slack scope exists for.
    """
    if handle.call_id == 0:                           # dispatched with mode off
        return handle.result
    out = handle.result
    if handle.profile:
        _host_event(handle.rank, 4, handle.call_id)   # wait enter (slack start)
    token = lax.psum(handle.probe, handle.axes)       # ---- artificial barrier ----
    if handle.profile:
        token = lax.optimization_barrier(token)
        _host_event(handle.rank, 1, handle.call_id)   # barrier exit (slack end)
    # the payload is forced only after the barrier: what remains of the
    # transfer past this point is the copy phase
    out, token = lax.optimization_barrier((out, token))
    if handle.profile:
        _host_event(handle.rank, 2, handle.call_id)   # copy exit
    return out


def cd_psum_async(tree: Any, axes: AxisNames) -> AsyncCollective:
    """Nonblocking ``cd_psum``: start/wait pair (``MPI_Iallreduce`` analogue)."""
    return _async_start(
        lambda t: jax.tree.map(lambda a: lax.psum(a, axes), t), tree, axes
    )


def cd_all_gather_async(tree: Any, axes: AxisNames, *, axis: int = 0,
                        tiled: bool = True) -> AsyncCollective:
    """Nonblocking ``cd_all_gather``: start/wait pair."""
    return _async_start(
        lambda t: jax.tree.map(lambda a: lax.all_gather(a, axes, axis=axis, tiled=tiled), t),
        tree, axes,
    )


def cd_ppermute(tree: Any, axis_name: str, perm) -> Any:
    """Instrumented ``lax.ppermute`` (P2P COUNTDOWN Slack barrier §4.2.2).

    The artificial barrier for P2P is a 1-element ppermute over the same
    permutation — the non-blocking send/recv + wait analogue: it involves
    exactly the communicating pair, not the world.
    """
    mode = _MODE

    def real_op(t):
        return jax.tree.map(lambda a: lax.ppermute(a, axis_name, perm), t)

    if mode == "off":
        return real_op(tree)
    call_id = _next_call_id()
    profile = mode == "profile" and _EVENTS_ENABLED
    if profile:
        rank = lax.axis_index(axis_name)
        _host_event(rank, 0, call_id)
    leaf = jax.tree.leaves(tree)[0]
    probe = jnp.real(jnp.ravel(leaf)[0]).astype(jnp.float32) * 0.0 + 1.0
    token = lax.ppermute(probe, axis_name, perm)      # P2P artificial barrier
    tree, token = lax.optimization_barrier((tree, token))
    if profile:
        _host_event(rank, 1, call_id)
    out = real_op(tree)
    if profile:
        out, token = lax.optimization_barrier((out, token))
        _host_event(rank, 2, call_id)
    return out
