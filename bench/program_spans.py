"""The program's own spans in a profiler trace, and six per-layer readings
of them.

The program writes spans into the profiler's trace while it records
(``repro.obs.tracer.span``), on the clock of the device's events: ``cd.emit``
(the host side of one instrumentation callback; arguments ``rank``,
``phase``, ``call``, ``t_ns``), ``governor.sink``, ``engine.decode`` with
its children ``engine.prepare``, ``engine.dispatch``, ``engine.wait`` and
``engine.commit``, and ``engine.admit`` with one ``engine.prefill`` per
join (``rid``, ``prompt_len``, ``wait_us``).  :func:`reduce` keeps, over the
``bench.window`` span:

- every program span on any line of the ``/host:CPU`` plane (the
  callbacks run on a runtime thread, not on ``python``);
- per TPU plane, its host callbacks, each from the start of its first
  ``io_callback`` op to the end of its last (only ``core/instrument.py``
  emits them; on a TPU an ordered callback runs as a ``send`` and
  ``send-done`` per argument, then a ``recv`` and ``recv-done`` that wait
  for the host's return);
- per TPU plane, the device's idle time split by the innermost (shortest)
  program span open on the host at each instant, ``none`` where none is.

``READINGS`` maps each metric's name to the function that reads it from
that reduction and the run's result (``bench.run``'s ``res``); each
returns None when its count check fails.  The harness reduces and deletes
its trace before any metric reads it (``bench/drivers/common.py``), so until
it keeps this reduction too, this module runs a cell itself:

    python3 -m bench.program_spans --workload <cell> --seed <n> --seconds <s>

runs the cell traced, exactly as ``bench/run.py --trace 1`` does, and prints
that line with the readings of the cell's metrics added under ``metrics``
and the reduction's totals under ``program_spans``.
"""
from __future__ import annotations

import collections
import heapq
import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

from bench.trace_reduce import _COLLECTIVES, merged, op_kind, op_name

PREFIXES = ("cd.", "governor.", "engine.")
CD_PSUM_PER_STEP = 2            # the train step's gradient tree and loss
PHASES = ("barrier_enter", "barrier_exit", "copy_exit")
EMITS_PER_STEP = CD_PSUM_PER_STEP * len(PHASES)
_OPCODE = re.compile(r" ([a-z][a-z-]*)\(")     # ``<shape> send-done(`` -> send-done
_CALLBACK_PARTS = ("send", "send-done", "recv")


@dataclass
class Span:
    name: str
    start: int                  # ns, the profiler's clock
    end: int
    line: str                   # the host thread's line
    args: Dict[str, object]

    @property
    def s(self) -> float:
        return (self.end - self.start) * 1e-9


@dataclass
class ProgramTrace:
    window_s: float
    spans: List[Span]                                     # in start order
    callbacks: Dict[str, List[Tuple[int, int]]]           # TPU plane -> host callbacks
    idle_by_span: Dict[str, Dict[str, float]] = field(default_factory=dict)

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]


def _stats(event) -> Dict[str, object]:
    return {k: v for k, v in event.stats}


def _callbacks(plane, lo, hi) -> List[Tuple[int, int]]:
    """The plane's host callbacks that start in ``[lo, hi]``, in start
    order: the ``io_callback`` ops up to and including one that is not a
    ``send``, ``send-done`` or ``recv`` (the ``recv-done``) are one call."""
    ops = sorted((e.start_ns, e.start_ns + e.duration_ns, _OPCODE.search(e.name.split(" = ", 1)[-1]))
                 for line in plane.lines if line.name == "XLA Ops" for e in line.events
                 if op_kind(op_name(e.name)) == "io_callback" and lo <= e.start_ns <= hi)
    out, first = [], None
    for a, b, code in ops:
        first = a if first is None else first
        if code is None or code.group(1) not in _CALLBACK_PARTS:
            out.append((first, b))
            first = None
    return out


def _busy(plane, lo, hi) -> List[Tuple[float, float]]:
    """The plane's busy intervals, as ``trace_reduce`` counts busy time:
    every ``XLA Ops`` event and the collectives of ``Async XLA Ops``."""
    iv = []
    for line in plane.lines:
        if line.name not in ("XLA Ops", "Async XLA Ops"):
            continue
        for e in line.events:
            a, b = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
            if b > a and (line.name == "XLA Ops"
                          or op_kind(op_name(e.name)).startswith(_COLLECTIVES)):
                iv.append((a, b))
    return merged(iv)


def _labelled(spans: List[Span], lo: float, hi: float) -> List[Tuple[float, float, str]]:
    """``[lo, hi]`` cut into pieces, each named by the shortest span open
    over it (``none`` where no span is)."""
    bounds = sorted({lo, hi, *(min(max(x, lo), hi) for s in spans for x in (s.start, s.end))})
    order = sorted(spans, key=lambda s: s.start)
    open_, i, out = [], 0, []
    for a, b in zip(bounds, bounds[1:]):
        while i < len(order) and order[i].start <= a:
            s = order[i]
            heapq.heappush(open_, (s.end - s.start, s.end, i, s.name))
            i += 1
        while open_ and open_[0][1] <= a:
            heapq.heappop(open_)
        name = open_[0][3] if open_ else "none"
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def _idle_by_span(busy, pieces, lo, hi) -> Dict[str, float]:
    """Seconds of ``[lo, hi]`` outside ``busy``, by the piece they fall in."""
    idle = [(a, b) for a, b in zip([lo] + [e for _, e in busy], [s for s, _ in busy] + [hi])
            if b > a]
    out: Dict[str, float] = collections.defaultdict(float)
    j = 0
    for a, b in idle:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            pa, pb, name = pieces[k]
            out[name] += (min(b, pb) - max(a, pa)) * 1e-9
            k += 1
    return dict(out)


def reduce(trace, window: str = "bench.window") -> ProgramTrace:
    """Reduce ``trace`` (an ``.xplane.pb`` path or a ``ProfileData``) over the
    host span named ``window``, or over the device's busy extent when that
    span is absent."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(trace) if isinstance(trace, str) else trace
    found, win, devices = [], [], []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == window:
                        win.append((e.start_ns, e.start_ns + e.duration_ns))
                    elif e.name.startswith(PREFIXES):
                        found.append(Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                                          line.name, _stats(e)))
        elif re.fullmatch(r"/device:TPU:\d+", plane.name):
            devices.append(plane)
    lo, hi = win[0] if win else (-math.inf, math.inf)
    busy = {p.name: _busy(p, lo, hi) for p in devices}
    if not win:
        ends = [x for iv in busy.values() if iv for x in (iv[0][0], iv[-1][1])]
        lo, hi = (min(ends), max(ends)) if ends else (0, 0)
    spans = sorted((s for s in found if lo <= s.start <= hi), key=lambda s: s.start)
    callbacks = {p.name: _callbacks(p, lo, hi) for p in devices}
    pieces = _labelled(spans, lo, hi)
    idle = {p.name: _idle_by_span(busy[p.name], pieces, lo, hi) for p in devices}
    return ProgramTrace(window_s=(hi - lo) * 1e-9, spans=spans, callbacks=callbacks,
                        idle_by_span=idle)


# ---- the readings ---------------------------------------------------------

def _by_rank(emits: List[Span]) -> Dict[int, List[Span]]:
    out = collections.defaultdict(list)
    for s in emits:
        out[int(s.args["rank"])].append(s)
    return dict(out)


def _emits_counted(pt: ProgramTrace, steps: int):
    """The window's ``cd.emit`` spans by rank, or None unless every rank
    has 3 phases x 2 calls for each step, give or take one step cut at
    each edge of the window."""
    ranks = _by_rank(pt.named("cd.emit"))
    if not ranks or not steps:
        return None
    for spans in ranks.values():
        if abs(len(spans) - EMITS_PER_STEP * steps) > 2 * EMITS_PER_STEP:
            return None
    return ranks


def cd_host_ms_per_step(pt: ProgramTrace, run: dict):
    """Host time of the instrumentation's callbacks (``cd.emit``) per step
    and rank, in ms: the mean span times the 6 a step."""
    ranks = _emits_counted(pt, run["layer"]["steps"])
    if ranks is None:
        return None
    spans = [s for v in ranks.values() for s in v]
    return 1e3 * EMITS_PER_STEP * sum(s.s for s in spans) / len(spans)


def governor_us_per_event(pt: ProgramTrace, run: dict):
    """Mean time of the governor's share of one event (``governor.sink``),
    in us; every ``cd.emit`` must have reached the governor once."""
    sinks = pt.named("governor.sink")
    if not sinks or _emits_counted(pt, run["layer"]["steps"]) is None \
            or len(sinks) != len(pt.named("cd.emit")):
        return None
    return 1e6 * sum(s.s for s in sinks) / len(sinks)


def _paired(pt: ProgramTrace, steps: int):
    """Per TPU plane, its host callbacks paired in start order with the
    window's ``cd.emit`` spans (the callbacks are ordered, and every rank
    makes the same calls in the same order), or None unless the ranks agree
    on that order and each plane has as many callbacks as a rank has spans.
    The pairing takes no time from the host's clock: the profiler lays the
    device's events beside the host's only to a fraction of a millisecond."""
    ranks = _emits_counted(pt, steps)
    if ranks is None or not pt.callbacks:
        return None
    orders = {tuple((s.args["phase"], s.args["call"]) for s in v) for v in ranks.values()}
    spans = next(iter(ranks.values()))
    if len(orders) != 1 or any(len(cbs) != len(spans) for cbs in pt.callbacks.values()):
        return None
    return {plane: list(zip(cbs, spans)) for plane, cbs in pt.callbacks.items()}


def cd_slack_device_ms_per_step(pt: ProgramTrace, run: dict):
    """Slack on the chip's clock, per step, in ms: for each call the start
    of its ``barrier_exit`` callback minus the end of its ``barrier_enter``
    callback, the mean over the paired calls times the 2 calls a step."""
    pairs = _paired(pt, run["layer"]["steps"])
    if pairs is None:
        return None
    slack = []
    for cbs in pairs.values():
        entered = {}
        for (a, b), s in cbs:
            call = s.args["call"]
            if s.args["phase"] == "barrier_enter":
                entered[call] = b
            elif s.args["phase"] == "barrier_exit" and call in entered:
                slack.append((a - entered.pop(call)) * 1e-9)
    if not slack:
        return None
    return 1e3 * CD_PSUM_PER_STEP * sum(slack) / len(slack)


def callback_ms_per_step(pt: ProgramTrace, run: dict):
    """Device time of the host callbacks per step and chip, in ms: the
    transport, the host's work and the return, seen from the chip."""
    pairs = _paired(pt, run["layer"]["steps"])
    if pairs is None:
        return None
    cbs = [cb for v in pairs.values() for cb, _ in v]
    return 1e-6 * EMITS_PER_STEP * sum(b - a for a, b in cbs) / len(cbs)


def _decode_steps(pt: ProgramTrace, run: dict):
    """``engine.decode`` spans with their ``engine.wait`` child, or None
    unless each of the four children appears once per decode span and the
    window's decode steps are all there (one more may close after it)."""
    decodes = pt.named("engine.decode")
    n = len(decodes)
    steps = len(run["layer"]["steps"])
    if not n or not steps <= n <= steps + 1:
        return None
    children = {c: pt.named(f"engine.{c}") for c in ("prepare", "dispatch", "wait", "commit")}
    if any(len(v) != n for v in children.values()):
        return None
    pairs = list(zip(decodes, children["wait"]))
    if not all(d.start <= w.start and w.end <= d.end for d, w in pairs):
        return None
    return pairs


def engine_host_ms_per_step(pt: ProgramTrace, run: dict):
    """The engine's host time per decode step, in ms: ``engine.decode``
    less its ``engine.wait`` child, the mean over the window's steps."""
    pairs = _decode_steps(pt, run)
    if pairs is None:
        return None
    return 1e3 * sum(d.s - w.s for d, w in pairs) / len(pairs)


def device_idle_engine(pt: ProgramTrace, run: dict):
    """Share of the window in which the device idled while the innermost
    open program span was an ``engine.*`` span other than ``engine.wait``,
    in % (a part of ``device_idle.serve``)."""
    if not pt.idle_by_span or _decode_steps(pt, run) is None or pt.window_s <= 0:
        return None
    idle = [sum(v for k, v in by.items() if k.startswith("engine.") and k != "engine.wait")
            for by in pt.idle_by_span.values()]
    return 100.0 * sum(idle) / len(idle) / pt.window_s


def queue_wait_p95_ms(pt: ProgramTrace, run: dict):
    """p95 of the requests' wait from arrival to the start of their join
    (``engine.prefill``'s ``wait_us``), in ms; every join the window
    recorded must have its span, each request once."""
    from bench import stats

    joins = pt.named("engine.prefill")
    rids = [s.args.get("rid") for s in joins]
    if not joins or len(set(rids)) != len(rids) or len(joins) < len(run["layer"]["joins"]) \
            or any("wait_us" not in s.args for s in joins):
        return None
    return stats.percentile([1e-3 * s.args["wait_us"] for s in joins], 95)


READINGS = {                     # metric -> (unit, reader)
    "cd_host_ms_per_step.train": ("ms", cd_host_ms_per_step),
    "governor_us_per_event.train": ("us", governor_us_per_event),
    "cd_slack_device_ms_per_step.train": ("ms", cd_slack_device_ms_per_step),
    "engine_host_ms_per_step.serve": ("ms", engine_host_ms_per_step),
    "device_idle_engine.serve": ("%", device_idle_engine),
    "queue_wait_p95_ms.serve": ("ms", queue_wait_p95_ms),
}


def summary(pt: ProgramTrace, run: dict, kind: str) -> dict:
    """What ``PERF.md`` reports beside the readings: each span's count and
    seconds, the host callbacks per TPU plane, the idle split and, for
    training, the callbacks' device time per step."""
    tot = collections.defaultdict(lambda: [0, 0.0])
    for s in pt.spans:
        tot[s.name][0] += 1
        tot[s.name][1] += s.s
    out = {"window_s": pt.window_s, "spans": dict(sorted(tot.items())),
           "callbacks": {k: len(v) for k, v in pt.callbacks.items()},
           "idle_by_span": pt.idle_by_span}
    if kind == "train":
        out["callback_ms_per_step"] = callback_ms_per_step(pt, run)
    return out


def traced_run(bench: dict, cell: dict, conf: dict, mix: dict, seed: int,
               seconds: float) -> dict:
    """``bench.run.run_cell`` traced, with this module's readings of the
    cell's trace added to its line."""
    import importlib

    from bench import run, trace_reduce

    driver = importlib.import_module(f"bench.drivers.{mix['driver']}")
    kept = {}
    bench_reduce, driver_run = trace_reduce.reduce, driver.run

    def reduce_both(path, *a, **kw):
        kept["pt"] = reduce(path)
        return bench_reduce(path, *a, **kw)

    def run_and_keep(ctx):
        kept["res"] = driver_run(ctx)
        return kept["res"]

    trace_reduce.reduce, driver.run = reduce_both, run_and_keep
    try:
        out = run.run_cell(bench, cell, conf, mix, seed, seconds, True)
    finally:
        trace_reduce.reduce, driver.run = bench_reduce, driver_run
    pt, res = kept.get("pt"), kept["res"]
    kind = "train" if mix["driver"] == "train" else "serve"
    if pt is not None:
        for name, (unit, read) in READINGS.items():
            v = read(pt, res) if name.endswith("." + kind) else None
            if v is not None and math.isfinite(v):
                out["metrics"][name] = {"value": v, "unit": unit}
        out["program_spans"] = summary(pt, res, kind)
    out["e2e"] = res["e2e"]
    return out


def main(argv=None) -> None:
    import argparse
    import json
    import os
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(root / "src"), str(root)]
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import model, run, workload

    bench = run.load_benchmark()
    cell = run.cell_of(bench, args.workload)
    out = traced_run(bench, cell, model.load_config(cell["config"]),
                     workload.load_mix(cell["traffic"]), args.seed, args.seconds)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
