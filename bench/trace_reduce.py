"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy time, time per program and per operation, the longest
idle gaps labelled by the benchmark's host spans, and the collectives'
exposed time.

Planes ``/device:TPU:<n>`` hold a line ``XLA Modules`` (one event per
program run, named ``jit_<function>(<id>)``) and a line ``XLA Ops`` (one
event per operation run, named by its HLO text, ``%<op> = ...``).  A
``while`` op spans its loop body's ops, so it counts towards busy time
(a union) but not towards any operation's own time.  The host plane's
``python`` line holds the benchmark's ``bench.*`` spans on the same clock.
"""
from __future__ import annotations

import collections
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

_MODULE = re.compile(r"^(.*?)(\(\d+\))?$")
_CONTAINERS = ("while", "conditional", "call")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
                "all-to-all")


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """``fusion.12`` -> ``fusion``; ``all-reduce-start.3`` -> ``all-reduce-start``."""
    return re.sub(r"\.\d+$", "", name)


def module_name(name: str) -> str:
    """``jit_step(1263735246228299254)`` -> ``jit_step``."""
    return _MODULE.match(name).group(1)


def union_length(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def merged(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclass
class TraceSummary:
    window_s: float
    n_devices: int
    busy_s: float                                   # mean over devices
    modules: Dict[str, Tuple[int, float]]           # name -> (runs, device s), all devices
    ops: Dict[str, Tuple[int, float]]               # op name -> (runs, device s), all devices
    gaps: List[Tuple[str, float]]                   # (host span, s), longest first
    collective_s: float = 0.0                       # mean over devices
    collective_exposed_s: float = 0.0               # mean over devices
    spans: Dict[str, Tuple[int, float]] = field(default_factory=dict)

    def module_time(self, prefix: str) -> Tuple[int, float]:
        runs = sum(v[0] for k, v in self.modules.items() if k.startswith(prefix))
        secs = sum(v[1] for k, v in self.modules.items() if k.startswith(prefix))
        return runs, secs

    def op_time(self, prefix: str) -> Tuple[int, float]:
        runs = sum(v[0] for k, v in self.ops.items() if k.startswith(prefix))
        secs = sum(v[1] for k, v in self.ops.items() if k.startswith(prefix))
        return runs, secs

    def top_ops(self, n: int = 10) -> List[List]:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:n]
        return [[k, v[1]] for k, v in top]


def _span_at(spans: List[Tuple[float, float, str]], t: float) -> str:
    """The innermost ``bench.*`` span that covers ``t``."""
    best, width = "none", float("inf")
    for a, b, name in spans:
        if a <= t <= b and b - a < width:
            best, width = name, b - a
    return best


def reduce(path: str, window: str = "bench.window", gaps_kept: int = 10) -> TraceSummary:
    """Read ``path`` and reduce it over the host span named ``window``
    (the whole trace when that span is absent)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
        elif re.fullmatch(r"/device:TPU:\d+", plane.name):
            devices.append(plane)
    win = [s for s in spans if s[2] == window]
    lo, hi = (win[0][0], win[0][1]) if win else (float("-inf"), float("inf"))
    inner = [s for s in spans if s[2] != window]

    modules: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    ops: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    busy, coll, exposed, gaps = [], [], [], []
    first, last = float("inf"), float("-inf")
    for plane in devices:
        all_iv, coll_iv, comp_iv = [], [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for e in line.events:
                    a, b = e.start_ns, e.start_ns + e.duration_ns
                    if b < lo or a > hi:
                        continue
                    m = modules[module_name(e.name)]
                    m[0] += 1
                    m[1] += (min(b, hi) - max(a, lo)) * 1e-9
            elif line.name in ("XLA Ops", "Async XLA Ops"):
                for e in line.events:
                    a, b = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
                    if b <= a:
                        continue
                    name = op_name(e.name)
                    kind = op_kind(name)
                    is_coll = kind.startswith(_COLLECTIVES)
                    if line.name == "XLA Ops" or is_coll:
                        all_iv.append((a, b))
                    if is_coll:
                        coll_iv.append((a, b))
                    elif line.name == "XLA Ops" and kind not in _CONTAINERS:
                        comp_iv.append((a, b))
                    if line.name == "XLA Ops" and kind not in _CONTAINERS:
                        o = ops[name]
                        o[0] += 1
                        o[1] += (b - a) * 1e-9
        busy.append(union_length(all_iv) * 1e-9)
        coll_len = union_length(coll_iv)
        both = union_length([iv for iv in merged(coll_iv)] + comp_iv) - union_length(comp_iv)
        coll.append(coll_len * 1e-9)
        exposed.append(both * 1e-9)
        m = merged(all_iv)
        if m:
            first, last = min(first, m[0][0]), max(last, m[-1][1])
        edges = ([(lo, m[0][0])] if m and lo > float("-inf") else [])
        edges += [(m[i][1], m[i + 1][0]) for i in range(len(m) - 1)]
        edges += ([(m[-1][1], hi)] if m and hi < float("inf") else [])
        for a, b in edges:
            if b > a:
                gaps.append((_span_at(inner, 0.5 * (a + b)), (b - a) * 1e-9))
    if win:
        window_s = (hi - lo) * 1e-9
    else:
        window_s = max(last - first, 0.0) * 1e-9
    n = max(len(devices), 1)
    span_tot: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    for a, b, name in inner:
        span_tot[name][0] += 1
        span_tot[name][1] += (b - a) * 1e-9
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=window_s, n_devices=len(devices), busy_s=sum(busy) / n,
        modules={k: (int(v[0]), v[1]) for k, v in modules.items()},
        ops={k: (int(v[0]), v[1]) for k, v in ops.items()},
        gaps=gaps[:gaps_kept],
        collective_s=sum(coll) / n, collective_exposed_s=sum(exposed) / n,
        spans={k: (int(v[0]), v[1]) for k, v in span_tot.items()})
