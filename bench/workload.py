"""The one traffic generator: reads a mix from ``traffic/<mix>.json``.

A serving mix is an open loop.  For a given mix and window every seed gets
the same multiset of prompt lengths, output lengths and inter-arrival
gaps: quantiles of the mix's distributions.  The seed draws their order
and the prompt tokens, so two seeds do the same work and compile the same
shapes, in another order.

Arrivals copy the burst shape of ``serve.scheduler.poisson_arrivals``:
exponential gaps, in an order drawn from the seed as a whole (so they
cluster as a Poisson process's do), with an extra pause after every
``burst_every``-th request.  The exponential rate is set so that the mean
offered rate is the mix's ``rate_per_s``.  The lengths are drawn in blocks
of ``STRATA`` requests that hold one value from each of ``STRATA`` strata,
so that long answers do not bunch at the window's close on some seeds.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
STRATA = 8


def load_mix(name: str) -> dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, seed])


@dataclass
class ServeRequest:
    arrival: float          # seconds after the window opens
    prompt: np.ndarray      # (n,) int32
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def stratified(values: np.ndarray, rng: np.random.Generator, k: int) -> np.ndarray:
    """``values`` in a seeded order in which every block of ``k``
    consecutive entries holds one value from each of ``k`` strata of the
    sorted values, so each stretch of the window gets the same mix of
    sizes, whatever the seed.  A remainder shorter than ``k`` goes last."""
    v = np.sort(values)
    if len(v) < k:
        return rng.permutation(v)
    n = len(v) // k * k
    strata = v[:n].reshape(k, n // k)
    blocks = np.stack([rng.permutation(row) for row in strata], axis=1)
    blocks = np.stack([rng.permutation(b) for b in blocks])
    return np.concatenate([blocks.reshape(-1), rng.permutation(v[n:])])


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles of a clipped lognormal, each moved
    to the nearest of ``buckets`` on a log scale when the mix lists them."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(x, spec["min"], spec["max"])
    if "buckets" in spec:
        b = np.asarray(spec["buckets"], np.float64)
        x = b[np.argmin(np.abs(np.log(x)[:, None] - np.log(b)[None, :]), axis=1)]
    return np.round(x).astype(np.int64)


def n_requests(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["arrivals"]["rate_per_s"] * seconds)))


def serve_schedule(mix: dict, seed: int, seconds: float, vocab: int) -> List[ServeRequest]:
    """The window's requests, arrivals spanning ``[0, seconds)``."""
    arr = mix["arrivals"]
    n = n_requests(mix, seconds)
    k, pause = int(arr.get("burst_every", 0)), float(arr.get("burst_gap_s", 0.0))
    rng = seed_rng(seed, 1)
    # gaps[i] follows request i; the last one closes the window
    gaps = rng.permutation(-np.log1p(-_quantiles(n)))  # unit-mean exponential
    if k:
        gaps[k - 1::k] += pause * arr["rate_per_s"]   # in units of 1/rate
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (seconds / gaps.sum())
    plen = stratified(lengths(mix["prompt_tokens"], n), rng, STRATA)
    olen = stratified(lengths(mix["output_tokens"], n), rng, STRATA)
    shared = system_prompts(mix, seed, vocab)
    reqs = []
    for i in range(n):
        prompt = rng.integers(0, vocab, int(plen[i])).astype(np.int32)
        if shared:
            prompt = np.concatenate([shared[rng.integers(len(shared))], prompt])
        reqs.append(ServeRequest(float(t[i]), prompt, int(olen[i])))
    return reqs


def system_prompts(mix: dict, seed: int, vocab: int) -> List[np.ndarray]:
    """With ``shared_prefix`` (``tokens``, ``distinct``), every prompt
    starts with one of ``distinct`` seeded system prompts of ``tokens``
    tokens, and its drawn length follows it."""
    sp = mix.get("shared_prefix")
    if not sp:
        return []
    rng = seed_rng(seed, 4)
    return [rng.integers(0, vocab, sp["tokens"]).astype(np.int32)
            for _ in range(sp["distinct"])]


def serve_shapes(mix: dict, seconds: float):
    """(distinct prompt lengths, longest prompt, longest output, shortest
    prompt) of the window, the same for every seed."""
    n = n_requests(mix, seconds)
    p = lengths(mix["prompt_tokens"], n) + mix.get("shared_prefix", {}).get("tokens", 0)
    o = lengths(mix["output_tokens"], n)
    return sorted(set(int(x) for x in p)), int(p.max()), int(o.max()), int(p.min())
