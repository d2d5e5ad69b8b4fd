"""Elastic device mesh + deterministic failure injection.

The recovery model is *mesh epochs* (see launch/train.py): training runs
under one mesh until a device fails; the driver then drains in-flight work,
marks the device failed on the :class:`ElasticMesh`, rebuilds a (smaller)
mesh from the survivors, restores the latest checkpoint and resumes.  This
is the 1000-node recovery path scaled down to whatever this host has — the
mesh factory, sharding rules and checkpoint protocol are identical at both
scales.

:class:`FailureInjector` drives the same path deterministically in tests
and demos: each configured (step, device) failure fires exactly once, so a
resume that replays the failing step does not re-fail forever.
"""
from __future__ import annotations

import itertools
from typing import List, Optional, Sequence, Tuple

import jax

from repro.dist.sharding import make_mesh


class ElasticMesh:
    """A device mesh factory that remembers failed devices across rebuilds.

    ``build(model_parallel=k)`` lays the surviving devices out as a
    (data, model) mesh with the largest model-parallel degree <= k that
    divides the survivor count (model parallelism degrades gracefully as
    devices die rather than refusing to build).
    """

    def __init__(self, axis_names: Sequence[str] = ("data", "model")):
        if len(axis_names) != 2:
            raise ValueError("ElasticMesh lays devices out over exactly two axes")
        self.axis_names: Tuple[str, ...] = tuple(axis_names)
        self._failed: set = set()

    def healthy_devices(self) -> List[jax.Device]:
        return [d for d in jax.devices() if d.id not in self._failed]

    def fail(self, device_id: int) -> None:
        """Mark a device as failed; it is excluded from every later build."""
        self._failed.add(int(device_id))

    def failed_ids(self) -> List[int]:
        return sorted(self._failed)

    def build(self, model_parallel: int = 1):
        devs = self.healthy_devices()
        n = len(devs)
        if n == 0:
            raise RuntimeError("ElasticMesh: no healthy devices left to build from")
        mp = max(g for g in range(1, min(model_parallel, n) + 1) if n % g == 0)
        return make_mesh((n // mp, mp), self.axis_names, devices=devs)


class FailureInjector:
    """Deterministic one-shot device failures at configured steps.

    ``check(step)`` returns the failing device id the first time ``step``
    matches a configured failure, and ``None`` otherwise.  Each failure is
    consumed when it fires — after recovery rewinds the step counter to the
    last checkpoint, replaying the same step does not re-kill the device.
    """

    def __init__(self, fail_at_steps: Sequence[int], device_ids: Sequence[int]):
        if fail_at_steps and not device_ids:
            raise ValueError("fail_at_steps given but no device_ids to fail")
        self._pending = dict(zip(fail_at_steps, itertools.cycle(device_ids))) \
            if fail_at_steps else {}

    def check(self, step: int) -> Optional[int]:
        return self._pending.pop(step, None)

    def pending(self) -> dict:
        return dict(self._pending)
