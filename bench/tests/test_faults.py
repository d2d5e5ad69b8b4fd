"""Runs of the small cells on the CPU, past the harness's look for a chip,
with the timed path broken underneath: ``correct`` has to come out false.

One test per fault the cells can have: a token altered where it is
produced (serving); a step that returns its state unchanged, and half of
the batch left out with the mean taken over the rest (training); the
exchange between chips left out (the training cell's step on four
virtual CPU devices, in a child process: the data mesh spans every
device the run finds)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import small

SEED = 2**40 + 17


def _run(cell_fn, *args):
    return small.run_cell(cell_fn, *args, seed=SEED)


def test_sound_small_runs_are_correct():
    assert _run(small.serve_cell)["correct"]
    assert _run(small.train_cell)["correct"]


def test_sound_small_prefix_cache_run_is_correct(monkeypatch):
    """A mix of shared system prompts on an engine with the prefix cache,
    both stated in the files alone: correct, with nothing compiled inside
    the window."""
    from bench import run

    lines = []
    monkeypatch.setattr(run, "log", lines.append)

    def cell():
        bench, cell, conf, mix = small.serve_cell()
        conf["serving"] = dict(conf["serving"], prefix_cache={})
        mix["shared_prefix"] = {"tokens": 32, "distinct": 2}
        return bench, cell, conf, mix

    out = _run(cell)
    assert out["correct"], out["checks"]
    assert "programs compiled inside the window: 0" in lines


def test_serve_token_altered_where_produced(monkeypatch):
    from repro.serve.engine import EngineSession

    real = EngineSession.decode_step

    def altered(self):
        before = {s: len(r.out) for s, r in self.sched.active.items()}
        vocab = self.engine.cfg.vocab
        reqs = dict(self.sched.active)
        real(self)
        for s, r in reqs.items():
            if len(r.out) > before[s]:
                r.out[-1] = (r.out[-1] + 1) % vocab
                if s in self.sched.active:
                    self.engine._tokens[s] = r.out[-1]

    monkeypatch.setattr(EngineSession, "decode_step", altered)
    out = _run(small.serve_cell)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > out["checks"]["max_logit_gap"]["limit"]


def _patch_step(monkeypatch, wrap):
    from repro.launch import train as program

    real = program.build_live

    def build_live(*a, **k):
        state, step = real(*a, **k)
        return state, wrap(step)

    monkeypatch.setattr(program, "build_live", build_live)


def test_train_state_left_unchanged(monkeypatch):
    _patch_step(monkeypatch, lambda step: lambda state, b: (state, step(state, b)[1]))
    out = _run(small.train_cell)
    assert not out["correct"]
    assert out["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_half_batch_left_out(monkeypatch):
    def wrap(step):
        def half(state, b):
            n = b["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in b.items()})
        return half

    _patch_step(monkeypatch, wrap)
    out = _run(small.train_cell)
    assert not out["correct"]


CHILD = """
import json, sys
sys.path[:0] = {paths!r}
import small
from repro.core import instrument
{patch}
out = small.run_cell(small.train_cell, seed={seed})
print(json.dumps(out))
"""


@pytest.mark.parametrize("exchange", [True, False])
def test_train_four_devices_exchange_left_out(exchange):
    here = Path(__file__).resolve().parent
    paths = [str(here), str(here.parents[1] / "src"), str(here.parents[1])]
    patch = "" if exchange else "instrument.cd_psum = lambda tree, axes: tree"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", CHILD.format(paths=paths, patch=patch, seed=SEED)],
                          capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"] is exchange
