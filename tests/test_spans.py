"""Program spans on the profiler's clock (``repro.obs.tracer.span``) and
their reduction (``bench/program_spans.py``).

A recording of ``scripts/record_span_trace.py`` on two virtual CPU devices
(profile-mode ``cd_psum`` with a governor, then a short continuous-batching
session) is made afresh in a subprocess and checked span by span;
``tests/data/spans_cpu.xplane.pb`` is one such recording, read with pinned
numbers.  A CPU trace has no TPU plane, so the device side of the
reduction reads traces written here as text.
"""
import json
import os
import subprocess
import sys
from collections import Counter
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import program_spans as S  # noqa: E402
from bench import trace_reduce  # noqa: E402
from repro.obs.tracer import NO_SPAN, recording, span  # noqa: E402

DATA = ROOT / "tests" / "data" / "spans_cpu.xplane.pb"
ENGINE_CHILDREN = ("engine.prepare", "engine.dispatch", "engine.wait", "engine.commit")


def test_span_is_the_shared_null_context_unless_recording():
    assert not recording()
    assert span("engine.decode") is NO_SPAN
    assert span("cd.emit", rank=0, t_ns=1) is NO_SPAN
    with span("governor.sink"):
        pass


# --------------------------------------------------------------------------
# a fresh recording
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    out = tmp_path_factory.mktemp("spans")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run([sys.executable, str(ROOT / "scripts" / "record_span_trace.py"),
                           str(out)], env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    path, info = proc.stdout.strip().splitlines()[-2:]
    return S.reduce(path), json.loads(info)


def test_cd_psum_writes_three_emit_spans_per_call_per_rank(recorded):
    pt, info = recorded
    assert info["ranks"] == 2
    emits = pt.named("cd.emit")
    per = Counter((s.args["rank"], s.args["call"], s.args["phase"]) for s in emits)
    calls = sorted({c for _, c, _ in per})
    assert len(calls) == info["calls_per_step"]
    # every execution of the step runs each call once: 3 phases per call per rank
    assert per == {(r, c, ph): info["steps"] for r in range(2) for c in calls
                   for ph in S.PHASES}
    assert all(isinstance(s.args["t_ns"], int) for s in emits)


def test_emit_spans_join_the_governors_clock(recorded):
    pt, _ = recorded
    offsets = [s.start - s.args["t_ns"] for s in pt.named("cd.emit")]
    assert max(offsets) - min(offsets) < 1e6          # one offset, within 1 ms


def test_governor_sink_is_its_share_of_each_emit(recorded):
    pt, _ = recorded
    emits, sinks = pt.named("cd.emit"), pt.named("governor.sink")
    assert len(sinks) == len(emits)
    for g in sinks:
        assert any(e.line == g.line and e.start <= g.start and g.end <= e.end
                   for e in emits)


def test_engine_spans(recorded):
    pt, info = recorded
    decodes = pt.named("engine.decode")
    assert len(decodes) == info["decode_steps"]
    kids = {c: pt.named(c) for c in ENGINE_CHILDREN}
    for k, d in enumerate(decodes):
        steps = [kids[c][k] for c in ENGINE_CHILDREN]
        assert all(d.start <= s.start and s.end <= d.end and s.line == d.line for s in steps)
        assert all(a.end <= b.start for a, b in zip(steps, steps[1:]))
    prefills = pt.named("engine.prefill")
    assert len(prefills) == info["joins"] == info["requests"]
    assert sorted(s.args["rid"] for s in prefills) == sorted({s.args["rid"] for s in prefills})
    assert all(s.args["prompt_len"] == 8 for s in prefills)
    admits = pt.named("engine.admit")
    assert all(any(a.start <= p.start and p.end <= a.end for a in admits) for p in prefills)
    # two slots: the third request waits at least for a decode step
    waits = sorted(s.args["wait_us"] for s in prefills)
    assert waits[-1] >= 1e6 * min(d.s for d in decodes)


# --------------------------------------------------------------------------
# the committed recording
# --------------------------------------------------------------------------

TRAIN_RUN = {"layer": {"steps": 3}}
SERVE_RUN = {"layer": {"steps": [(0.0, [9, 9])] * 6, "joins": [(0.0, 8)] * 3}}


@pytest.fixture(scope="module")
def committed():
    return S.reduce(str(DATA))


def test_reduction_of_the_committed_recording(committed):
    pt = committed
    assert pt.window_s == pytest.approx(0.112361463, rel=1e-9)
    assert Counter(s.name for s in pt.spans) == {
        "cd.emit": 36, "governor.sink": 36, "engine.admit": 6, "engine.decode": 6,
        "engine.prepare": 6, "engine.dispatch": 6, "engine.wait": 6, "engine.commit": 6,
        "engine.prefill": 3}
    assert [s.args["wait_us"] for s in pt.named("engine.prefill")] == [115, 11129, 37991]
    assert pt.callbacks == {} and pt.idle_by_span == {}          # no TPU plane
    assert {s.line for s in pt.named("cd.emit")}.isdisjoint({"python"})


def test_readings_of_the_committed_recording(committed):
    r = {name: read(committed, TRAIN_RUN if name.endswith(".train") else SERVE_RUN)
         for name, (_, read) in S.READINGS.items()}
    assert r["cd_host_ms_per_step.train"] == pytest.approx(1.1807125, rel=1e-9)
    assert r["governor_us_per_event.train"] == pytest.approx(17.392055556, rel=1e-9)
    assert r["engine_host_ms_per_step.serve"] == pytest.approx(2.744512, rel=1e-9)
    assert r["queue_wait_p95_ms.serve"] == pytest.approx(35.3048, rel=1e-9)
    # the device's side needs a TPU plane
    assert r["cd_slack_device_ms_per_step.train"] is None
    assert r["device_idle_engine.serve"] is None


@pytest.mark.parametrize("run, name", [
    ({"layer": {"steps": 6}}, "cd_host_ms_per_step.train"),          # 3 steps recorded
    ({"layer": {"steps": 6}}, "governor_us_per_event.train"),
    ({"layer": {"steps": [(0.0, [9])] * 8, "joins": []}}, "engine_host_ms_per_step.serve"),
    ({"layer": {"steps": [], "joins": [(0.0, 8)] * 4}}, "queue_wait_p95_ms.serve"),
])
def test_a_failed_count_check_reads_nothing(committed, run, name):
    assert S.READINGS[name][1](committed, run) is None


# --------------------------------------------------------------------------
# the device's side, on traces written as text
# --------------------------------------------------------------------------

def xspace(host, device):
    """A ``ProfileData`` from ``host`` (line -> [(name, start, end, args)])
    and ``device`` (``/device:TPU:0``'s ``XLA Ops``: [(hlo, start, end)]),
    times in ns."""
    from jax.profiler import ProfileData

    def plane(pid, name, lines):
        names, stats, out = {}, {}, [f'planes {{ id: {pid} name: "{name}"']
        for lid, (line, events) in enumerate(lines.items(), 1):
            out.append(f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0')
            for ev in events:
                ev_name, a, b, args = (ev + ({},))[:4]
                mid = names.setdefault(ev_name, len(names) + 1)
                st = " ".join(
                    f'stats {{ metadata_id: {stats.setdefault(k, len(stats) + 1)} '
                    + (f'int64_value: {v} }}' if isinstance(v, int) else f'str_value: "{v}" }}')
                    for k, v in args.items())
                out.append(f"events {{ metadata_id: {mid} offset_ps: {a * 1000} "
                           f"duration_ps: {(b - a) * 1000} {st} }}")
            out.append("}")
        out += [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                for n, i in names.items()]
        out += [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                for n, i in stats.items()]
        return "\n".join(out + ["}"])

    return ProfileData.from_text_proto(
        plane(1, "/host:CPU", host) + "\n" + plane(2, "/device:TPU:0", {"XLA Ops": device}))


CALLBACK_OPS = [          # one ordered callback of three scalars on a TPU, as the trace names them
    ("send", "(s32[], u32[], token[]) send(s32[] %c, token[] %t)"),
    ("send-done", "token[] send-done((s32[], u32[], token[]) %s)"),
] * 3 + [
    ("recv", "(f32[], u32[], token[]) recv(token[] %t)"),
    ("recv-done", "(f32[], token[]) recv-done((f32[], u32[], token[]) %r)"),
]


def train_trace(n_calls=12):
    """Two steps of two calls on one chip: callback k spans [8000k + 1000,
    8000k + 3000] as 8 ops (250 ns each), its ``cd.emit`` inside it."""
    emits, sinks, ops = [], [], []
    for k in range(12):
        a = 8000 * k + 1000
        call, phase = 1 + (k // 3) % 2, S.PHASES[k % 3]
        emits.append(("cd.emit", a + 500, a + 1500,
                      {"rank": 0, "phase": phase, "call": call, "t_ns": a + 507}))
        sinks.append(("governor.sink", a + 600, a + 1000))
        if k < n_calls:
            ops += [(f"%io_callback.{8 * k + j} = {hlo}", a + 250 * j, a + 250 * (j + 1))
                    for j, (_, hlo) in enumerate(CALLBACK_OPS)]
    host = {"python": [("bench.window", 0, 100_000)], "runtime": sorted(emits + sinks, key=lambda e: e[1])}
    return xspace(host, ops)


def test_callbacks_pair_with_emit_spans_on_the_chips_clock():
    pt = S.reduce(train_trace())
    run = {"layer": {"steps": 2}}
    assert pt.callbacks["/device:TPU:0"] == [(8000 * k + 1000, 8000 * k + 3000) for k in range(12)]
    # barrier_exit's callback starts 8000 ns after barrier_enter's began, 6000 after it ended
    assert S.cd_slack_device_ms_per_step(pt, run) == pytest.approx(2 * 6e-3)
    assert S.callback_ms_per_step(pt, run) == pytest.approx(6 * 2e-3)
    assert S.cd_host_ms_per_step(pt, run) == pytest.approx(6 * 1e-3)
    assert S.governor_us_per_event(pt, run) == pytest.approx(0.4)
    # busy only in the callbacks: the rest of the window idles under no span or cd.emit
    idle = pt.idle_by_span["/device:TPU:0"]
    assert sum(idle.values()) == pytest.approx((100_000 - 12 * 2000) * 1e-9)
    assert set(idle) == {"none"}


def test_callbacks_unpaired_when_the_counts_disagree():
    pt = S.reduce(train_trace(n_calls=11))
    assert len(pt.callbacks["/device:TPU:0"]) == 11
    assert S.cd_slack_device_ms_per_step(pt, {"layer": {"steps": 2}}) is None


def test_device_idle_split_by_the_innermost_engine_span():
    host = {"python": [("bench.window", 0, 100_000), ("engine.decode", 5000, 45_000),
                       ("engine.prepare", 5000, 8000), ("engine.dispatch", 8000, 12_000),
                       ("engine.wait", 12_000, 42_000), ("engine.commit", 42_000, 45_000)]}
    device = [("%fusion.1 = f32[] fusion()", 10_000, 20_000),
              ("%fusion.2 = f32[] fusion()", 40_000, 100_000)]
    pt = S.reduce(xspace(host, device))
    idle = pt.idle_by_span["/device:TPU:0"]
    assert idle == pytest.approx({"none": 5e-6, "engine.prepare": 3e-6,
                                  "engine.dispatch": 2e-6, "engine.wait": 20e-6})
    run = {"layer": {"steps": [(0.0, [9])], "joins": []}}
    assert S.device_idle_engine(pt, run) == pytest.approx(5.0)     # of 30 % idle
    assert S.engine_host_ms_per_step(pt, run) == pytest.approx(0.010)


# --------------------------------------------------------------------------
# the reduction the harness runs, pinned
# --------------------------------------------------------------------------

def test_trace_reduce_fields_of_the_recorded_decode_trace():
    s = trace_reduce.reduce(str(ROOT / "bench" / "tests" / "data" / "tiny_decode.xplane.pb"))
    assert (s.n_devices, s.collective_s, s.collective_exposed_s) == (1, 0.0, 0.0)
    assert s.window_s == pytest.approx(0.014540995, rel=1e-9)
    assert s.busy_s == pytest.approx(0.003945551, rel=1e-9)
    assert {k: v[0] for k, v in s.modules.items()} == {"jit_step": 3}
    assert s.modules["jit_step"][1] == pytest.approx(0.003946555, rel=1e-9)
    assert len(s.ops) == 101 and sum(v[0] for v in s.ops.values()) == 433
    assert sum(v[1] for v in s.ops.values()) == pytest.approx(0.003945166, rel=1e-9)
    assert s.top_ops(3) == [["convert.32", pytest.approx(923.75e-6, rel=1e-9)],
                            ["convert.33", pytest.approx(923.049e-6, rel=1e-9)],
                            ["bitcast_add_fusion.3", pytest.approx(541.939e-6, rel=1e-9)]]
    assert s.gaps == [("bench.sleep", pytest.approx(0.005399213, rel=1e-9)),
                      ("bench.sleep", pytest.approx(0.005196047, rel=1e-9)),
                      ("bench.decode", pytest.approx(4e-9, rel=1e-6))] + \
        [("bench.decode", pytest.approx(2e-9, rel=1e-6))] * 7
    assert s.spans == {"bench.decode": (3, pytest.approx(0.012948588, rel=1e-9)),
                       "bench.sleep": (3, pytest.approx(0.007492469, rel=1e-9))}


# --------------------------------------------------------------------------
# a traced run of the harness, read
# --------------------------------------------------------------------------

def test_traced_run_adds_the_readings_to_the_result_line(monkeypatch):
    from bench import peaks, run
    from bench.tests import small

    monkeypatch.setattr(run, "require_chips", lambda devices, cell: None)
    v5e = peaks.peaks_for("TPU v5 lite")
    monkeypatch.setattr(peaks, "peaks_for", lambda kind: v5e)
    bench, cell, conf, mix = small.serve_cell()
    out = S.traced_run(bench, cell, conf, mix, seed=2**40 + 17, seconds=2.0)
    assert out["correct"]
    m = out["metrics"]
    assert m["engine_host_ms_per_step.serve"]["value"] > 0
    assert 0 <= m["queue_wait_p95_ms.serve"]["value"] <= out["e2e"]["ttft_p95_ms"]
    assert "device_idle_engine.serve" not in m          # no TPU plane on the CPU
    assert not any(k.endswith(".train") for k in m)
    assert out["program_spans"]["spans"]["engine.decode"][0] >= 1
