"""Observability stack (repro.obs): registry/tracer/export/log contracts.

Three load-bearing guarantees live here:

* **Golden Perfetto fixture** — a canned multi-stream capture (raw rank
  events, phase records, actuations, theta decisions, serve lifecycle,
  counter samples) serializes to the committed ``tests/goldens/
  perfetto.json`` byte-for-byte.  Any change to span reconstruction,
  track layout, or export ordering fails loudly; intentional changes are
  made by re-running ``scripts/regen_goldens.py --perfetto``.
* **Histogram/accumulator equivalence** — over any ``publish_phase``
  stream, ``BusMetrics``' slack/copy histogram sums equal the governor's
  ``GovernorReport`` totals with exact ``==`` (same clamp, same addition
  order).  Property-tested on random streams.
* **Exact-report JSONL** — every ``MetricsJsonlWriter`` line embeds
  ``GovernorReport.to_dict()`` verbatim (modulo the JSON round-trip's
  int-key stringification), and ``validate_metrics_jsonl`` passes.
"""
import io
import json
import os
import time

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.events import EventBus, PhaseRecord
from repro.core.governor import Actuation, Governor
from repro.core.timeout import ThetaDecision
from repro.obs import log as obslog
from repro.obs.export import (
    ConsoleDashboard,
    MetricsJsonlWriter,
    prometheus_text,
    validate_metrics_jsonl,
)
from repro.obs.metrics import (
    DEFAULT_EDGES,
    BusMetrics,
    GovernorCollector,
    MetricsRegistry,
)
from repro.obs.tracer import (
    TRACK_PIDS,
    GovernorTap,
    RecorderFanout,
    SpanTracer,
    validate_trace,
)

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "goldens")


# --------------------------------------------------------------------------
# golden Perfetto fixture
# --------------------------------------------------------------------------
def golden_tracer() -> SpanTracer:
    """The canned capture behind ``goldens/perfetto.json`` — every stream
    kind the tracer folds, with hand-picked times so each reconstruction
    path (rotation-rule spans, overlap spans, phase records, instants,
    counters) appears at least once.  Shared with the regeneration helper."""
    tr = SpanTracer(meta={"driver": "golden"})
    # rank 0/1, call 0: plain barrier -> slack + copy spans
    for r, t in ((0, 1.000), (1, 1.0002)):
        tr.on_event(r, "barrier_enter", 0, t)
    for r in (0, 1):
        tr.on_event(r, "barrier_exit", 0, 1.001)
        tr.on_event(r, "copy_exit", 0, 1.0015 + r * 1e-4)
    # call 1: async occurrence -> overlap span on rank 0
    tr.on_event(0, "dispatch_enter", 1, 1.002)
    tr.on_event(0, "wait_enter", 1, 1.0028)
    tr.on_event(0, "barrier_exit", 1, 1.0031)
    # a fully-formed phase record with a site tag (serve-meter shape)
    tr.on_phase(PhaseRecord(rank=1, call_id=7, t_enter=1.004,
                            t_slack_end=1.0052, t_copy_end=1.0055, site=3))
    # governor outputs
    tr.on_actuation(Actuation(t=1.0012, rank=1, action="set_pstate_min",
                              call_id=0, slack=8e-4))
    tr.on_actuation(Actuation(t=1.0019, rank=1, action="restore_pstate_max",
                              call_id=0, slack=8e-4))
    tr.on_theta(ThetaDecision(t=1.003, site=2, rank=0, theta_before=5e-4,
                              theta_after=3e-4, reason="decay", slack=1e-4))
    # serve lifecycle + driver counter samples
    tr.serve_event("join", 1.0005, rid=4, slot=1)
    tr.serve_event("evict", 1.0056, rid=4, slot=1)
    tr.sample("governor", "slack_ratio_pct", 1.006, 12.5)
    tr.sample("arbiter", "cap_w[train]", 1.006, 1500.0)
    tr.sample("slo", "ttft_p95_ms", 1.006, 41.0)
    return tr


def test_perfetto_golden_bytes():
    path = os.path.join(GOLDEN_DIR, "perfetto.json")
    got = json.dumps(golden_tracer().build(), sort_keys=True)
    with open(path) as f:
        want = f.read()
    assert got == want, "trace export drifted from goldens/perfetto.json " \
                        "(regen via scripts/regen_goldens.py --perfetto)"


def test_perfetto_golden_schema():
    probs = validate_trace(os.path.join(GOLDEN_DIR, "perfetto.json"),
                           require_tracks=tuple(TRACK_PIDS))
    assert probs == []


def test_perfetto_deterministic_rebuild():
    a = golden_tracer()
    assert json.dumps(a.build(), sort_keys=True) \
        == json.dumps(golden_tracer().build(), sort_keys=True)
    # build() is a pure function of the capture: rebuilding does not mutate
    assert json.dumps(a.build(), sort_keys=True) \
        == json.dumps(a.build(), sort_keys=True)


def test_trace_span_reconstruction_shapes():
    ev = golden_tracer().build()["traceEvents"]
    spans = [e for e in ev if e["ph"] == "X"]
    names = sorted(e["name"] for e in spans)
    # 2 slack + 2 copy from call 0, 1 overlap + 1 slack from call 1,
    # 1 slack + 1 copy from the phase record
    assert names == ["copy"] * 3 + ["overlap"] + ["slack"] * 4
    sited = [e for e in spans if e["args"].get("site") is not None]
    assert {e["args"]["site"] for e in sited} == {3}
    assert all(e["dur"] >= 0 for e in spans)
    insts = {e["name"] for e in ev if e["ph"] == "i"}
    assert {"set_pstate_min", "restore_pstate_max", "join", "evict"} <= insts
    ctrs = {e["name"] for e in ev if e["ph"] == "C"}
    assert {"theta_us[2]", "slack_ratio_pct", "cap_w[train]",
            "ttft_p95_ms"} <= ctrs


def test_validate_trace_catches_problems():
    bad = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 0, "name": "slack", "ts": -1, "dur": -2},
        {"ph": "C", "pid": 2, "tid": 0, "name": "c", "ts": 0,
         "args": {"value": "nan-string"}},
        {"ph": "Z", "pid": 1, "tid": 0, "name": "?", "ts": 0},
    ]}
    probs = validate_trace(bad, require_tracks=("ranks",))
    assert len(probs) == 5  # bad ts, bad dur, bad C args, bad ph, no track
    assert validate_trace({"nope": 1}) == ["traceEvents missing or not a list"]


def test_tracer_bounded_capacity():
    tr = SpanTracer(capacity=10)
    for i in range(25):
        tr.on_event(0, "barrier_enter", i, float(i))
    assert tr.n_seen == 25 and tr.n_dropped == 15
    assert validate_trace(tr.build()) == []


# --------------------------------------------------------------------------
# recorder plumbing
# --------------------------------------------------------------------------
class _SpyRecorder:
    def __init__(self):
        self.events, self.phases, self.acts, self.thetas = [], [], [], []

    def on_event(self, rank, phase, call_id, t):
        self.events.append((rank, phase, call_id, t))

    def on_phase(self, record):
        self.phases.append(record)

    def on_actuation(self, act):
        self.acts.append(act)

    def on_theta(self, dec):
        self.thetas.append(dec)


def test_recorder_fanout_and_tap():
    spy = _SpyRecorder()
    tr = SpanTracer()
    fan = RecorderFanout([spy, GovernorTap(tr)])
    act = Actuation(t=0.0, rank=0, action="set_pstate_min", call_id=0, slack=0.1)
    dec = ThetaDecision(t=0.0, site=0, rank=0, theta_before=1e-3,
                        theta_after=5e-4, reason="decay", slack=1e-4)
    fan.on_event(0, "barrier_enter", 0, 0.0)
    fan.on_phase(PhaseRecord(0, 0, 0.0, 0.1, 0.2, None))
    fan.on_actuation(act)
    fan.on_theta(dec)
    assert len(spy.events) == 1 and len(spy.phases) == 1
    assert spy.acts == [act] and spy.thetas == [dec]
    # the tap forwards ingested phases and theta decisions but neither raw
    # events nor eager actuations — those stay off the telemetry hot path
    # (actuations are pulled from the governor's spine log at export)
    assert tr.n_seen == 2
    kinds = {rec[0] for rec in tr._raw}
    assert kinds == {"ph", "theta"}


def test_fanout_skips_missing_hooks():
    class ActsOnly:
        def __init__(self):
            self.acts = []

        def on_actuation(self, act):
            self.acts.append(act)

    partial, spy = ActsOnly(), _SpyRecorder()
    fan = RecorderFanout([partial, spy])
    fan.on_event(0, "barrier_enter", 0, 0.0)     # must not raise
    fan.on_actuation("a")
    assert partial.acts == ["a"] and len(spy.events) == 1


def test_fanout_expands_pairs_for_eager_children():
    # a spine pair reaching the fanout lands once (compact form) on
    # pair-aware children and as two eager Actuations on children that
    # only speak on_actuation (TraceRecorder and friends)
    spy = _SpyRecorder()
    tr = SpanTracer()
    fan = RecorderFanout([spy, tr])
    fan.on_actuation_pair(1.0, 2, 7, 3e-4)
    assert [a.action for a in spy.acts] == ["set_pstate_min",
                                            "restore_pstate_max"]
    assert spy.acts[0].rank == 2 and spy.acts[0].call_id == 7
    assert spy.acts[0].slack == 3e-4
    assert [rec[0] for rec in tr._raw] == ["actp"]


def _downshift_stream(sink, n_calls=6, n_ranks=3):
    """Raw 3-phase stream with 1 ms slack (over the 500 us default theta,
    so every occurrence downshifts) and recurring call ids (so every
    occurrence except the last per id retires by rotation)."""
    t = 0.0
    for c in range(n_calls):
        cid = c % 2
        for r in range(n_ranks):
            sink(r, "barrier_enter", cid, t + r * 1e-6)
        for r in range(n_ranks):
            sink(r, "barrier_exit", cid, t + 1e-3)
            sink(r, "copy_exit", cid, t + 1.2e-3)
        t += 2e-3
    return n_calls, n_ranks


def test_governor_tap_production_wiring():
    """The launch drivers' wiring end to end: governor with a GovernorTap
    recorder streaming raw events — spans come from retired occurrences,
    event counts from the metrics retire hook, actuation instants from the
    spine log pulled at export time (never the hot path)."""
    reg = MetricsRegistry()
    tr = SpanTracer()
    gov = Governor(recorder=GovernorTap(tr, metrics=BusMetrics(reg)))
    n_calls, n_ranks = _downshift_stream(gov.sink)
    n_retired = n_calls - 2                       # one in flight per call id

    assert sum(1 for rec in tr._raw if rec[0] == "ret") == n_retired
    # nothing actuation-shaped was streamed during the run
    assert not any(rec[0] in ("act", "actp") for rec in tr._raw)

    # retired-record event counts are exact (in-flight tail not yet booked)
    snap = reg.snapshot()
    assert "bus_events_total" in snap
    for phase in ("barrier_enter", "barrier_exit", "copy_exit"):
        assert reg.get_value("bus_events_total", phase) == n_ranks * n_retired

    # export: slack + copy span per (rank, retired occurrence), and the
    # spine pull adds two instants per booked pair
    tr.ingest_governor(gov)
    assert gov.n_actuations == 2 * n_ranks * n_calls
    trace = tr.build()
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == 2 * n_ranks * n_retired
    instants = [e for e in trace["traceEvents"]
                if e["ph"] == "i" and e["pid"] == TRACK_PIDS["governor"]]
    assert len(instants) == gov.n_actuations
    assert validate_trace(trace, require_tracks=("ranks", "governor")) == []


def test_serve_instants_share_the_rank_spans_clock():
    """A continuous serve run with a governor and a SpanTracer: the join
    and evict instants land among the rank spans, on one monotonic clock
    whose epoch the export names."""
    import jax
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.models import init_params
    from repro.serve import ContinuousEngine, Request

    cfg = reduced(get_config("llama3.2-1b"))
    eng = ContinuousEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                           n_slots=2, max_len=32, page=8)
    eng.tracer = tr = SpanTracer()
    gov = Governor(recorder=GovernorTap(tr))
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (3, 8)).astype(np.int32)
    # the first arrival is late, so the meter books an idle phase before it
    t0 = time.monotonic()
    eng.serve([Request(prompt=p, max_new=3, arrival=0.05) for p in prompts],
              governor=gov)
    t1 = time.monotonic()
    trace = tr.build()
    assert validate_trace(trace, require_tracks=("ranks", "serve")) == []
    ev = trace["traceEvents"]
    spans = [e for e in ev if e["ph"] == "X" and e["pid"] == TRACK_PIDS["ranks"]]
    serve = [e for e in ev if e["ph"] == "i" and e["pid"] == TRACK_PIDS["serve"]]
    assert sorted(e["name"] for e in serve) == ["evict"] * 3 + ["join"] * 3
    lo = min(e["ts"] for e in spans)
    hi = max(e["ts"] + e["dur"] for e in spans)
    # the last evict follows the last step's span by the step's readback
    assert all(lo <= e["ts"] <= hi + 50e3 for e in serve)
    assert t0 <= trace["otherData"]["monotonic_epoch_s"] <= t1


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------
def test_registry_kind_and_label_conflicts():
    reg = MetricsRegistry()
    fam = reg.counter("x_total", "x", ("a",))
    assert reg.counter("x_total", "x", ("a",)) is fam       # get-or-create
    with pytest.raises(ValueError):
        reg.gauge("x_total", label_names=("a",))            # kind conflict
    with pytest.raises(ValueError):
        reg.counter("x_total", label_names=("b",))          # label conflict


def test_registry_label_stringify_and_get_value():
    reg = MetricsRegistry()
    g = reg.gauge("theta", "t", ("site",))
    g.labels(3).set(1.5)
    assert g.labels("3").value == 1.5
    assert reg.get_value("theta", 3) == 1.5
    assert reg.get_value("theta", 4) is None
    assert reg.get_value("missing") is None
    h = reg.histogram("h")
    h.observe(0.5)
    h.observe(-1.0)                               # clamps to 0.0
    assert reg.get_value("h") == 0.5              # histogram -> sum
    with pytest.raises(ValueError):
        g.labels()                                # label arity enforced


def test_default_edges_match_tuner_binning():
    np = pytest.importorskip("numpy")
    ref = np.geomspace(1e-6, 30.0, 97)
    assert len(DEFAULT_EDGES) == 97
    assert np.allclose(DEFAULT_EDGES, ref, rtol=1e-12, atol=0.0)


def test_histogram_bucket_edges_clamp():
    reg = MetricsRegistry()
    h = reg.histogram("h").labels()
    h.observe(0.0)            # below first edge -> first bucket
    h.observe(1e9)            # beyond last edge -> last bucket
    assert h.counts[0] == 1 and h.counts[-1] == 1 and h.count == 2


def test_bus_metrics_sync_is_delta_based():
    reg = MetricsRegistry()
    bm = BusMetrics(reg)
    bus = EventBus()
    bus.subscribe(bm)
    for i in range(5):
        bus.publish(0, "barrier_enter", i, float(i))
    snap = reg.snapshot()                       # collector hook syncs
    [cell] = snap["bus_events_total"]["values"]
    assert cell["labels"] == {"phase": "barrier_enter"} and cell["value"] == 5
    reg.snapshot()                              # re-sync: no double count
    assert reg.get_value("bus_events_total", "barrier_enter") == 5
    bus.publish(1, "barrier_enter", 9, 9.0)
    reg.snapshot()
    assert reg.get_value("bus_events_total", "barrier_enter") == 6


# --------------------------------------------------------------------------
# histogram sums == governor accumulators (exact), property-tested
# --------------------------------------------------------------------------
phase_streams = st.integers(min_value=0, max_value=10_000).map(lambda seed: seed)


def _random_records(seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 60))
    records, t = [], 1.0
    for i in range(n):
        slack = float(rng.uniform(-1e-4, 5e-3))   # negatives: clamp path
        copy = float(rng.uniform(0.0, 2e-3))
        site = int(rng.integers(0, 3)) if rng.random() < 0.5 else None
        records.append(PhaseRecord(
            rank=int(rng.integers(0, 4)), call_id=i, t_enter=t,
            t_slack_end=t + slack, t_copy_end=t + max(slack, 0.0) + copy,
            site=site))
        t += 1e-2
    return records


@given(phase_streams)
@settings(max_examples=30, deadline=None)
def test_histogram_totals_equal_governor_totals(seed):
    records = _random_records(seed)
    reg = MetricsRegistry()
    bm = BusMetrics(reg)
    gov = Governor()
    bus = EventBus()
    bus.subscribe(gov)
    bus.subscribe(bm)
    for rec in records:
        bus.publish_phase(rec)
    rep = gov.finalize()
    slack_cell = reg.histogram("phase_slack_seconds").labels()
    copy_cell = reg.histogram("phase_copy_seconds").labels()
    # exact float equality: same clamp, same addition order
    assert slack_cell.sum == rep.total_slack
    assert copy_cell.sum == rep.total_copy
    assert slack_cell.count == rep.n_calls == len(records)
    assert reg.get_value("bus_phase_records_total") == len(records)


# --------------------------------------------------------------------------
# governor collector + JSONL writer
# --------------------------------------------------------------------------
def _feed(gov_or_bus, n=20, seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)
    t = 1.0
    for i in range(n):
        gov_or_bus.publish_phase(PhaseRecord(
            rank=0, call_id=i, t_enter=t, t_slack_end=t + rng.uniform(0, 2e-3),
            t_copy_end=t + rng.uniform(2e-3, 3e-3), site=int(i % 2)))
        t += 5e-3


def test_collector_exact_report_roundtrip(tmp_path):
    gov = Governor()
    bus = EventBus()
    bus.subscribe(gov)
    reg = MetricsRegistry()
    coll = GovernorCollector(reg, gov)
    path = str(tmp_path / "metrics.jsonl")
    with MetricsJsonlWriter(path, reg, coll) as w:
        _feed(bus, n=10, seed=1)
        w.write(step=0)
        _feed(bus, n=10, seed=2)
        w.write(step=1)
    assert validate_metrics_jsonl(path) == []
    lines = [json.loads(s) for s in open(path)]
    assert [r["step"] for r in lines] == [0, 1]
    # the embedded report is the exact cumulative finalize() at write time
    # (JSON round-trip stringifies straggler_summary's int keys, so compare
    # against the same round-trip of the live report)
    want = json.loads(json.dumps(gov.finalize().to_dict()))
    assert lines[-1]["report"] == want
    # cumulative counters track the report totals across interval polls
    assert reg.get_value("governor_slack_seconds_total") \
        == pytest.approx(want["total_slack"], rel=1e-12)
    assert reg.get_value("governor_calls_total") == want["n_calls"] == 20


def test_validate_metrics_jsonl_catches_problems(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"t": 1}\nnot json\n'
                 '{"t": 1, "metrics": {"f": []}, "report": {"n_calls": 1}}\n')
    probs = validate_metrics_jsonl(str(p))
    assert any("envelope" in s for s in probs)
    assert any("not JSON" in s for s in probs)
    assert any("malformed" in s for s in probs)
    assert any("report missing" in s for s in probs)
    (tmp_path / "empty.jsonl").write_text("")
    assert validate_metrics_jsonl(str(tmp_path / "empty.jsonl")) \
        == ["no snapshot lines"]


def test_collector_single_poller_handoff():
    """collect() returns the IntervalStats it polled so a driver can hand
    it to GovernorJob.run_epoch(stats=...) — the governor keeps one
    snapshot mark, so double-polling would split the stream."""
    gov = Governor()
    bus = EventBus()
    bus.subscribe(gov)
    reg = MetricsRegistry()
    coll = GovernorCollector(reg, gov, auto_collect=False)
    _feed(bus, n=8)
    stats = coll.collect()
    assert stats.n_calls == 8
    # a second immediate poll sees an empty interval: the mark moved
    assert gov.interval_snapshot().n_calls == 0


# --------------------------------------------------------------------------
# prometheus text + dashboard
# --------------------------------------------------------------------------
def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("a_total", "things", ("k",)).labels("x").inc(3)
    reg.gauge("b", 'quo"te').set(1.25)
    h = reg.histogram("h_seconds", "hist", edges=(0.0, 1.0, 2.0))
    h.observe(0.5)
    h.observe(1.5)
    text = prometheus_text(reg)
    assert 'a_total{k="x"} 3.0' in text
    assert "# TYPE b gauge" in text and "b 1.25" in text
    assert 'h_seconds_bucket{le="1"} 1' in text
    assert 'h_seconds_bucket{le="2"} 2' in text       # cumulative
    assert 'h_seconds_bucket{le="+Inf"} 2' in text
    assert "h_seconds_sum 2.0" in text and "h_seconds_count 2" in text
    assert text == prometheus_text(reg)               # deterministic


def test_dashboard_renders_available_sections():
    reg = MetricsRegistry()
    out = io.StringIO()
    dash = ConsoleDashboard(reg, title="t", stream=out)
    assert dash.render() == "== t =="                 # empty registry: header
    gov = Governor()
    coll = GovernorCollector(reg, gov)
    bus = EventBus()
    bus.subscribe(gov)
    _feed(bus, n=6)
    coll.collect()        # the driver's interval poll populates the gauges
    reg.gauge("job_cap_watts", "", ("job",)).labels("train").set(2000.0)
    reg.gauge("job_power_watts", "", ("job",)).labels("train").set(81.0)
    frame = dash.tick(step=3)
    assert "step 3" in frame and "slack" in frame and "energy saved" in frame
    assert "power[train]" in frame and "/2000W cap" in frame
    assert dash.n_renders == 1 and frame in out.getvalue()
    del coll


def test_dashboard_serve_rows():
    reg = MetricsRegistry()
    for q, v in (("p50", 0.01), ("p99", 0.05)):
        reg.gauge("serve_ttft_seconds", "", ("q",)).labels(q).set(v)
    reg.counter("serve_completed_total").inc(7)
    frame = ConsoleDashboard(reg).render()
    assert "ttft p50    10.0ms   p99    50.0ms" in frame
    assert "completed 7" in frame


# --------------------------------------------------------------------------
# profiler bus subscription (regression: EventProfiler as a subscriber)
# --------------------------------------------------------------------------
def test_event_profiler_consumes_phase_records():
    from repro.core.profiler import UNSITED, EventProfiler, hierarchical_report

    prof = EventProfiler()
    bus = EventBus()
    bus.subscribe(prof)
    bus.publish_phase(PhaseRecord(rank=2, call_id=0, t_enter=0.0,
                                  t_slack_end=0.5, t_copy_end=0.7, site=4))
    bus.publish_phase(PhaseRecord(rank=0, call_id=1, t_enter=1.0,
                                  t_slack_end=0.9, t_copy_end=1.2, site=None))
    assert prof.sites[4]["calls"] == 1 and prof.sites[4]["tslack"] == 0.5
    # negative slack clamps; site=None books under the UNSITED bucket
    assert prof.sites[UNSITED]["tslack"] == 0.0
    assert prof.sites[UNSITED]["tcopy"] == pytest.approx(0.3)
    rep = hierarchical_report(prof)               # n_ranks inferred = 3
    assert rep["summary"]["n_ranks"] == 3
    assert rep["summary"]["total_tslack_s"] == 0.5
    assert rep["nodes"]["node0"]["tslack_s"] == 0.5


# --------------------------------------------------------------------------
# structured logging
# --------------------------------------------------------------------------
@pytest.fixture
def _log_reset():
    yield
    obslog.configure()                            # restore defaults


def test_log_text_and_levels(_log_reset):
    out = io.StringIO()
    obslog.configure(level="info", stream=out)
    log = obslog.get_logger("train")
    log.debug("hidden", x=1)
    log.info("step", loss=1.23456789, note="two words")
    log.warning("careful", n=3)
    text = out.getvalue()
    assert "hidden" not in text
    assert "[train] step loss=1.23457 note='two words'" in text
    assert "[train] WARNING careful n=3" in text


def test_log_json_mode(_log_reset):
    out = io.StringIO()
    obslog.configure(level="info", json_logs=True, stream=out)
    obslog.get_logger("serve").info("done", tokens=42)
    rec = json.loads(out.getvalue())
    assert rec["logger"] == "serve" and rec["event"] == "done"
    assert rec["fields"] == {"tokens": 42} and rec["lvl"] == "info"


def test_log_flags_roundtrip(_log_reset):
    import argparse

    ap = argparse.ArgumentParser()
    obslog.add_flags(ap)
    args = ap.parse_args(["--quiet", "--json-logs"])
    obslog.configure_from_args(args)
    out = io.StringIO()
    obslog.configure(level="warning", json_logs=True, stream=out)
    log = obslog.get_logger("x")
    log.info("suppressed")
    log.error("boom")
    lines = [json.loads(s) for s in out.getvalue().splitlines()]
    assert [r["event"] for r in lines] == ["boom"]
    with pytest.raises(ValueError):
        obslog.configure(level="nope")
