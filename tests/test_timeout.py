"""Unit tests for the theta auto-tuner (repro.core.timeout), its governor
wiring, the 5-phase overlap-aware event taxonomy, and the instrumentation
reset helper."""
import numpy as np
import pytest

from repro.core.governor import Governor
from repro.core.policies import CNTD_ADAPTIVE, COUNTDOWN_SLACK
from repro.core.pstate import DEFAULT_HW, HwModel
from repro.core.simulator import Workload, simulate
from repro.core.timeout import ThetaDecision, ThetaTuner


# --------------------------------------------------------------------------
# tuner dynamics
# --------------------------------------------------------------------------

def test_theta_for_unknown_site_is_clamped_theta0():
    tuner = ThetaTuner(theta0=500e-6)
    assert tuner.theta_for(42) == 500e-6
    lo, hi = DEFAULT_HW.theta_bounds()
    assert ThetaTuner(theta0=1e-9).theta_for(0) == lo       # clamped up
    assert ThetaTuner(theta0=10.0).theta_for(0) == hi       # clamped down


def test_heavy_slack_decays_theta_toward_floor():
    """A site with consistently huge slack can afford an aggressive theta:
    the CDF target sits at the histogram floor and theta relaxes to it."""
    tuner = ThetaTuner()
    for i in range(100):
        tuner.observe_slack(7, 20e-3, t=float(i))
    assert tuner.theta_for(7) < 300e-6
    assert tuner.theta_for(7) >= DEFAULT_HW.switch_latency / 2


def test_unprofitable_slack_keeps_theta_above_it():
    """300 us slacks with no compute to amortize against: the residue cost
    (75 us) dwarfs the 1% budget (3 us/call), so the CDF target lands ABOVE
    the slack — the tuner refuses to fire where a fixed 250 us theta would
    have pinned every call."""
    tuner = ThetaTuner()
    for i in range(100):
        tuner.observe_slack(3, 300e-6, t=float(i))
    assert tuner.theta_for(3) > 300e-6

    # the same slack backed by 30 ms of compute per call IS affordable
    rich = ThetaTuner()
    for i in range(100):
        rich.observe_slack(3, 300e-6, t=float(i), comp=30e-3)
    assert rich.theta_for(3) < 300e-6


def test_theta0_held_until_min_samples():
    tuner = ThetaTuner(min_samples=8)
    for i in range(7):
        dec = tuner.observe_slack(1, 20e-3, t=float(i))
        assert dec is None and tuner.theta_for(1) == tuner.theta0
    assert tuner.observe_slack(1, 20e-3, t=8.0) is not None
    assert tuner.theta_for(1) != tuner.theta0


def test_copy_slowdown_triggers_aimd_raise():
    tuner = ThetaTuner()
    # establish a copy EMA and busy mass at site 0
    for i in range(20):
        tuner.observe_slack(0, 2e-3, t=float(i))
        tuner.observe_copy(0, 1e-3, t=float(i), downshifted=False)
    before = tuner.theta_for(0)
    # a downshifted call whose copy ran 2x the reference and far over budget
    dec = tuner.observe_copy(0, 2e-3, t=30.0, downshifted=True)
    assert dec is not None and dec.reason == "raise"
    assert tuner.theta_for(0) == pytest.approx(
        min(before * tuner.raise_factor, tuner.theta_max))


def test_downshifted_copy_never_seeds_the_reference():
    """A site whose FIRST observed copy is already residue-stretched (the
    common case: long first slack -> immediate downshift) must not lock the
    reference at the stretched value and disarm the raise forever."""
    tuner = ThetaTuner()
    for i in range(20):
        tuner.observe_slack(0, 20e-3, t=float(i))
    # all copies downshifted: the min of them is the fallback reference
    assert tuner.observe_copy(0, 1.5e-3, t=21.0, downshifted=True) is None
    dec = tuner.observe_copy(0, 3e-3, t=22.0, downshifted=True)
    assert dec is not None and dec.reason == "raise"
    # a later clean copy still seeds the EMA at its own (unstretched) value
    tuner.observe_copy(0, 1.0e-3, t=23.0, downshifted=False)
    dec2 = tuner.observe_copy(0, 1.5e-3, t=24.0, downshifted=True)
    assert dec2 is not None and dec2.reason == "raise"   # vs clean 1.0 ms ref


def test_immaterial_copy_slowdown_does_not_raise():
    """Relatively slow but tiny: a 60 us excess on a site with 30 ms busy
    per call must not stampede theta upward."""
    tuner = ThetaTuner()
    for i in range(20):
        tuner.observe_slack(0, 25e-3, t=float(i))
        tuner.observe_copy(0, 100e-6, t=float(i), downshifted=False)
    assert tuner.observe_copy(0, 160e-6, t=30.0, downshifted=True) is None


def test_decisions_are_structured_and_suppressed_when_stable():
    tuner = ThetaTuner()
    for i in range(40):
        tuner.observe_slack(5, 15e-3, t=float(i))
    assert tuner.decisions, "adaptation must log decisions"
    d = tuner.decisions[0]
    assert isinstance(d, ThetaDecision) and d.site == 5 and d.reason == "decay"
    assert d.theta_after != d.theta_before
    # once converged to the clamped target, no-op decisions are suppressed
    n = len(tuner.decisions)
    for i in range(40, 60):
        tuner.observe_slack(5, 15e-3, t=float(i))
    assert len(tuner.decisions) == n


def test_batch_path_matches_scalar_direction():
    """The simulator's batched observe moves theta the same direction as the
    governor's scalar path on the same data (one decay step per batch)."""
    a, b = ThetaTuner(), ThetaTuner()
    slacks = np.full(8, 10e-3)
    for i in range(30):
        a.observe_slack_batch(0, slacks, t=float(i))
        for s in slacks:
            b.observe_slack(0, float(s), t=float(i))
    assert a.theta_for(0) < a.theta0 and b.theta_for(0) < b.theta0


# --------------------------------------------------------------------------
# governor wiring
# --------------------------------------------------------------------------

def _stream(gov, n_calls, slack, copy=1e-3, n_ranks=4, call_id=9):
    t = 1.0
    for _ in range(n_calls):
        for r in range(n_ranks):
            gov.sink(r, "barrier_enter", call_id, t if r == 0 else t - slack)
        for r in range(n_ranks):
            gov.sink(r, "barrier_exit", call_id, t)
            gov.sink(r, "copy_exit", call_id, t + copy)
        t += 10e-3


def test_adaptive_policy_autocreates_tuner_and_exploits_more():
    """600 us slack: fixed cntd_slack (theta_eff 750 us) rejects everything;
    the adaptive governor decays theta and starts exploiting."""
    fixed = Governor(policy=COUNTDOWN_SLACK)
    _stream(fixed, 60, slack=600e-6)
    adaptive = Governor(policy=CNTD_ADAPTIVE)
    assert adaptive.tuner is not None                 # auto-created
    _stream(adaptive, 60, slack=600e-6)
    rep_f, rep_a = fixed.finalize(), adaptive.finalize()
    assert rep_f.exploited_slack == 0.0
    assert rep_a.exploited_slack > 0.0
    assert rep_a.n_theta_decisions > 0
    assert rep_a.energy_policy < rep_f.energy_policy
    # priced downshifts follow the tuned threshold (600 us < fixed 750 us
    # eff, but above the adapted one)
    assert rep_a.n_downshifts > rep_f.n_downshifts == 0


def test_tuned_theta_priced_per_observation_not_retroactively():
    """Records priced before the tuner adapted keep the theta they were
    observed under (theta_eff is stored per rank at barrier_exit)."""
    gov = Governor(policy=CNTD_ADAPTIVE)
    _stream(gov, 1, slack=600e-6)                     # theta still ~theta0
    early = gov.finalize().exploited_slack
    assert early == 0.0                               # priced at 750 us eff
    _stream(gov, 59, slack=600e-6)
    rep = gov.finalize()
    # exploited accrues only from post-adaptation calls: strictly less than
    # pricing every call at the final theta would give
    final_eff = gov.tuner.theta_for(9) + 0.5 * gov.hw.switch_latency
    per_call_all = max(600e-6 - final_eff, 0.0) * 3 * 60
    assert 0.0 < rep.exploited_slack < per_call_all


def test_ingest_phase_site_keys_one_histogram():
    gov = Governor(policy=CNTD_ADAPTIVE)
    for i in range(40):
        t0 = float(i)
        gov.ingest_phase(0, 1000 + i, t0, t0 + 5e-3, t0 + 6e-3, site=77)
    assert list(gov.tuner.summary()) == [77]          # one site, not 40
    assert gov.finalize().n_theta_decisions > 0


def test_serve_meter_feeds_stable_sites():
    from repro.serve.slack import SITE_DECODE_STEP, SITE_IDLE_GAP, DecodeSlackMeter

    gov = Governor(policy=CNTD_ADAPTIVE)
    meter = DecodeSlackMeter(gov)
    t = 0.0
    for _ in range(30):
        meter.step(t, t + 4e-3, filled=1, capacity=4)  # 3 ms underfill slack
        meter.idle(t + 4e-3, t + 9e-3)                 # 5 ms idle gap
        t += 10e-3
    sites = set(gov.tuner.summary())
    assert sites == {SITE_DECODE_STEP, SITE_IDLE_GAP}
    assert gov.finalize().n_theta_decisions > 0


# --------------------------------------------------------------------------
# 5-phase taxonomy: overlap is not slack
# --------------------------------------------------------------------------

def test_async_overlap_accounted_as_non_slack():
    gov = Governor()
    t = 1.0
    for call in range(10):
        for r in range(2):
            gov.sink(r, "dispatch_enter", call, t)         # overlap start
        for r in range(2):
            gov.sink(r, "wait_enter", call, t + 2e-3)      # slack starts HERE
        for r in range(2):
            gov.sink(r, "barrier_exit", call, t + 3e-3)
            gov.sink(r, "copy_exit", call, t + 3.5e-3)
        t += 10e-3
    rep = gov.finalize()
    assert rep.total_overlap == pytest.approx(10 * 2 * 2e-3)
    assert rep.total_slack == pytest.approx(10 * 2 * 1e-3)  # wait->exit only
    assert rep.total_copy == pytest.approx(10 * 2 * 0.5e-3)
    # 3-phase-naive accounting would have booked 3 ms of "slack" per rank
    # and downshifted into the overlap; here only the true 1 ms is priced
    assert rep.n_downshifts == 20                           # 1 ms > 750 us eff


def test_async_redispatch_rotates_occurrence():
    gov = Governor()
    for occurrence in range(3):
        t = 1.0 + occurrence
        gov.sink(0, "dispatch_enter", 5, t)
        gov.sink(0, "wait_enter", 5, t + 1e-3)
        gov.sink(0, "barrier_exit", 5, t + 2e-3)
        gov.sink(0, "copy_exit", 5, t + 2.2e-3)
    rep = gov.finalize()
    assert rep.n_calls == 3
    assert rep.total_slack == pytest.approx(3 * 1e-3)


# --------------------------------------------------------------------------
# simulator: adaptive theta series + overlap isolation
# --------------------------------------------------------------------------

def _overlap_workload(n_tasks=50, n_ranks=4, slack=4e-3, overlap=2.5e-3):
    comp = np.full((n_tasks, n_ranks), 8e-3)
    comp[:, 0] += slack                               # rank 0 critical
    return Workload(
        name="ovl", n_ranks=n_ranks, comp=comp,
        copy=np.full(n_tasks, 0.5e-3), is_p2p=np.zeros(n_tasks, bool),
        partner=np.zeros((n_tasks, n_ranks), np.int64),
        site=np.zeros(n_tasks, np.int64), nbytes=np.zeros(n_tasks),
        beta_comp=0.8, beta_copy=0.1,
        overlap=np.full(n_tasks, overlap),
    )


def test_simulator_overlap_aware_books_overlap_not_slack():
    wl = _overlap_workload()
    aware, _ = simulate(wl, COUNTDOWN_SLACK, overlap_aware=True, power_dt=5e-3)
    naive, _ = simulate(wl, COUNTDOWN_SLACK, overlap_aware=False, power_dt=5e-3)
    # both accounting modes keep the power series energy-conserving (the
    # unaware payback window is binned after the copy, where it happens)
    for res in (aware, naive):
        assert res.power_series.sum() * 5e-3 == pytest.approx(res.energy, rel=1e-9)
    assert aware.toverlap > 0.0 and naive.toverlap == 0.0
    assert aware.tslack < naive.tslack                # naive inflates slack
    assert aware.exploited_slack < naive.exploited_slack
    # the naive view pins the core during overlapped compute and pays the
    # lost work back after the barrier: measurable wall-clock harm
    assert naive.time > aware.time


def test_simulator_adaptive_emits_theta_series():
    wl = _overlap_workload(overlap=0.0)
    res, _ = simulate(wl, CNTD_ADAPTIVE, power_dt=2e-3)
    assert res.theta_series is not None and len(res.theta_series) == wl.n_tasks
    lo, hi = DEFAULT_HW.theta_bounds()
    assert np.all(res.theta_series >= lo)             # theta_eff >= theta_min
    assert np.all(res.theta_series <= hi + 0.5 * DEFAULT_HW.switch_latency)
    # 4 ms slack every call: the tuner relaxes theta below theta0
    assert res.theta_series[-1] < res.theta_series[0]
    assert res.theta_bins is not None
    assert res.theta_bins.shape[0] == res.power_series.shape[0]


def test_simulator_fixed_policy_unchanged_by_taxonomy_fields():
    """No-overlap workloads: the new accounting is bit-identical."""
    wl = _overlap_workload(overlap=0.0)
    a, _ = simulate(wl, COUNTDOWN_SLACK, overlap_aware=True)
    b, _ = simulate(wl, COUNTDOWN_SLACK, overlap_aware=False)
    assert a.time == b.time and a.energy == b.energy
    assert a.tslack == b.tslack and a.toverlap == b.toverlap == 0.0


# --------------------------------------------------------------------------
# instrumentation reset helper
# --------------------------------------------------------------------------

def test_reset_instrumentation_restores_defaults():
    from repro.core import instrument

    seen = []
    instrument.set_mode("profile")
    instrument.enable_events(True)
    instrument.set_event_sink(lambda *a: seen.append(a))
    instrument.set_event_tee(lambda *a: None)
    instrument._next_call_id()
    assert instrument._CALL_COUNTER[0] > 0
    instrument.reset_instrumentation()
    assert instrument.get_mode() == "off"
    assert len(instrument.get_event_bus()) == 0
    assert instrument._EVENTS_ENABLED is False
    assert instrument._CALL_COUNTER[0] == 0
    instrument._emit(0, 0, 1)                         # sinkless: no-op
    assert seen == []


def test_async_pair_jax_numerics_and_event_order():
    """cd_psum_async/cd_wait under a real shard_map: same numbers as the
    blocking path, and the 5-phase event sequence in dispatch -> wait ->
    barrier_exit -> copy_exit order."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import instrument
    from repro.core.instrument import cd_psum_async, cd_wait

    mesh = jax.make_mesh((1,), ("r",))
    events = []
    instrument.set_mode("profile")
    instrument.enable_events(True)
    instrument.set_event_sink(lambda r, p, c, t: events.append(p))

    def f(x):
        h = cd_psum_async(x, "r")
        y = x * 2.0                                   # overlapped compute
        return cd_wait(h) + y

    with jax.set_mesh(mesh):
        g = jax.shard_map(f, mesh=mesh, in_specs=P("r"), out_specs=P("r"),
                          axis_names={"r"}, check_vma=False)
        x = jnp.arange(4.0)
        res = jax.block_until_ready(jax.jit(g)(x))
    np.testing.assert_allclose(np.asarray(res), np.asarray(x) * 3.0)
    assert events == ["dispatch_enter", "wait_enter", "barrier_exit", "copy_exit"]


def test_blocking_wrappers_numerics_and_events_per_mode():
    """cd_psum/cd_pmean/cd_all_gather/cd_ppermute across off/barrier/profile:
    numerics never change; profile mode emits the 3-phase sequence."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import instrument
    from repro.core.instrument import cd_all_gather, cd_pmean, cd_ppermute, cd_psum

    mesh = jax.make_mesh((1,), ("r",))
    x = jnp.arange(4.0)
    events = []
    instrument.set_event_sink(lambda r, p, c, t: events.append(p))

    def make_fn():
        # a FRESH closure per mode: the ambient mode is read at trace time
        # and jax caches traces per function object
        def f(x):
            a = cd_psum(x, "r")
            b = cd_pmean(x, "r")
            c = cd_all_gather(x, "r", tiled=True)
            d = cd_ppermute(x, "r", [(0, 0)])
            return a + b + c + d

        return jax.shard_map(f, mesh=mesh, in_specs=P("r"), out_specs=P("r"),
                             axis_names={"r"}, check_vma=False)

    results = {}
    with jax.set_mesh(mesh):
        for mode in ("off", "barrier", "profile"):
            instrument.set_mode(mode)
            instrument.enable_events(mode == "profile")
            events.clear()
            results[mode] = np.asarray(jax.block_until_ready(jax.jit(make_fn())(x)))
            if mode == "profile":
                # 4 wrappers x (enter, exit, copy_exit), in order per call
                assert events == ["barrier_enter", "barrier_exit", "copy_exit"] * 4
            else:
                assert events == []
    np.testing.assert_array_equal(results["off"], results["barrier"])
    np.testing.assert_array_equal(results["off"], results["profile"])


def test_compressed_psum_async_pair_matches_blocking():
    """Mode off: the start/wait pair is numerically the blocking path."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.dist.compression import (
        compressed_psum, compressed_psum_start, compressed_psum_wait,
    )

    mesh = jax.make_mesh((1,), ("r",))
    grads = {"w": jnp.linspace(-1.0, 1.0, 8), "b": jnp.ones((4,))}

    def blocking(g):
        return compressed_psum(g, "r")

    def split(g):
        h = compressed_psum_start(g, "r")
        return compressed_psum_wait(h)

    with jax.set_mesh(mesh):
        spec = {"w": P(), "b": P()}
        a = jax.shard_map(blocking, mesh=mesh, in_specs=(spec,), out_specs=spec,
                          axis_names={"r"}, check_vma=False)(grads)
        b = jax.shard_map(split, mesh=mesh, in_specs=(spec,), out_specs=spec,
                          axis_names={"r"}, check_vma=False)(grads)
    for k in grads:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


def test_emit_maps_all_five_phase_codes():
    from repro.core import instrument

    seen = []
    instrument.set_event_sink(lambda r, p, c, t: seen.append(p))
    try:
        for code in range(5):
            instrument._emit(0, code, 1)
    finally:
        instrument.set_event_sink(None)
    assert seen == ["barrier_enter", "barrier_exit", "copy_exit",
                    "dispatch_enter", "wait_enter"]
