"""Continuous-batching subsystem: paged pool, scheduler, slack bridge, SLO."""
import dataclasses
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.governor import Governor
from repro.models import init_params
from repro.models.inputs import make_batch
from repro.models.transformer import init_cache
from repro.serve import (
    ContinuousEngine,
    PagedKVPool,
    Request,
    Scheduler,
    ServeEngine,
    SLOTracker,
    poisson_arrivals,
)


# --------------------------------------------------------------------------
# page pool accounting
# --------------------------------------------------------------------------

def test_pool_freelist_reserve_alloc_release():
    cfg = reduced(get_config("llama3.2-1b"))
    pool = PagedKVPool(cfg, n_slots=2, max_len=32, page=8, num_pages=9)
    assert pool.capacity_pages == 8 and pool.free_pages == 8
    assert pool.reserve("a", 20)                     # 3 pages
    assert pool.free_pages == 5
    got = pool.alloc("a", 2)
    assert len(got) == 2 and 0 not in got            # scratch page never handed out
    assert pool.reserve("b", 40)                     # 5 pages -> pool exactly full
    assert pool.free_pages == 0
    assert not pool.reserve("c", 8)                  # admission blocked
    with pytest.raises(RuntimeError):
        pool.alloc("a", 2)                           # beyond its reservation
    pool.release("a")
    assert pool.free_pages == 3                      # b's IOU still outstanding
    pool.release("b")
    assert pool.free_pages == 8
    with pytest.raises(ValueError):
        pool.reserve("huge", 1000)                   # can never fit


def test_scheduler_fifo_and_page_bounded_admission():
    cfg = reduced(get_config("llama3.2-1b"))
    pool = PagedKVPool(cfg, n_slots=2, max_len=32, page=8, num_pages=5)  # 4 usable
    sched = Scheduler(pool, n_slots=2)
    toks = np.arange(16, dtype=np.int32)
    r1 = Request(prompt=toks, max_new=8, arrival=0.0)   # needs 3 pages
    r2 = Request(prompt=toks, max_new=8, arrival=0.0)
    sched.submit(r1)
    sched.submit(r2)
    joins = sched.admit(now=0.0)
    assert [r.rid for r in joins] == [r1.rid]        # only one fits the pool
    assert sched.n_active == 1 and sched.n_queued == 1
    sched.release(r1)
    assert [r.rid for r in sched.admit(now=0.0)] == [r2.rid]
    with pytest.raises(ValueError):
        sched.submit(Request(prompt=np.zeros(40, np.int32), max_new=8))


# --------------------------------------------------------------------------
# legacy ServeEngine coverage (satellite)
# --------------------------------------------------------------------------

def test_legacy_greedy_vs_temperature_determinism(rng_key):
    cfg = reduced(get_config("llama3.2-1b"))
    params = init_params(cfg, rng_key)
    batch = make_batch(cfg, batch=2, seq_len=12, kind="prefill")
    greedy = ServeEngine(cfg, params, max_len=48)
    g1 = np.asarray(greedy.generate(batch, n_steps=6))
    # greedy ignores the key entirely
    g2 = np.asarray(greedy.generate(batch, n_steps=6, key=jax.random.PRNGKey(3)))
    np.testing.assert_array_equal(g1, g2)
    sampled = ServeEngine(cfg, params, max_len=48, temperature=1.0)
    s1 = np.asarray(sampled.generate(batch, n_steps=6, key=jax.random.PRNGKey(3)))
    s2 = np.asarray(sampled.generate(batch, n_steps=6, key=jax.random.PRNGKey(3)))
    np.testing.assert_array_equal(s1, s2)            # fixed key => deterministic
    assert not np.array_equal(s1, g1)                # and != greedy
    # temperature with no key falls back to greedy
    s3 = np.asarray(sampled.generate(batch, n_steps=6))
    np.testing.assert_array_equal(s3, g1)


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "mamba2-130m",
                                  "recurrentgemma-2b"])
def test_continuous_matches_serve_engine_token_for_token(rng_key, arch):
    cfg = reduced(get_config(arch))
    params = init_params(cfg, rng_key)
    batch = make_batch(cfg, batch=2, seq_len=16, kind="prefill")
    ref = np.asarray(ServeEngine(cfg, params, max_len=64).generate(batch, n_steps=8))
    eng = ContinuousEngine(cfg, params, n_slots=3, max_len=64, page=8)
    out = np.asarray(eng.generate(batch, n_steps=8))
    np.testing.assert_array_equal(ref, out)


def test_continuous_prefix_arch_parity_and_guard(rng_key):
    cfg = reduced(get_config("internvl2-1b"))         # n_prefix=8 frontend
    assert cfg.n_prefix > 0
    params = init_params(cfg, rng_key)
    batch = make_batch(cfg, batch=1, seq_len=12, kind="prefill")
    assert "prefix_embeds" in batch
    ref = np.asarray(ServeEngine(cfg, params, max_len=64).generate(batch, n_steps=6))
    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=64, page=8)
    out = np.asarray(eng.generate(batch, n_steps=6))
    np.testing.assert_array_equal(ref, out)
    # a request without its prefix would attend phantom zero K/V: refused
    with pytest.raises(ValueError, match="prefix_embeds"):
        eng.serve([Request(prompt=np.arange(12, dtype=np.int32), max_new=4)])


def test_continuous_int8_pages_match_dense_kv_quant(rng_key):
    cfg = dataclasses.replace(reduced(get_config("llama3.2-1b")), kv_quant=True)
    params = init_params(cfg, rng_key)
    batch = make_batch(cfg, batch=1, seq_len=16, kind="prefill")
    ref = np.asarray(ServeEngine(cfg, params, max_len=64).generate(batch, n_steps=6))
    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=64, page=8)
    out = np.asarray(eng.generate(batch, n_steps=6))
    np.testing.assert_array_equal(ref, out)
    assert eng.pool.blocks["stack"]["0"]["k_pages"].dtype == np.int8


# --------------------------------------------------------------------------
# continuous batching behavior
# --------------------------------------------------------------------------

def test_join_on_prefill_evict_on_eos_reuses_slots(rng_key):
    cfg = reduced(get_config("llama3.2-1b"))
    params = init_params(cfg, rng_key)
    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=32, page=8)
    prompt = np.asarray(make_batch(cfg, batch=1, seq_len=8, kind="prefill")["tokens"])[0]
    reqs = [Request(prompt=prompt, max_new=m, arrival=0.0) for m in (2, 9, 3, 7)]
    done = eng.serve(reqs)
    assert sorted(len(r.out) for r in done) == [2, 3, 7, 9]
    # slots were reused: 4 requests through 2 slots, pool fully reclaimed
    assert eng.pool.free_pages == eng.pool.capacity_pages
    assert eng._last_meter is None                   # no governor attached
    for r in done:
        assert r.slot == -1 and not r.pages          # evicted + reclaimed


def test_eos_stops_generation_early(rng_key):
    cfg = reduced(get_config("llama3.2-1b"))
    params = init_params(cfg, rng_key)
    eng = ContinuousEngine(cfg, params, n_slots=1, max_len=32, page=8)
    prompt = np.arange(8, dtype=np.int32)
    free_run = eng.serve([Request(prompt=prompt, max_new=10)])[0]
    eos = free_run.out[2]                            # force EOS at the 3rd token
    capped = eng.serve([Request(prompt=prompt, max_new=10, eos_id=int(eos))])[0]
    assert len(capped.out) <= 3 and capped.out[-1] == eos


def test_decode_slack_priced_with_actuation_pairs(rng_key):
    cfg = reduced(get_config("llama3.2-1b"))
    params = init_params(cfg, rng_key)
    eng = ContinuousEngine(cfg, params, n_slots=4, max_len=32, page=8)
    prompt = np.arange(8, dtype=np.int32)
    eng.serve([Request(prompt=prompt, max_new=2)])   # warmup/compile
    gov = Governor()
    # one early request, a 60 ms idle gap, then a second: guarantees both
    # underfill (1 of 4 slots) and an idle interval >> theta_eff
    reqs = [Request(prompt=prompt, max_new=6, arrival=0.0),
            Request(prompt=prompt, max_new=6, arrival=0.06)]
    eng.serve(reqs, governor=gov)
    rep = gov.finalize()
    assert rep.total_slack > 0
    assert rep.energy_baseline > rep.energy_policy   # slack priced in joules
    downs = [a for a in gov.actuation_log if a[2] == "set_pstate_min"]
    restores = [a for a in gov.actuation_log if a[2] == "restore_pstate_max"]
    assert len(downs) >= 1 and len(downs) == len(restores)
    assert rep.n_downshifts >= 1
    meter = eng._last_meter
    assert meter.n_idle >= 1 and meter.fill_fraction < 1.0


def test_governor_ingest_phase_matches_sink_accounting():
    gov = Governor()
    # same phase through both paths: 2 ms slack, 1 ms copy
    gov.sink(0, "barrier_enter", 7, 1.000)
    gov.sink(0, "barrier_exit", 7, 1.002)
    gov.sink(0, "copy_exit", 7, 1.003)
    gov.ingest_phase(1, 1 << 20, 1.000, 1.002, 1.003)
    rep = gov.finalize()
    assert rep.n_calls == 2 and rep.n_downshifts == 2
    assert rep.total_slack == pytest.approx(0.004)
    assert rep.total_copy == pytest.approx(0.002)
    assert len(gov.actuation_log) == 4               # a pair per phase


# --------------------------------------------------------------------------
# SLO tracking
# --------------------------------------------------------------------------

def test_slo_percentiles_and_throttle():
    slo = SLOTracker(tpot_target=0.01, window=8, adjust_every=4)
    req = Request(prompt=np.zeros(4, np.int32), max_new=16, arrival=0.0)
    slo.on_first_token(req, 0.05)
    now = 0.05
    for _ in range(12):                              # sustained 20 ms TPOT
        now += 0.02
        slo.on_token(req, now)
    s = slo.summary()
    assert s["ttft"]["n"] == 1 and s["ttft"]["p95"] == pytest.approx(0.05)
    assert s["tpot"]["p50"] == pytest.approx(0.02)
    assert s["tpot"]["violations"] == 12
    assert slo.max_concurrency(4) < 4                # throttled below capacity
    for _ in range(40):                              # recovery: 1 ms TPOT
        now += 0.001
        slo.on_token(req, now)
        slo.max_concurrency(4)
    assert slo.max_concurrency(4) == 4               # additive regrowth


def test_slo_tracker_records_through_engine(rng_key):
    cfg = reduced(get_config("llama3.2-1b"))
    params = init_params(cfg, rng_key)
    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=32, page=8)
    prompt = np.arange(8, dtype=np.int32)
    slo = SLOTracker()
    done = eng.serve([Request(prompt=prompt, max_new=5, arrival=0.0),
                      Request(prompt=prompt, max_new=5, arrival=0.01)], slo=slo)
    s = slo.summary()
    assert s["completed"] == 2 and s["ttft"]["n"] == 2
    assert s["tpot"]["n"] == 8                       # 4 decode tokens per request
    for r in done:
        assert r.t_first >= 0 and r.t_done >= r.t_first


def test_page_pool_shardings_rules():
    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import page_pool_shardings

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = reduced(get_config("llama3.2-1b"))
    attn = PagedKVPool(cfg, n_slots=2, max_len=32, page=8).blocks
    sh = page_pool_shardings(mesh, attn)
    # stacked page arrays: TP over the KV-head dim, pages replicated
    assert sh["stack"]["0"]["k_pages"].spec == P(None, None, None, "model", None)
    cfg_ssm = reduced(get_config("mamba2-130m"))
    state = PagedKVPool(cfg_ssm, n_slots=2, max_len=32, page=8).blocks
    sh = page_pool_shardings(mesh, state)
    # recurrent per-slot state: slot (batch) dim over the data axes
    # (compared as specs: ``P`` normalises a 1-tuple entry to its axis name)
    assert sh["stack"]["0"]["conv"].spec == P(None, ("data",), None, "model")


def test_poisson_arrivals_shape_and_bursts():
    a = poisson_arrivals(8, rate=100.0, seed=0, burst_every=4, burst_gap=0.5)
    assert a.shape == (8,) and a[0] == 0.0
    assert np.all(np.diff(a) >= 0)
    assert a[4] - a[3] >= 0.5                        # burst gap inserted


# --------------------------------------------------------------------------
# Pallas paged-decode kernel: parity matrix vs the dense oracle
# --------------------------------------------------------------------------

# Every case uses page=8 with a 12- or 16-token prompt and 8 decode steps,
# so generation crosses a page boundary mid-decode (position 16 opens page
# 2 while slots are live), and max_len=40 gives a non-power-of-two table
# width (5 pages per request).
#
# The oracle is the dense-cache ServeEngine — except for MoE archs, where
# prefill expert capacity scales with total batch tokens, so the dense
# engine's batched prefill routes differently than the continuous
# engine's per-request prefill (pre-existing batching semantics, not an
# attention property).  MoE rows instead oracle against the XLA paged
# engine: identical batching discipline, so any divergence localizes to
# the kernel under test.
PALLAS_MATRIX = [
    # (arch, config overrides, prompt_len, max_len, oracle)
    ("llama3.2-1b", {}, 12, 40, "dense"),                                  # GQA
    ("llama3.2-1b", {"kv_quant": True}, 12, 40, "dense"),                  # int8
    ("llama3.2-1b", {"attention": "swa", "window": 16}, 12, 40, "dense"),  # window
    ("granite-moe-3b-a800m", {"kv_quant": True}, 12, 40, "xla"),           # MoE+int8
    ("granite-moe-3b-a800m", {}, 12, 40, "xla"),                           # MoE
    ("recurrentgemma-2b", {}, 16, 48, "dense"),                   # SSM-hybrid+local
    ("internlm2-1.8b", {"kv_quant": True}, 12, 40, "dense"),               # GQA+int8
]


@pytest.mark.parametrize("arch,mods,prompt_len,max_len,oracle", PALLAS_MATRIX)
def test_pallas_paged_decode_matches_oracle(rng_key, arch, mods,
                                            prompt_len, max_len, oracle):
    cfg = dataclasses.replace(reduced(get_config(arch)), **mods)
    params = init_params(cfg, rng_key)
    batch = make_batch(cfg, batch=2, seq_len=prompt_len, kind="prefill")
    if oracle == "dense":
        ref = np.asarray(
            ServeEngine(cfg, params, max_len=64).generate(batch, n_steps=8)
        )
    else:
        ref = np.asarray(
            ContinuousEngine(cfg, params, n_slots=3, max_len=max_len, page=8)
            .generate(batch, n_steps=8)
        )
    pal = ContinuousEngine(cfg, params, n_slots=3, max_len=max_len, page=8,
                           attn_kernel="pallas")
    np.testing.assert_array_equal(ref, np.asarray(pal.generate(batch, n_steps=8)))


def test_pallas_xla_dense_three_way_parity(rng_key):
    """One case asserting all three paths pairwise (the two paged engines
    share pool geometry, so any divergence localizes to the kernel)."""
    cfg = reduced(get_config("llama3.2-1b"))
    params = init_params(cfg, rng_key)
    batch = make_batch(cfg, batch=3, seq_len=14, kind="prefill")
    dense = np.asarray(ServeEngine(cfg, params, max_len=64).generate(batch, n_steps=10))
    xla = np.asarray(ContinuousEngine(cfg, params, n_slots=3, max_len=40, page=8)
                     .generate(batch, n_steps=10))
    pal = np.asarray(ContinuousEngine(cfg, params, n_slots=3, max_len=40, page=8,
                                      attn_kernel="pallas").generate(batch, n_steps=10))
    np.testing.assert_array_equal(dense, xla)
    np.testing.assert_array_equal(dense, pal)


def test_pallas_fused_sample_only_for_greedy(rng_key):
    """temperature > 0 needs host-side logits: the fused (B,) token step is
    reserved for greedy engines, and sampled output stays deterministic."""
    cfg = reduced(get_config("llama3.2-1b"))
    params = init_params(cfg, rng_key)
    greedy = ContinuousEngine(cfg, params, n_slots=2, max_len=40, page=8,
                              attn_kernel="pallas")
    assert greedy._fused_sample
    sampled = ContinuousEngine(cfg, params, n_slots=2, max_len=40, page=8,
                               attn_kernel="pallas", temperature=1.0)
    assert not sampled._fused_sample
    batch = make_batch(cfg, batch=2, seq_len=12, kind="prefill")
    s1 = np.asarray(sampled.generate(batch, n_steps=6, key=jax.random.PRNGKey(3)))
    s2 = np.asarray(sampled.generate(batch, n_steps=6, key=jax.random.PRNGKey(3)))
    np.testing.assert_array_equal(s1, s2)
    with pytest.raises(ValueError, match="attn_kernel"):
        ContinuousEngine(cfg, params, attn_kernel="mosaic")


# --------------------------------------------------------------------------
# the pool is updated in place: donated, carried through the layer scan
# --------------------------------------------------------------------------

_HLO_OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\((.*)")


def _pool_copies(hlo: str, blocks) -> list:
    """The instructions of a compiled module that copy the pool: a
    ``copy`` or ``dynamic-slice`` whose result is a whole pool leaf or one
    layer of it, or a ``dynamic-update-slice`` that writes a whole layer."""
    layer, whole = set(), set()
    for a in jax.tree.leaves(blocks):
        whole.add(a.shape)
        layer |= {a.shape[1:], (1,) + a.shape[1:]}
    shapes, ops = {}, []
    for line in hlo.splitlines():
        m = _HLO_OP.match(line)
        if m:
            shapes[m[1]] = tuple(int(x) for x in m[2].split(",") if x)
            ops.append((m[1], m[3], re.findall(r"%([\w.-]+)", m[4])))
    bad = []
    for name, op, operands in ops:
        if op in ("copy", "dynamic-slice") and shapes[name] in whole | layer:
            bad.append(name)
        if (op == "dynamic-update-slice" and len(operands) > 1
                and shapes.get(operands[1]) in layer):
            bad.append(name)
    return bad


@pytest.mark.parametrize("program", ["decode", "join", "clone"])
def test_pool_writing_programs_update_the_pool_in_place(rng_key, program):
    """The compiled decode step, join and clone alias the donated pool to
    their output and never slice out, write back or copy a layer of it."""
    cfg = reduced(get_config("llama3.2-1b"))
    params = init_params(cfg, rng_key)
    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=32, page=8)
    blocks = eng.pool.blocks
    zeros = jnp.zeros((2,), jnp.int32)
    if program == "decode":
        lowered = eng._decode.lower(params, zeros, zeros,
                                    jnp.zeros((2, 2), jnp.int32), blocks)
    elif program == "join":
        lowered = eng._join.lower(blocks, init_cache(cfg, 1, 16), zeros, jnp.int32(0))
    else:
        lowered = eng._clone.lower(blocks, jnp.int32(1), jnp.int32(2))
    compiled = lowered.compile()
    assert _pool_copies(compiled.as_text(), blocks) == []
    pool_bytes = sum(a.nbytes for a in jax.tree.leaves(blocks))
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes


def test_pool_copy_finder_sees_a_per_layer_walk(rng_key):
    """The finder above flags what a walk with the pool in the scan's
    ``xs``/``ys`` compiles to: a layer sliced out, written and stacked back."""
    cfg = reduced(get_config("llama3.2-1b"))
    eng = ContinuousEngine(cfg, init_params(cfg, rng_key), n_slots=2, max_len=32, page=8)

    def per_layer_walk(blocks):
        def layer(c, block):
            return c, jax.tree.map(lambda a: a.at[1, 0].add(1.0), block)
        return jax.lax.scan(layer, 0, blocks["stack"])[1]

    text = jax.jit(per_layer_walk).lower(eng.pool.blocks).compile().as_text()
    assert _pool_copies(text, eng.pool.blocks)


@pytest.mark.parametrize("attn_kernel", ["xla", "pallas"])
def test_session_pool_is_never_copied(rng_key, attn_kernel):
    """A short run through every pool-writing program (prefill joins,
    decode steps at two table widths, a join from a partly filled cached
    page, which clones it): each donated its pool, so ``pool_copied``
    stays 0, and the pool the engine started with was consumed."""
    cfg = reduced(get_config("llama3.2-1b"))
    params = init_params(cfg, rng_key)
    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=32, page=8,
                           attn_kernel=attn_kernel)
    eng.enable_prefix_cache()
    first = eng.pool.blocks
    prompt = np.arange(1, 13, dtype=np.int32)
    (a,) = eng.serve([Request(prompt=prompt, max_new=4)])
    # positions 0..14 are written: 8 in a full page, 7 in a partial one
    follow = np.concatenate([prompt, np.asarray(a.out[:3], np.int32),
                             np.arange(50, 55, dtype=np.int32)])
    (b,) = eng.serve([Request(prompt=follow, max_new=3)])
    assert len(b.out) == 3
    assert {p[0] for p in eng._pool_programs} == {"join", "decode", "clone"}
    assert eng.pool_copied == 0
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(first))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(eng.pool.blocks))


def test_pool_copied_counts_a_program_that_kept_its_input(rng_key):
    """A program whose pool was not donated wrote a copy: counted once,
    on its first call only."""
    cfg = reduced(get_config("llama3.2-1b"))
    params = init_params(cfg, rng_key)
    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=32, page=8)
    eng._join = jax.jit(eng._join.__wrapped__)          # no donation
    prompt = np.arange(1, 13, dtype=np.int32)
    eng.serve([Request(prompt=prompt, max_new=2), Request(prompt=prompt, max_new=2)])
    assert eng.pool_copied == 1
