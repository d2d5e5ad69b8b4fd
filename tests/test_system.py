"""End-to-end behaviour: real training runs (loss decreases), fault-tolerant
restart resumes identically, and the multi-device distributed path
(FSDP jit + pod-explicit instrumented shard_map) in a subprocess."""
import os
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.dist.checkpoint import CheckpointManager
from repro.models.inputs import make_batch
from repro.train.data import DataLoader
from repro.train.loop import init_state, make_train_step
from repro.train.optimizer import OptConfig

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _tiny_cfg():
    return reduced(get_config("countdown-100m"), n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=128, vocab=256)


def test_training_reduces_loss():
    cfg = _tiny_cfg()
    opt_cfg = OptConfig(lr=3e-3, warmup_steps=5, total_steps=60)
    state = init_state(cfg, opt_cfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, opt_cfg))
    loader = DataLoader(cfg, batch=8, seq_len=33, seed=0)
    losses = []
    for i, batch in zip(range(60), loader):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    loader.close()
    first, last = np.mean(losses[:5]), np.mean(losses[-5:])
    assert last < first - 0.25, (first, last)


def test_data_loader_stream_ignores_consumer_speed():
    """A consumer that leaves the prefetch queue full for longer than the
    worker's put timeout (a first-step compile) sees the same batches, in
    the same order, as one that drains it at once."""
    import time

    cfg = _tiny_cfg()
    fast = DataLoader(cfg, batch=2, seq_len=17, seed=3)
    want = [np.asarray(next(fast)["tokens"]) for _ in range(5)]
    fast.close()
    slow = DataLoader(cfg, batch=2, seq_len=17, seed=3)
    time.sleep(2.5)                          # > the worker's 1 s put timeout
    got = [np.asarray(next(slow)["tokens"]) for _ in range(5)]
    slow.close()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_checkpoint_restart_resumes_identically():
    cfg = _tiny_cfg()
    opt_cfg = OptConfig(lr=1e-3, warmup_steps=2, total_steps=30)
    step = jax.jit(make_train_step(cfg, opt_cfg))
    batch = make_batch(cfg, batch=4, seq_len=33, kind="train")
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)
        state = init_state(cfg, opt_cfg, jax.random.PRNGKey(0))
        for i in range(4):
            state, _ = step(state, batch)
        mgr.save(4, state)
        state_a = state
        for i in range(3):
            state_a, ma = step(state_a, batch)
        # simulated crash: reload from step 4 and replay
        _, state_b = mgr.restore_latest(jax.tree.map(jnp.zeros_like, state))
        for i in range(3):
            state_b, mb = step(state_b, batch)
        np.testing.assert_allclose(float(ma["loss"]), float(mb["loss"]), rtol=1e-5)


def test_microbatch_accumulation_matches_full_batch():
    cfg = _tiny_cfg()
    from repro.train.loop import TrainConfig

    opt_cfg = OptConfig(lr=1e-3, warmup_steps=0, total_steps=10)
    batch = make_batch(cfg, batch=8, seq_len=33, kind="train")
    state = init_state(cfg, opt_cfg, jax.random.PRNGKey(0))
    full = jax.jit(make_train_step(cfg, opt_cfg))
    micro = jax.jit(make_train_step(cfg, opt_cfg, TrainConfig(microbatch=2)))
    _, mf = full(state, batch)
    _, mm = micro(state, batch)
    np.testing.assert_allclose(float(mf["loss"]), float(mm["loss"]), rtol=1e-4)
    np.testing.assert_allclose(
        float(mf["grad_norm"]), float(mm["grad_norm"]), rtol=1e-3
    )


@pytest.mark.slow
def test_multidevice_fsdp_and_instrumented_pod_step():
    """8 fake CPU devices in a subprocess: FSDP auto-jit step, pod-explicit
    instrumented step (artificial barriers in HLO), int8-compressed reduce."""
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config, reduced
        from repro.dist import sharding as SH
        from repro.models.hooks import install_constraint
        from repro.train.loop import make_train_step, make_pod_train_step, init_state, TrainConfig
        from repro.train.optimizer import OptConfig
        from repro.models.inputs import make_batch
        from repro.core import instrument

        mesh = SH.make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = reduced(get_config("llama3.2-1b"))
        opt_cfg = OptConfig(warmup_steps=2, total_steps=10)
        state = init_state(cfg, opt_cfg, jax.random.PRNGKey(0))
        batch = make_batch(cfg, batch=8, seq_len=32, kind="train")
        ps = SH.param_shardings(mesh, state["params"], include_pod=False, gather_safe=True)
        os_ = SH.opt_state_shardings(mesh, ps, state["opt"])
        bs = SH.batch_shardings(mesh, batch)
        state = {"params": jax.device_put(state["params"], ps),
                 "opt": jax.device_put(state["opt"], os_)}
        batch = jax.device_put(batch, bs)
        install_constraint(SH.activation_constraint_fn(mesh))
        with jax.set_mesh(mesh):
            auto = jax.jit(make_train_step(cfg, opt_cfg))
            s1, m1 = auto(state, batch)
            assert jnp.isfinite(m1["loss"])
            instrument.set_mode("barrier")
            pstep = jax.jit(make_pod_train_step(cfg, opt_cfg, mesh, TrainConfig(pod_reduce="manual")),
                            in_shardings=({"params": ps, "opt": os_}, bs),
                            out_shardings=({"params": ps, "opt": os_}, None))
            comp = pstep.lower(state, batch).compile()
            txt = comp.as_text()
            assert "all-reduce" in txt
            s2, m2 = pstep(state, batch)
            assert jnp.isfinite(m2["loss"])
            import numpy as np
            np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-4)
            instrument.set_mode("off")
            cstep = jax.jit(make_pod_train_step(cfg, opt_cfg, mesh, TrainConfig(pod_reduce="compressed")),
                            in_shardings=({"params": ps, "opt": os_}, bs),
                            out_shardings=({"params": ps, "opt": os_}, None))
            s3, m3 = cstep(state, batch)
            np.testing.assert_allclose(float(m3["loss"]), float(m2["loss"]), rtol=1e-4)
        print("MULTIDEVICE-OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True, text=True,
        timeout=900,
    )
    assert "MULTIDEVICE-OK" in out.stdout, out.stderr[-3000:]


@pytest.mark.slow
def test_elastic_restart_on_smaller_mesh():
    """Checkpoint on 8 devices, simulated node failure, resume on 4."""
    script = textwrap.dedent("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.dist import sharding as SH
        from repro.dist.checkpoint import CheckpointManager
        from repro.dist.elastic import ElasticMesh
        from repro.models.hooks import install_constraint
        from repro.train.loop import make_train_step, init_state
        from repro.train.optimizer import OptConfig
        from repro.models.inputs import make_batch

        cfg = reduced(get_config("olmo-1b"))
        opt_cfg = OptConfig(warmup_steps=2, total_steps=10)
        em = ElasticMesh(axis_names=("data", "model"))
        mesh = em.build(model_parallel=2)
        install_constraint(SH.activation_constraint_fn(mesh))
        state = init_state(cfg, opt_cfg, jax.random.PRNGKey(0))
        batch = make_batch(cfg, batch=8, seq_len=32, kind="train")
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            with jax.set_mesh(mesh):
                step = jax.jit(make_train_step(cfg, opt_cfg))
                state, m_before = step(state, batch)
                mgr.save(1, state)
            for dev in jax.devices()[4:]:
                em.fail(dev.id)
            mesh2 = em.build(model_parallel=2)
            assert int(np.prod(list(mesh2.shape.values()))) == 4
            install_constraint(SH.activation_constraint_fn(mesh2))
            skel = jax.tree.map(lambda a: np.zeros(a.shape, a.dtype), state)
            ps = SH.param_shardings(mesh2, state["params"])
            os_ = SH.opt_state_shardings(mesh2, ps, state["opt"])
            _, restored = mgr.restore_latest(skel, {"params": ps, "opt": os_})
            with jax.set_mesh(mesh2):
                step2 = jax.jit(make_train_step(cfg, opt_cfg))
                restored, m_after = step2(restored, batch)
                assert jnp.isfinite(m_after["loss"])
        print("ELASTIC-OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True, text=True,
        timeout=900,
    )
    assert "ELASTIC-OK" in out.stdout, out.stderr[-3000:]
