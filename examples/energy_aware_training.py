"""COUNTDOWN Slack LIVE: data-parallel training with instrumented collectives.

This is the paper's runtime working end-to-end on real execution (not the
simulator): 8 (emulated) devices train data-parallel under shard_map; every
gradient all-reduce goes through ``cd_psum`` which (i) inserts the
artificial barrier and (ii) emits host phase events; the Governor
reconstructs per-rank slack, applies the 500 us timeout policy, logs the
P-state actuations it would issue, estimates energy saving, and feeds the
straggler detector.

  PYTHONPATH=src python examples/energy_aware_training.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, reduced
from repro.core import instrument
from repro.core.governor import Governor
from repro.core.instrument import cd_psum
from repro.core.policies import COUNTDOWN_SLACK
from repro.dist.sharding import make_mesh
from repro.models.inputs import make_batch
from repro.models.transformer import init_params, loss_fn
from repro.train.optimizer import OptConfig, adamw_update, init_opt_state


def main() -> None:
    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev,), ("data",))
    cfg = reduced(get_config("countdown-100m"), n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=256)
    opt_cfg = OptConfig(lr=1e-3, warmup_steps=2, total_steps=30)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params, opt_cfg)

    governor = Governor(policy=COUNTDOWN_SLACK)
    instrument.set_mode("profile")
    instrument.enable_events(True)          # fully-manual mesh: events legal
    instrument.get_event_bus().subscribe(governor)

    def per_device_step(params, opt, batch):
        # Tcomp: local forward/backward -- then the instrumented collective:
        # artificial barrier (isolates slack) + the real grad all-reduce.
        loss, grads = jax.value_and_grad(lambda p: loss_fn(cfg, p, batch)[0])(params)
        grads = cd_psum(grads, "data")
        grads = jax.tree.map(lambda g: g / n_dev, grads)
        loss = cd_psum(loss, "data") / n_dev
        params, opt, _ = adamw_update(params, grads, opt, opt_cfg)
        return params, opt, loss

    # fully-specified jit shardings: the production idiom
    repl = NamedSharding(mesh, P())
    dsh = NamedSharding(mesh, P("data"))
    params = jax.device_put(params, jax.tree.map(lambda _: repl, params))
    opt = jax.device_put(opt, jax.tree.map(lambda _: repl, opt))
    step = jax.jit(
        jax.shard_map(
            per_device_step,
            mesh=mesh,
            in_specs=(P(), P(), P("data")),
            out_specs=(P(), P(), P()),
            axis_names={"data"}, check_vma=False,
        ),
        in_shardings=(
            jax.tree.map(lambda _: repl, params),
            jax.tree.map(lambda _: repl, opt),
            {"tokens": dsh, "labels": dsh, "mask": dsh},
        ),
        out_shardings=(
            jax.tree.map(lambda _: repl, params),
            jax.tree.map(lambda _: repl, opt),
            repl,
        ),
    )

    print(f"data-parallel training on {n_dev} devices, COUNTDOWN Slack live:")
    with jax.set_mesh(mesh):
        for i in range(30):
            batch = make_batch(cfg, batch=8, seq_len=33, seed=i, kind="train")
            batch = {k: jax.device_put(v, dsh) for k, v in batch.items()}
            params, opt, loss = step(params, opt, batch)
            jax.block_until_ready(loss)
            if i % 10 == 0 or i == 29:
                print(f"  step {i:3d}  loss {float(loss):.3f}")

    rep = governor.finalize()
    print("\nGovernor report (reconstructed from live phase events):")
    print(f"  instrumented collectives : {rep.n_calls}")
    print(f"  total slack observed     : {rep.total_slack*1e3:.2f} ms")
    print(f"  timeout downshifts       : {rep.n_downshifts}")
    print(f"  exploitable slack        : {rep.exploited_slack*1e3:.2f} ms")
    print(f"  est. energy saving (comm): {rep.energy_saving_pct:.2f}%")
    print(f"  P-state actuations logged: {len(governor.actuation_log)}")
    if rep.stragglers:
        print(f"  stragglers flagged       : {rep.stragglers}")
    else:
        print("  stragglers flagged       : none (balanced ranks)")

    instrument.set_mode("off")
    instrument.enable_events(False)
    instrument.get_event_bus().unsubscribe(governor)


if __name__ == "__main__":
    main()
