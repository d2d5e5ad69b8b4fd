"""The paged-decode kernels compile for a TPU v5e (Mosaic), at the serving
widths of ``internlm2-1.8b``: 8 slots, 8 KV heads, GQA group 2, head dim
128, page 16, bf16 and int8 pools.

Nothing runs: the v5e is described, not attached, so the TPU compiler
refuses here what it would refuse on the chip (tiling, memory spaces,
scratch that does not fit).  The topology is described inside a fixture,
never at import, so every test worker collects the same tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

B, HKV, G, D, PAGE, M = 8, 8, 2, 128, 16, 64      # 1024-token table rows
N_PAGES = B * M + 1                                # + the scratch page


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # entries compiled for a described device cannot be read back without
    # one: keep the persistent cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _args(variant, sharding):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    quant = variant.endswith("_quant")
    pool = s((N_PAGES, PAGE, HKV, D), jnp.int8 if quant else jnp.bfloat16)
    scales = s((N_PAGES, PAGE, HKV), jnp.float32)
    q, table, pos = s((B, HKV, G, D), jnp.bfloat16), s((B, M), jnp.int32), s((B,), jnp.int32)
    if variant == "paged_attention":
        return q, pool, pool, table, pos
    if variant == "paged_attention_quant":
        return q, pool, pool, scales, scales, table, pos
    new = s((B, HKV, D), pool.dtype)
    if variant == "paged_attention_scatter":
        return q, new, new, pool, pool, table, pos, pos, pos
    new_scale = s((B, HKV), jnp.float32)
    return (q, new, new, new_scale, new_scale, pool, pool, scales, scales,
            table, pos, pos, pos)


@pytest.mark.parametrize("variant", [
    "paged_attention", "paged_attention_quant",
    "paged_attention_scatter", "paged_attention_scatter_quant",
])
def test_paged_kernel_compiles_for_v5e(one_chip, monkeypatch, variant):
    # the platform check sees this host's CPU; the described chip compiles
    # with Mosaic, so steer the wrapper there and trace it afresh
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    fn = jax.jit(getattr(ops, variant).__wrapped__)
    compiled = fn.lower(*_args(variant, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("variant", [
    "paged_attention", "paged_attention_quant",
    "paged_attention_scatter", "paged_attention_scatter_quant",
])
def test_paged_kernel_is_named_for_the_trace(one_chip, monkeypatch, variant):
    # the kernel's op keeps its own name inside a program of any other
    # name: the trace (and ``paged_attention_roofline``) reads it by prefix
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    inner = getattr(ops, variant).__wrapped__

    def serving_step(*args):
        return inner(*args)

    text = jax.jit(serving_step).lower(*_args(variant, one_chip)).compile().as_text()
    kernels = [line.split(" = ", 1)[0].strip().lstrip("%") for line in text.splitlines()
               if "tpu_custom_call" in line and " = " in line]
    assert kernels and all(k.startswith("paged_attention") for k in kernels)
