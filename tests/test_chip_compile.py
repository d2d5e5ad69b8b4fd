"""The paged-decode kernels compile for a TPU v5e (Mosaic), at the serving
widths of ``internlm2-1.8b``: 8 slots, 8 KV heads, GQA group 2, head dim
128, page 16, bf16 and int8 pools, one layer's or a stack of layers'.
So do the serving engine's decode step and join, which update the
stacked pool in place.

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
LAYERS = 4                                         # the ``_stacked`` variants


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

    quant = "_quant" in variant
    stacked = variant.endswith("_stacked")
    lead = (LAYERS,) if stacked else ()
    pool = s(lead + (N_PAGES, PAGE, HKV, D), jnp.int8 if quant else jnp.bfloat16)
    # a stack comes with the serving pool's flat scale rows
    scales = s(lead + ((N_PAGES, 1, PAGE * HKV) if stacked else (N_PAGES, PAGE, HKV)),
               jnp.float32)
    q, table, pos = s((B, HKV, G, D), jnp.bfloat16), s((B, M), jnp.int32), s((B,), jnp.int32)
    if variant == "paged_attention":
        return q, pool, pool, table, pos
    if variant == "paged_attention_quant":
        return q, pool, pool, scales, scales, table, pos
    new = s((B, HKV, D), pool.dtype)
    layer = (s((), jnp.int32),) if stacked else ()
    if not quant:
        return (q, new, new, pool, pool, table, pos, pos, pos) + layer
    new_scale = s((B, HKV), jnp.float32)
    return (q, new, new, new_scale, new_scale, pool, pool, scales, scales,
            table, pos, pos, pos) + layer


def _kernel(variant):
    return getattr(ops, variant.removesuffix("_stacked")).__wrapped__


@pytest.mark.parametrize("variant", [
    "paged_attention", "paged_attention_quant",
    "paged_attention_scatter", "paged_attention_scatter_quant",
    "paged_attention_scatter_stacked", "paged_attention_scatter_quant_stacked",
])
def test_paged_kernel_compiles_for_v5e(one_chip, monkeypatch, variant):
    # the platform check sees this host's CPU; the described chip compiles
    # with Mosaic, so steer the wrapper there and trace it afresh
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    fn = jax.jit(_kernel(variant))
    compiled = fn.lower(*_args(variant, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("variant", [
    "paged_attention", "paged_attention_quant",
    "paged_attention_scatter", "paged_attention_scatter_quant",
    "paged_attention_scatter_stacked",
])
def test_paged_kernel_is_named_for_the_trace(one_chip, monkeypatch, variant):
    # the kernel's op keeps its own name inside a program of any other
    # name: the trace (and ``paged_attention_roofline``) reads it by prefix
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    inner = _kernel(variant)

    def serving_step(*args):
        return inner(*args)

    text = jax.jit(serving_step).lower(*_args(variant, one_chip)).compile().as_text()
    kernels = [line.split(" = ", 1)[0].strip().lstrip("%") for line in text.splitlines()
               if "tpu_custom_call" in line and " = " in line]
    assert kernels and all(k.startswith("paged_attention") for k in kernels)


@pytest.mark.parametrize("kv_quant", [False, True])
@pytest.mark.parametrize("program", ["decode", "join"])
def test_pool_writing_programs_update_the_pool_in_place_on_v5e(one_chip, monkeypatch,
                                                                program, kv_quant):
    """The engine's Pallas decode step and its join, compiled for the chip
    at the serving widths (two layers): the donated pool is aliased to the
    new one, and no layer of it is sliced out, written back or copied (a
    scale pool relaid out for the kernel or the join is a copy too)."""
    from test_serve_paged import _pool_copies

    from repro.configs.base import ModelConfig
    from repro.models.transformer import init_cache, init_params
    from repro.serve.engine import make_join_step, make_paged_decode_step
    from repro.serve.kvcache import init_pool_blocks

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = ModelConfig(name="internlm2-2-layers", family="dense", n_layers=2,
                      d_model=HKV * G * D, n_heads=HKV * G, n_kv_heads=HKV,
                      d_ff=4 * HKV * G * D, vocab=1024, kv_quant=kv_quant)

    def shapes(make):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            jax.eval_shape(make))

    blocks = shapes(lambda: init_pool_blocks(cfg, N_PAGES, PAGE, B))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    if program == "decode":
        params = shapes(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        step = jax.jit(make_paged_decode_step(cfg, "pallas", fused_sample=True),
                       donate_argnums=4)
        lowered = step.lower(params, i32(B), i32(B), i32(B, M), blocks)
    else:
        cache = shapes(lambda: init_cache(cfg, 1, 4 * PAGE))
        lowered = jax.jit(make_join_step(cfg), donate_argnums=0).lower(
            blocks, cache, i32(4), i32())
    compiled = lowered.compile()
    text = compiled.as_text()
    assert program == "join" or "tpu_custom_call" in text
    assert _pool_copies(text, blocks) == []
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(blocks))
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
