"""Per-kernel shape/dtype sweeps asserting allclose against the pure-jnp
oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------
# rmsnorm
# --------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 128), (3, 17, 256), (2, 5, 7, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    x = jnp.asarray(RNG.normal(0, 2, shape), dtype)
    scale = jnp.asarray(RNG.normal(1, 0.2, shape[-1:]), dtype)
    out = ops.rmsnorm(x, scale, row_block=8)
    expect = ref.rmsnorm_ref(x, scale)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), **_tol(dtype)
    )


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

@pytest.mark.parametrize(
    "b,hq,hkv,s,d,window",
    [
        (1, 4, 4, 64, 32, 0),       # MHA causal
        (2, 8, 2, 96, 64, 0),       # GQA causal, non-multiple seq
        (1, 4, 1, 128, 32, 0),      # MQA
        (1, 4, 2, 128, 32, 48),     # sliding window
    ],
)
def test_flash_attention(b, hq, hkv, s, d, window):
    q = jnp.asarray(RNG.normal(0, 1, (b, hq, s, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, hkv, s, d)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, window=window, block_q=32, block_k=32)
    expect = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5, rtol=2e-4)


def test_flash_attention_bf16():
    b, hq, hkv, s, d = 1, 4, 2, 64, 32
    q = jnp.asarray(RNG.normal(0, 1, (b, hq, s, d)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(0, 1, (b, hkv, s, d)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(0, 1, (b, hkv, s, d)), jnp.bfloat16)
    out = ops.flash_attention(q, k, v, block_q=32, block_k=32)
    expect = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=3e-2, rtol=3e-2
    )


def test_flash_matches_model_layer_attention():
    """The kernel semantics mirror the model's chunked XLA attention."""
    from repro.models.layers import chunked_causal_attention

    b, hkv, g, s, d = 1, 2, 3, 80, 32
    q = jnp.asarray(RNG.normal(0, 1, (b, s, hkv, g, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, s, hkv, d)), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    xla_out = chunked_causal_attention(q, k, v, pos, pos, kv_chunk=32)
    qk = q.transpose(0, 2, 3, 1, 4).reshape(b, hkv * g, s, d)
    pl_out = ops.flash_attention(
        qk, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), block_q=32, block_k=32
    )
    pl_out = pl_out.reshape(b, hkv, g, s, d).transpose(0, 3, 1, 2, 4)
    np.testing.assert_allclose(np.asarray(pl_out), np.asarray(xla_out), atol=2e-5, rtol=2e-4)


# --------------------------------------------------------------------------
# SSD (Mamba-2)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("s,chunk", [(64, 16), (100, 32), (32, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd(s, chunk, dtype):
    b, h, p, n = 2, 3, 16, 8
    x = jnp.asarray(RNG.normal(0, 1, (b, s, h, p)), dtype)
    dt = jnp.asarray(RNG.uniform(0.01, 0.5, (b, s, h)), jnp.float32)
    a_log = -jnp.asarray(RNG.uniform(0.5, 2.0, (h,)), jnp.float32)
    bb = jnp.asarray(RNG.normal(0, 1, (b, s, n)), dtype)
    cc = jnp.asarray(RNG.normal(0, 1, (b, s, n)), dtype)
    out = ops.ssd_scan(x, dt, a_log, bb, cc, chunk=chunk)
    expect = ref.ssd_ref(x, dt, a_log, bb, cc)
    tol = dict(atol=3e-1, rtol=5e-2) if dtype == jnp.bfloat16 else dict(atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), **tol
    )


# --------------------------------------------------------------------------
# RG-LRU scan
# --------------------------------------------------------------------------

@pytest.mark.parametrize("s,w,chunk,wb", [(64, 96, 16, 32), (50, 64, 32, 64), (16, 128, 16, 128)])
def test_rglru_scan(s, w, chunk, wb):
    a = jnp.asarray(RNG.uniform(0.3, 0.999, (2, s, w)), jnp.float32)
    b = jnp.asarray(RNG.normal(0, 0.3, (2, s, w)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(0, 1, (2, w)), jnp.float32)
    out = ops.rglru_scan(a, b, h0, chunk=chunk, width_block=wb)
    expect = ref.rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5, rtol=2e-4)


def test_rglru_matches_model_associative_scan():
    from repro.models.rglru import linear_scan

    a = jnp.asarray(RNG.uniform(0.3, 0.999, (2, 40, 64)), jnp.float32)
    b = jnp.asarray(RNG.normal(0, 0.3, (2, 40, 64)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(0, 1, (2, 64)), jnp.float32)
    h_assoc, _ = linear_scan(a, b, h0)
    h_pallas = ops.rglru_scan(a, b, h0, chunk=8, width_block=64)
    np.testing.assert_allclose(np.asarray(h_pallas), np.asarray(h_assoc), atol=2e-5, rtol=2e-4)


# --------------------------------------------------------------------------
# paged decode attention + fused scatter epilogue
# --------------------------------------------------------------------------

def _paged_case(b, hkv, g, d, page, m, n_pages, quant):
    q = jnp.asarray(RNG.normal(0, 1, (b, hkv, g, d)), jnp.float32)
    table = jnp.asarray(
        RNG.choice(n_pages, size=(b, m), replace=False).reshape(b, m)
        if b * m <= n_pages else RNG.integers(0, n_pages, (b, m)),
        jnp.int32,
    )
    pos = jnp.asarray(RNG.integers(0, m * page, (b,)), jnp.int32)
    if quant:
        kp = jnp.asarray(RNG.integers(-127, 128, (n_pages, page, hkv, d)), jnp.int8)
        vp = jnp.asarray(RNG.integers(-127, 128, (n_pages, page, hkv, d)), jnp.int8)
        ks = jnp.asarray(RNG.uniform(1e-3, 0.1, (n_pages, page, hkv)), jnp.float32)
        vs = jnp.asarray(RNG.uniform(1e-3, 0.1, (n_pages, page, hkv)), jnp.float32)
        return q, kp, vp, table, pos, ks, vs
    kp = jnp.asarray(RNG.normal(0, 1, (n_pages, page, hkv, d)), jnp.float32)
    vp = jnp.asarray(RNG.normal(0, 1, (n_pages, page, hkv, d)), jnp.float32)
    return q, kp, vp, table, pos, None, None


@pytest.mark.parametrize(
    "b,hkv,g,d,page,m,window,quant",
    [
        (2, 2, 4, 32, 8, 4, 0, False),     # GQA
        (3, 1, 4, 32, 8, 5, 0, False),     # MQA, non-pow2 table width
        (2, 4, 1, 32, 16, 3, 0, False),    # MHA
        (2, 2, 2, 32, 8, 4, 12, False),    # sliding window
        (2, 2, 4, 32, 8, 5, 0, True),      # int8 pages, fused dequant
        (2, 2, 2, 32, 8, 4, 12, True),     # int8 + window
    ],
)
def test_paged_attention_matches_ref(b, hkv, g, d, page, m, window, quant):
    q, kp, vp, table, pos, ks, vs = _paged_case(b, hkv, g, d, page, m, 32, quant)
    if quant:
        out = ops.paged_attention_quant(q, kp, vp, ks, vs, table, pos, window=window)
    else:
        out = ops.paged_attention(q, kp, vp, table, pos, window=window)
    expect = ref.paged_attention_ref(
        q, kp, vp, table, pos, k_scale_pages=ks, v_scale_pages=vs, window=window
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=2e-4)


def test_paged_scatter_bit_equal_to_at_set():
    """The fused epilogue's aliased page write must be bit-identical to the
    ``.at[page_idx, off].set()`` path — including every untouched page."""
    n_pages, page, hkv, d, b = 12, 8, 2, 16, 4
    kp = jnp.asarray(RNG.normal(0, 1, (n_pages, page, hkv, d)), jnp.float32)
    vp = jnp.asarray(RNG.normal(0, 1, (n_pages, page, hkv, d)), jnp.float32)
    k_new = jnp.asarray(RNG.normal(0, 1, (b, hkv, d)), jnp.float32)
    v_new = jnp.asarray(RNG.normal(0, 1, (b, hkv, d)), jnp.float32)
    page_idx = jnp.asarray([3, 7, 1, 10], jnp.int32)
    off = jnp.asarray([0, 5, 7, 2], jnp.int32)
    got_k, got_v = ops.paged_scatter(kp, vp, k_new, v_new, page_idx, off)
    np.testing.assert_array_equal(np.asarray(got_k),
                                  np.asarray(kp.at[page_idx, off].set(k_new)))
    np.testing.assert_array_equal(np.asarray(got_v),
                                  np.asarray(vp.at[page_idx, off].set(v_new)))


def test_paged_scatter_quant_bit_equal_to_at_set():
    n_pages, page, hkv, d, b = 10, 8, 2, 16, 3
    kp = jnp.asarray(RNG.integers(-127, 128, (n_pages, page, hkv, d)), jnp.int8)
    vp = jnp.asarray(RNG.integers(-127, 128, (n_pages, page, hkv, d)), jnp.int8)
    ks = jnp.asarray(RNG.uniform(0, 1, (n_pages, page, hkv)), jnp.float32)
    vs = jnp.asarray(RNG.uniform(0, 1, (n_pages, page, hkv)), jnp.float32)
    k_new = jnp.asarray(RNG.integers(-127, 128, (b, hkv, d)), jnp.int8)
    v_new = jnp.asarray(RNG.integers(-127, 128, (b, hkv, d)), jnp.int8)
    ks_new = jnp.asarray(RNG.uniform(0, 1, (b, hkv)), jnp.float32)
    vs_new = jnp.asarray(RNG.uniform(0, 1, (b, hkv)), jnp.float32)
    page_idx = jnp.asarray([2, 9, 5], jnp.int32)
    off = jnp.asarray([7, 0, 3], jnp.int32)
    got = ops.paged_scatter_quant(kp, vp, ks, vs, k_new, v_new, ks_new, vs_new,
                                  page_idx, off)
    want = (kp.at[page_idx, off].set(k_new), vp.at[page_idx, off].set(v_new),
            ks.at[page_idx, off].set(ks_new), vs.at[page_idx, off].set(vs_new))
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("quant,window", [(False, 0), (False, 12), (True, 0)])
def test_paged_attention_scatter_fuses_bit_equal(quant, window):
    """The fused single-dispatch step (scatter prologue + page walk) must
    be bit-identical to standalone scatter followed by standalone
    attention — outputs AND every page of the updated pools."""
    b, hkv, g, d, page, m = 3, 2, 2, 32, 8, 4
    n_pages = b * m + 2                  # distinct live pages per slot
    q, kp, vp, table, pos, ks, vs = _paged_case(b, hkv, g, d, page, m, n_pages, quant)
    page_idx = table[jnp.arange(b), pos // page]
    off = pos % page
    if quant:
        k_new = jnp.asarray(RNG.integers(-127, 128, (b, hkv, d)), jnp.int8)
        v_new = jnp.asarray(RNG.integers(-127, 128, (b, hkv, d)), jnp.int8)
        ks_new = jnp.asarray(RNG.uniform(1e-3, 0.1, (b, hkv)), jnp.float32)
        vs_new = jnp.asarray(RNG.uniform(1e-3, 0.1, (b, hkv)), jnp.float32)
        want_pools = ops.paged_scatter_quant(
            kp, vp, ks, vs, k_new, v_new, ks_new, vs_new, page_idx, off)
        want_out = ops.paged_attention_quant(
            q, *want_pools, table, pos, window=window)
        got_out, got_pools = ops.paged_attention_scatter_quant(
            q, k_new, v_new, ks_new, vs_new, kp, vp, ks, vs,
            table, pos, page_idx, off, window=window)
    else:
        k_new = jnp.asarray(RNG.normal(0, 1, (b, hkv, d)), jnp.float32)
        v_new = jnp.asarray(RNG.normal(0, 1, (b, hkv, d)), jnp.float32)
        want_pools = ops.paged_scatter(kp, vp, k_new, v_new, page_idx, off)
        want_out = ops.paged_attention(q, *want_pools, table, pos, window=window)
        got_out, got_pools = ops.paged_attention_scatter(
            q, k_new, v_new, kp, vp, table, pos, page_idx, off, window=window)
    np.testing.assert_array_equal(np.asarray(got_out), np.asarray(want_out))
    for got, want in zip(got_pools, want_pools):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("quant,layer", [(False, 0), (False, 2), (True, 1)])
def test_paged_attention_scatter_indexes_the_layer(quant, layer):
    """On a stacked pool the layer-indexed fused kernel equals the
    per-layer kernel on layer ``layer`` (output and that layer's pages),
    and leaves every other layer's pages bit-for-bit as they were.  The
    int8 scale pools come in the serving pool's flat-row form."""
    n_layers, b, hkv, g, d, page, m = 3, 3, 2, 2, 32, 8, 4
    n_pages = b * m + 2
    cases = [_paged_case(b, hkv, g, d, page, m, n_pages, quant) for _ in range(n_layers)]
    q, _, _, table, pos, _, _ = cases[layer]
    page_idx = table[jnp.arange(b), pos // page]
    off = pos % page
    stack = lambda i: jnp.stack([c[i] for c in cases])
    kp, vp = stack(1), stack(2)
    if quant:
        flat = lambda a: a.reshape(n_layers, n_pages, 1, page * hkv)
        ks, vs = flat(stack(5)), flat(stack(6))
        k_new = jnp.asarray(RNG.integers(-127, 128, (b, hkv, d)), jnp.int8)
        v_new = jnp.asarray(RNG.integers(-127, 128, (b, hkv, d)), jnp.int8)
        ks_new = jnp.asarray(RNG.uniform(1e-3, 0.1, (b, hkv)), jnp.float32)
        vs_new = jnp.asarray(RNG.uniform(1e-3, 0.1, (b, hkv)), jnp.float32)
        before = (kp, vp, ks, vs)
        want_out, want = ops.paged_attention_scatter_quant(
            q, k_new, v_new, ks_new, vs_new, kp[layer], vp[layer],
            cases[layer][5], cases[layer][6], table, pos, page_idx, off)
        want = want[:2] + tuple(a.reshape(n_pages, 1, page * hkv) for a in want[2:])
        got_out, got = ops.paged_attention_scatter_quant(
            q, k_new, v_new, ks_new, vs_new, kp, vp, ks, vs,
            table, pos, page_idx, off, jnp.int32(layer))
    else:
        k_new = jnp.asarray(RNG.normal(0, 1, (b, hkv, d)), jnp.float32)
        v_new = jnp.asarray(RNG.normal(0, 1, (b, hkv, d)), jnp.float32)
        before = (kp, vp)
        want_out, want = ops.paged_attention_scatter(
            q, k_new, v_new, kp[layer], vp[layer], table, pos, page_idx, off)
        got_out, got = ops.paged_attention_scatter(
            q, k_new, v_new, kp, vp, table, pos, page_idx, off, jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(got_out), np.asarray(want_out))
    others = [i for i in range(n_layers) if i != layer]
    for g_pool, w_layer, b_pool in zip(got, want, before):
        assert g_pool.shape == b_pool.shape and g_pool.dtype == b_pool.dtype
        np.testing.assert_array_equal(np.asarray(g_pool[layer]), np.asarray(w_layer))
        np.testing.assert_array_equal(np.asarray(g_pool)[others], np.asarray(b_pool)[others])


def test_interpret_follows_platform(monkeypatch):
    """Interpret mode on a CPU backend, Mosaic on any other — decided by
    the platform alone, with no environment override."""
    assert ops._interpret() is (jax.default_backend() == "cpu")
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "cpu")
    assert ops._interpret() is True
