"""Shared neural-net layers: norms, RoPE, attention (full / banded / decode),
MLPs and the chunked cross-entropy.

Everything is a pure function over explicit parameter pytrees — no framework
dependency.  Attention is implemented three ways:

* ``naive``   — materialized scores, used for tiny smoke shapes;
* ``chunked`` — online-softmax over KV chunks (flash-equivalent in XLA), the
  default for long sequences and the semantics the Pallas kernel mirrors;
* ``banded``  — chunk-local attention for SWA / local-attention archs
  (sub-quadratic: each chunk attends to itself + the previous chunk).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Params = Dict[str, Any]

NEG_INF = -1e30


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}[name]


def compute_weights(cfg, p):
    """One block's weight matrices in ``cfg.compute_dtype`` (mixed
    precision: ``param_dtype`` master weights, compute-dtype matmuls), so
    the residual stream keeps the compute dtype through every block.
    Vectors (norm scales, SSM / RG-LRU gate parameters) keep their
    storage dtype; the code reading them upcasts where it needs to."""
    dtype = dtype_of(cfg.compute_dtype)
    return jax.tree.map(
        lambda a: a.astype(dtype)
        if a.ndim >= 2 and jnp.issubdtype(a.dtype, jnp.floating) else a, p)


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def init_norm(cfg, d: int, dtype):
    if cfg.norm == "nonparametric":
        return {}
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype)}


def apply_norm(cfg, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm" or cfg.norm == "nonparametric":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + 1e-5)
        if cfg.norm == "layernorm":
            y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * lax.rsqrt(ms + 1e-6) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_frequencies(d_head: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, D); positions: (S,) absolute positions."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                       # (D/2,)
    angles = positions[:, None].astype(jnp.float32) * freqs  # (S, D/2)
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention cores
# --------------------------------------------------------------------------

def _gqa_reshape(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """(B,S,Hq,D) -> (B,S,Hkv,G,D)."""
    b, s, hq, d = q.shape
    return q.reshape(b, s, n_kv, hq // n_kv, d)


def naive_causal_attention(q, k, v, q_pos, k_pos, window: int = 0):
    """Materialized-scores attention.  q: (B,Sq,Hkv,G,D); k/v: (B,T,Hkv,D)."""
    d = q.shape[-1]
    s = jnp.einsum("bqkgd,btkd->bqkgt", q.astype(jnp.float32), k.astype(jnp.float32))
    s *= 1.0 / math.sqrt(d)
    mask = k_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= k_pos[None, :] > (q_pos[:, None] - window)
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqkgt,btkd->bqkgd", p.astype(v.dtype), v)
    return out


def chunked_causal_attention(q, k, v, q_pos, k_pos, kv_chunk: int = 1024,
                             use_scan: bool = False):
    """Online-softmax attention over KV chunks (flash-equivalent, pure XLA).

    q: (B,Sq,Hkv,G,D); k/v: (B,T,Hkv,D); q_pos: (Sq,), k_pos: (T,).
    ``use_scan``: loop chunks with lax.scan (production: one reused score
    buffer) vs python-unrolled (cost-analysis module: while bodies are
    counted once by XLA, see launch/dryrun.py).
    """
    b, sq, hkv, g, d = q.shape
    t = k.shape[1]
    kv_chunk = min(kv_chunk, t)
    n = t // kv_chunk
    rem = t - n * kv_chunk
    scale = 1.0 / math.sqrt(d)

    def chunk_update(carry, kc, vc, kposc):
        m, l, acc = carry
        # bf16 operands, fp32 accumulation (no materialized fp32 copies)
        s = jnp.einsum(
            "bqkgd,btkd->bqkgt", q, kc, preferred_element_type=jnp.float32
        ) * scale
        mask = kposc[None, :] <= q_pos[:, None]                     # (Sq, Tc)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqkgt,btkd->bqkgd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    init = (
        jnp.full((b, sq, hkv, g), NEG_INF, jnp.float32),
        jnp.zeros((b, sq, hkv, g), jnp.float32),
        jnp.zeros((b, sq, hkv, g, d), jnp.float32),
    )
    if use_scan and n > 1:
        ks = k[:, : n * kv_chunk].reshape(b, n, kv_chunk, hkv, d).transpose(1, 0, 2, 3, 4)
        vs = v[:, : n * kv_chunk].reshape(b, n, kv_chunk, hkv, d).transpose(1, 0, 2, 3, 4)
        kps = k_pos[: n * kv_chunk].reshape(n, kv_chunk)

        def body(carry, xs):
            kc, vc, kpc = xs
            return chunk_update(carry, kc, vc, kpc), None

        init, _ = lax.scan(body, init, (ks, vs, kps))
    else:
        for i in range(n):
            sl = slice(i * kv_chunk, (i + 1) * kv_chunk)
            init = chunk_update(init, k[:, sl], v[:, sl], k_pos[sl])
    if rem:
        init = chunk_update(init, k[:, n * kv_chunk:], v[:, n * kv_chunk:], k_pos[n * kv_chunk:])
    m, l, acc = init
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.astype(v.dtype)


def banded_attention(q, k, v, positions, window: int):
    """Sub-quadratic sliding-window attention.

    Sequence is cut into chunks of ``window``; each query chunk attends to
    (previous chunk ++ own chunk) with a causal + window mask.  O(S * 2W).
    q: (B,S,Hkv,G,D); k/v: (B,S,Hkv,D); positions: (S,).
    """
    b, s, hkv, g, d = q.shape
    w = min(window, s)
    pad = (-s) % w
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        positions = jnp.concatenate(
            [positions, positions[-1] + 1 + jnp.arange(pad, dtype=positions.dtype)]
        )
    sp = s + pad
    nc = sp // w
    qc = q.reshape(b, nc, w, hkv, g, d)
    kc = k.reshape(b, nc, w, hkv, d)
    vc = v.reshape(b, nc, w, hkv, d)
    pc = positions.reshape(nc, w)
    # previous chunk (chunk -1 is all-masked via position trick)
    k_prev = jnp.concatenate([jnp.zeros_like(kc[:, :1]), kc[:, :-1]], axis=1)
    v_prev = jnp.concatenate([jnp.zeros_like(vc[:, :1]), vc[:, :-1]], axis=1)
    p_prev = jnp.concatenate(
        [jnp.full_like(pc[:1], -(10 ** 9)), pc[:-1]], axis=0
    )
    k2 = jnp.concatenate([k_prev, kc], axis=2)               # (B,nc,2W,Hkv,D)
    v2 = jnp.concatenate([v_prev, vc], axis=2)
    p2 = jnp.concatenate([p_prev, pc], axis=1)               # (nc, 2W)
    scale = 1.0 / math.sqrt(d)
    sco = jnp.einsum(
        "bcqkgd,bctkd->bcqkgt", qc, k2, preferred_element_type=jnp.float32
    )
    sco *= scale
    mask = (p2[:, None, :] <= pc[:, :, None]) & (p2[:, None, :] > pc[:, :, None] - window)
    sco = jnp.where(mask[None, :, :, None, None, :], sco, NEG_INF)
    prob = jax.nn.softmax(sco, axis=-1)
    out = jnp.einsum(
        "bcqkgt,bctkd->bcqkgd", prob.astype(v2.dtype), v2,
        preferred_element_type=jnp.float32,
    ).astype(v2.dtype)
    out = out.reshape(b, sp, hkv, g, d)
    return out[:, :s]


# --------------------------------------------------------------------------
# attention block (projections + dispatch + cache handling)
# --------------------------------------------------------------------------

def init_attention(cfg, key, dtype) -> Params:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, d, hq * hd, dtype),
        "wk": dense_init(k2, d, hkv * hd, dtype),
        "wv": dense_init(k3, d, hkv * hd, dtype),
        "wo": dense_init(k4, hq * hd, d, dtype, scale=1.0 / math.sqrt(hq * hd)),
    }


def _project_qkv(cfg, p, x):
    b, s, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, s, hq, hd)
    k = (x @ p["wk"]).reshape(b, s, hkv, hd)
    v = (x @ p["wv"]).reshape(b, s, hkv, hd)
    return q, k, v


def attention_forward(cfg, p, x, positions, *, impl: str = "auto"):
    """Training / prefill attention over a full sequence.  x: (B,S,d)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    qg = _gqa_reshape(q, cfg.n_kv_heads)
    windowed = cfg.attention in ("swa", "local") and cfg.window
    if impl == "auto":
        if windowed and s > cfg.window:
            impl = "banded"
        elif s > 512:
            impl = "chunked"
        else:
            impl = "naive"
    if impl == "banded" and windowed:
        out = banded_attention(qg, k, v, positions, cfg.window)
    elif impl == "chunked":
        # production (scanned) path: small chunks bound the f32 score tile
        # (VMEM/HBM working set); the unrolled cost-analysis module instead
        # bounds the CHUNK COUNT to keep HLO size / compile time tractable
        if cfg.scan_layers:
            kv_chunk = min(1024, max(512, s // 32))
        else:
            kv_chunk = max(1024, s // 8)
        out = chunked_causal_attention(
            qg, k, v, positions, positions, kv_chunk=kv_chunk,
            use_scan=cfg.scan_layers,
        )
        if windowed and s > cfg.window:
            raise ValueError("use banded impl for windowed attention on long seqs")
    else:
        out = naive_causal_attention(qg, k, v, positions, positions, window=cfg.window if windowed else 0)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return out @ p["wo"]


def init_kv_cache(cfg, batch: int, max_len: int, dtype) -> Params:
    """Ring cache for windowed attention; linear cache otherwise.

    With ``cfg.kv_quant`` the cache is int8 with a per-(token, head) scale —
    halves the dominant decode memory (cache) at ~1 LSB/127 error.
    """
    windowed = cfg.attention in ("swa", "local") and cfg.window
    t = min(cfg.window, max_len) if windowed else max_len
    kv_dtype = jnp.int8 if cfg.kv_quant else dtype
    cache = {
        "k": jnp.zeros((batch, t, cfg.n_kv_heads, cfg.head_dim), kv_dtype),
        "v": jnp.zeros((batch, t, cfg.n_kv_heads, cfg.head_dim), kv_dtype),
        "slot_pos": jnp.full((t,), -1, jnp.int32),
    }
    if cfg.kv_quant:
        cache["k_scale"] = jnp.zeros((batch, t, cfg.n_kv_heads), jnp.float32)
        cache["v_scale"] = jnp.zeros((batch, t, cfg.n_kv_heads), jnp.float32)
    return cache


def _kv_quantize(x: jnp.ndarray):
    """x: (..., D) -> (int8 values, per-(...,) scale multiplier)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def _kv_dequantize(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def attention_prefill(cfg, p, x, positions, cache):
    """Run full-sequence attention and fill the cache.  Returns (out, cache)."""
    out = attention_forward(cfg, p, x, positions)
    _, k, v = _project_qkv(cfg, p, x)
    k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.kv_quant:
        k, k_sc = _kv_quantize(k)
        v, v_sc = _kv_quantize(v)
    t = cache["k"].shape[1]
    s = x.shape[1]
    new_cache = dict(cache)
    if s >= t:
        # keep the last t entries (ring fully covered)
        slots = (positions[-t:] % t)
        new_cache["k"] = jnp.zeros_like(cache["k"]).at[:, slots].set(k[:, -t:])
        new_cache["v"] = jnp.zeros_like(cache["v"]).at[:, slots].set(v[:, -t:])
        new_cache["slot_pos"] = (
            jnp.full_like(cache["slot_pos"], -1)
            .at[slots].set(positions[-t:].astype(jnp.int32))
        )
        if cfg.kv_quant:
            new_cache["k_scale"] = jnp.zeros_like(cache["k_scale"]).at[:, slots].set(k_sc[:, -t:])
            new_cache["v_scale"] = jnp.zeros_like(cache["v_scale"]).at[:, slots].set(v_sc[:, -t:])
    else:
        slots = positions % t
        new_cache["k"] = cache["k"].at[:, slots].set(k)
        new_cache["v"] = cache["v"].at[:, slots].set(v)
        new_cache["slot_pos"] = cache["slot_pos"].at[slots].set(positions.astype(jnp.int32))
        if cfg.kv_quant:
            new_cache["k_scale"] = cache["k_scale"].at[:, slots].set(k_sc)
            new_cache["v_scale"] = cache["v_scale"].at[:, slots].set(v_sc)
    return out, new_cache


def attention_decode(cfg, p, x, pos, cache):
    """Single-token decode.  x: (B,1,d); pos: scalar int32 position."""
    b = x.shape[0]
    q, k, v = _project_qkv(cfg, p, x)                          # (B,1,H,D)
    pos_arr = jnp.reshape(pos, (1,)).astype(jnp.int32)
    q = apply_rope(q, pos_arr, cfg.rope_theta)
    k = apply_rope(k, pos_arr, cfg.rope_theta)
    t = cache["k"].shape[1]
    slot = (pos % t).astype(jnp.int32)
    new_cache = dict(cache)
    if cfg.kv_quant:
        kq, k_sc = _kv_quantize(k)
        vq, v_sc = _kv_quantize(v)
        new_cache["k_scale"] = lax.dynamic_update_slice(cache["k_scale"], k_sc, (0, slot, 0))
        new_cache["v_scale"] = lax.dynamic_update_slice(cache["v_scale"], v_sc, (0, slot, 0))
        k, v = kq, vq
    ck = lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    cv = lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    cp = lax.dynamic_update_slice(cache["slot_pos"], pos_arr, (slot,))
    new_cache.update(k=ck, v=cv, slot_pos=cp)
    if cfg.kv_quant:
        ck = _kv_dequantize(ck, new_cache["k_scale"], x.dtype)
        cv = _kv_dequantize(cv, new_cache["v_scale"], x.dtype)
    qg = _gqa_reshape(q, cfg.n_kv_heads)                       # (B,1,Hkv,G,D)
    d = cfg.head_dim
    # bf16 operands + fp32 accumulation: casting the cache to fp32 would
    # materialize a 2x-sized copy of the (dominant) KV traffic per step
    s = jnp.einsum(
        "bqkgd,btkd->bqkgt", qg, ck, preferred_element_type=jnp.float32
    )
    s *= 1.0 / math.sqrt(d)
    valid = (cp >= 0) & (cp <= pos)
    if cfg.attention in ("swa", "local") and cfg.window:
        valid &= cp > pos - cfg.window
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bqkgt,btkd->bqkgd", prob.astype(cv.dtype), cv,
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    out = out.reshape(b, 1, cfg.n_heads * cfg.head_dim) @ p["wo"]
    return out, new_cache


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def init_mlp(cfg, key, dtype) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.family == "audio":  # musicgen: classic GELU MLP
        k1, k2 = jax.random.split(key)
        return {"w1": dense_init(k1, d, f, dtype), "w2": dense_init(k2, f, d, dtype)}
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": dense_init(k1, d, f, dtype),
        "w3": dense_init(k2, d, f, dtype),
        "w2": dense_init(k3, f, d, dtype),
    }


def mlp_forward(cfg, p, x):
    if "w3" not in p:
        return jax.nn.gelu(x @ p["w1"]) @ p["w2"]
    return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


# --------------------------------------------------------------------------
# chunked cross-entropy (never materializes full (B,S,V) logits)
# --------------------------------------------------------------------------

def chunked_cross_entropy(x, embed_t, labels, mask, chunk: int = 512,
                          use_scan: bool = False):
    """x: (B,S,d); embed_t: (d,V); labels,mask: (B,S).  Mean NLL over mask.

    ``use_scan`` as in chunked_causal_attention: production modules scan
    (one reused logits buffer); cost modules unroll.
    """
    b, s, d = x.shape
    chunk = min(chunk, s)
    n = s // chunk
    rem = s - n * chunk

    def chunk_loss(xc, lc, mc):
        from repro.models.hooks import constrain

        logits = constrain(xc @ embed_t, "logits").astype(jnp.float32)  # (B,C,V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = (lse - tgt) * mc
        return jnp.sum(nll), jnp.sum(mc)

    total, count = jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)
    if use_scan and n > 1:
        xs = x[:, : n * chunk].reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
        ls = labels[:, : n * chunk].reshape(b, n, chunk).transpose(1, 0, 2)
        ms = mask[:, : n * chunk].reshape(b, n, chunk).transpose(1, 0, 2)

        def body(carry, inp):
            t0, c0 = carry
            tl, cl = chunk_loss(*inp)
            return (t0 + tl, c0 + cl), None

        (total, count), _ = lax.scan(body, (total, count), (xs, ls, ms))
    else:
        for i in range(n):
            sl = slice(i * chunk, (i + 1) * chunk)
            tl, cl = chunk_loss(x[:, sl], labels[:, sl], mask[:, sl])
            total, count = total + tl, count + cl
    if rem:
        tl, cl = chunk_loss(x[:, n * chunk:], labels[:, n * chunk:], mask[:, n * chunk:])
        total, count = total + tl, count + cl
    return total / jnp.maximum(count, 1.0)
