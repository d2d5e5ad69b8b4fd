"""Model assembly: scan-over-layers stack covering all assigned families.

The layer stack is organized as *periods* of the config's block pattern
(dense/moe: ("attn",); ssm: ("ssm",); recurrentgemma: ("rglru","rglru","attn")).
``n_full = n_layers // len(pattern)`` periods are executed under one
``lax.scan`` with parameters stacked on a leading axis — essential to keep
HLO size and 512-device compile times tractable — plus an unrolled remainder.

Public API:
    init_params(cfg, key)             -> params
    forward(cfg, params, batch)       -> (hidden (B,S,d), aux)
    loss_fn(cfg, params, batch)       -> (loss, metrics)
    init_cache(cfg, batch, max_len)   -> cache
    prefill(cfg, params, batch, cache)-> (logits_last (B,V), cache)
    decode_step(cfg, params, tok, pos, cache) -> (logits (B,V), cache)
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import ssm as S
from repro.models.hooks import constrain

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _init_block(cfg, kind: str, key, dtype) -> Params:
    d = cfg.d_model
    if kind == "attn":
        k1, k2 = jax.random.split(key)
        p = {
            "ln1": L.init_norm(cfg, d, dtype),
            "attn": L.init_attention(cfg, k1, dtype),
            "ln2": L.init_norm(cfg, d, dtype),
        }
        p["ffn"] = M.init_moe(cfg, k2, dtype) if cfg.is_moe else L.init_mlp(cfg, k2, dtype)
        return p
    if kind == "ssm":
        return {"ln1": L.init_norm(cfg, d, dtype), "ssm": S.init_ssm(cfg, key, dtype)}
    if kind == "rglru":
        k1, k2 = jax.random.split(key)
        return {
            "ln1": L.init_norm(cfg, d, dtype),
            "rglru": R.init_rglru(cfg, k1, dtype),
            "ln2": L.init_norm(cfg, d, dtype),
            "ffn": L.init_mlp(cfg, k2, dtype),
        }
    raise ValueError(f"unknown block kind {kind!r}")


def _init_period(cfg, key, dtype) -> Params:
    keys = jax.random.split(key, len(cfg.pattern))
    return {str(j): _init_block(cfg, kind, keys[j], dtype) for j, kind in enumerate(cfg.pattern)}


def stack_layout(cfg) -> Tuple[int, Tuple[str, ...]]:
    """(n_full periods, remainder block kinds)."""
    plen = len(cfg.pattern)
    n_full = cfg.n_layers // plen
    rem = cfg.n_layers % plen
    return n_full, cfg.pattern[:rem]


def init_params(cfg, key) -> Params:
    dtype = L.dtype_of(cfg.param_dtype)
    n_full, rem_kinds = stack_layout(cfg)
    k_emb, k_stack, k_rem, k_head = jax.random.split(key, 4)
    params: Params = {
        "embed": L.embed_init(k_emb, cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": L.init_norm(cfg, cfg.d_model, dtype),
    }
    stack_keys = jax.random.split(k_stack, n_full)
    params["stack"] = jax.vmap(lambda k: _init_period(cfg, k, dtype))(stack_keys)
    if rem_kinds:
        rks = jax.random.split(k_rem, len(rem_kinds))
        params["rem"] = {
            str(j): _init_block(cfg, kind, rks[j], dtype) for j, kind in enumerate(rem_kinds)
        }
    if not cfg.tie_embeddings:
        params["head"] = L.dense_init(k_head, cfg.d_model, cfg.padded_vocab, dtype)
    return params


def param_count(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


# --------------------------------------------------------------------------
# forward (train / scoring)
# --------------------------------------------------------------------------

def _block_forward(cfg, kind: str, p: Params, x, positions):
    """One block, full-sequence.  Returns (x, aux)."""
    p = L.compute_weights(cfg, p)
    aux = jnp.zeros((), jnp.float32)
    if kind == "attn":
        h = L.apply_norm(cfg, p["ln1"], x)
        x = x + L.attention_forward(cfg, p["attn"], h, positions)
        x = constrain(x, "residual")
        h = L.apply_norm(cfg, p["ln2"], x)
        if cfg.is_moe:
            y, aux = M.moe_forward(cfg, p["ffn"], h)
        else:
            y = L.mlp_forward(cfg, p["ffn"], h)
        x = x + y
    elif kind == "ssm":
        h = L.apply_norm(cfg, p["ln1"], x)
        y, _ = S.ssm_forward(cfg, p["ssm"], h)
        x = x + y
    elif kind == "rglru":
        h = L.apply_norm(cfg, p["ln1"], x)
        y, _ = R.rglru_forward(cfg, p["rglru"], h)
        x = x + y
        h = L.apply_norm(cfg, p["ln2"], x)
        x = x + L.mlp_forward(cfg, p["ffn"], h)
    x = constrain(x, "residual")
    return x, aux


def _embed_inputs(cfg, params, batch) -> jnp.ndarray:
    """Token embeddings, with frontend prefix embeddings when configured."""
    dtype = L.dtype_of(cfg.compute_dtype)
    tok = batch["tokens"]
    x = jnp.take(params["embed"], tok, axis=0).astype(dtype)
    if cfg.n_prefix and "prefix_embeds" in batch:
        pre = batch["prefix_embeds"].astype(dtype)             # (B, P, d)
        x = jnp.concatenate([pre, x], axis=1)
    return x * math.sqrt(cfg.d_model)


def forward(cfg, params, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """-> (final hidden states (B,S,d), aux loss)."""
    x = _embed_inputs(cfg, params, batch)
    x = constrain(x, "residual")
    s = x.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)
    n_full, rem_kinds = stack_layout(cfg)

    def period_forward(x, pp):
        aux = jnp.zeros((), jnp.float32)
        for j, kind in enumerate(cfg.pattern):
            x, a = _block_forward(cfg, kind, pp[str(j)], x, positions)
            aux = aux + a
        return x, aux

    body = jax.checkpoint(period_forward) if cfg.remat else period_forward

    aux = jnp.zeros((), jnp.float32)
    if cfg.scan_layers:

        def scan_body(carry, pp):
            x, aux = carry
            x, a = body(x, pp)
            return (x, aux + a), None

        (x, aux), _ = lax.scan(scan_body, (x, aux), params["stack"])
    else:
        for i in range(n_full):
            pp = jax.tree.map(lambda a: a[i], params["stack"])
            x, a = body(x, pp)
            aux = aux + a
    for j, kind in enumerate(rem_kinds):
        x, a = _block_forward(cfg, kind, params["rem"][str(j)], x, positions)
        aux = aux + a
    x = L.apply_norm(cfg, params["final_norm"], x)
    return x, aux


def _unembed_matrix(cfg, params) -> jnp.ndarray:
    dtype = L.dtype_of(cfg.compute_dtype)
    if cfg.tie_embeddings:
        return params["embed"].T.astype(dtype)
    return params["head"].astype(dtype)


def logits_fn(cfg, params, hidden) -> jnp.ndarray:
    logits = hidden @ _unembed_matrix(cfg, params)
    if cfg.logits_softcap:
        c = cfg.logits_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


def loss_fn(cfg, params, batch) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """batch: tokens (B,S'), labels (B,S), mask (B,S) [, prefix_embeds]."""
    hidden, aux = forward(cfg, params, batch)
    if cfg.logits_softcap:
        # softcap requires materialized logits; cap archs have small B*S*V
        logits = logits_fn(cfg, params, hidden)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        tgt = jnp.take_along_axis(
            logits.astype(jnp.float32), batch["labels"][..., None], axis=-1
        )[..., 0]
        mask = batch["mask"].astype(jnp.float32)
        nll = jnp.sum((lse - tgt) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    else:
        nll = L.chunked_cross_entropy(
            hidden, _unembed_matrix(cfg, params), batch["labels"],
            batch["mask"].astype(jnp.float32), use_scan=cfg.scan_layers,
        )
    loss = nll + aux
    return loss, {"nll": nll, "aux": aux}


# --------------------------------------------------------------------------
# cache / prefill / decode
# --------------------------------------------------------------------------

def _init_block_cache(cfg, kind: str, batch: int, max_len: int, dtype) -> Params:
    if kind == "attn":
        return L.init_kv_cache(cfg, batch, max_len, dtype)
    if kind == "ssm":
        return S.init_ssm_state(cfg, batch, dtype)
    if kind == "rglru":
        return R.init_rglru_state(cfg, batch, dtype)
    raise ValueError(kind)


def init_cache(cfg, batch: int, max_len: int) -> Params:
    dtype = L.dtype_of(cfg.compute_dtype)
    n_full, rem_kinds = stack_layout(cfg)
    proto = {
        str(j): _init_block_cache(cfg, kind, batch, max_len, dtype)
        for j, kind in enumerate(cfg.pattern)
    }
    stack = jax.tree.map(lambda a: jnp.tile(a[None], (n_full,) + (1,) * a.ndim), proto)
    cache: Params = {"stack": stack}
    if rem_kinds:
        cache["rem"] = {
            str(j): _init_block_cache(cfg, kind, batch, max_len, dtype)
            for j, kind in enumerate(rem_kinds)
        }
    return cache


def _block_prefill(cfg, kind, p, x, positions, bc):
    p = L.compute_weights(cfg, p)
    if kind == "attn":
        h = L.apply_norm(cfg, p["ln1"], x)
        y, bc = L.attention_prefill(cfg, p["attn"], h, positions, bc)
        x = x + y
        h = L.apply_norm(cfg, p["ln2"], x)
        if cfg.is_moe:
            y, _ = M.moe_forward(cfg, p["ffn"], h)
        else:
            y = L.mlp_forward(cfg, p["ffn"], h)
        x = x + y
    elif kind == "ssm":
        h = L.apply_norm(cfg, p["ln1"], x)
        y, bc = S.ssm_forward(cfg, p["ssm"], h, bc)
        x = x + y
    elif kind == "rglru":
        h = L.apply_norm(cfg, p["ln1"], x)
        y, bc = R.rglru_forward(cfg, p["rglru"], h, bc)
        x = x + y
        h = L.apply_norm(cfg, p["ln2"], x)
        x = x + L.mlp_forward(cfg, p["ffn"], h)
    x = constrain(x, "residual")
    return x, bc


def _block_decode(cfg, kind, p, x, pos, bc, attn_fn=None):
    """One block's single-token step.  ``attn_fn(p_attn, h, bc) -> (y, bc)``
    overrides the dense-cache attention (the paged serving engine's walker
    passes a page-table closure); everything else is shared."""
    p = L.compute_weights(cfg, p)
    if kind == "attn":
        h = L.apply_norm(cfg, p["ln1"], x)
        if attn_fn is None:
            y, bc = L.attention_decode(cfg, p["attn"], h, pos, bc)
        else:
            y, bc = attn_fn(p["attn"], h, bc)
        x = x + y
        h = L.apply_norm(cfg, p["ln2"], x)
        if cfg.is_moe:
            # dropless exact routing for decode (serving-correct)
            y, _ = M.moe_forward(cfg, p["ffn"], h, cap_override=h.shape[0] * h.shape[1])
        else:
            y = L.mlp_forward(cfg, p["ffn"], h)
        x = x + y
    elif kind == "ssm":
        h = L.apply_norm(cfg, p["ln1"], x)
        y, bc = S.ssm_decode(cfg, p["ssm"], h, bc)
        x = x + y
    elif kind == "rglru":
        h = L.apply_norm(cfg, p["ln1"], x)
        y, bc = R.rglru_decode(cfg, p["rglru"], h, bc)
        x = x + y
        h = L.apply_norm(cfg, p["ln2"], x)
        x = x + L.mlp_forward(cfg, p["ffn"], h)
    return x, bc


def prefill(cfg, params, batch, cache) -> Tuple[jnp.ndarray, Params]:
    """Full-sequence prefill.  Returns (last-token logits (B,V), cache)."""
    x = _embed_inputs(cfg, params, batch)
    s = x.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)

    def period_prefill(x, pp, pc):
        new_pc = {}
        for j, kind in enumerate(cfg.pattern):
            x, new_pc[str(j)] = _block_prefill(cfg, kind, pp[str(j)], x, positions, pc[str(j)])
        return x, new_pc

    body = jax.checkpoint(period_prefill) if cfg.remat else period_prefill

    if cfg.scan_layers:

        def scan_body(x, inp):
            pp, pc = inp
            x, new_pc = body(x, pp, pc)
            return x, new_pc

        x, new_stack = lax.scan(scan_body, x, (params["stack"], cache["stack"]))
    else:
        n_full, _ = stack_layout(cfg)
        outs = []
        for i in range(n_full):
            pp = jax.tree.map(lambda a: a[i], params["stack"])
            pc = jax.tree.map(lambda a: a[i], cache["stack"])
            x, new_pc = body(x, pp, pc)
            outs.append(new_pc)
        new_stack = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    new_cache: Params = {"stack": new_stack}
    _, rem_kinds = stack_layout(cfg)
    if rem_kinds:
        new_cache["rem"] = {}
        for j, kind in enumerate(rem_kinds):
            x, bc = _block_prefill(
                cfg, kind, params["rem"][str(j)], x, positions, cache["rem"][str(j)]
            )
            new_cache["rem"][str(j)] = bc
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = logits_fn(cfg, params, x[:, -1:])[:, 0]
    return logits, new_cache


def decode_step(cfg, params, token, pos, cache) -> Tuple[jnp.ndarray, Params]:
    """One decode step over the dense per-slot cache from
    :func:`init_cache`, which flows through the layer scan's ``xs`` and
    ``ys``.  token: (B,) int32; pos: scalar int32 position.  (The paged
    pool has its own walker, ``serve.engine.make_paged_decode_step``,
    which carries the pool instead.)"""
    dtype = L.dtype_of(cfg.compute_dtype)
    x = jnp.take(params["embed"], token[:, None], axis=0).astype(dtype)
    x = x * math.sqrt(cfg.d_model)

    def decode_period(x, pp, pc):
        new_pc = {}
        for j, kind in enumerate(cfg.pattern):
            x, new_pc[str(j)] = _block_decode(cfg, kind, pp[str(j)], x, pos, pc[str(j)])
        return x, new_pc

    if cfg.scan_layers:

        def scan_body(x, inp):
            pp, pc = inp
            return decode_period(x, pp, pc)

        x, new_stack = lax.scan(scan_body, x, (params["stack"], cache["stack"]))
    else:
        n_full, _ = stack_layout(cfg)
        outs = []
        for i in range(n_full):
            pp = jax.tree.map(lambda a: a[i], params["stack"])
            pc = jax.tree.map(lambda a: a[i], cache["stack"])
            x, new_pc = decode_period(x, pp, pc)
            outs.append(new_pc)
        new_stack = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    new_cache: Params = {"stack": new_stack}
    _, rem_kinds = stack_layout(cfg)
    if rem_kinds:
        new_cache["rem"] = {}
        for j, kind in enumerate(rem_kinds):
            x, bc = _block_decode(cfg, kind, params["rem"][str(j)], x, pos, cache["rem"][str(j)])
            new_cache["rem"][str(j)] = bc
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = logits_fn(cfg, params, x)[:, 0]
    return logits, new_cache
