"""Jit'd dispatch wrappers for the Pallas kernels.

The platform alone picks how a kernel runs: on a CPU backend the kernel
body executes in interpret mode (traced JAX ops, validating BlockSpec
indexing, DMAs and numerics); on a TPU backend the same call sites always
compile with Mosaic.  There is no override, so a chip run can never
quietly fall back to the interpreter.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import (
    paged_attention_pallas,
    paged_attention_scatter_pallas,
    paged_scatter_pallas,
)
from repro.kernels.rglru_scan import rglru_scan_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd import ssd_pallas


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("eps", "row_block"))
def rmsnorm(x, scale, eps: float = 1e-6, row_block: int = 256):
    return rmsnorm_pallas(x, scale, eps=eps, row_block=row_block, interpret=_interpret())


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=_interpret(),
    )


@partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, a_log, b, c, chunk: int = 128):
    return ssd_pallas(x, dt, a_log, b, c, chunk=chunk, interpret=_interpret())


@partial(jax.jit, static_argnames=("chunk", "width_block"))
def rglru_scan(a, b, h0, chunk: int = 64, width_block: int = 512):
    return rglru_scan_pallas(
        a, b, h0, chunk=chunk, width_block=width_block, interpret=_interpret()
    )


@partial(jax.jit, static_argnames=("window",))
def paged_attention(q, k_pages, v_pages, table, pos, window: int = 0):
    """q: (B,Hkv,G,D); pages: (P,page,Hkv,D); table: (B,M); pos: (B,)."""
    return paged_attention_pallas(
        q, k_pages, v_pages, table, pos, window=window, interpret=_interpret()
    )


@partial(jax.jit, static_argnames=("window",))
def paged_attention_quant(q, k_pages, v_pages, k_scale_pages, v_scale_pages,
                          table, pos, window: int = 0):
    """int8 pages + (P,page,Hkv) float32 scale pages, dequant fused in."""
    return paged_attention_pallas(
        q, k_pages, v_pages, table, pos,
        k_scale_pages=k_scale_pages, v_scale_pages=v_scale_pages,
        window=window, interpret=_interpret(),
    )


@partial(jax.jit, static_argnames=("window",))
def paged_attention_scatter(q, k_new, v_new, k_pages, v_pages, table, pos,
                            page_idx, off, layer=None, window: int = 0):
    """Fused decode step (scatter prologue + paged attention, one
    dispatch) of layer ``layer`` of stacked (L,P,page,Hkv,D) pools, or of
    one layer's pools when ``layer`` is None.  Returns ``(out, (k_pages,
    v_pages))``."""
    return paged_attention_scatter_pallas(
        q, k_new, v_new, k_pages, v_pages, table, pos, page_idx, off, layer,
        window=window, interpret=_interpret(),
    )


@partial(jax.jit, static_argnames=("window",))
def paged_attention_scatter_quant(q, k_new, v_new, k_scale_new, v_scale_new,
                                  k_pages, v_pages, k_scale_pages,
                                  v_scale_pages, table, pos, page_idx, off,
                                  layer=None, window: int = 0):
    """Fused decode step over int8 pages; the prologue also lands the new
    row's scales, the walk dequants in-flight.  Pools and ``layer`` as
    :func:`paged_attention_scatter`.  Returns
    ``(out, (k_pages, v_pages, k_scale_pages, v_scale_pages))``."""
    return paged_attention_scatter_pallas(
        q, k_new, v_new, k_pages, v_pages, table, pos, page_idx, off, layer,
        k_scale_new=k_scale_new, v_scale_new=v_scale_new,
        k_scale_pages=k_scale_pages, v_scale_pages=v_scale_pages,
        window=window, interpret=_interpret(),
    )


@jax.jit
def paged_scatter(k_pages, v_pages, k_new, v_new, page_idx, off):
    """In-place (aliased) scatter of each slot's new K/V row into its page."""
    return paged_scatter_pallas(
        (k_pages, v_pages), (k_new, v_new), page_idx, off,
        interpret=_interpret(),
    )


@jax.jit
def paged_scatter_quant(k_pages, v_pages, k_scale_pages, v_scale_pages,
                        k_new, v_new, k_scale_new, v_scale_new, page_idx, off):
    """One grid pass updates the int8 K/V pages and both scale pools."""
    return paged_scatter_pallas(
        (k_pages, v_pages, k_scale_pages, v_scale_pages),
        (k_new, v_new, k_scale_new, v_scale_new),
        page_idx, off, interpret=_interpret(),
    )
