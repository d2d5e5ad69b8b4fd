"""Paged-decode attention Pallas kernel + fused K/V scatter epilogue.

The serving hot path (vLLM-style): each decode step attends one new
query token per slot against that slot's K/V pages, addressed through a
per-request page table.  The XLA reference in ``serve/kvcache.py``
materializes a contiguous ``(B, M*page, Hkv, D)`` gather every step;
this kernel never does — the page table is a *scalar-prefetch* operand,
so the kernel body reads ``table[b, j]`` itself and pulls exactly the
physical pages it names out of the HBM-resident pool.

TPU mapping: grid = (batch,) — one program per slot, every KV head at
once.  The K/V pools are ``memory_space=ANY`` operands (they stay in
HBM): the program walks its table row with a ``fori_loop`` and, per
iteration, DMAs two whole pages ``(page, Hkv, D)`` of each pool
(``pltpu.make_async_copy``) into one half of a double-buffered VMEM
scratch while it attends the pair already on-chip.  Mosaic only loads
from VMEM/SMEM refs, so the ANY-space pools are never indexed directly.
A page tile is read as a flat ``(page*Hkv, D)`` matrix (row
``t*Hkv + h`` is token ``t`` of head ``h``) and all ``Hkv*G`` query
rows score it in one ``(Hkv*G, 2*page*Hkv)`` MXU matmul; each query row
keeps only its own head's columns, masked exactly like future
positions.  That trades a few idle MXU columns for no per-head strided
loads.  Each block is folded into an online-softmax carry (m, l, acc)
exactly like ``flash_attention.py`` folds k-blocks.  Under
``kv_quant`` the pages are int8 with per-(token, head) float32 scales;
a ``(P, page, Hkv)`` scale pool has too narrow a minor dim to slice, so
the kernel sees it as ``(P, 1, page*Hkv)`` — one lane-dense row per
page, laid out like the flat page tile's rows, the form in which the
serving pool stores its scale pages, so the view costs no relayout —
and applies the K scales to the score columns and the V scales to the
probabilities (dequant fused into the tile already on-chip, no
dequantized cache).

Causal masking needs no query position: decode queries sit at position
``pos[b]`` and every stored key at ``j*page + offset`` is valid iff it
is ``<= pos[b]`` (sliding window additionally requires
``> pos[b] - window``).  Pages past the live prefix belong to other
requests or the scratch page — their positions exceed ``pos[b]``, so
the same mask that implements causality also implements isolation.

The scatter (``paged_scatter_pallas``) is the write half of the step:
the new token's K/V row (and scale rows) land at
``pages[table[b, pos // page], pos % page]`` via ``input_output_aliases``
— an in-place block write into the existing page arrays, bit-identical
to the ``.at[page_idx, off].set()`` path (tier-1 asserted) without XLA's
copy-on-donate round trip.  The serving engine uses the *fused* form
(``paged_attention_scatter_pallas``): the scatter is a prologue of the
attention program itself.  Program b DMAs slot b's new ``(Hkv, D)`` K/V
rows (blocked ``(1, Hkv, D)``, which meets the (8, 128) tiling rule)
into their page, read-modify-writes the page's flat scale row under
``kv_quant``, waits for those writes, and only then starts its walk,
which reads only slot b's pages — so the in-place store can never race
another program's page reads, and the whole read-modify-attend step is
one dispatch.  (Idle slots are parked on the scratch page, which every
program's mask excludes, so even a torn scratch write is unobservable.)

The fused form takes the pools of every scanned layer at once, stacked
``(L, P, page, Hkv, D)``, and the layer as a fifth scalar-prefetch
operand: the prologue writes ``pool[layer, page, off]`` and the walk
reads ``pool[layer, pid]``, so the decode step's layer scan hands the
kernel the whole stacked pool from its carry and no layer is ever sliced
out of it or written back (the stacked arrays are the aliased operands).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flat_scales(s_pages):
    """([L,] P, page, Hkv) scale pool -> ([L,] P, 1, page*Hkv): one
    lane-dense row per page, the only shape in which a narrow-minor float32
    page can be DMA'd (Mosaic slices the lane dim in whole 128-lane tiles)."""
    *lead, page, hkv = s_pages.shape
    return s_pages.reshape(*lead, 1, page * hkv)


def _page_walk(tbl_ref, pos_ref, b, q, pools, bufs, sems, lead=(),
               *, scale: float, window: int, page: int, n_pages: int,
               hkv: int, g: int):
    """Online-softmax walk over slot ``b``'s table row, two pages per
    iteration, every KV head at once.

    ``pools`` are the ANY-space (HBM) page pools: ``(k, v)`` or ``(k, v,
    k_scale, v_scale)``; ``lead`` indexes them down to one layer's pages
    (``(layer,)`` for stacked pools).  Each iteration DMAs two whole
    pages (all heads) of each pool into one half of a double-buffered
    VMEM scratch while the other half is attended, so the next pair is in
    flight during the current pair's matmuls.  A page tile ``(page, Hkv,
    D)`` is read as a flat ``(page*Hkv, D)`` matrix: row ``t*Hkv + h`` is
    token ``t`` of head ``h``.  The score tile is one ``(Hkv*G, 2*page*Hkv)`` MXU matmul
    in which each query row keeps only its own head's columns (the rest
    are masked like future positions), so no per-head strided load is
    needed.  For odd ``n_pages`` the trailing phantom page re-loads the
    last table entry; its key positions ``>= n_pages * page`` exceed every
    legal ``pos``, so the causal mask zeroes it.  Returns the normalized
    ``(Hkv*G, D)`` float32 attention output."""
    quant = len(pools) == 4
    d = q.shape[-1]
    rows, cols = hkv * g, 2 * page * hkv
    n_iter = (n_pages + 1) // 2
    p0 = pos_ref[b]

    def copies(jj, slot):
        j0 = 2 * jj
        pids = (tbl_ref[b, j0], tbl_ref[b, jnp.minimum(j0 + 1, n_pages - 1)])
        return [pltpu.make_async_copy(pool.at[(*lead, pid)], buf.at[slot, i],
                                      sems.at[slot])
                for i, pid in enumerate(pids) for pool, buf in zip(pools, bufs)]

    col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    own_head = (col % hkv) == (row // g)
    tok = col // hkv                                   # token within the pair

    def body(jj, carry):
        m_prev, l_prev, acc = carry
        slot = jj % 2

        @pl.when(jj + 1 < n_iter)
        def _prefetch():
            for c in copies(jj + 1, 1 - slot):
                c.start()

        for c in copies(jj, slot):
            c.wait()
        k = bufs[0].at[slot].reshape(cols, d)[...].astype(jnp.float32)
        v = bufs[1].at[slot].reshape(cols, d)[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if quant:                                      # dequant on the scores
            ks = jnp.concatenate([bufs[2][slot, 0], bufs[2][slot, 1]], axis=1)
            s = s * ks                                 # (rows, cols) * (1, cols)
        s = s * scale
        k_pos = 2 * jj * page + tok
        valid = own_head & (k_pos <= p0)
        if window:
            valid &= k_pos > p0 - window
        s = jnp.where(valid, s, NEG_INF)

        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                         # (rows, cols)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        if quant:                                      # ... and on the probs
            vs = jnp.concatenate([bufs[3][slot, 0], bufs[3][slot, 1]], axis=1)
            p = p * vs
        acc = acc * corr + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    for c in copies(0, 0):
        c.start()
    init = (jnp.full((rows, 1), NEG_INF, jnp.float32),  # m (running max)
            jnp.zeros((rows, 1), jnp.float32),          # l (running denom)
            jnp.zeros((rows, d), jnp.float32))          # acc (weighted values)
    _, l_f, acc = jax.lax.fori_loop(0, n_iter, body, init)
    return acc / jnp.maximum(l_f, 1e-20)


def _walk_scratch(pools, n_lead: int = 1):
    """Double-buffered VMEM page tiles (2 slots x 2 pages) for each pool
    (whose first ``n_lead`` axes index a page), plus DMA semaphores: one
    per walk slot, then one each for the prologue's row writes and its
    scale-row read-modify-write."""
    bufs = [pltpu.VMEM((2, 2) + a.shape[n_lead:], a.dtype) for a in pools]
    return bufs + [pltpu.SemaphoreType.DMA((4,))]


def _paged_kernel(tbl_ref, pos_ref, q_ref, *rest, n_pools: int, **kw):
    pools = rest[:n_pools]
    o_ref = rest[n_pools]
    bufs = rest[n_pools + 1:2 * n_pools + 1]
    sems = rest[2 * n_pools + 1]
    b = pl.program_id(0)
    q = q_ref[0].astype(jnp.float32)                   # (Hkv*G, D)
    out = _page_walk(tbl_ref, pos_ref, b, q, pools, bufs, sems, **kw)
    o_ref[0] = out.astype(o_ref.dtype)


def paged_attention_pallas(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    table: jnp.ndarray,
    pos: jnp.ndarray,
    *,
    k_scale_pages: Optional[jnp.ndarray] = None,
    v_scale_pages: Optional[jnp.ndarray] = None,
    window: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-token decode attention over paged KV.

    q: (B, Hkv, G, D) post-RoPE queries (GQA groups under their kv head);
    k_pages/v_pages: (P, page, Hkv, D) physical pool (int8 when quantized);
    k/v_scale_pages: (P, page, Hkv) float32 dequant scales (or None);
    table: (B, M) int32 page table; pos: (B,) int32 query positions.
    Returns (B, Hkv, G, D) in q.dtype.
    """
    bsz, hkv, g, d = q.shape
    page = k_pages.shape[1]
    m = table.shape[1]
    pools = [k_pages, v_pages]
    if k_scale_pages is not None:
        pools += [_flat_scales(k_scale_pages), _flat_scales(v_scale_pages)]

    # table/pos are scalar-prefetch operands: available before the body
    # runs, so the walk can chase ``table[b, j]`` page indices.  The pools
    # are ANY-space refs — never block-mapped; only the pages the walk
    # names are DMA'd into VMEM.
    q_spec = pl.BlockSpec((1, hkv * g, d), lambda b, *_: (b, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz,),
        in_specs=[q_spec] + [any_spec] * len(pools),
        out_specs=q_spec,
        scratch_shapes=_walk_scratch(pools),
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, n_pools=len(pools), scale=1.0 / math.sqrt(d),
            window=window, page=page, n_pages=m, hkv=hkv, g=g,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, hkv * g, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(table, pos, q.reshape(bsz, hkv * g, d), *pools)
    return out.reshape(bsz, hkv, g, d)


def _paged_step_kernel(tbl_ref, pos_ref, pidx_ref, offw_ref, layer_ref, q_ref,
                       *rest, n_pools: int, **kw):
    # rest = (*new_rows, *pool_inputs, o, *pool_outputs, *bufs, sems); the
    # pool outputs alias the pool inputs, so the body only ever touches
    # the output refs
    news = rest[:n_pools]
    o_ref = rest[2 * n_pools]
    pools = rest[2 * n_pools + 1:3 * n_pools + 1]
    bufs = rest[3 * n_pools + 1:4 * n_pools + 1]
    sems = rest[4 * n_pools + 1]
    b = pl.program_id(0)
    lyr = layer_ref[0]
    pw = pidx_ref[b]
    ow = offw_ref[b]
    page, hkv = kw["page"], kw["hkv"]

    # scatter prologue: land the new token's row (every head) in its page,
    # in place (aliased), *before* the walk, so the walk attends to it.
    # Program b writes only slot b's row and reads only slot b's pages —
    # no cross-program hazard.  K/V rows are one (Hkv, D) tile each; a
    # scale row is a lane slice of its page's flat row, so it goes in by
    # read-modify-write of that row through the walk buffer.
    rows = [pltpu.make_async_copy(news[i].at[0], pools[i].at[lyr, pw, ow], sems.at[2])
            for i in range(2)]
    for c in rows:
        c.start()
    if n_pools == 4:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, page * hkv), 1)
        mine = lane // hkv == ow
        for i in (2, 3):
            tile = bufs[i].at[0, 0]
            c = pltpu.make_async_copy(pools[i].at[lyr, pw], tile, sems.at[3])
            c.start()
            c.wait()
            tile[...] = jnp.where(mine, news[i][0], tile[...])
            c = pltpu.make_async_copy(tile, pools[i].at[lyr, pw], sems.at[3])
            c.start()
            c.wait()
    for c in rows:
        c.wait()

    q = q_ref[0].astype(jnp.float32)                   # (Hkv*G, D)
    out = _page_walk(tbl_ref, pos_ref, b, q, pools, bufs, sems, (lyr,), **kw)
    o_ref[0] = out.astype(o_ref.dtype)


def paged_attention_scatter_pallas(
    q: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    table: jnp.ndarray,
    pos: jnp.ndarray,
    page_idx: jnp.ndarray,
    off: jnp.ndarray,
    layer: Optional[jnp.ndarray] = None,
    *,
    k_scale_new: Optional[jnp.ndarray] = None,
    v_scale_new: Optional[jnp.ndarray] = None,
    k_scale_pages: Optional[jnp.ndarray] = None,
    v_scale_pages: Optional[jnp.ndarray] = None,
    window: int = 0,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, ...]]:
    """Fused decode step of one layer: scatter the new K/V row, then
    attend — one dispatch.  Bit-identical to ``paged_scatter_pallas``
    followed by ``paged_attention_pallas`` on that layer's pages, with
    every other layer's pages untouched (tier-1 asserted).

    k_pages/v_pages: (L, P, page, Hkv, D) the pools of every scanned layer
    (int8 when quantized), k/v_scale_pages: their float32 scales, (L, P,
    page, Hkv) or the serving pool's flat rows (L, P, 1, page*Hkv), which
    the kernel reads with no relayout, and ``layer`` the int32 scalar
    index of the layer this step attends; with ``layer=None`` the pools
    are one layer's, without the L axis, given a unit layer axis by
    reshape.
    k_new/v_new: (B, Hkv, D) the new token's K/V rows (pool dtype —
    already quantized when the pool is int8); k/v_scale_new: (B, Hkv)
    their dequant scales; page_idx/off: (B,) int32 write destinations
    (idle slots point at the scratch page).  Other shapes as
    :func:`paged_attention_pallas`.  Returns ``(out, updated_pools)``,
    the pools in the shapes given.  The pools are aliased to the
    updated ones: under a caller's ``jit`` that donates them (the serving
    engine's decode step), the row lands in place and the caller's input
    pools are invalidated.
    """
    bsz, hkv, g, d = q.shape
    quant = k_scale_pages is not None
    one_layer = layer is None
    if one_layer:                              # a unit layer axis, by reshape
        layer = 0
        k_pages, v_pages = k_pages[None], v_pages[None]
        if quant:
            k_scale_pages, v_scale_pages = k_scale_pages[None], v_scale_pages[None]
    page = k_pages.shape[2]
    m = table.shape[1]

    q_spec = pl.BlockSpec((1, hkv * g, d), lambda b, *_: (b, 0, 0))
    row_spec = pl.BlockSpec((1, hkv, d), lambda b, *_: (b, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    news = [k_new, v_new]
    pools = [k_pages, v_pages]
    new_specs = [row_spec, row_spec]
    if quant:
        # a scale row travels as the whole flat page row it lands in,
        # repeated per token; the prologue keeps only lanes of token ``off``
        srow_spec = pl.BlockSpec((1, 1, page * hkv), lambda b, *_: (b, 0, 0))
        news += [jnp.tile(s, (1, page))[:, None] for s in (k_scale_new, v_scale_new)]
        pools += [_flat_scales(k_scale_pages), _flat_scales(v_scale_pages)]
        new_specs += [srow_spec, srow_spec]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,                 # table, pos, page_idx, off, layer
        grid=(bsz,),
        in_specs=[q_spec] + new_specs + [any_spec] * len(pools),
        out_specs=[q_spec] + [any_spec] * len(pools),
        scratch_shapes=_walk_scratch(pools, n_lead=2),
    )
    base = 5 + 1 + len(news)                   # scalar-prefetch + q + rows
    out = pl.pallas_call(
        functools.partial(_paged_step_kernel, n_pools=len(pools),
                          scale=1.0 / math.sqrt(d), window=window, page=page,
                          n_pages=m, hkv=hkv, g=g),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bsz, hkv * g, d), q.dtype)]
                  + [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in pools],
        input_output_aliases={base + i: 1 + i for i in range(len(pools))},
        interpret=interpret,
        name="paged_attention_scatter",
    )(table, pos, page_idx, off, jnp.reshape(layer, (1,)).astype(jnp.int32),
      q.reshape(bsz, hkv * g, d), *news, *pools)
    new_pools = list(out[1:])
    if quant:
        new_pools[2:] = [a.reshape(s.shape) for a, s in
                         zip(new_pools[2:], (k_scale_pages, v_scale_pages))]
    if one_layer:
        new_pools = [a.reshape(a.shape[1:]) for a in new_pools]
    return out[0].reshape(bsz, hkv, g, d), tuple(new_pools)


def _scatter_kernel(pi_ref, off_ref, *refs, n_arrays: int):
    # refs = (*page_inputs, *new_rows, *page_outputs); the page outputs
    # alias the page inputs, so the only work is one row store per array
    news = refs[n_arrays:2 * n_arrays]
    outs = refs[2 * n_arrays:]
    for new_ref, o_ref in zip(news, outs):
        o_ref[0, 0] = new_ref[0]


def paged_scatter_pallas(
    pages: Sequence[jnp.ndarray],
    new_rows: Sequence[jnp.ndarray],
    page_idx: jnp.ndarray,
    off: jnp.ndarray,
    *,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, ...]:
    """Scatter each slot's new token row into its page, in place.

    pages[i]: (P, page, ...) pool array; new_rows[i]: (B, ...) the new
    token's row per slot; page_idx/off: (B,) int32 destinations.  All
    arrays share one grid pass (one call updates k, v and both scale
    pools).  Idle slots target (SCRATCH_PAGE, 0); the grid is sequential
    so coinciding writes resolve last-wins, and scratch is never read.
    """
    n = len(pages)
    bsz = new_rows[0].shape[0]

    def page_spec(a):
        blk = (1, 1) + a.shape[2:]
        zeros = (0,) * (a.ndim - 2)
        return pl.BlockSpec(blk, lambda b, pi, of, z=zeros: (pi[b], of[b]) + z)

    def row_spec(a):
        blk = (1,) + a.shape[1:]
        zeros = (0,) * (a.ndim - 1)
        return pl.BlockSpec(blk, lambda b, pi, of, z=zeros: (b,) + z)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz,),
        in_specs=[page_spec(a) for a in pages] + [row_spec(a) for a in new_rows],
        out_specs=[page_spec(a) for a in pages],
    )
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, n_arrays=n),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in pages],
        # operand indices count the 2 scalar-prefetch refs
        input_output_aliases={2 + i: i for i in range(n)},
        interpret=interpret,
        name="paged_attention_row_scatter",
    )(page_idx, off, *pages, *new_rows)
    return tuple(out)
