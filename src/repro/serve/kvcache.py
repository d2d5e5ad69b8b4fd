"""Block-paged KV cache pool for continuous-batching serving.

The serving analogue of the paper's slack isolation needs decode batches
that stay full, and decode batches stay full only if KV memory is handed
out in small reclaimable units instead of one max-length strip per slot.
This module provides exactly that:

* :class:`PagedKVPool` — one physical pool of fixed-size pages per
  attention layer (``k_pages``/``v_pages``: ``(n_pages, page, Hkv, D)``,
  int8 + per-(token, head) scale pages when ``cfg.kv_quant``, reusing the
  ``_kv_quantize`` path from :mod:`repro.models.layers`; a scale page is
  stored as one flat row ``(1, page*Hkv)``, entry ``t*Hkv + h`` for token
  ``t`` of head ``h``, the form the paged kernel reads, so no step
  relays the scale pool out), a host-side
  free-list allocator with *reservations* (admission control books the
  worst-case page need up front, physical pages are allocated lazily, so
  a lazily-grown request can never hit an empty free list), and
  per-request page tables.  Page id 0 is the scratch page: idle decode
  slots write into it and nothing ever reads it.
* ``paged_attention_decode`` — single-token decode attention over the
  pool: scatter the new K/V into ``table[b, pos // page]``, gather the
  request's pages back into a ``(B, T, Hkv, D)`` view (the gather *is*
  the KV read every decode step pays anyway), and run the same
  fp32-accumulation attention as ``layers.attention_decode`` with a
  per-request validity mask — so a single request matches the dense-cache
  engine token for token.

Recurrent state (SSM / RG-LRU blocks) is O(1) per request and is *not*
paged: the pool keeps a per-slot state tree next to the page arrays.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models import rglru as R
from repro.models import ssm as S
from repro.models.layers import NEG_INF, _kv_dequantize, _kv_quantize, _project_qkv
from repro.models.transformer import stack_layout

Params = Dict[str, Any]

SCRATCH_PAGE = 0          # page id reserved for idle slots; never read


def rope_at(x: jnp.ndarray, pos: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Per-request RoPE for single-token decode.  x: (B,1,H,D); pos: (B,)."""
    d = x.shape[-1]
    freqs = L.rope_frequencies(d, theta)                       # (D/2,)
    angles = pos[:, None].astype(jnp.float32) * freqs          # (B, D/2)
    cos = jnp.cos(angles)[:, None, None, :]
    sin = jnp.sin(angles)[:, None, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# device-side pool construction (mirrors transformer.init_cache structure)
# --------------------------------------------------------------------------

def _attn_page_block(cfg, num_pages: int, page: int, dtype) -> Params:
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    kv_dtype = jnp.int8 if cfg.kv_quant else dtype
    block = {
        "k_pages": jnp.zeros((num_pages, page, hkv, hd), kv_dtype),
        "v_pages": jnp.zeros((num_pages, page, hkv, hd), kv_dtype),
    }
    if cfg.kv_quant:
        block["k_scale_pages"] = jnp.zeros((num_pages, 1, page * hkv), jnp.float32)
        block["v_scale_pages"] = jnp.zeros((num_pages, 1, page * hkv), jnp.float32)
    return block


def _block_pool(cfg, kind: str, num_pages: int, page: int, n_slots: int, dtype) -> Params:
    if kind == "attn":
        return _attn_page_block(cfg, num_pages, page, dtype)
    if kind == "ssm":
        return S.init_ssm_state(cfg, n_slots, dtype)
    if kind == "rglru":
        return R.init_rglru_state(cfg, n_slots, dtype)
    raise ValueError(kind)


def init_pool_blocks(cfg, num_pages: int, page: int, n_slots: int) -> Params:
    """Device tree mirroring ``init_cache``: {"stack": {j: block}, "rem": ...}.

    Attention blocks hold shared page arrays; SSM/RG-LRU blocks hold
    per-slot recurrent state.  Stacked entries carry the scan layer axis.
    """
    dtype = L.dtype_of(cfg.compute_dtype)
    n_full, rem_kinds = stack_layout(cfg)
    proto = {
        str(j): _block_pool(cfg, kind, num_pages, page, n_slots, dtype)
        for j, kind in enumerate(cfg.pattern)
    }
    stack = jax.tree.map(lambda a: jnp.tile(a[None], (n_full,) + (1,) * a.ndim), proto)
    blocks: Params = {"stack": stack}
    if rem_kinds:
        blocks["rem"] = {
            str(j): _block_pool(cfg, kind, num_pages, page, n_slots, dtype)
            for j, kind in enumerate(rem_kinds)
        }
    return blocks


# --------------------------------------------------------------------------
# paged decode attention
# --------------------------------------------------------------------------

def paged_attention_decode(cfg, p, x, pos, table, block, kernel: str = "xla",
                           layer=None):
    """Single-token attention over paged KV.

    x: (B,1,d); pos: (B,) int32 write positions (the new token's absolute
    position per request); table: (B, M) int32 page table (0 = scratch);
    block: one attention page block, or with ``layer`` (an int32 scalar)
    the stacked block of every scanned layer, whose leaves lead with the
    layer axis, of which layer ``layer`` is read and written and every
    other left as it is.  Returns (out (B,1,d), new block).

    ``kernel`` selects the hot path: ``"xla"`` scatters with
    ``.at[].set()`` and gathers a contiguous ``(B, M*page, Hkv, D)`` view
    (the reference oracle — callers bound its cost by passing a table
    clamped to the live pages); ``"pallas"`` routes through
    :mod:`repro.kernels.ops` — one fused dispatch whose prologue lands
    the new K/V row in its page (aliased, in place) and whose body walks
    the page table block-by-block, with int8 dequant fused into the page
    loads.  Both paths quantize the new token's K/V in XLA first, so the
    *stored* pages are bit-identical.
    """
    b = x.shape[0]
    page = block["k_pages"].shape[-3]
    m = table.shape[1]
    lead = () if layer is None else (layer,)
    q, k, v = _project_qkv(cfg, p, x)                          # (B,1,H*,D)
    q = rope_at(q, pos, cfg.rope_theta)
    k = rope_at(k, pos, cfg.rope_theta)

    page_idx = table[jnp.arange(b), jnp.minimum(pos // page, m - 1)]  # (B,)
    off = pos % page
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    window = cfg.window if cfg.attention in ("swa", "local") and cfg.window else 0
    new_block = dict(block)
    if cfg.kv_quant:
        kq, k_sc = _kv_quantize(k)                             # (B,1,H,D),(B,1,H)
        vq, v_sc = _kv_quantize(v)
        k, v = kq, vq

    if kernel == "pallas":
        from repro.kernels import ops as pallas_ops

        qg = L._gqa_reshape(q, hkv)[:, 0]                      # (B,Hkv,G,D)
        if cfg.kv_quant:
            out, (kp, vp, ksp, vsp) = pallas_ops.paged_attention_scatter_quant(
                qg, k[:, 0], v[:, 0], k_sc[:, 0], v_sc[:, 0],
                block["k_pages"], block["v_pages"],
                block["k_scale_pages"], block["v_scale_pages"],
                table, pos, page_idx, off, layer, window=window,
            )
            new_block.update(k_pages=kp, v_pages=vp,
                             k_scale_pages=ksp, v_scale_pages=vsp)
        else:
            out, (kp, vp) = pallas_ops.paged_attention_scatter(
                qg, k[:, 0], v[:, 0], block["k_pages"], block["v_pages"],
                table, pos, page_idx, off, layer, window=window,
            )
            new_block.update(k_pages=kp, v_pages=vp)
        out = out.astype(x.dtype).reshape(b, 1, cfg.n_heads * hd) @ p["wo"]
        return out, new_block
    if kernel != "xla":
        raise ValueError(f"unknown attention kernel {kernel!r}")

    # one scatter and one gather per leaf, each indexed by the layer too:
    # no layer of a stacked pool is sliced out or written back whole
    if cfg.kv_quant:
        lanes = off[:, None] * hkv + jnp.arange(hkv)           # (B, Hkv) in the flat row
        at = lead + (page_idx[:, None], 0, lanes)
        new_block["k_scale_pages"] = block["k_scale_pages"].at[at].set(k_sc[:, 0])
        new_block["v_scale_pages"] = block["v_scale_pages"].at[at].set(v_sc[:, 0])
    new_block["k_pages"] = block["k_pages"].at[lead + (page_idx, off)].set(k[:, 0])
    new_block["v_pages"] = block["v_pages"].at[lead + (page_idx, off)].set(v[:, 0])

    # gather this batch's logical KV views: (B, M, page, H, D) -> (B, T, H, D)
    t = m * page
    ck = new_block["k_pages"][lead + (table,)].reshape(b, t, hkv, hd)
    cv = new_block["v_pages"][lead + (table,)].reshape(b, t, hkv, hd)
    if cfg.kv_quant:
        k_sc = new_block["k_scale_pages"][lead + (table,)].reshape(b, t, hkv)
        v_sc = new_block["v_scale_pages"][lead + (table,)].reshape(b, t, hkv)
        ck = _kv_dequantize(ck, k_sc, x.dtype)
        cv = _kv_dequantize(cv, v_sc, x.dtype)

    qg = L._gqa_reshape(q, hkv)                                # (B,1,Hkv,G,D)
    s = jnp.einsum("bqkgd,btkd->bqkgt", qg, ck, preferred_element_type=jnp.float32)
    s *= 1.0 / math.sqrt(hd)
    k_pos = jnp.arange(t, dtype=jnp.int32)
    valid = k_pos[None, :] <= pos[:, None]                     # (B, T)
    if window:
        valid &= k_pos[None, :] > pos[:, None] - window
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bqkgt,btkd->bqkgd", prob.astype(cv.dtype), cv,
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    out = out.reshape(b, 1, cfg.n_heads * cfg.head_dim) @ p["wo"]
    return out, new_block


def make_clone_pages(cfg) -> "Any":
    """clone(blocks, src (int32), dst (int32)) -> blocks with page dst a copy
    of page src in every attention leaf.

    The copy-on-write primitive for prefix sharing: a joiner extending a
    *partially filled* cached page writes into fresh storage while every
    other referent keeps reading the original.  Recurrent-state blocks are
    per-slot, not paged, so only attention leaves participate (the prefix
    cache is gated to attention-only archs anyway).  Stacked leaves copy
    the page in every layer at once.  ``ContinuousEngine`` donates
    ``blocks``: one page is written in place and the caller's input pool
    is invalidated.
    """

    page_leaves = ("k_pages", "v_pages", "k_scale_pages", "v_scale_pages")

    def clone_block(block, src, dst, *, stacked: bool):
        new = dict(block)
        for name in page_leaves:
            if name not in block:
                continue
            leaf = block[name]
            if stacked:
                new[name] = leaf.at[:, dst].set(leaf[:, src])
            else:
                new[name] = leaf.at[dst].set(leaf[src])
        return new

    def clone(blocks, src, dst):
        new_stack = {}
        for j, kind in enumerate(cfg.pattern):
            b = blocks["stack"][str(j)]
            new_stack[str(j)] = (
                clone_block(b, src, dst, stacked=True) if kind == "attn" else b
            )
        out: Params = {"stack": new_stack}
        if "rem" in blocks:
            _, rem_kinds = stack_layout(cfg)
            out["rem"] = {}
            for j, kind in enumerate(rem_kinds):
                b = blocks["rem"][str(j)]
                out["rem"][str(j)] = (
                    clone_block(b, src, dst, stacked=False) if kind == "attn" else b
                )
        return out

    return clone


def scatter_prefill_attn(block, cache_block, page_ids, *, stacked: bool):
    """Scatter a contiguous prefill cache into pool pages.

    cache_block leaves come from ``transformer.prefill`` with batch 1 and
    a linear layout of ``n_used * page`` positions; ``page_ids``:
    (n_used,) int32 physical destinations.  ``stacked`` marks entries
    under the scan layer axis (leaves lead with n_full).  Each prefill
    leaf is cut into pages of the pool leaf's own page shape (a scale
    page's flat row included), and each page lands in every layer by one
    ``dynamic_update_slice``: in place in a donated pool on every backend,
    where a scatter along the axis after the layer axis makes XLA lay the
    whole pool out again on some (CPU; the scale rows on a TPU).
    """
    n_used = page_ids.shape[0]
    axis = 1 if stacked else 0
    pairs = [("k", "k_pages"), ("v", "v_pages")]
    if "k_scale_pages" in block:
        pairs += [("k_scale", "k_scale_pages"), ("v_scale", "v_scale_pages")]
    chunks = {}
    for name, pname in pairs:
        pool = block[pname]
        leaf = cache_block[name][:, 0] if stacked else cache_block[name][0]
        shape = pool.shape[:axis] + (n_used,) + pool.shape[axis + 1:]
        chunks[pname] = leaf.reshape(shape).astype(pool.dtype)

    def put(i, pools):
        return {k: lax.dynamic_update_slice_in_dim(
                    p, lax.dynamic_slice_in_dim(chunks[k], i, 1, axis), page_ids[i], axis)
                for k, p in pools.items()}

    new = dict(block)
    new.update(lax.fori_loop(0, n_used, put, {k: block[k] for k in chunks}))
    return new


# --------------------------------------------------------------------------
# host-side pool accounting
# --------------------------------------------------------------------------

class PagedKVPool:
    """Fixed-size page pool: refcounted free-list + admission reservations.

    ``reserve`` is the admission-control primitive: it books a request's
    *worst-case* page need against the pool; ``alloc`` then hands out
    physical pages lazily (prefill pages at join, one page per crossed
    boundary during decode).  Because allocations never exceed the sum of
    reservations, lazy growth can never fail after admission succeeded.
    ``release`` drops one reference per attached page on completion
    (evict-on-EOS); a page returns to the free list only when its last
    referent lets go.

    Prefix sharing adds two reference paths on top of ``alloc``'s owning
    reference: :meth:`share` attaches an *existing* page to another
    request (refcount +1, no free-list traffic), and :meth:`retain` /
    :meth:`unretain` let a resident :class:`~repro.serve.fleet.prefix.
    PrefixCache` keep pages alive after their writer finished.  When a
    reservation cannot be met, the optional ``on_pressure`` hook (the
    cache's LRU evictor) is asked to surrender resident pages before
    admission fails.

    ``materialize=False`` skips building the device arrays — the fleet
    simulator runs thousands of admission/join/evict decisions through the
    *real* accounting (this class, the scheduler, the prefix cache)
    without paying for KV storage it never reads.
    """

    def __init__(self, cfg, n_slots: int, max_len: int, page: int = 16,
                 num_pages: Optional[int] = None, materialize: bool = True):
        if max_len % page:
            raise ValueError(f"max_len {max_len} must be a multiple of page {page}")
        self.cfg = cfg
        self.page = page
        self.n_slots = n_slots
        self.max_len = max_len
        self.max_pages_per_req = max_len // page
        # +1 for the scratch page idle slots write into
        self.num_pages = num_pages or n_slots * self.max_pages_per_req + 1
        if self.num_pages < 2:
            raise ValueError("pool needs at least one non-scratch page")
        self._free: List[int] = list(range(self.num_pages - 1, SCRATCH_PAGE, -1))
        self._reserved: Dict[Any, int] = {}    # rid -> pages still reservable
        self._allocated: Dict[Any, List[int]] = {}
        self._ref: Dict[int, int] = {}         # page id -> reference count
        # asked to free >= n resident pages; returns how many it freed
        self.on_pressure: Optional[Any] = None
        self.blocks = (
            init_pool_blocks(cfg, self.num_pages, page, n_slots)
            if materialize else None
        )

    # ---- accounting ------------------------------------------------------
    def pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page)

    @property
    def capacity_pages(self) -> int:
        return self.num_pages - 1

    @property
    def free_pages(self) -> int:
        """Pages available to NEW reservations (free minus outstanding IOUs)."""
        outstanding = sum(self._reserved.values())
        return len(self._free) - outstanding

    @property
    def utilization(self) -> float:
        in_use = self.capacity_pages - len(self._free)
        return in_use / max(self.capacity_pages, 1)

    def refcount(self, page_id: int) -> int:
        return self._ref.get(page_id, 0)

    def can_admit(self, n_tokens: int) -> bool:
        return self.pages_needed(n_tokens) <= self.free_pages

    def reserve(self, rid, n_tokens: int) -> bool:
        return self.reserve_pages(rid, self.pages_needed(n_tokens))

    def reserve_pages(self, rid, need: int) -> bool:
        """Book ``need`` physical pages for ``rid`` (prefix-aware admission
        reserves only the *unshared* remainder).  Under pressure the
        resident-prefix evictor is asked to free pages before giving up."""
        if need > self.capacity_pages:
            raise ValueError(
                f"request {rid!r} needs {need} pages, pool holds {self.capacity_pages}"
            )
        if need > self.free_pages and self.on_pressure is not None:
            self.on_pressure(need - self.free_pages)
        if need > self.free_pages:
            return False
        self._reserved[rid] = need
        self._allocated[rid] = []
        return True

    def alloc(self, rid, n: int = 1) -> List[int]:
        if self._reserved.get(rid, 0) < n:
            raise RuntimeError(f"request {rid!r} exceeded its page reservation")
        ids = [self._free.pop() for _ in range(n)]
        self._reserved[rid] -= n
        self._allocated[rid].extend(ids)
        for pid in ids:
            self._ref[pid] = 1
        return ids

    def share(self, rid, page_ids: List[int]) -> None:
        """Attach already-allocated pages to ``rid`` (prefix reuse): one
        reference each, released with the rest of ``rid``'s pages."""
        if rid not in self._allocated:
            raise RuntimeError(f"request {rid!r} has no reservation to share into")
        for pid in page_ids:
            if self._ref.get(pid, 0) <= 0:
                raise RuntimeError(f"page {pid} is not live; cannot share")
            self._ref[pid] += 1
        self._allocated[rid].extend(page_ids)

    def retain(self, page_ids: List[int]) -> None:
        """Anonymous reference (prefix-cache residency): keeps pages out of
        the free list after their writer releases."""
        for pid in page_ids:
            if self._ref.get(pid, 0) <= 0:
                raise RuntimeError(f"page {pid} is not live; cannot retain")
            self._ref[pid] += 1

    def unretain(self, page_ids: List[int]) -> None:
        for pid in page_ids:
            self._drop_ref(pid)

    def _drop_ref(self, pid: int) -> None:
        n = self._ref.get(pid, 0)
        if n <= 0:
            raise RuntimeError(f"double free of page {pid}")
        if n == 1:
            del self._ref[pid]
            self._free.append(pid)
        else:
            self._ref[pid] = n - 1

    def release(self, rid) -> None:
        for pid in reversed(self._allocated.pop(rid, [])):
            self._drop_ref(pid)
        self._reserved.pop(rid, None)
