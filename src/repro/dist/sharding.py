"""Partition rules: parameters, optimizer state, batches, caches, activations.

One table of *requested* specs plus one safety pass:

* ``_param_spec(name, ndim, fsdp)`` — the Megatron/FSDP rule table, keyed by
  the leaf's name.  Column-parallel matrices (``wq/wk/wv/w1/w3/...``) put
  tensor-parallel ``'model'`` on the output dim and FSDP axes on the input
  dim; row-parallel ones (``wo/w2/w_out``) the reverse; the embedding shards
  its (padded) vocab over ``'model'``.  Extra leading dims (the scan-stacked
  layer axis, the MoE expert axis) are left unsharded by left-padding the
  base rule with ``None``.
* ``sanitize_spec(mesh, spec, shape)`` — drops any spec entry whose mesh-axis
  product does not divide the corresponding dim, so every *requested* layout
  degrades to a legal one on any mesh (1-device smoke runs, 7-survivor
  elastic rebuilds, 512-device dry-runs) instead of failing to compile.

``make_mesh`` is the program's one mesh factory.  Every mesh it builds has
``AxisType.Auto`` axes: the rules below *request* layouts and XLA
propagates the rest, which is the semantics this code was written for
(``jax.make_mesh`` alone now defaults to ``Explicit`` axes, under which
ops such as ``jnp.take`` refuse operands whose sharding is not spelled
out).

Entry points (all return pytrees of ``NamedSharding`` matching the input):

  ``param_shardings``       2-d FSDPxTP (default) or ``mode="zero3"``;
                            ``include_pod=False`` keeps parameters replicated
                            over the pod axis (the explicit cross-pod-reduce
                            step); ``gather_safe=True`` additionally drops
                            tensor-parallel entries so each leaf is sharded
                            along at most the FSDP axes, and keeps the
                            embedding (a gather operand) replicated — the
                            layout whose all-gathers and gathers stay legal
                            inside a partial-manual ``shard_map`` region.
  ``opt_state_shardings``   mirrors the parameter rules onto m/v/master.
  ``batch_shardings``       batch dim over the data axes (all axes in zero3).
  ``cache_shardings``       KV/recurrent-state layout; ``serve_tp=True``
                            shards heads/channels over ``'model'``.
  ``serve_param_shardings`` pure tensor-parallel serving rules (no FSDP).
  ``activation_constraint_fn``  the hook installed into the model layer
                            (see repro.models.hooks): constrains residuals /
                            logits under a mesh, excluding any manual axes.
"""
from __future__ import annotations

from typing import Any, FrozenSet, Iterable, Optional, Tuple, Union

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

Axes = Union[None, str, Tuple[str, ...]]


def make_mesh(shape: Tuple[int, ...], axis_names: Tuple[str, ...],
              devices: Optional[Iterable[Any]] = None) -> Mesh:
    """A mesh with ``AxisType.Auto`` axes over ``devices`` (default: all
    of them, laid out by ``jax.make_mesh``); an explicit device list is
    placed row-major, in the order given."""
    axis_types = (AxisType.Auto,) * len(axis_names)
    if devices is None:
        return jax.make_mesh(shape, axis_names, axis_types=axis_types)
    grid = np.asarray(list(devices), dtype=object).reshape(shape)
    return Mesh(grid, axis_names, axis_types=axis_types)

# leaf-name rule tables -------------------------------------------------------
# column-parallel: (d_in -> fsdp, d_out -> model)
_COL = frozenset({
    "wq", "wk", "wv", "w1", "w3", "w_in", "w_gelu", "router", "head",
})
# row-parallel: (d_in -> model, d_out -> fsdp)
_ROW = frozenset({"wo", "w2", "w_out", "w_r", "w_i"})


def _axis_sizes(mesh) -> dict:
    return dict(mesh.shape)


def sanitize_spec(mesh, spec: P, shape: Tuple[int, ...]) -> P:
    """Drop spec entries whose mesh-axis product does not divide the dim.

    Partial sharding of a non-dividing dim is never attempted: the whole
    entry (including grouped ``(a, b)`` tuples) falls back to ``None``.
    """
    sizes = _axis_sizes(mesh)
    out = []
    for i, entry in enumerate(tuple(spec)):
        if entry is None:
            out.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        if any(n not in sizes for n in names):
            out.append(None)                 # axis absent from this mesh
            continue
        prod = 1
        for n in names:
            prod *= int(sizes[n])
        if i < len(shape) and shape[i] % prod == 0:
            out.append(entry)
        else:
            out.append(None)
    return P(*out)


def _param_spec(name: str, ndim: int, fsdp: Axes) -> P:
    """Requested spec for a parameter leaf called ``name`` with ``ndim`` dims.

    The base rule is 2-d; higher ranks (scan-stacked layers, MoE expert
    axes) left-pad with ``None`` so only the trailing matrix is sharded.
    """
    if name == "embed":
        base: Tuple[Axes, ...] = ("model", fsdp)        # (padded vocab, d)
    elif name in _COL:
        base = (fsdp, "model")
    elif name in _ROW:
        base = ("model", fsdp)
    elif name == "conv_w":
        base = (None, "model")                          # (K, channels)
    else:                                               # vectors / scalars
        return P(*([None] * ndim))
    if ndim < len(base):
        return P(*([None] * ndim))
    return P(*(((None,) * (ndim - len(base))) + base))


def _leaf_name(path) -> str:
    for entry in reversed(path):
        key = getattr(entry, "key", None)
        if key is None:
            key = getattr(entry, "name", None)
        if isinstance(key, str) and not key.isdigit():
            return key
    return ""


def _zero3_spec(mesh, shape: Tuple[int, ...], axes: Tuple[str, ...]) -> P:
    """Pure ZeRO-3: flat-shard the first dim the full axis product divides."""
    sizes = _axis_sizes(mesh)
    prod = 1
    for a in axes:
        prod *= int(sizes[a])
    for i, dim in enumerate(shape):
        if dim % prod == 0 and dim >= prod:
            return P(*([None] * i + [axes] + [None] * (len(shape) - i - 1)))
    return P(*([None] * len(shape)))


def _fsdp_axes(mesh, include_pod: bool) -> Axes:
    if "pod" in mesh.axis_names and include_pod:
        return ("pod", "data")
    return "data"


def _data_axes(mesh, exclude: FrozenSet[str] = frozenset()) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model" and a not in exclude)


def param_shardings(
    mesh,
    params: Any,
    *,
    mode: str = "2d",
    include_pod: bool = True,
    gather_safe: bool = False,
) -> Any:
    """Pytree of NamedSharding for a parameter tree (see module docstring)."""
    fsdp = _fsdp_axes(mesh, include_pod)
    zero3_axes = tuple(
        a for a in mesh.axis_names if include_pod or a != "pod"
    )

    def leaf(path, x):
        if mode == "zero3":
            spec = _zero3_spec(mesh, x.shape, zero3_axes)
        else:
            name = _leaf_name(path)
            spec = _param_spec(name, x.ndim, fsdp)
            if gather_safe:
                # XLA's SPMD partitioner aborts (ExpandDeviceGroupsWithIota)
                # when it must reshard a gather operand inside a
                # partial-manual region, so the token-embedding table stays
                # whole there
                spec = P() if name == "embed" else P(
                    *(None if e == "model" else e for e in tuple(spec)))
        return NamedSharding(mesh, sanitize_spec(mesh, spec, x.shape))

    return jax.tree_util.tree_map_with_path(leaf, params)


def opt_state_shardings(mesh, param_sh: Any, opt_state: Any) -> Any:
    """Optimizer-state shardings: m/v/master mirror the parameter layout
    (FSDP over optimizer state is what makes 100B+ models fit per-chip HBM);
    scalars like ``step`` replicate."""
    repl = NamedSharding(mesh, P())
    out = {}
    for key, sub in opt_state.items():
        if key in ("m", "v", "master"):
            out[key] = jax.tree.map(
                lambda s: s, param_sh, is_leaf=lambda x: hasattr(x, "spec")
            )
        else:
            out[key] = jax.tree.map(lambda _: repl, sub)
    return out


def batch_shardings(mesh, batch: Any, mode: str = "2d") -> Any:
    """Batch-dim data parallelism: dim 0 over the data(+pod) axes — over
    *every* axis in zero3 mode (no tensor parallelism to reserve 'model')."""
    if mode == "zero3":
        data = tuple(mesh.axis_names)
    else:
        data = _data_axes(mesh)

    def leaf(x):
        if x.ndim == 0:
            return NamedSharding(mesh, P())
        spec = P(*((data,) + (None,) * (x.ndim - 1)))
        return NamedSharding(mesh, sanitize_spec(mesh, spec, x.shape))

    return jax.tree.map(leaf, batch)


# cache leaf rules: name -> (batch-dim index, tp-dim index) with the leading
# scan-stack axis (if any) stripped before indexing.
_CACHE_RULES = {
    "k": (0, 2),          # (B, T, H, D)
    "v": (0, 2),
    "k_scale": (0, 2),    # (B, T, H)
    "v_scale": (0, 2),
    "conv": (0, 2),       # (B, K, C)
    "h": (0, 1),          # (B, heads/width, ...)
    "slot_pos": (None, None),
}


def cache_shardings(mesh, cache: Any, *, serve_tp: bool = False) -> Any:
    """Decode/prefill cache layout: batch over data axes; with ``serve_tp``
    the KV-head / state-channel dim additionally shards over 'model'."""
    data = _data_axes(mesh)

    def leaf(path, x):
        name = _leaf_name(path)
        stacked = bool(path) and getattr(path[0], "key", None) == "stack"
        offset = 1 if stacked else 0
        b_dim, tp_dim = _CACHE_RULES.get(name, (0, None))
        entries: list = [None] * x.ndim
        if b_dim is not None and b_dim + offset < x.ndim:
            entries[b_dim + offset] = data
        if serve_tp and tp_dim is not None and tp_dim + offset < x.ndim:
            entries[tp_dim + offset] = "model"
        return NamedSharding(mesh, sanitize_spec(mesh, P(*entries), x.shape))

    return jax.tree_util.tree_map_with_path(leaf, cache)


# page-pool leaf rules: name -> KV-head dim index with the leading scan-stack
# axis (if any) stripped, or None.  Page arrays are (n_pages, page, Hkv, D);
# scale pages are flat rows (n_pages, 1, page*Hkv) with no head dim of their
# own, so they replicate; recurrent-state leaves fall through to
# _CACHE_RULES (per-slot batch dim over the data axes).
_PAGE_RULES = {
    "k_pages": 2,
    "v_pages": 2,
    "k_scale_pages": None,
    "v_scale_pages": None,
}


def page_pool_shardings(mesh, blocks: Any, *, serve_tp: bool = True) -> Any:
    """Paged serving-pool layout (see ``repro.serve.kvcache``).

    Page arrays replicate over the data axes — any decode slot must reach
    any physical page, so the pool cannot shard over requests — and with
    ``serve_tp`` split the KV-head dim over ``'model'``, matching the
    tensor-parallel head split of ``serve_param_shardings`` (the flat
    scale rows have no head dim and replicate).  Recurrent
    per-slot state shards its slot (batch) dim over the data axes like the
    dense cache.
    """
    data = _data_axes(mesh)

    def leaf(path, x):
        name = _leaf_name(path)
        stacked = bool(path) and getattr(path[0], "key", None) == "stack"
        offset = 1 if stacked else 0
        entries: list = [None] * x.ndim
        if name in _PAGE_RULES:
            if serve_tp and _PAGE_RULES[name] is not None:
                entries[_PAGE_RULES[name] + offset] = "model"
        else:
            b_dim, tp_dim = _CACHE_RULES.get(name, (0, None))
            if b_dim is not None and b_dim + offset < x.ndim:
                entries[b_dim + offset] = data
            if serve_tp and tp_dim is not None and tp_dim + offset < x.ndim:
                entries[tp_dim + offset] = "model"
        return NamedSharding(mesh, sanitize_spec(mesh, P(*entries), x.shape))

    return jax.tree_util.tree_map_with_path(leaf, blocks)


def serve_param_shardings(mesh, params: Any) -> Any:
    """Pure tensor-parallel serving rules: weights replicated over 'data'
    (throughput replicas), matrices Megatron-split over 'model' only."""

    def leaf(path, x):
        name = _leaf_name(path)
        if name == "embed":
            base: Tuple[Axes, ...] = ("model", None)
        elif name in _COL:
            base = (None, "model")
        elif name in _ROW:
            base = ("model", None)
        else:
            base = ()
        if len(base) > x.ndim:
            base = ()
        spec = P(*(((None,) * (x.ndim - len(base))) + base))
        return NamedSharding(mesh, sanitize_spec(mesh, spec, x.shape))

    return jax.tree_util.tree_map_with_path(leaf, params)


def activation_constraint_fn(
    mesh,
    exclude: Optional[Iterable[str]] = None,
    mode: str = "2d",
):
    """Build the hook for ``repro.models.hooks.install_constraint``.

    Maps the model's logical activation names onto specs under ``mesh``:

      residual  (B, S, d)    batch over data(+pod) axes
      logits    (B, C, V)    batch over data axes, vocab over 'model'

    ``exclude`` removes axes that are *manual* in the calling context (the
    pod-explicit train step runs the model inside a shard_map over 'pod',
    where constraints must not name 'pod').  Specs are sanitized per call,
    so odd batch remainders after an elastic rebuild simply replicate.
    """
    excluded = frozenset(exclude or ())
    data = _data_axes(mesh, excluded)
    if mode == "zero3":
        data = tuple(a for a in mesh.axis_names if a not in excluded)
        tp = None
    else:
        tp = "model" if ("model" in mesh.axis_names and "model" not in excluded) else None
    batch_axes: Axes = data if data else None

    def constrain(x, name: str):
        if x.ndim < 2:
            return x
        if name == "logits":
            entries = (batch_axes,) + (None,) * (x.ndim - 2) + (tp,)
        elif name == "residual":
            entries = (batch_axes,) + (None,) * (x.ndim - 1)
        else:
            return x
        spec = sanitize_spec(mesh, P(*entries), x.shape)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return constrain
