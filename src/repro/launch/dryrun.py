import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    # dry-run compiles CPU code it never runs: skip expensive LLVM passes
    # (post-HLO, so memory/cost/collective analyses are unaffected)
    "--xla_llvm_disable_expensive_passes=true"
)

"""Multi-pod dry-run: AOT-lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. eval_shape's the model/optimizer/cache state (no allocation),
  3. jits the right step function with explicit in/out shardings,
  4. ``.lower().compile()``s it — proving the distribution config is
     coherent (shardings divide, collectives legal, memory fits),
  5. records ``memory_analysis()``, ``cost_analysis()`` and the per-op
     collective schedule (parsed from post-SPMD HLO) into
     ``artifacts/dryrun/<cell>.json`` for the roofline harness.

Artifacts are cached: finished cells are skipped on re-run, so the full
sweep is resumable.  ``--instrument barrier`` lowers the paper-faithful
variant (artificial barriers in the collective schedule).

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all [--mesh pod|multipod|both]
  python -m repro.launch.dryrun --list
"""
import argparse
import dataclasses
import json
import re
import sys
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, SHAPES, cell_is_runnable, get_config
from repro.core import instrument
from repro.dist import sharding as SH
from repro.launch.mesh import make_production_mesh
from repro.obs import log as obslog

log = obslog.get_logger("dryrun")
from repro.models.hooks import install_constraint
from repro.models.inputs import decode_inputs_specs, input_specs
from repro.models.transformer import init_cache, init_params
from repro.serve.engine import make_serve_steps
from repro.train.loop import TrainConfig, make_pod_train_step, make_train_step
from repro.train.optimizer import OptConfig, init_opt_state

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")

COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8, "s32": 4,
    "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "c64": 8, "c128": 16,
}


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of every typed shape in an HLO type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-op-type: count, per-device result bytes, estimated wire bytes.

    Wire-bytes model (ring algorithms, per chip):
      all-gather:        out*(g-1)/g      reduce-scatter: out*(g-1)
      all-reduce:        2*size*(g-1)/g   all-to-all:     size*(g-1)/g
      collective-permute: size
    """
    out = {op: {"count": 0, "result_bytes": 0, "wire_bytes": 0.0} for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s*((?:[\w\-]+)-start|[\w\-]+)\(", ls)
        if not m:
            continue
        opname = m.group(2).replace("-start", "")
        if opname not in COLLECTIVE_OPS:
            continue
        result_bytes = _shape_bytes(m.group(1))
        g = 1
        rg = re.search(r"replica_groups=\{?\{([^}]*)\}", ls)
        if rg:
            g = len(rg.group(1).split(","))
        else:
            rg2 = re.search(r"replica_groups=\[(\d+),(\d+)\]", ls)
            if rg2:
                g = int(rg2.group(2))
        g = max(g, 1)
        if opname == "all-gather":
            wire = result_bytes * (g - 1) / g
        elif opname == "reduce-scatter":
            wire = result_bytes * (g - 1)
        elif opname == "all-reduce":
            wire = 2 * result_bytes * (g - 1) / g
        elif opname == "all-to-all":
            wire = result_bytes * (g - 1) / g
        else:  # collective-permute
            wire = result_bytes
        rec = out[opname]
        rec["count"] += 1
        rec["result_bytes"] += result_bytes
        rec["wire_bytes"] += wire
    return out


_SKIP_OPS = {
    "parameter", "bitcast", "get-tuple-element", "constant", "tuple",
    "after-all", "iota",
}


def parse_memory_traffic(hlo_text: str) -> dict:
    """HBM-traffic proxy from post-fusion HLO: unique top-level tensor bytes.

    ``cost_analysis()['bytes accessed']`` counts every op inside fusion
    computations (logical bytes) plus CPU-backend bf16->f32 convert
    materializations that a TPU's MXU never performs — a 10-100x
    overestimate.  Here we count only tensors that exist between fusions
    (each written once, read >= once): entry parameters once, plus the
    output of every instruction in non-fusion-internal computations.
    """
    # pass 1: computations called by fusions / reducers (skip their bodies)
    fused = set(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", hlo_text))
    total = 0
    params = 0
    current_skipped = False
    in_entry = False
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"(ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?\s*->.*\{$", s)
        if m:
            name = m.group(2)
            in_entry = bool(m.group(1))
            current_skipped = name in fused
            continue
        if s == "}":
            current_skipped = False
            in_entry = False
            continue
        if current_skipped:
            continue
        im = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", s)
        if not im:
            continue
        ty, op = im.groups()
        op = op.replace("-start", "")
        if op == "parameter":
            if in_entry:
                params += _shape_bytes(ty)
            continue
        if op in _SKIP_OPS:
            continue
        total += _shape_bytes(ty)
    return {"tensor_bytes": total, "param_bytes": params,
            "traffic_bytes": total + params}


def _specs(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def production_config(arch: str, *, unroll: bool = False):
    """Production dtypes; ``unroll`` trades HLO size for cost fidelity.

    Two compiles per cell: the *scanned* module is what production runs and
    gives faithful ``memory_analysis`` (XLA reuses the loop body buffers);
    the *unrolled* module gives faithful ``cost_analysis`` + collective
    counts (XLA counts while-loop bodies exactly once, a 1/n_layers
    undercount).  The CPU buffer assigner does not reuse buffers across
    unrolled layers, so unrolled memory numbers are ignored.
    """
    cfg = get_config(arch)
    return dataclasses.replace(
        cfg, param_dtype="bfloat16", compute_dtype="bfloat16",
        scan_layers=not unroll,
        # production trick (Megatron-style): pad the embedding table so the
        # vocab dim shards over 'model'; odd vocabs otherwise replicate the
        # (B,C,V) fp32 loss chunks on every chip
        pad_vocab_to=256,
    )


def build_cell(arch: str, shape_name: str, mesh, *, microbatch: int = 0,
               unroll: bool = False, serve_tp: bool = False, kv_int8: bool = False,
               grad_bf16: bool = False, zero3: bool = False):
    """Returns (fn, args_specs, in_shardings, out_shardings, donate_argnums).

    Donation mirrors production: the train state and the KV/recurrent caches
    are donated (updated in place), so memory_analysis reflects the real
    footprint instead of double-counting input+output buffers.

    ``serve_tp`` switches prefill/decode cells to the tensor-parallel
    serving partition rules (hillclimb; see dist.sharding).
    """
    cfg = production_config(arch, unroll=unroll)
    if kv_int8:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    shape = SHAPES[shape_name]
    opt_cfg = OptConfig()

    params_s = jax.eval_shape(partial(init_params, cfg), jax.random.PRNGKey(0))
    if serve_tp and shape.kind != "train":
        psh = SH.serve_param_shardings(mesh, params_s)
    elif zero3:
        psh = SH.param_shardings(mesh, params_s, mode="zero3")
    else:
        psh = SH.param_shardings(mesh, params_s)

    if shape.kind == "train":
        opt_s = jax.eval_shape(partial(init_opt_state, cfg=opt_cfg), params_s)
        osh = SH.opt_state_shardings(mesh, psh, opt_s)
        batch_s = input_specs(cfg, shape)
        bsh = SH.batch_shardings(mesh, batch_s, mode="zero3" if zero3 else "2d")
        state_s = {"params": params_s, "opt": opt_s}
        ssh = {"params": psh, "opt": osh}
        if "pod" in mesh.axis_names and instrument.get_mode() != "off":
            # paper-faithful multi-pod step: explicit cross-pod reduce
            psh2 = SH.param_shardings(mesh, params_s, include_pod=False, gather_safe=True)
            osh2 = SH.opt_state_shardings(mesh, psh2, opt_s)
            ssh = {"params": psh2, "opt": osh2}
            fn = make_pod_train_step(cfg, opt_cfg, mesh, TrainConfig(pod_reduce="manual"))
        else:
            fn = make_train_step(cfg, opt_cfg, TrainConfig(
                microbatch=microbatch,
                grad_reduce_dtype="bfloat16" if grad_bf16 else "",
            ))
        return fn, (state_s, batch_s), (ssh, bsh), (ssh, None), (0,)

    prefill_step, decode_step = make_serve_steps(cfg)
    cache_s = jax.eval_shape(
        partial(init_cache, cfg, shape.global_batch, shape.seq_len)
    )
    csh = SH.cache_shardings(mesh, cache_s, serve_tp=serve_tp)
    if shape.kind == "prefill":
        batch_s = input_specs(cfg, shape)
        bsh = SH.batch_shardings(mesh, batch_s)
        return (
            prefill_step,
            (params_s, batch_s, cache_s),
            (psh, bsh, csh),
            (None, csh),
            (2,),
        )
    # decode
    token_s, pos_s = decode_inputs_specs(cfg, shape)
    tsh = SH.batch_shardings(mesh, token_s)
    return (
        decode_step,
        (params_s, token_s, pos_s, cache_s),
        (psh, tsh, None, csh),
        (None, csh),
        (3,),
    )


def run_cell(arch: str, shape_name: str, mesh_kind: str, *, force=False,
             instrument_mode: str = "off", tag: str = "", microbatch: int = 0,
             serve_tp: bool = False, kv_int8: bool = False,
             skip_unroll: bool = False, grad_bf16: bool = False,
             zero3: bool = False) -> dict:
    os.makedirs(ART_DIR, exist_ok=True)
    suffix = f"-{tag}" if tag else ""
    path = os.path.join(ART_DIR, f"{arch}__{shape_name}__{mesh_kind}{suffix}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": tag,
        "instrument": instrument_mode, "status": "started",
    }
    if not cell_is_runnable(arch, shape_name):
        record["status"] = "skipped"
        record["reason"] = "long_500k requires sub-quadratic attention (see DESIGN.md)"
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
        return record

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    install_constraint(SH.activation_constraint_fn(
        mesh, mode="zero3" if zero3 else "2d"))
    instrument.set_mode(instrument_mode)
    try:
        with jax.set_mesh(mesh):
            # ---- phase 1: scanned module -> memory analysis (production) --
            t0 = time.time()
            fn, args_s, in_sh, out_sh, donate = build_cell(
                arch, shape_name, mesh, microbatch=microbatch, unroll=False,
                serve_tp=serve_tp, kv_int8=kv_int8, grad_bf16=grad_bf16,
                zero3=zero3,
            )
            compiled = (
                jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                        donate_argnums=donate)
                .lower(*args_s)
                .compile()
            )
            t_scan = time.time() - t0
            ma = compiled.memory_analysis()
            log.debug("memory_analysis", cell=f"{arch}/{shape_name}/{mesh_kind}",
                      analysis=str(ma))
            record.update(
                status="ok",
                compile_scan_s=round(t_scan, 2),
                n_devices=int(np.prod(list(mesh.shape.values()))),
                memory={
                    "argument_bytes": ma.argument_size_in_bytes,
                    "output_bytes": ma.output_size_in_bytes,
                    "temp_bytes": ma.temp_size_in_bytes,
                    "alias_bytes": ma.alias_size_in_bytes,
                    # args already include donated buffers; outputs alias
                    # into them, so only the non-aliased output remainder
                    # adds to the physical peak
                    "peak_args_plus_temp": ma.argument_size_in_bytes
                    + ma.temp_size_in_bytes
                    + max(0, ma.output_size_in_bytes - ma.alias_size_in_bytes),
                },
            )
            del compiled

            # ---- phase 2: unrolled module -> cost + collective analysis ---
            # (skippable for multi-pod cells: compile success + memory are
            # the deliverable there; the roofline table is single-pod)
            if skip_unroll:
                record["cost_phase"] = "skipped"
                with open(path, "w") as f:
                    json.dump(record, f, indent=1)
                return record
            t0 = time.time()
            fn, args_s, in_sh, out_sh, donate = build_cell(
                arch, shape_name, mesh, microbatch=microbatch, unroll=True,
                serve_tp=serve_tp, kv_int8=kv_int8, grad_bf16=grad_bf16,
                zero3=zero3,
            )
            compiled = (
                jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                        donate_argnums=donate)
                .lower(*args_s)
                .compile()
            )
            t_unroll = time.time() - t0
            ca = compiled.cost_analysis()
            log.debug("cost_analysis", cell=f"{arch}/{shape_name}/{mesh_kind}",
                      flops=ca.get("flops"),
                      bytes_accessed=ca.get("bytes accessed"))
            hlo = compiled.as_text()
            record.update(
                compile_unroll_s=round(t_unroll, 2),
                cost={
                    "flops": ca.get("flops", 0.0),
                    "bytes_accessed": ca.get("bytes accessed", 0.0),
                    "transcendentals": ca.get("transcendentals", 0.0),
                },
                collectives=parse_collectives(hlo),
                traffic=parse_memory_traffic(hlo),
                hlo_bytes=len(hlo),
            )
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      trace=traceback.format_exc()[-4000:])
    finally:
        instrument.set_mode("off")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ARCHS), default=None)
    ap.add_argument("--shape", choices=list(SHAPES), default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--instrument", choices=["off", "barrier"], default="off")
    ap.add_argument("--tag", default="")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--serve-tp", action="store_true",
                    help="TP serving shardings for prefill/decode (hillclimb)")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8-quantized KV cache (hillclimb)")
    ap.add_argument("--skip-unroll", action="store_true",
                    help="phase 1 only: prove compile + memory (multipod)")
    ap.add_argument("--grad-bf16", action="store_true",
                    help="bf16 gradient reduction (hillclimb)")
    ap.add_argument("--zero3", action="store_true",
                    help="pure ZeRO-3 sharding, no TP (hillclimb)")
    obslog.add_flags(ap)
    args = ap.parse_args()
    obslog.configure_from_args(args)

    cells = []
    archs = list(ARCHS[:10]) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    for a in archs:
        for s in shapes:
            for m in meshes:
                cells.append((a, s, m))
    if args.list:
        for c in cells:
            print(*c)
        return
    ok = err = skip = 0
    for a, s, m in cells:
        rec = run_cell(a, s, m, force=args.force, instrument_mode=args.instrument,
                       tag=args.tag, microbatch=args.microbatch,
                       serve_tp=args.serve_tp, kv_int8=args.kv_int8,
                       skip_unroll=args.skip_unroll, grad_bf16=args.grad_bf16,
                       zero3=args.zero3)
        status = rec["status"]
        ok += status == "ok"
        err += status == "error"
        skip += status == "skipped"
        fields = {"cell": f"{a} {s} {m}", "status": status}
        if status == "ok":
            fields["peak_gib"] = rec["memory"]["peak_args_plus_temp"] / 2**30
            fields["compile_s"] = (f"{rec.get('compile_scan_s')}+"
                                   f"{rec.get('compile_unroll_s')}")
            log.info("cell", **fields)
        elif status == "error":
            fields["error"] = rec["error"][:120]
            log.error("cell", **fields)
        else:
            log.info("cell", **fields)
    log.info("done", ok=ok, skipped=skip, errors=err)
    sys.exit(1 if err else 0)


if __name__ == "__main__":
    main()
