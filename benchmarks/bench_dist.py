"""Distribution-substrate micro-benchmarks.

  compressed_psum vs raw psum   — step latency of the int8 cross-pod codec
                                  against the uncompressed reduction, plus
                                  the wire-bytes ratio it buys.
  StragglerDetector throughput  — observe_barrier calls/s at fleet sizes
                                  from 8 to 1024 ranks (the governor calls
                                  this once per reconstructed collective, so
                                  it must stay far off the step critical
                                  path).

Emits the standard ``name,us_per_call,derived`` CSV contract.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, save_json, time_call


def _bench_compressed_psum(full: bool) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.dist.compression import compressed_psum, compression_ratio
    from repro.dist.sharding import make_mesh

    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev,), ("data",))
    sizes = [1 << 16, 1 << 20] + ([1 << 22] if full else [])
    results = {}
    for size in sizes:
        grads = {"g": jnp.asarray(np.random.default_rng(0).normal(size=size), jnp.float32)}

        def reduce_with(fn):
            return jax.jit(
                jax.shard_map(
                    fn, mesh=mesh, in_specs=(P(),), out_specs=P(),
                    axis_names={"data"}, check_vma=False,
                )
            )

        raw = reduce_with(lambda g: jax.tree.map(lambda a: jax.lax.psum(a, "data"), g))
        comp = reduce_with(lambda g: compressed_psum(g, "data"))
        jax.block_until_ready(raw(grads))            # compile outside timing
        jax.block_until_ready(comp(grads))
        us_raw, _ = time_call(lambda: jax.block_until_ready(raw(grads)), repeats=5)
        us_comp, _ = time_call(lambda: jax.block_until_ready(comp(grads)), repeats=5)
        ratio = compression_ratio(grads)
        emit(f"dist.psum_raw.{size}", us_raw, f"devices={n_dev}")
        emit(f"dist.psum_int8.{size}", us_comp, f"wire_ratio={ratio:.2f}x")
        results[size] = {
            "us_raw": us_raw, "us_int8": us_comp, "wire_ratio": ratio,
        }
    return results


def _bench_straggler(full: bool) -> dict:
    from repro.dist.straggler import StragglerDetector

    rng = np.random.default_rng(0)
    results = {}
    for n_ranks in [8, 64, 1024] if full else [8, 64]:
        det = StragglerDetector()
        barriers = [
            {r: float(t) for r, t in enumerate(rng.normal(0, 1e-3, n_ranks))}
            for _ in range(64)
        ]

        def run():
            for b in barriers:
                det.observe_barrier(b)
            return det.stragglers()

        us, _ = time_call(run, repeats=5)
        per_call = us / len(barriers)
        emit(f"dist.straggler_observe.{n_ranks}r", per_call,
             f"{1e6 / max(per_call, 1e-9):.0f}calls_per_s")
        results[n_ranks] = {"us_per_observe": per_call}
    return results


def run(full: bool = False) -> None:
    payload = {
        "compressed_psum": _bench_compressed_psum(full),
        "straggler": _bench_straggler(full),
    }
    save_json("bench_dist", payload)


if __name__ == "__main__":
    run()
