#!/usr/bin/env python
"""Record a small profiler trace of the program's spans.

Under one ``bench.window`` span, as the benchmark's traced window has it:
a data-parallel step with two profile-mode ``cd_psum`` calls under
``shard_map`` over every device (two virtual devices on the CPU), with a
governor on the event bus, run ``--steps`` times; then a continuous-
batching session of a tiny model in which one request waits for a slot.
Both are compiled before the profiler starts.

    JAX_PLATFORMS=cpu PYTHONPATH=src python scripts/record_span_trace.py OUT_DIR

Prints the ``.xplane.pb`` path, then one JSON line of what ran: ranks,
steps, ``cd_psum`` calls per step, requests, joins and decode steps.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

CALLS_PER_STEP = 2


def dp_step(mesh):
    """Two instrumented all-reduces a step, as the train step's gradient
    tree and loss."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.core import instrument

    def body(x):
        g = instrument.cd_psum({"g": x * 2.0}, "data")["g"]
        return instrument.cd_psum(jax.numpy.sum(g), "data")

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P(),
                                 check_vma=False))


def serve_session(eng, prompts):
    """Serve ``prompts`` (all arrived at 0) to the end; returns the
    session's joins and decode steps."""
    from repro.serve.engine import EngineSession
    from repro.serve.scheduler import Request

    sess = EngineSession(eng)
    for p in prompts:
        sess.submit(Request(prompt=p, max_new=4, arrival=0.0))
    joins = steps = 0
    while not sess.done:
        joins += len(sess.admit())
        if sess.n_active:
            sess.decode_step()
            steps += 1
    return joins, steps


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out", help="directory the profiler writes into")
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.core import instrument
    from repro.core.governor import Governor
    from repro.dist.sharding import make_mesh
    from repro.models import init_params
    from repro.serve import ContinuousEngine

    n = len(jax.devices())
    mesh = make_mesh((n,), ("data",))
    instrument.set_mode("profile")
    instrument.enable_events(True)
    gov = instrument.get_event_bus().subscribe(Governor())
    step = dp_step(mesh)
    x = jax.numpy.arange(4.0 * n)
    jax.block_until_ready(step(x))

    cfg = reduced(get_config("llama3.2-1b"))
    eng = ContinuousEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                           n_slots=2, max_len=32, page=8)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, 8).astype(np.int32) for _ in range(3)]
    serve_session(eng, prompts[:1])                     # compiles every shape

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0                        # keeps the file small
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(args.out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(args.steps):
            jax.block_until_ready(step(x))
        joins, steps = serve_session(eng, prompts)
    jax.profiler.stop_trace()
    instrument.get_event_bus().unsubscribe(gov)
    print(glob.glob(f"{args.out}/**/*.xplane.pb", recursive=True)[0])
    print(json.dumps({"ranks": n, "steps": args.steps, "calls_per_step": CALLS_PER_STEP,
                      "requests": len(prompts), "joins": joins, "decode_steps": steps}))


if __name__ == "__main__":
    main()
