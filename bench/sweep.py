"""The knee sweep of a serving cell: one set-up, then the cell's window at
each offered rate in turn, in one process.

    python3 bench/sweep.py --workload serve-internlm2-chat --rates 4,5,6,7,8 \
        --seconds 51 --seed 1

Each rate runs the cell's own mix, pool and engine with only
``rate_per_s`` changed, and prints one line: the end-to-end metrics, the
median TTFT of the first and the last quarter's arrivals (a backlog that
grows through the window shows there) and the programs compiled inside
the window.  The highest rate the engine sustains is the knee; a cell
below it offers about four fifths of it.  The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="offered rates in requests/s, comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import model, run, workload
    from bench.drivers import serve
    from bench.drivers.common import CompileCounter

    cell = run.cell_of(run.load_benchmark(), args.workload)
    conf = model.load_config(cell["config"])
    base = workload.load_mix(cell["traffic"])
    rates = [float(r) for r in args.rates.split(",")]
    mixes = {r: dict(base, arrivals=dict(base["arrivals"], rate_per_s=r)) for r in rates}
    counter = CompileCounter()
    cfg, eng = serve.build(conf, args.seed, run.log)
    # the most requests reach the widest shapes, which cover the others'
    serve.warm(eng, mixes[max(rates)], args.seconds, workload.seed_rng(args.seed, 2),
               cfg.vocab, run.log)
    for r in rates:
        schedule = workload.serve_schedule(mixes[r], args.seed, args.seconds, cfg.vocab)
        before = counter.lowered
        w = serve.window(eng, schedule, args.seconds, False)
        e2e, ttft, _, _ = serve.end_to_end(w["reqs"], w["times"], args.seconds)
        rec = {"rate_per_s": r, "requests": len(schedule), **e2e,
               "ttft_p50_ms_first_last_quarter": serve.quarters(w["reqs"], ttft, args.seconds),
               "drain_s": w["t_drain"] - args.seconds,
               "compiled_in_window": counter.lowered - before}
        print("SWEEP " + json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
