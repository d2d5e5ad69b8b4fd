"""Readings that set the limits of ``correct``: the program's numbers over
many seeds, and the control's and the faults' over a few, in one process.

    python3 bench/control.py --workload <cell> --seeds 12 --control-seeds 3 --seconds 20

Each seed runs the cell's window as a normal run does (``run_cell``), at
the cell's own sizes and load, and prints the program's compared numbers.
For the first ``--control-seeds`` seeds it also prints what the control
reads on the same inputs:

- serving: at each position of the sampled prompts and served tokens, the
  gap of the token that the reference computed with float8 (e4m3) matmul
  operands puts first;
- training: the float8 reference's three steps in the program's place,
  and the fault of half the batch left out (the mean over the other
  half), read against the float32 reference.  A state left unchanged reads
  1 on the change by construction.  ``--fault exchange`` plants the
  exchange between chips left out in the program itself, for every seed.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent / "src"),
                str(Path(__file__).resolve().parent.parent)]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--first-seed", type=int, default=7_000_000_001)
    ap.add_argument("--fault", choices=("none", "exchange"), default="none",
                    help="plant a fault in the program for every seed: 'exchange' "
                         "leaves out the all-reduce inside cd_psum")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import model, run, workload
    from bench.drivers import serve, train

    bench = run.load_benchmark()
    cell = run.cell_of(bench, args.workload)
    conf = model.load_config(cell["config"])
    mix = workload.load_mix(cell["traffic"])
    extra = {}

    def serve_gap(conf_, seed, sample, mix_, seconds, control=False):
        g = orig_gap(conf_, seed, sample, mix_, seconds)
        if extra.get("control"):
            extra["control_logit_gap"] = orig_gap(conf_, seed, sample, mix_, seconds,
                                                  control=True)
        return g

    def train_ref(conf_, seed, batches, precision="f32"):
        out = orig_ref(conf_, seed, batches, precision)
        if extra.get("control"):
            for tag, bs, prec in (("fp8", batches, "fp8"),
                                  ("half_batch", [(t[: len(t) // 2], l[: len(l) // 2])
                                                  for t, l in batches], "f32")):
                extra[tag] = {k: v[0] for k, v in train.compare(
                    conf_, *orig_ref(conf_, seed, bs, prec), *out).items()}
        return out

    orig_gap = serve.served_gap
    serve.served_gap = serve_gap
    if mix["driver"] == "train":
        orig_ref = model.reference(conf).train_reference
        model_reference = model.reference

        def patched(conf_):
            m = model_reference(conf_)
            m.train_reference = train_ref
            return m

        model.reference = patched
    if args.fault == "exchange":
        from repro.core import instrument

        instrument.cd_psum = lambda tree, axes: tree
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        extra.clear()
        extra["control"] = i < args.control_seeds
        out = run.run_cell(bench, cell, conf, mix, seed, args.seconds, False)
        rec = {"seed": seed, "correct": out["correct"],
               "checks": {k: v["value"] for k, v in out["checks"].items()},
               "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        rec.update({k: v for k, v in extra.items() if k != "control"})
        print("READING " + json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
