"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); the mix names its window
loop (``bench/drivers/<driver>.py``).  Per-layer metrics are read by
``bench/metrics/<metric>.py``.  A new cell, configuration, mix or metric is
new files and entries, never an edit of these.

Set-up (imports, weights from the seed, compilation or the persistent
compile cache, warm-up of every shape the window uses) is ``setup_s``; the
window then runs for ``--seconds``.  With ``--trace 1`` the profiler
records the window and the per-layer metrics are reported instead of the
end-to-end ones.  The reference check runs after the window and is not
timed.  The last stdout line is one JSON object; the numbers compared for
``correct`` are printed beside their limits as the last stderr lines and
under ``checks``, the result's last key.

Exits non-zero with no result line when the first device is not a TPU or
the device count is not the cell's.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Context:
    """What a window loop gets: the cell's files, the run's arguments, and
    the two marks that bound the measured window."""

    def __init__(self, cell, conf, mix, seed, seconds, trace, counter):
        self.cell, self.conf, self.mix = cell, conf, mix
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.counter = counter
        self.log = log
        self.setup_s = None
        self.lowered_at_open = self.lowered_at_close = None

    def mark_setup_done(self) -> None:
        """The window opens: set-up ends here."""
        self.setup_s = time.perf_counter() - T_START
        self.lowered_at_open = self.counter.lowered
        log(f"set-up {self.setup_s:.3f} s; programs warmed {self.counter.lowered}, "
            f"{self.counter.cache_hits} of them from the persistent compile cache")

    def mark_window_done(self) -> None:
        self.lowered_at_close = self.counter.lowered
        log(f"programs compiled inside the window: "
            f"{self.lowered_at_close - self.lowered_at_open}")


def _read_metric(name: str, res: dict):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(res)


def require_chips(devices, cell: dict) -> None:
    """Exit with no result unless JAX found the cell's number of TPU chips."""
    if devices[0].platform != "tpu" or len(devices) != cell["chips"]:
        log(f"no chip for this cell: {len(devices)} {devices[0].platform} device(s), "
            f"the cell needs {cell['chips']} tpu")
        sys.exit(3)


def run_cell(bench: dict, cell: dict, conf: dict, mix: dict, seed: int,
             seconds: float, trace: bool) -> dict:
    """Run the cell and build its result line (a dict)."""
    import jax

    from bench import peaks
    from bench.drivers.common import CompileCounter

    devices = jax.devices()
    require_chips(devices, cell)
    chip_peaks = peaks.peaks_for(devices[0].device_kind) if trace else None
    counter = CompileCounter()
    ctx = Context(cell, conf, mix, seed, seconds, trace, counter)
    driver = importlib.import_module(f"bench.drivers.{mix['driver']}")
    res = driver.run(ctx)
    res["peaks"] = chip_peaks

    metrics = {}
    if not trace:
        for m in bench["end_to_end"]:
            if applies(m, cell["name"]):
                v = ctx.setup_s if m["name"] == "setup_s" else res["e2e"][m["name"]]
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in bench["per_layer"]:
            if applies(m, cell["name"]):
                v = _read_metric(m["name"], res)
                if v is not None and math.isfinite(v):
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"attempted": res["attempted"], "failed": res["failed"],
           "metrics": metrics, "device": dev}
    summary = res.get("summary")
    if summary is not None:
        top = sorted(summary.modules.items(), key=lambda kv: -kv[1][1])[:8]
        log("programs by device time: " + ", ".join(
            f"{k} {v[0]} runs {v[1]:.4f} s" for k, v in top))
    if trace and summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.top_ops(10),
                            "idle_gaps": [[n, s] for n, s in summary.gaps[:10]]}
    checks = res["checks"]
    correct = all(v <= lim for v, lim in checks.values())
    out = {"correct": correct, **out,
           "checks": {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}}
    for k, (v, lim) in checks.items():
        log(f"check {k} = {v!r} (limit {lim!r}) {'ok' if v <= lim else 'FAILED'}")
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("bench: --seed must be a whole number >= 0")

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    import jax

    # every program goes to the persistent cache, however quick to compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench import model, workload

    bench = load_benchmark()
    cell = cell_of(bench, args.workload)
    conf = model.load_config(cell["config"])
    mix = workload.load_mix(cell["traffic"])
    out = run_cell(bench, cell, conf, mix, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
