"""Model FLOP/s utilization of training: the FLOPs a step needs (forward
and backward, no recomputation, ``bench/counts.py``) times the steps
completed in the window, over the window, the chips and the bf16 peak,
in %."""
from bench import counts


def read(run):
    lay, cfg, peaks = run["layer"], run["cfg"], run["peaks"]
    if peaks is None or not lay["steps"]:
        return None
    flops = counts.train_flops(cfg, lay["rows"], lay["seq"]) * lay["steps"]
    return 100.0 * flops / lay["window_s"] / (lay["chips"] * peaks["bf16_flops"])
