"""Model FLOP/s utilization of serving: the forward FLOPs of every prompt
token prefilled and every token decoded in the window (``bench/counts.py``),
over the window and the chip's bf16 peak, in %."""
from bench import counts


def read(run):
    lay, cfg, peaks = run["layer"], run["cfg"], run["peaks"]
    if peaks is None or not lay["steps"]:
        return None
    flops = sum(counts.prefill_flops(cfg, n) for _, n in lay["joins"])
    flops += sum(counts.decode_flops(cfg, c) for _, c in lay["steps"])
    return 100.0 * flops / lay["window_s"] / peaks["bf16_flops"]
