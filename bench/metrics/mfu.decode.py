"""The decode step's share of the chip's bf16 peak while it runs: the
forward FLOPs of one step over the window's decode steps
(``bench/counts.py``, every active slot at its context) over the device
time of one run of the decode program (``jit_step``), in %.  It bounds
``paged_attention_roofline``, the kernel inside that step."""
from bench import counts


def read(run):
    s, lay, cfg, peaks = run.get("summary"), run["layer"], run["cfg"], run["peaks"]
    if s is None or peaks is None or not lay["steps"]:
        return None
    runs, secs = s.module_time("jit_step")
    if not runs:
        return None
    flops = sum(counts.decode_flops(cfg, c) for _, c in lay["steps"]) / len(lay["steps"])
    return 100.0 * flops / (secs / runs) / peaks["bf16_flops"]
