"""Roofline share of the Pallas paged-decode kernel (ops named
``paged_attention*`` in the trace): the least time its bytes or its FLOPs
need at the chip's peaks (live K/V pages, q and out; ``bench/counts.py``),
over its measured time per call, in %."""
from bench import counts


def read(run):
    s, lay, cfg, peaks = run.get("summary"), run["layer"], run["cfg"], run["peaks"]
    if s is None or peaks is None or not lay["steps"]:
        return None
    calls, secs = s.op_time("paged_attention")
    if not calls:
        return None
    n = len(lay["steps"])
    bytes_ = sum(counts.paged_attention_bytes(cfg, c, lay["page"]) for _, c in lay["steps"]) / n
    flops = sum(counts.paged_attention_flops(cfg, c) for _, c in lay["steps"]) / n
    need = max(bytes_ / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops"])
    return 100.0 * need / (secs / calls)
