"""Small stand-ins for the cells, sized for the CPU (Pallas in interpret
mode): the same files and code paths, tiny widths and a short window.
``run_cell`` is called past the harness's look for a chip."""
from bench import model, workload
from bench.run import load_benchmark, cell_of

TINY = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_hidden_layers=2, vocab_size=512)


def serve_cell():
    bench = load_benchmark()
    cell = cell_of(bench, "serve-internlm2-chat")
    conf = model.load_config(cell["config"])
    conf.update(TINY, num_key_value_heads=2)
    conf["serving"] = dict(conf["serving"], n_slots=4, max_len=96, num_pages=25)
    mix = workload.load_mix(cell["traffic"])
    mix["arrivals"] = dict(mix["arrivals"], rate_per_s=3.0, burst_every=4, burst_gap_s=0.2)
    mix["prompt_tokens"] = dict(mix["prompt_tokens"], median=24, min=16, max=48,
                               buckets=[16, 32, 48])
    mix["output_tokens"] = dict(mix["output_tokens"], median=8, min=4, max=16)
    mix["check_requests"] = 3
    return bench, cell, conf, mix


def train_cell(name="train-olmo1b-profile"):
    bench = load_benchmark()
    cell = cell_of(bench, name)
    conf = model.load_config(cell["config"])
    # float32 compute: at these widths bfloat16's error is not what the
    # full-size limits were set for, and these runs test the harness
    conf.update(TINY, num_key_value_heads=4)
    conf["program"] = dict(conf["program"], compute_dtype="float32")
    mix = workload.load_mix(cell["traffic"])
    mix.update(per_chip_batch=2, seq_len=64)
    return bench, cell, conf, mix


def run_cell(cell_fn, *args, seed, seconds=2.0):
    """Drive ``bench.run.run_cell`` on this machine's devices."""
    from bench import run

    bench, cell, conf, mix = cell_fn(*args)
    real, run.require_chips = run.require_chips, lambda devices, cell: None
    try:
        return run.run_cell(bench, cell, conf, mix, seed, seconds, False)
    finally:
        run.require_chips = real
