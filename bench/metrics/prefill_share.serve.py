"""Share of the device's busy time spent admitting requests: the prefill
program and the join that scatters its cache into the pool, in %.

The engine jits ``functools.partial(transformer.prefill, cfg)``, which the
trace names ``jit__unknown``; the join is ``jit_join``."""

PREFILL, JOIN = "jit__unknown", "jit_join"


def read(run):
    s = run.get("summary")
    if s is None or s.busy_s <= 0:
        return None
    _, pre = s.module_time(PREFILL)
    _, join = s.module_time(JOIN)
    if not pre:
        return None
    return 100.0 * (pre + join) / (s.busy_s * s.n_devices)
