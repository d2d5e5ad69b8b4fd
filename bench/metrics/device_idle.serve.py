"""Share of the traced serving window in which no operation ran on the
device (1 - busy/window), in %."""


def read(run):
    s = run.get("summary")
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
