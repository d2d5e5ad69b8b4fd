"""Device time of one run of the paged decode program (``jit_step``, built
by ``serve.engine.make_paged_decode_step``), in ms."""


def read(run):
    s = run.get("summary")
    if s is None:
        return None
    runs, secs = s.module_time("jit_step")
    return 1e3 * secs / runs if runs else None
