"""The program's departures from the published models, on the CPU at a
small size: against the reference as the configuration states the
departures, a small run is correct; against the reference in its
published form (no ``departures`` group), the same run is not.  The
readings are printed for PERF.md's Open questions."""
import pytest

import small

SEED = 2**35 + 3


@pytest.mark.parametrize("cell_fn,number", [(small.serve_cell, "max_logit_gap"),
                                            (small.train_cell, "loss_gap")])
def test_published_form_departs_from_the_program(cell_fn, number):
    def published(*args):
        bench, cell, conf, mix = cell_fn(*args)
        conf.pop("departures")
        return bench, cell, conf, mix

    as_stated = small.run_cell(cell_fn, seed=SEED)
    as_published = small.run_cell(published, seed=SEED)
    read = lambda out: out["checks"][number]["value"]
    print(f"{number}: departures as stated {read(as_stated)!r}, "
          f"published form {read(as_published)!r}, limit "
          f"{as_stated['checks'][number]['limit']!r}")
    assert as_stated["correct"]
    assert not as_published["correct"]
    assert read(as_published) > as_published["checks"][number]["limit"]
