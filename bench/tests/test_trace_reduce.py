"""The trace reduction on a small trace recorded on a TPU v5e: three paged
decode steps of a 2-layer model under ``bench.decode`` spans, with
``bench.sleep`` spans between them."""
from pathlib import Path

import pytest

from bench import trace_reduce as T

TRACE = str(Path(__file__).parent / "data" / "tiny_decode.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return T.reduce(TRACE)


def test_busy_and_idle_share(summary):
    assert summary.n_devices == 1
    assert summary.window_s == pytest.approx(0.014540995, rel=1e-9)
    assert summary.busy_s == pytest.approx(0.003945551, rel=1e-9)
    idle = 1 - summary.busy_s / summary.window_s
    assert idle == pytest.approx(0.72866, abs=1e-4)


def test_time_per_program_and_kernel(summary):
    runs, secs = summary.module_time("jit_step")
    assert runs == 3 and secs == pytest.approx(0.003946555, rel=1e-9)
    calls, ksecs = summary.op_time("paged_attention")
    assert calls == 6 and ksecs == pytest.approx(19.632e-6, rel=1e-6)
    # a while op spans its body's ops: busy time, never an op's own time
    assert not any(k.startswith("while") for k in summary.ops)
    assert summary.top_ops(2)[0] == ["convert.32", pytest.approx(923.75e-6, rel=1e-6)]


def test_gaps_are_attributed_to_host_spans(summary):
    names = [n for n, _ in summary.gaps[:2]]
    assert names == ["bench.sleep", "bench.sleep"]
    assert summary.gaps[0][1] == pytest.approx(0.005399213, rel=1e-6)
    assert summary.spans["bench.decode"][0] == 3


def test_interval_arithmetic():
    iv = [(0, 4), (2, 6), (8, 9), (9, 10)]
    assert T.union_length(iv) == 8
    assert T.merged(iv) == [(0, 6), (8, 10)]
    assert T.op_name("%fusion.12 = bf16[2] fusion(x)") == "fusion.12"
    assert T.op_kind("all-reduce-start.3") == "all-reduce-start"
    assert T.module_name("jit_step(1263735246228299254)") == "jit_step"
