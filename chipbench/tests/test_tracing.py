"""The trace reduction, on a trace recorded on a TPU v5 lite: a window
of warm vecadd launches at 2^18 elements, 32 chunk programs each.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
from pathlib import Path

import pytest

from chipbench import tracing

TRACE = Path(__file__).parent / "data" / "vecadd_2e18.xplane.pb"


def test_union_merges_and_clips():
    got = tracing.union([(5, 9), (0, 2), (1, 3), (8, 12)], 1, 10)
    assert got == [[1, 3], [5, 10]]


@pytest.fixture(scope="module")
def summary():
    return tracing.reduce(str(TRACE))


def test_busy_time_lies_inside_the_window(summary):
    assert 0 < summary["busy_s"] < summary["window_s"]


def test_one_device_program_per_chunk(summary):
    launches = [s for _p, lines in tracing.planes(str(TRACE))
                for _ln, evs in lines for s in evs
                if s[0] == "chipbench.launch"]
    assert launches
    assert summary["programs"] == 32 * len(launches)


def test_breakdown_lists_the_largest_first(summary):
    for key in ("device_ops", "idle_gaps"):
        rows = summary[key]
        assert 0 < len(rows) <= 10
        secs = [s for _n, s in rows]
        assert secs == sorted(secs, reverse=True) and min(secs) > 0
    assert sum(s for _n, s in summary["idle_gaps"]) == pytest.approx(
        summary["window_s"] - summary["busy_s"], rel=1e-6)
    assert {n for n, _s in summary["idle_gaps"]} <= {
        "launch", "copy_inputs", "outside_spans"}


def test_a_trace_without_a_device_plane_reads_nothing(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        jax.numpy.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    assert tracing.reduce(tracing.find_trace(str(tmp_path))) is None
