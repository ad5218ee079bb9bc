"""The program's own spans in a trace (``chipbench/spans.py``), on two
traces recorded on a TPU v5 lite: warm vecadd launches at 2^18
elements, 32 chunk programs each, one recorded before the program had
spans and one with its ``volt.*`` spans; and the transfer counter's
reader.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
import sys
from pathlib import Path

import pytest

from chipbench import spans, spec, tracing

DATA = Path(__file__).parent / "data"
OLD = DATA / "vecadd_2e18.xplane.pb"
NEW = DATA / "vecadd_2e18_spans.xplane.pb"


@pytest.fixture(scope="module")
def new():
    return spans.read(str(NEW)), tracing.reduce(str(NEW))


@pytest.fixture(scope="module")
def old():
    return spans.read(str(OLD)), tracing.reduce(str(OLD))


def test_idle_time_is_filed_under_the_program_spans(new):
    got, summary = new
    idle = dict(got["idle_gaps"])
    total = sum(idle.values())
    assert total == pytest.approx(summary["window_s"] - summary["busy_s"],
                                  rel=1e-6)
    assert got["idle_gaps"][0][0] == "volt.jax.dispatch"
    program = sum(s for n, s in idle.items() if n.startswith("volt."))
    assert program > 0.75 * total
    # the chain's own self time names no cause; its children do
    assert idle.get("volt.launch", 0.0) < 0.25 * total


def test_every_span_total_lies_inside_the_window(new):
    got, summary = new
    assert set(got["spans"]) == {
        "volt.launch", "volt.launch.snapshot", "volt.jax.prepare",
        "volt.jax.upload", "volt.jax.dispatch", "volt.jax.sync",
        "volt.jax.download", "volt.jax.apply"}
    launch = got["spans"]["volt.launch"]
    for name, v in got["spans"].items():
        assert 0 < v["s"] <= launch["s"] <= summary["window_s"], name
        assert v["n"] == launch["n"], name
    launches = [s for _p, lines in tracing.planes(str(NEW))
                for _ln, evs in lines for s in evs
                if s[0] == "chipbench.launch"]
    assert launch["n"] == len(launches)


def test_per_launch_times_read_the_spans_and_nothing_without_them(new, old):
    got = spans.per_launch(new[0]["spans"])
    assert set(got) == {"runtime_host_ms", "upload_ms", "dispatch_ms",
                        "download_ms"}
    assert all(v > 0 for v in got.values())
    n = new[0]["spans"]["volt.launch"]["n"]
    assert got["dispatch_ms"] == pytest.approx(
        new[0]["spans"]["volt.jax.dispatch"]["s"] * 1e3 / n)
    assert old[0]["spans"] == {}
    assert spans.per_launch(old[0]["spans"]) == {}


def test_without_program_spans_the_idle_time_is_filed_as_before(old):
    got, summary = old
    assert [n for n, _s in got["idle_gaps"]] == \
        [n for n, _s in summary["idle_gaps"]]
    assert [s for _n, s in got["idle_gaps"]] == pytest.approx(
        [s for _n, s in summary["idle_gaps"]], rel=1e-9)


def test_the_benchmark_reduction_reads_no_program_span(new):
    _got, summary = new
    assert {n for n, _s in summary["idle_gaps"]} <= {
        "launch", "copy_inputs", "outside_spans"}


@pytest.mark.parametrize("cell", ["vecadd", "sgemm"])
def test_transfer_reader_reads_the_counters_or_nothing(cell, monkeypatch):
    src = str(spec.ROOT / "src")
    if src not in sys.path:
        monkeypatch.syspath_prepend(src)
    from repro.core.backends import jaxgen
    read = spec.load_module(spec.HERE / "metrics" / f"transfer_mib.{cell}.py",
                            f"transfer_mib_{cell}").read
    tel = {"engaged": 4, "upload_bytes": 4 * 3 * 2**20,
           "download_bytes": 4 * 3 * 2**20}
    monkeypatch.setattr(jaxgen, "JAX_TELEMETRY", tel)
    assert read(None) == 6.0
    tel["engaged"] = 0
    assert read(None) is None
    monkeypatch.setattr(jaxgen, "JAX_TELEMETRY", {"engaged": 4})
    assert read(None) is None
