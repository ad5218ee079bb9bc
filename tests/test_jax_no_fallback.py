"""The jax rung never hides the device behind a silent fallback.

  * a backend compile error is a compile failure — counted under its own
    telemetry key, its text in the ``LaunchReport``, retried on the next
    launch — never a cached licence refusal;
  * an exception of the jitted program during certification is kept
    with the verdict ("error", with its text), never turned into a plain
    mismatch; a real mismatch names the first differing element;
  * verdicts are keyed by the device they were certified on: a ``.vjc``
    verdict recorded under another platform is not used, and the launch
    certifies again.
"""
import numpy as np
import pytest

from repro.core import diskcache
from repro.core.backends import jaxgen
from repro.core.passes.pipeline import ABLATION_LADDER, run_pipeline
from repro.core.runtime import Runtime
from repro.volt_bench import BENCHES


def _fresh(name="vecadd"):
    """A freshly compiled kernel (no trace or verdict caches) and its
    suite-size inputs with the numpy reference."""
    b = BENCHES[name]
    fn = run_pipeline(b.handle.build(None), b.handle.name,
                      ABLATION_LADDER[-1]).fn
    bufs, scalars, params = b.make(np.random.default_rng(0))
    ref = b.ref({k: v.copy() for k, v in bufs.items()}, scalars)
    return fn, bufs, scalars, params, ref


def _launch(rt, fn, bufs, scalars, params):
    return rt.launch(fn, grid=params.grid, block=params.local_size,
                     scalar_args=scalars, buffers=bufs)


@pytest.fixture(autouse=True)
def _isolated(monkeypatch, tmp_path):
    monkeypatch.setenv("VOLT_CACHE_DIR", str(tmp_path))
    jaxgen.reset_jax_telemetry()


def test_compile_error_is_reported_not_refused(monkeypatch):
    fn, bufs, scalars, params, ref = _fresh()
    fast = jaxgen._TIER_OPTIONS["fast"]
    monkeypatch.setitem(jaxgen._TIER_OPTIONS, "fast",
                        {"xla_no_such_compile_option": 1})
    rt = Runtime(jax=True)
    _launch(rt, fn, bufs, scalars, params)
    rep = rt.last_report
    first = rep.attempts[0]
    assert (first.rung, first.outcome) == ("jax", "engine_fault")
    assert "compile failed" in first.reason
    assert "xla_no_such_compile_option" in first.reason
    assert rep.executor == "grid"
    np.testing.assert_array_equal(bufs["z"], ref["z"])
    t = jaxgen.JAX_TELEMETRY
    assert t["compile_errors"] == 1
    assert t["refusals"] == 0 and t["cert_runs"] == 0
    assert jaxgen.describe(fn, params, bufs, scalars)["refused"] is None

    # not cached: the next launch compiles again, and once the compiler
    # accepts the program the rung certifies it
    monkeypatch.setitem(jaxgen._TIER_OPTIONS, "fast", fast)
    _launch(rt, fn, bufs, scalars, params)
    assert t["compile_errors"] == 1 and t["cert_runs"] == 1
    assert jaxgen.describe(fn, params, bufs, scalars)["verdict"] == "pass"


def test_licence_check_names_a_compile_error(monkeypatch):
    fn, bufs, scalars, params, _ = _fresh()
    monkeypatch.setitem(jaxgen._TIER_OPTIONS, "fast",
                        {"xla_no_such_compile_option": 1})
    ok, why = jaxgen.licence_check(fn, params, bufs, scalars)
    assert not ok and "compile failed" in why


def test_device_exception_during_certification_is_an_error_verdict(
        monkeypatch):
    fn, bufs, scalars, params, ref = _fresh()

    def lost(*a, **k):
        raise RuntimeError("device halted")

    monkeypatch.setattr(jaxgen, "_run", lost)
    rt = Runtime(jax=True)
    _launch(rt, fn, bufs, scalars, params)
    # the launch's results come from the normal chain, bit-exact
    np.testing.assert_array_equal(bufs["z"], ref["z"])
    d = jaxgen.describe(fn, params, bufs, scalars)
    assert d["verdict"] == "error"
    assert "fast tier raised RuntimeError: device halted" in d["detail"]
    assert "exact tier raised RuntimeError: device halted" in d["detail"]
    assert jaxgen.JAX_TELEMETRY["cert_errors"] == 2
    assert jaxgen.JAX_TELEMETRY["certified"] == 0
    # an "error" verdict keeps the pair off the rung, like "fail"
    _launch(rt, fn, bufs, scalars, params)
    assert rt.last_report.executor == "grid"
    assert jaxgen.JAX_TELEMETRY["cert_runs"] == 1


def test_mismatch_names_the_first_differing_element(monkeypatch):
    fn, bufs, scalars, params, _ = _fresh()
    real_run = jaxgen._run

    def one_ulp_off(*a, **k):
        host_bufs, jstats = real_run(*a, **k)
        z = host_bufs["z"].copy()
        z.view(np.int32)[3] += 1
        return {**host_bufs, "z": z}, jstats

    monkeypatch.setattr(jaxgen, "_run", one_ulp_off)
    _launch(Runtime(jax=True), fn, bufs, scalars, params)
    d = jaxgen.describe(fn, params, bufs, scalars)
    assert d["verdict"] == "fail"
    assert "exact tier: z[3]:" in d["detail"]
    assert "1 ulp" in d["detail"]
    assert jaxgen.JAX_TELEMETRY["cert_errors"] == 0


def test_verdict_of_another_platform_is_not_used(monkeypatch):
    fn, bufs, scalars, params, _ = _fresh()
    here = jaxgen._device_key()
    other = ("tpu", "TPU v5 lite", here[2])
    monkeypatch.setattr(jaxgen, "_device_key", lambda: other)
    rt = Runtime(jax=True)
    _launch(rt, fn, bufs, scalars, params)       # certifies under `other`
    assert jaxgen.JAX_TELEMETRY["certified"] == 1
    monkeypatch.setattr(jaxgen, "_device_key", lambda: here)

    # a new process: the verdict comes back from the .vjc file only
    fn2, bufs2, scalars2, params2, ref2 = _fresh()
    stored = diskcache.load_verdicts(fn2)
    assert len(stored) == 1 and repr(other) in next(iter(stored))
    _launch(rt, fn2, bufs2, scalars2, params2)
    assert jaxgen.JAX_TELEMETRY["cert_runs"] == 2
    assert rt.last_report.executor == "grid"     # certification run
    np.testing.assert_array_equal(bufs2["z"], ref2["z"])
    assert len(diskcache.load_verdicts(fn2)) == 2
    # under its own key the new verdict promotes the launch
    _launch(rt, fn2, bufs2, scalars2, params2)
    assert rt.last_report.executor == "jax"
    assert jaxgen.JAX_TELEMETRY["cert_runs"] == 2
