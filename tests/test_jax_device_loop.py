"""The jax rung's chunk loop runs inside one device program.

  * a launch with no deadline armed makes one executable call, whatever
    the chunk width; with a deadline armed the same executable is
    stepped a chunk at a time (``JAX_TELEMETRY["dispatches"]``);
  * either way buffers and ``ExecStats`` are bit-identical to the
    oracle, on a streaming kernel and on a ragged ``vx_pred`` loop;
  * a ``jax.exec`` fault after the single call returns discards the
    staged device buffers and leaves the host buffers untouched, and
    the launch demotes to the grid rung bit-exactly;
  * verdicts certified for the per-chunk program layout do not promote
    the looped program.
"""
import numpy as np
import pytest

from repro.core import faults, interp
from repro.core.backends import jaxgen
from repro.core.passes.pipeline import ABLATION_LADDER, run_pipeline
from repro.core.runtime import Runtime
from repro.core.vir import Op
from repro.volt_bench import BENCHES

#: elements (vecadd) and rows (spmv_csr): 512 workgroups of 32, so every
#: chunk width below splits the grid into more than one chunk
SIZES = {"vecadd": 2**14, "spmv_csr": 2**14}

_FNS: dict = {}
_ORACLES: dict = {}


@pytest.fixture(autouse=True)
def _isolated(monkeypatch, tmp_path):
    monkeypatch.setenv("VOLT_CACHE_DIR", str(tmp_path / "volt"))
    monkeypatch.setenv("VOLT_DISK_CACHE", "0")
    # the small-launch router compares two host timings and may send a
    # launch this small to the grid rung; every launch here has to take
    # the jax rung
    monkeypatch.setattr(jaxgen, "_ROUTE_MARGIN", 0.0)
    jaxgen.reset_jax_telemetry()


def _inputs(name: str):
    """(fn, bufs, scalars, params); ``fn`` is compiled once."""
    b = BENCHES[name]
    if name not in _FNS:
        _FNS[name] = run_pipeline(b.handle.build(None), b.handle.name,
                                  ABLATION_LADDER[-1]).fn
    bufs, scalars, params = b.make(np.random.default_rng(3),
                                   size=SIZES[name])
    return _FNS[name], bufs, scalars, params


def _case(name: str):
    """``_inputs`` with the jax caches of ``fn`` dropped for a cold
    start, and the oracle's run made."""
    _oracle(name)
    fn, bufs, scalars, params = _inputs(name)
    for attr in ("_jaxgen_cache", "_jax_certs"):
        if hasattr(fn, attr):
            delattr(fn, attr)
    return fn, bufs, scalars, params


def _stats_tuple(st):
    return (st.instrs, {k: v for k, v in st.by_op.items() if v},
            st.mem_requests, st.mem_insts, st.shared_requests,
            st.atomic_serial, st.max_ipdom_depth, st.prints)


def _oracle(name: str):
    if name not in _ORACLES:
        fn, bufs, scalars, params = _inputs(name)
        st = interp.launch(fn, bufs, params, scalar_args=scalars,
                           decoded=False)
        _ORACLES[name] = (_stats_tuple(st), bufs)
    return _ORACLES[name]


def _assert_oracle(name, st, bufs, what):
    want_st, want_bufs = _oracle(name)
    assert _stats_tuple(st) == want_st, f"{what}: ExecStats diverged"
    for k, v in want_bufs.items():
        np.testing.assert_array_equal(bufs[k], v,
                                      err_msg=f"{what}: buffer {k}")


def _launch(rt, fn, bufs, scalars, params, deadline_ms=None):
    return rt.launch(fn, grid=params.grid, block=params.local_size,
                     scalar_args=scalars, buffers=bufs,
                     deadline_ms=deadline_ms)


def test_ragged_case_has_a_pred_loop():
    fn = _case("spmv_csr")[0]
    assert any(i.op is Op.PRED for i in fn.instructions())


@pytest.mark.parametrize("deadline_ms", [None, 600_000.0],
                         ids=["no-deadline", "deadline"])
@pytest.mark.parametrize("chunk", [1, 3, 256])
@pytest.mark.parametrize("name", sorted(SIZES))
def test_calls_per_launch_and_bits(monkeypatch, name, chunk, deadline_ms):
    monkeypatch.setattr(jaxgen, "_CHUNK_WGS", chunk)
    fn, bufs0, scalars, params = _case(name)
    n_wg = params.grid * params.grid_y
    chunks = -(-n_wg // min(chunk, n_wg))
    assert chunks > 1
    rt = Runtime(jax=True)
    for rep in ("certification", "primary"):
        bufs = {k: v.copy() for k, v in bufs0.items()}
        st = _launch(rt, fn, bufs, scalars, params, deadline_ms)
        _assert_oracle(name, st, bufs, f"{name} chunk={chunk} {rep}")
    assert rt.last_report.executor == "jax"
    t = jaxgen.JAX_TELEMETRY
    assert t["certified"] == 1 and t["engaged"] == 1
    assert t["dispatches"] == (1 if deadline_ms is None else chunks)


def test_exec_fault_after_the_call_leaves_host_buffers(monkeypatch):
    """Default chunk width: the site is checked before the one call and
    again after it returns; a fault at the second check comes after
    the device ran the whole grid, and still discards its results."""
    name = "spmv_csr"
    fn, bufs0, scalars, params = _case(name)
    rec = jaxgen._trace(fn, params, bufs0, scalars,
                        jaxgen._chunk_width(params))
    rec.lowered = rec.jitted.lower(*rec.abstract)
    exe = rec.executable("fast")
    calls = []
    rec.tiers["fast"] = lambda *a: (calls.append(1), exe(*a))[1]
    bufs = {k: v.copy() for k, v in bufs0.items()}
    with faults.rung("jax"), faults.inject("jax.exec", after=1) as inj:
        with pytest.raises(faults.InjectedFault):
            jaxgen._run(rec, fn, bufs, scalars, params)
    assert (inj.hits, inj.fired, len(calls)) == (2, 1, 1)
    for k, v in bufs0.items():
        assert bufs[k].tobytes() == v.tobytes(), f"buffer {k} written"

    # through the chain: certified primary faults, the grid rung serves
    rt = Runtime(jax=True)
    bufs = {k: v.copy() for k, v in bufs0.items()}
    _launch(rt, fn, bufs, scalars, params)          # certification
    jaxgen.reset_jax_telemetry()
    bufs = {k: v.copy() for k, v in bufs0.items()}
    with faults.inject("jax.exec", after=1) as inj:
        st = _launch(rt, fn, bufs, scalars, params)
    rep = rt.last_report
    assert inj.fired == 1
    assert rep.attempts[0].rung == "jax"
    assert rep.attempts[0].outcome == "engine_fault"
    assert rep.executor == "grid"
    assert jaxgen.JAX_TELEMETRY["engaged"] == 0
    assert jaxgen.JAX_TELEMETRY["dispatches"] == 0
    _assert_oracle(name, st, bufs, "demoted after the call")


def test_per_chunk_layout_verdict_does_not_promote():
    name = "vecadd"
    fn, bufs0, scalars, params = _case(name)
    cw = jaxgen._chunk_width(params)
    p = params
    per_chunk = repr((jaxgen._device_key(),
                      p.grid, p.grid_y, p.local_size, p.local_size_y,
                      p.warp_size, p.fuel, bool(p.strict_oob_loads), cw,
                      tuple(sorted((nm, tuple(b.shape), b.dtype.name)
                                   for nm, b in bufs0.items())),
                      tuple(sorted(scalars))))
    sig = jaxgen._shape_sig(params, bufs0, scalars, cw)
    assert sig != per_chunk and repr(jaxgen._PROGRAM) in sig
    # a "pass" on record for the per-chunk layout: the looped program
    # is certified anew before it serves a launch
    fn._jax_certs = (fn.ir_version, {per_chunk: ("pass", 1.0, 2.0, "")})
    rt = Runtime(jax=True)
    bufs = {k: v.copy() for k, v in bufs0.items()}
    st = _launch(rt, fn, bufs, scalars, params)
    assert jaxgen.JAX_TELEMETRY["cert_runs"] == 1
    assert jaxgen.JAX_TELEMETRY["engaged"] == 0
    assert fn._jax_certs[1][sig][0] == "pass"
    _assert_oracle(name, st, bufs, "certification of the looped program")
