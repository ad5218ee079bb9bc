"""Warp-affine global accesses as row windows on the jax rung.

The middle end gives each load and store whose index is warp-affine a
candidate class (``analysis.window_classes``): "window" (lane stride 1)
or "row_uniform" (lane stride 0).  The lowering checks a candidate's
form on every lane of the chunk on the device and serves it as one
slice of the chunk, a window a row, or an element a row broadcast; any
other chunk takes the element gather or scatter.  Every case here is
bit-exact with the oracle (``interp.launch(decoded=False)``) in buffers
and ``ExecStats``, and reads how many accesses each way served
(``mem_window`` / ``mem_gather`` of ``JAX_TELEMETRY``):

  * vecadd at a chunk multiple: every chunk one run;
  * vecadd with a last chunk whose lanes run out of bounds: that chunk
    falls back;
  * sgemm at 64: ``b`` a window a row, ``a`` broadcast; at 48, where a
    warp straddles two rows of the matrix, both fall back;
  * a stride-2 load, which gets no class;
  * a masked window store, whose lanes not written keep their values;
  * the benchmark's Kronecker SpMV: its data-dependent loop loads stay
    gathers;
  * a verdict certified for the gather-only program does not promote
    this one.
"""
import dataclasses
import importlib.util
from pathlib import Path

import numpy as np
import pytest

from repro.core import interp
from repro.core.backends import jaxgen
from repro.core.frontends import opencl
from repro.core.runtime import Runtime, compile_kernel
from repro.volt_bench import BENCHES

ROOT = Path(__file__).resolve().parents[1]
CONFIGS = ROOT / "chipbench" / "configs"
FUEL = 2**30


@pytest.fixture(autouse=True)
def _isolated(monkeypatch, tmp_path):
    monkeypatch.setenv("VOLT_CACHE_DIR", str(tmp_path / "volt"))
    monkeypatch.setenv("VOLT_DISK_CACHE", "0")
    # launches this small could be routed to the grid rung; every launch
    # here has to take the jax rung
    monkeypatch.setattr(jaxgen, "_ROUTE_MARGIN", 0.0)
    jaxgen.reset_jax_telemetry()


@opencl.kernel
def stride2(x: "ptr_f32 const", y: "ptr_f32", n: "i32 uniform"):
    gid = get_global_id(0)  # noqa: F821 - an intrinsic of the dialect
    if gid < n:
        y[gid] = x[2 * gid]


@opencl.kernel
def masked_store(x: "ptr_f32 const", y: "ptr_f32", n: "i32 uniform"):
    gid = get_global_id(0)  # noqa: F821 - an intrinsic of the dialect
    if gid % 3 != 0:
        y[gid] = x[gid] * 2.0


def _stats(st):
    return (st.instrs, {k: v for k, v in st.by_op.items() if v},
            st.mem_requests, st.mem_insts, st.shared_requests,
            st.atomic_serial, st.max_ipdom_depth, st.prints)


def _params(grid: int):
    return dataclasses.replace(
        interp.LaunchParams(grid=grid, local_size=32, warp_size=32),
        fuel=FUEL)


def _bench(name: str, size: int):
    b = BENCHES[name]
    bufs, scalars, p = b.make(np.random.default_rng(size), size=size)
    return (compile_kernel(b.handle, use_disk_cache=False).fn, bufs,
            scalars, dataclasses.replace(p, fuel=FUEL))


def _own(kernel, n: int, x_len: int):
    rng = np.random.default_rng(n)
    bufs = {"x": rng.standard_normal(x_len, dtype=np.float32),
            "y": rng.standard_normal(n, dtype=np.float32)}
    return (compile_kernel(kernel, use_disk_cache=False).fn, bufs,
            {"n": n}, _params(n // 32))


#: case -> (launch, (mem_window, mem_gather) of the primary launch)
CASES = {
    # 512 workgroups, two chunks of three windows
    "vecadd-run": (lambda: _bench("vecadd", 2**14), (6, 0)),
    # 513: the third chunk has one workgroup, its other rows out of
    # place, and its last lanes past n
    "vecadd-tail": (lambda: _bench("vecadd", 2**14 + 5), (6, 3)),
    # one chunk, 64 trips: a broadcast and a window a trip, one store
    "sgemm-64": (lambda: _bench("sgemm", 64), (1 + 2 * 64, 0)),
    # n = 48: the loads of every trip fall back; the store is one run
    "sgemm-48": (lambda: _bench("sgemm", 48), (1, 2 * 48)),
    "stride-2": (lambda: _own(stride2, 2**13, 2**14), (1, 1)),
    "masked-store": (lambda: _own(masked_store, 2**13, 2**13), (2, 0)),
}


def _oracle(fn, bufs, scalars, params):
    out = {k: v.copy() for k, v in bufs.items()}
    st = interp.launch(fn, out, params, scalar_args=scalars, decoded=False)
    return _stats(st), out


def _jax(fn, bufs, scalars, params):
    """Certification, then the certified primary; the primary's stats
    and buffers, and its counters alone in the telemetry."""
    rt = Runtime(jax=True)
    for step in ("certification", "primary"):
        if step == "primary":
            jaxgen.reset_jax_telemetry()
        out = {k: v.copy() for k, v in bufs.items()}
        st = rt.launch(fn, grid=params.grid, block=params.local_size,
                       scalar_args=scalars, buffers=out, fuel=params.fuel)
    assert rt.last_report.executor == "jax"
    assert jaxgen.JAX_TELEMETRY["engaged"] == 1
    return _stats(st), out


def _assert_same(got, want, what):
    assert got[0] == want[0], f"{what}: ExecStats diverged"
    for k, v in want[1].items():
        assert got[1][k].tobytes() == v.tobytes(), f"{what}: buffer {k}"


def _served():
    t = jaxgen.JAX_TELEMETRY
    return t["mem_window"], t["mem_gather"]


@pytest.mark.parametrize("case", sorted(CASES))
def test_windows_bits_and_counters(case):
    make, want = CASES[case]
    fn, bufs, scalars, p = make()
    _assert_same(_jax(fn, bufs, scalars, p),
                 _oracle(fn, bufs, scalars, p), case)
    assert _served() == want


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"test_windows_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kron_loop_loads_stay_gathers():
    """``vals[e]``, ``cols[e]`` and ``x[cols[e]]`` have no class: every
    trip gathers three times.  The chunk's ``row_ptr`` loads and the
    ``y`` store are one run each."""
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(str(ROOT))       # the reference imports chipbench
        ref = _load(CONFIGS / "spmv_kron_ref.py")
        kernel = _load(CONFIGS / "spmv_kron_kernel.py").KERNEL
    fn = compile_kernel(kernel, use_disk_cache=False).fn
    bufs, scalars, grid = ref.make(np.random.default_rng(10), 10)
    p = _params(grid)
    _assert_same(_jax(fn, bufs, scalars, p),
                 _oracle(fn, bufs, scalars, p), "kron 10")
    win, gat = _served()
    assert win == 3 * -(-grid // jaxgen._CHUNK_WGS)
    assert gat == 3 * jaxgen.JAX_TELEMETRY["loop_trips"]
    assert win / (win + gat) < 0.01


def test_gather_only_verdict_does_not_promote(monkeypatch):
    fn, bufs0, scalars, p = _bench("vecadd", 2**14)
    cw = jaxgen._chunk_width(p)
    sig = jaxgen._shape_sig(p, bufs0, scalars, cw)
    with monkeypatch.context() as m:
        m.setattr(jaxgen, "_PROGRAM", "device-chunk-loop")
        old = jaxgen._shape_sig(p, bufs0, scalars, cw)
    assert old != sig
    # a "pass" on record for the program that gathers every access: the
    # windowed program is certified anew before it serves a launch
    fn._jax_certs = (fn.ir_version, {old: ("pass", 1.0, 2.0, "")})
    rt = Runtime(jax=True)
    bufs = {k: v.copy() for k, v in bufs0.items()}
    st = rt.launch(fn, grid=p.grid, block=p.local_size, scalar_args=scalars,
                   buffers=bufs, fuel=p.fuel)
    t = jaxgen.JAX_TELEMETRY
    assert (t["cert_runs"], t["engaged"]) == (1, 0)
    assert fn._jax_certs[1][sig][0] == "pass"
    _assert_same((_stats(st), bufs), _oracle(fn, bufs0, scalars, p),
                 "certification of the windowed program")
