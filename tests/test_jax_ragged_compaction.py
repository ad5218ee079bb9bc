"""Row compaction of the jax rung's ragged ``vx_pred`` loops.

A ``vx_pred`` loop runs on a ladder of row counts (``jaxgen._ladder``):
as rows leave the loop, the live ones are gathered into fewer rows, and
put back in their places after it.  Certification still demands
bit-identical buffers and ``ExecStats``, so every case here compares
both with the oracle (``interp.launch(decoded=False)``), and reads the
loop counters of the program's counter pack:

  * the benchmark's Kronecker SpMV (``chipbench/configs/spmv_kron*``)
    at scales 10 and 12: bit-exact with its numpy reference, its stats
    the oracle's, and compactions made;
  * ``loop_rows_live`` and ``loop_trips`` against counts taken from the
    matrix alone;
  * the suite's ragged kernels, and a loop that reads an intrinsic and
    a scalar argument, which compaction has to gather too;
  * sgemm's k-loop, whose exit test is launch-uniform: one stage, no
    compaction, every row paid for live; vecadd, with no loop, carries
    no counters;
  * the generator: symmetric, no self-loops or duplicates, sorted
    columns, the same matrix from the same seed.
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


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"test_ragged_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def kron():
    """(reference module, compiled kernel) of the benchmark's
    ``spmv_kron`` configuration, loaded by path."""
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(str(ROOT))       # the reference imports chipbench
        ref = _load(CONFIGS / "spmv_kron_ref.py")
        kernel = _load(CONFIGS / "spmv_kron_kernel.py").KERNEL
    return ref, compile_kernel(kernel, use_disk_cache=False).fn


@pytest.fixture(autouse=True)
def _isolated(monkeypatch, tmp_path):
    monkeypatch.setenv("VOLT_CACHE_DIR", str(tmp_path / "volt"))
    monkeypatch.setenv("VOLT_DISK_CACHE", "0")
    # launches this small could be routed to the grid rung; every launch
    # here has to take the jax rung
    monkeypatch.setattr(jaxgen, "_ROUTE_MARGIN", 0.0)
    jaxgen.reset_jax_telemetry()


def _stats(st):
    return (st.instrs, {k: v for k, v in st.by_op.items() if v},
            st.mem_requests, st.mem_insts, st.shared_requests,
            st.atomic_serial, st.max_ipdom_depth, st.prints)


def _params(grid: int, block: int = 32):
    return dataclasses.replace(
        interp.LaunchParams(grid=grid, local_size=block, warp_size=32),
        fuel=FUEL)


def _oracle(fn, bufs, scalars, params):
    out = {k: v.copy() for k, v in bufs.items()}
    st = interp.launch(fn, out, params, scalar_args=scalars, decoded=False)
    return _stats(st), out


def _jax(fn, bufs, scalars, params):
    """Certification (where this shape class has no verdict yet), then
    the certified primary; the primary's stats and buffers.  The loop
    counters are the primary's alone."""
    rt = Runtime(jax=True)
    for step in ("certification", "primary"):
        if step == "primary":
            jaxgen.reset_jax_telemetry()
        out = {k: v.copy() for k, v in bufs.items()}
        st = rt.launch(fn, grid=params.grid, block=params.local_size,
                       scalar_args=scalars, buffers=out, fuel=FUEL)
    assert rt.last_report.executor == "jax"
    assert jaxgen.JAX_TELEMETRY["engaged"] == 1
    return _stats(st), out


def _assert_same(got, want, what):
    assert got[0] == want[0], f"{what}: ExecStats diverged"
    for k, v in want[1].items():
        assert got[1][k].tobytes() == v.tobytes(), f"{what}: buffer {k}"


def _loops():
    return {k: jaxgen.JAX_TELEMETRY[k] for k in jaxgen.LOOP_KEYS}


@pytest.mark.parametrize("scale", [10, 12])
def test_kron_bits_stats_and_compactions(kron, scale):
    ref, fn = kron
    bufs, scalars, grid = ref.make(np.random.default_rng(scale), scale)
    p = _params(grid)
    got = _jax(fn, bufs, scalars, p)
    assert got[1]["y"].tobytes() == \
        ref.reference(bufs, scalars)["y"].tobytes()
    _assert_same(got, _oracle(fn, bufs, scalars, p), f"kron {scale}")
    assert _loops()["loop_compactions"] > 0


def test_kron_loop_counters_match_the_matrix(kron):
    """A warp makes as many trips as its longest row, and a chunk as
    many as its longest warp: ``loop_rows_live`` is the sum over warps
    of the warp's longest row, ``loop_trips`` the sum over chunks of the
    chunk's longest.  Rows paid for lie between the live ones and what
    one stage of the chunk's width would pay."""
    ref, fn = kron
    bufs, scalars, grid = ref.make(np.random.default_rng(5), 12)
    _jax(fn, bufs, scalars, _params(grid))
    warp = np.diff(bufs["row_ptr"]).reshape(-1, 32).max(axis=1)
    cw = min(jaxgen._CHUNK_WGS, grid)
    chunk = np.array([warp[c:c + cw].max() for c in range(0, grid, cw)])
    lp = _loops()
    assert lp["loop_rows_live"] == warp.sum()
    assert lp["loop_trips"] == chunk.sum()
    assert lp["loop_rows_live"] <= lp["loop_rows_paid"] \
        < cw * lp["loop_trips"]


@pytest.mark.parametrize("name", ["spmv_csr", "spmv_tail", "bfs_frontier"])
def test_suite_ragged_loops_match_the_oracle(name):
    b = BENCHES[name]
    bufs, scalars, p = b.make(np.random.default_rng(7))
    fn = compile_kernel(b.handle, use_disk_cache=False).fn
    p = dataclasses.replace(p, fuel=FUEL)
    _assert_same(_jax(fn, bufs, scalars, p),
                 _oracle(fn, bufs, scalars, p), name)
    assert _loops()["loop_compactions"] > 0


@opencl.kernel
def ragged_mix(row_ptr: "ptr_i32 const", vals: "ptr_f32 const",
               y: "ptr_f32", n: "i32 uniform", scale: "f32 uniform"):
    gid = get_global_id(0)  # noqa: F821 - an intrinsic of the dialect
    if gid < n:
        acc = 0.0
        for e in range(row_ptr[gid], row_ptr[gid + 1]):
            acc += vals[e] * scale + get_local_id(0)  # noqa: F821
        y[gid] = acc


def test_compacted_loop_gathers_intrinsics_and_scalars():
    rng = np.random.default_rng(11)
    g = 64
    n = g * 32 - 5
    deg = rng.integers(0, 3, g * 32)
    deg[rng.uniform(size=g * 32) < 0.01] = 90
    row_ptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    bufs = {"row_ptr": row_ptr,
            "vals": rng.standard_normal(row_ptr[-1], dtype=np.float32),
            "y": np.zeros(g * 32, np.float32)}
    scalars = {"n": n, "scale": 0.75}
    fn = compile_kernel(ragged_mix, use_disk_cache=False).fn
    p = _params(g)
    _assert_same(_jax(fn, bufs, scalars, p),
                 _oracle(fn, bufs, scalars, p), "ragged_mix")
    assert _loops()["loop_compactions"] > 0


def test_sgemm_never_compacts():
    """sgemm's k-loop exits on ``i < k``, one value in every lane of the
    launch: every row makes the same trips, so the loop keeps one stage
    (the chunk loop and the k-loop are the program's only loops)."""
    b = BENCHES["sgemm"]
    bufs, scalars, p = b.make(np.random.default_rng(3))
    fn = compile_kernel(b.handle, use_disk_cache=False).fn
    p = dataclasses.replace(p, fuel=FUEL)
    _assert_same(_jax(fn, bufs, scalars, p),
                 _oracle(fn, bufs, scalars, p), "sgemm")
    lp = _loops()
    assert lp["loop_trips"] > 0 and lp["loop_compactions"] == 0
    assert lp["loop_rows_live"] == lp["loop_rows_paid"]
    rec = jaxgen._trace(fn, p, bufs, scalars, jaxgen._chunk_width(p))
    assert rec.jitted.lower(*rec.abstract).as_text().count(
        "stablehlo.while") == 2


def test_loop_free_kernel_carries_no_loop_counters():
    b = BENCHES["vecadd"]
    bufs, scalars, p = b.make(np.random.default_rng(3))
    fn = compile_kernel(b.handle, use_disk_cache=False).fn
    rec = jaxgen._trace(fn, p, bufs, scalars, jaxgen._chunk_width(p))
    assert rec.loop_keys == ()
    assert rec.abstract[-1][-1] == ()


@pytest.mark.parametrize("scale", [10, 12])
def test_kron_generator(kron, scale):
    ref, _ = kron
    bufs, scalars, grid = ref.make(np.random.default_rng(scale), scale)
    n = scalars["n"]
    row_ptr, nnz = bufs["row_ptr"], bufs["row_ptr"][-1]
    assert n == 2**scale and grid * 32 == n
    assert row_ptr.dtype == bufs["cols"].dtype == np.int32
    # every seed of a scale gives the same shapes, room for every edge
    assert len(bufs["cols"]) == len(bufs["vals"]) == ref.capacity(scale) \
        == 2 * ref.EDGEFACTOR * n
    assert row_ptr[0] == 0 and 0 < nnz <= ref.capacity(scale)
    assert not bufs["cols"][nnz:].any() and not bufs["vals"][nnz:].any()
    cols = bufs["cols"][:nnz]
    rows = np.repeat(np.arange(n), np.diff(row_ptr))
    # strictly increasing columns within each row: sorted, no duplicates
    same_row = rows[1:] == rows[:-1]
    assert (cols[1:][same_row] > cols[:-1][same_row]).all()
    assert (rows != cols).all()
    edges = rows.astype(np.int64) * n + cols
    assert np.array_equal(np.sort(cols.astype(np.int64) * n + rows), edges)
    again, _, _ = ref.make(np.random.default_rng(scale), scale)
    for k, v in bufs.items():
        assert v.tobytes() == again[k].tobytes(), k
    other, _, _ = ref.make(np.random.default_rng(scale + 1), scale)
    assert other["cols"].tobytes() != bufs["cols"].tobytes()
