"""Metamorphic tests for the grid-level batcher's scheduling freedoms.

The grid executor earns its speed from three internal degrees of freedom
that must all be semantically invisible:

  * CHUNKING — a launch is split into (#wg x warps/wg)-row batches of at
    most ``interp._GRID_BATCH_MAX`` rows; results must not depend on
    where the chunk boundaries fall ({1, 3, 64} sweeps both the
    degenerate one-workgroup-per-batch case and odd boundaries);
  * COMPACTION — when ride-along leaves most rows empty, live rows move
    into a dense sub-batch (``interp._COMPACT_FRACTION``); results must
    be identical with compaction off (0.0), default (0.25) and maximally
    eager (1.0);
  * RE-MERGE — desynced workgroups rejoin lockstep at congruent
    top-level barriers; parity across warps/wg shapes exercises it.

Each sweep asserts BIT-identical ExecStats + buffers against the
``decoded=False`` oracle, so any schedule leak — a store resolving in
batch order instead of workgroup order, a fabricated barrier arrival, a
resurrected compacted row — fails loudly.  A workgroup-permutation test
adds the classic metamorphic relation: permuting which workgroup owns
which CSR row must permute the output the same way, bit for bit.

The jax-codegen rung (PR 8) sits one level up and has its own internal
freedoms, swept in the same style: host-loop CHUNK WIDTH
(``jaxgen._CHUNK_WGS`` — part of the certification shape signature, so
every width retraces AND re-certifies from scratch), trace/cert CACHE
temperature (cold-compile, hot-cache and re-cold runs must be
bit-identical), and the ``jax.disable_jit()`` escape hatch (eager
op-by-op execution of the traced chunk function must match both the
AOT-compiled executable and the oracle).

Deterministic sweeps run everywhere; a hypothesis section fuzzes ragged
trip vectors, grid shapes and config combinations, plus the jax rung's
distinct-cache-line counting against ``interp_mem.reference_counting``
(skipped without hypothesis; CI installs it from requirements-dev.txt
and caps the example budget via VOLT_HYPOTHESIS_MAX_EXAMPLES).
"""
import os
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent / "kernels"))

import jax
import jax.numpy as jnp

from repro.core import interp, interp_mem
from repro.core.backends import jaxgen
from repro.core.passes.pipeline import ABLATION_LADDER, run_pipeline
from repro.volt_bench import BENCHES

import rungs
import volt_kernels as K

FULL = ABLATION_LADDER[-1]

_CK_CACHE = {}


def _compiled(handle, name):
    fn = _CK_CACHE.get(name)
    if fn is None:
        fn = run_pipeline(handle.build(None), handle.name, FULL).fn
        _CK_CACHE[name] = fn
    return fn


def _stats_tuple(st: interp.ExecStats):
    return (st.instrs, dict(st.by_op), st.mem_requests, st.mem_insts,
            st.shared_requests, st.atomic_serial, st.max_ipdom_depth,
            st.prints)


def _launch(fn, bufs0, params, scalars, **kw):
    bufs = {k: v.copy() for k, v in bufs0.items()}
    st = rungs.launch(fn, bufs, params, scalar_args=scalars, **kw)
    return _stats_tuple(st), bufs


def _assert_same(name, a, b):
    assert a[0] == b[0], f"{name}: ExecStats diverged"
    for k in a[1]:
        np.testing.assert_array_equal(a[1][k], b[1][k],
                                      err_msg=f"{name}: buffer {k}")


def _ragged_cases(seed=7):
    """(name, fn, bufs, scalars, params) for the grid-mode targets."""
    rng = np.random.default_rng(seed)
    out = []
    for bname in ("spmv_csr", "spmv_tail", "bfs_frontier"):
        b = BENCHES[bname]
        bufs, sc, params = b.make(rng)
        out.append((bname, _compiled(b.handle, bname), bufs, sc, params))
    return out


# --------------------------------------------------------------------------
# deterministic sweeps (always run)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [1, 3, 64])
@pytest.mark.parametrize("factor", [1, 2, 4])
def test_chunk_size_invariance(monkeypatch, chunk, factor):
    """Results must not depend on where grid-chunk boundaries fall, at
    any warps/wg (chunk=1 degenerates to one workgroup per batch, which
    for multi-warp folds still exercises per-wg barrier groups)."""
    monkeypatch.setattr(interp, "_GRID_BATCH_MAX", chunk)
    for name, fn, bufs, sc, params in _ragged_cases():
        p = interp.fold_warps(params, factor)
        oracle = _launch(fn, bufs, p, sc, decoded=False)
        got = _launch(fn, bufs, p, sc, grid=True)
        _assert_same(f"{name} x{factor} chunk={chunk}", oracle, got)


@pytest.mark.parametrize("fraction", [0.0, 0.25, 1.0])
@pytest.mark.parametrize("factor", [1, 2])
def test_compaction_threshold_invariance(monkeypatch, fraction, factor):
    """Compaction off / default / maximally eager must be bit-invisible
    (min-wgs floor lowered so small test grids can compact at all)."""
    monkeypatch.setattr(interp, "_COMPACT_FRACTION", fraction)
    monkeypatch.setattr(interp, "_COMPACT_MIN_WGS", 2)
    for name, fn, bufs, sc, params in _ragged_cases():
        p = interp.fold_warps(params, factor)
        oracle = _launch(fn, bufs, p, sc, decoded=False)
        got = _launch(fn, bufs, p, sc, grid=True)
        _assert_same(f"{name} x{factor} compact={fraction}", oracle, got)


@pytest.mark.parametrize("factor", [1, 2])
def test_workgroup_permutation(factor):
    """Permuting which workgroup owns which CSR row permutes the output
    identically: y'[i] == y[perm[i]] bit for bit (per-row accumulation
    order is preserved, only the row-to-workgroup assignment moves)."""
    rng = np.random.default_rng(11)
    b = BENCHES["spmv_tail"]
    bufs, sc, params = b.make(rng)
    fn = _compiled(b.handle, "spmv_tail")
    n = sc["n"]
    p = interp.fold_warps(params, factor)
    _, out1 = _launch(fn, bufs, p, sc, grid=True)

    # thread-level permutation moving whole 32-thread workgroup blocks
    wg_perm = rng.permutation(params.grid)
    tperm = (wg_perm[:, None] * params.local_size
             + np.arange(params.local_size)).ravel()
    rp, cols, vals = bufs["row_ptr"], bufs["cols"], bufs["vals"]
    deg = np.diff(rp)
    deg2 = deg[tperm]
    rp2 = np.zeros(n + 1, np.int32)
    rp2[1:] = np.cumsum(deg2)
    cols2 = np.zeros_like(cols)
    vals2 = np.zeros_like(vals)
    for i in range(n):
        src = tperm[i]
        cols2[rp2[i]:rp2[i + 1]] = cols[rp[src]:rp[src + 1]]
        vals2[rp2[i]:rp2[i + 1]] = vals[rp[src]:rp[src + 1]]
    bufs2 = dict(bufs, row_ptr=rp2, cols=cols2, vals=vals2)
    _, out2 = _launch(fn, bufs2, p, sc, grid=True)
    np.testing.assert_array_equal(out2["y"], out1["y"][tperm],
                                  err_msg="permuted grid output")


def test_remerge_fires_and_stays_exact(monkeypatch):
    """Crafted workload for the desync re-merge: a multi-warp grid of a
    barrier-in-loop kernel with per-WORKGROUP-uniform but cross-workgroup
    ragged trips.  Ride-along is off (barrier function, multi-warp), so
    every trip-count disagreement desyncs; the batch must re-merge at
    the loop barrier instead of draining, and stay bit-exact."""
    fn = _compiled(K.ragged_barrier_loop, "ragged_barrier_loop")
    rng = np.random.default_rng(5)
    W, n_warps, grid = 32, 2, 6
    local = n_warps * W
    total = grid * local
    params = interp.LaunchParams(grid=grid, local_size=local, warp_size=W)
    trips = np.repeat(rng.integers(1, 6, grid), local).astype(np.int32)
    bufs = {"trip": trips,
            "x": rng.standard_normal(total).astype(np.float32),
            "out": np.zeros(total, np.float32)}
    sc = {"n": total}
    t = interp.GRID_TELEMETRY
    t.reset()
    oracle = _launch(fn, bufs, params, sc, decoded=False)
    got = _launch(fn, bufs, params, sc, grid=True)
    _assert_same("remerge barrier loop", oracle, got)
    assert t.desyncs > 0, "crafted workload must desync"
    assert t.remerges > 0, "desynced workgroups must re-merge at the " \
                           "congruent loop barrier"


def test_compaction_fires_and_stays_exact(monkeypatch):
    """Crafted workload for row compaction: the pareto-tail CSR leaves a
    handful of workgroups looping hundreds of trips after the rest of
    the chunk went empty — the live-row fraction must cross the
    threshold, compaction must fire, and results stay bit-exact."""
    monkeypatch.setattr(interp, "_COMPACT_MIN_WGS", 4)
    b = BENCHES["spmv_tail"]
    rng = np.random.default_rng(7)
    bufs, sc, params = b.make(rng)
    fn = _compiled(b.handle, "spmv_tail")
    t = interp.GRID_TELEMETRY
    for factor in (1, 2):
        p = interp.fold_warps(params, factor)
        t.reset()
        oracle = _launch(fn, bufs, p, sc, decoded=False)
        got = _launch(fn, bufs, p, sc, grid=True)
        _assert_same(f"compaction x{factor}", oracle, got)
        assert t.compactions > 0, \
            f"x{factor}: pareto-tail workload must compact"


def test_2d_launch_licenses_runahead(monkeypatch):
    """The widened affine licence: a kernel whose store index is the
    full 2-D linear id (gid_x + gid_y * global_size(0)) keeps re-merge
    and row compaction on 2-D launches — before PR 5 any grid_y > 1
    launch forced the exact drain-to-completion path.  A pareto-tail
    trip distribution over a (4 x 3)-workgroup grid must compact, and
    stay bit-identical to the oracle."""
    monkeypatch.setattr(interp, "_COMPACT_MIN_WGS", 2)
    fn = _compiled(K.ragged2d, "ragged2d")
    prog = interp._decode_batched(fn, 32, False, 4, grid_mode=True,
                                  wg_rows=1)
    assert prog.private_stores_2d, "2-D linear-id chain must classify"
    rng = np.random.default_rng(11)
    params = interp.LaunchParams(grid=4, local_size=32, warp_size=32,
                                 grid_y=3)
    total = 4 * 32 * 3
    trip = rng.integers(0, 40, total).astype(np.int32)
    trip[rng.uniform(0, 1, total) < 0.9] = 0    # few hot threads
    bufs = {"trip": trip,
            "x": rng.standard_normal(total).astype(np.float32),
            "out": np.zeros(total, np.float32)}
    sc = {"n": total}
    t = interp.GRID_TELEMETRY
    t.reset()
    oracle = _launch(fn, bufs, params, sc, decoded=False)
    got = _launch(fn, bufs, params, sc, grid=True)
    _assert_same("ragged2d 2-D compaction", oracle, got)
    assert t.compactions > 0, \
        "2-D launch with a 2-D-injective store must still compact"
    # a kernel with a BARE gid_x store (spmv_csr: 1-D privacy only)
    # must NOT run ahead on a 2-D launch: the 1-D licence collapses
    # when gid_x repeats across gy (threads at gy > 0 redo gy == 0's
    # work bit-identically, so parity still holds — just via the exact
    # drain path)
    fn1 = _compiled(BENCHES["spmv_csr"].handle, "spmv_csr")
    prog1 = interp._decode_batched(fn1, 32, False, 4, grid_mode=True,
                                   wg_rows=1)
    assert prog1.private_stores and not prog1.private_stores_2d
    nx = 4 * 32
    deg = rng.integers(0, 30, nx)
    deg[rng.uniform(0, 1, nx) < 0.85] = 0
    rp = np.zeros(nx + 1, np.int32)
    rp[1:] = np.cumsum(deg)
    bufs1 = {"row_ptr": rp,
             "cols": rng.integers(0, nx, int(rp[-1])).astype(np.int32),
             "vals": rng.standard_normal(int(rp[-1])).astype(np.float32),
             "x": rng.standard_normal(nx).astype(np.float32),
             "y": np.zeros(nx, np.float32)}
    t.reset()
    oracle1 = _launch(fn1, bufs1, params, {"n": nx}, decoded=False)
    got1 = _launch(fn1, bufs1, params, {"n": nx}, grid=True)
    _assert_same("spmv_csr 2-D exact drain", oracle1, got1)
    assert t.compactions == 0, \
        "bare gid_x stores must not license run-ahead on a 2-D grid"


def test_shared_tiles_survive_compaction(monkeypatch):
    """Row compaction on a private-shared-tile kernel: the live
    sub-batch must carry its workgroups' TILE rows into the dense
    sub-batch (and the dead sub-batch its own), so post-compaction
    tile reads still see each workgroup's private state — bit-exact
    against the oracle, with the compaction counter proving the path
    actually ran."""
    monkeypatch.setattr(interp, "_COMPACT_MIN_WGS", 4)
    fn = _compiled(K.shared_tail, "shared_tail")
    prog = interp._decode_batched(fn, 32, False, 4, grid_mode=True,
                                  wg_rows=1)
    assert prog.order_free and prog.private_stores, \
        "shared-tile stores must be exempt from the privacy scan"
    rng = np.random.default_rng(3)
    g = 16
    total = g * 32
    params = interp.LaunchParams(grid=g, local_size=32, warp_size=32)
    trip = rng.integers(0, 4, total).astype(np.int32)
    hot = rng.integers(0, g, 2)      # two hot workgroups loop long
    for h in hot:
        trip[h * 32 + 3] = 200
    bufs = {"trip": trip,
            "x": rng.standard_normal(total).astype(np.float32),
            "out": np.zeros(total, np.float32)}
    sc = {"n": total}
    t = interp.GRID_TELEMETRY
    t.reset()
    oracle = _launch(fn, bufs, params, sc, decoded=False)
    got = _launch(fn, bufs, params, sc, grid=True)
    _assert_same("shared_tail compaction", oracle, got)
    assert t.compactions > 0, \
        "pareto-tail shared-tile workload must compact"


def test_grid_shared_tiles_survive_config_sweeps(monkeypatch):
    """Private-shared grid batching under the scheduling-freedom sweeps:
    chunk size and workgroup count must be invisible for tile kernels
    too (tiles travel with their workgroup through desync slicing and
    sub-batch gathering)."""
    for chunk in (1, 3, 64):
        monkeypatch.setattr(interp, "_GRID_BATCH_MAX", chunk)
        for bname in ("reduce0", "psum", "vote_sw"):
            b = BENCHES[bname]
            rng = np.random.default_rng(9)
            bufs, sc, params = b.make(rng)
            fn = _compiled(b.handle, bname)
            oracle = _launch(fn, bufs, params, sc, decoded=False)
            got = _launch(fn, bufs, params, sc, grid=True)
            _assert_same(f"{bname} chunk={chunk}", oracle, got)


def test_compaction_needs_private_stores():
    """A kernel whose store index is NOT provably thread-private (a
    fixed-cell scatter) must never take the run-ahead paths: its store
    order across workgroups is observable, so order_free/private_stores
    stay False and compaction/partial-park never fire."""
    fn = _compiled(K.loop_store_conflict, "loop_store_conflict")
    prog = interp._decode_batched(fn, 32, False, 4, grid_mode=True)
    assert not prog.order_free
    assert not prog.private_stores
    fn2 = _compiled(BENCHES["spmv_tail"].handle, "spmv_tail")
    prog2 = interp._decode_batched(fn2, 32, False, 4, grid_mode=True)
    assert prog2.order_free and prog2.private_stores


# --------------------------------------------------------------------------
# jax-rung metamorphic sweeps
# --------------------------------------------------------------------------

_JAX_KW = rungs.JAX


def _jax_cases(factor=1):
    """Licence-admitted (name, fn, bufs, scalars, params-at-factor)
    tuples from the ragged registry (bfs_frontier refuses the
    order-free licence and drops out)."""
    out = []
    for name, fn, bufs, sc, params in _ragged_cases():
        p = interp.fold_warps(params, factor)
        ok, _why = jaxgen.licence_check(fn, p, bufs, sc or {}, {})
        if ok:
            out.append((name, fn, bufs, sc, p))
    return out


def _jax_launch(fn, bufs0, params, sc):
    """Certification warm-up launch + certified primary launch; returns
    the primary's (stats, buffers)."""
    warm = {k: v.copy() for k, v in bufs0.items()}
    rungs.launch(fn, warm, params, scalar_args=sc, **_JAX_KW)
    return _launch(fn, bufs0, params, sc, **_JAX_KW)


def _drop_jax_caches(fn):
    for attr in ("_jaxgen_cache", "_jax_certs"):
        if hasattr(fn, attr):
            delattr(fn, attr)


@pytest.mark.parametrize("chunk", [1, 3, 64])
def test_jax_chunk_size_invariance(monkeypatch, chunk):
    """Results must not depend on how the jax host loop chunks the
    workgroup axis.  The chunk width is part of the shape signature, so
    each width is a fresh trace + fresh differential certification —
    this sweeps the whole certify-then-promote machine, not just the
    compiled executable."""
    monkeypatch.setenv("VOLT_DISK_CACHE", "0")
    monkeypatch.setattr(jaxgen, "_CHUNK_WGS", chunk)
    engaged = 0
    for factor in (1, 2):
        cases = _jax_cases(factor)
        assert len(cases) >= 2, "ragged registry must license >= 2 cases"
        for name, fn, bufs, sc, p in cases:
            oracle = _launch(fn, bufs, p, sc, decoded=False)
            jaxgen.reset_jax_telemetry()
            got = _jax_launch(fn, bufs, p, sc)
            _assert_same(f"{name} x{factor} jax chunk={chunk}",
                         oracle, got)
            engaged += jaxgen.JAX_TELEMETRY["engaged"]
    assert engaged >= 4, "jax rung must engage on every licensed case"


def test_jax_cache_hot_cold_invariance(monkeypatch):
    """Cold trace+certify, hot cache, and re-cold runs must be
    bit-identical — the caches are pure memoisation, never semantics.
    Telemetry proves each temperature actually took its intended path."""
    monkeypatch.setenv("VOLT_DISK_CACHE", "0")
    cases = _jax_cases()
    assert cases, "ragged registry must license jax cases"
    for name, fn, bufs, sc, p in cases:
        _drop_jax_caches(fn)
        oracle = _launch(fn, bufs, p, sc, decoded=False)
        jaxgen.reset_jax_telemetry()
        cold = _jax_launch(fn, bufs, p, sc)
        t_cold = dict(jaxgen.JAX_TELEMETRY)
        jaxgen.reset_jax_telemetry()
        hot = _jax_launch(fn, bufs, p, sc)
        t_hot = dict(jaxgen.JAX_TELEMETRY)
        _drop_jax_caches(fn)
        jaxgen.reset_jax_telemetry()
        recold = _jax_launch(fn, bufs, p, sc)
        _assert_same(f"{name} jax cold vs oracle", oracle, cold)
        _assert_same(f"{name} jax hot vs cold", cold, hot)
        _assert_same(f"{name} jax re-cold vs hot", hot, recold)
        assert t_cold["cert_runs"] >= 1 and t_cold["certified"] >= 1, \
            f"{name}: cold run must certify"
        assert t_hot["cert_runs"] == 0, \
            f"{name}: hot run must not re-certify"
        assert t_hot["trace_cache_hits"] >= 1, \
            f"{name}: hot run must hit the trace cache"


def test_jax_disable_jit_invariance(monkeypatch):
    """Under ``jax.disable_jit()`` the rung runs the traced chunk
    function eagerly, op by op — same code path the oracle differential
    certifies, minus XLA entirely.  Eager, AOT-compiled and oracle
    results must all agree bit for bit."""
    monkeypatch.setenv("VOLT_DISK_CACHE", "0")
    cases = _jax_cases()
    assert cases, "ragged registry must license jax cases"
    for name, fn, bufs, sc, p in cases:
        oracle = _launch(fn, bufs, p, sc, decoded=False)
        compiled = _jax_launch(fn, bufs, p, sc)
        jaxgen.reset_jax_telemetry()
        with jax.disable_jit():
            eager = _launch(fn, bufs, p, sc, **_JAX_KW)
        assert jaxgen.JAX_TELEMETRY["engaged"] >= 1, \
            f"{name}: rung must engage eagerly under disable_jit"
        _assert_same(f"{name} jax compiled vs oracle", oracle, compiled)
        _assert_same(f"{name} jax eager vs compiled", compiled, eager)


# --------------------------------------------------------------------------
# parallel dispatch (core/parallel.py): scheduling nondeterminism — worker
# count, pool backend, submission interleaving — must be bit-invisible
# --------------------------------------------------------------------------

def _parallel_case(seed=3, g=96):
    """Large-grid spmv_csr: enough workgroups that the widened parallel
    chunk plan has several spans at every swept worker count (the
    native bench shapes fit in one or two chunks and would leave the
    merge path untested)."""
    from repro.volt_bench.suite import _params, _ragged_csr
    rng = np.random.default_rng(seed)
    n = g * 32
    row_ptr, cols = _ragged_csr(rng, n)
    bufs = {"row_ptr": row_ptr, "cols": cols,
            "vals": rng.standard_normal(len(cols)).astype(np.float32),
            "x": rng.standard_normal(n).astype(np.float32),
            "y": np.zeros(n, np.float32)}
    fn = _compiled(BENCHES["spmv_csr"].handle, "spmv_csr")
    return fn, bufs, {"n": n}, _params(g)


def _tel_snapshot():
    t = interp.GRID_TELEMETRY
    return (t.desyncs, t.remerges, t.compactions, t.batches)


@pytest.mark.parametrize("w", [2, 4, 8])
def test_worker_count_invariance(w):
    """Buffers AND ExecStats are bit-identical to single-worker (and
    oracle) dispatch at every worker count — the merge order is chunk
    order, never completion order."""
    fn, bufs, sc, params = _parallel_case()
    oracle = _launch(fn, bufs, params, sc, decoded=False)
    seq = _launch(fn, bufs, params, sc, grid=True, workers=1)
    par = _launch(fn, bufs, params, sc, grid=True, workers=w)
    _assert_same("spmv_csr workers=1 vs oracle", oracle, seq)
    _assert_same(f"spmv_csr workers={w}", seq, par)


def test_worker_env_knob(monkeypatch):
    """VOLT_WORKERS is the deployment knob: unset/auto/explicit all
    resolve through the same clamp, and results stay bit-identical."""
    fn, bufs, sc, params = _parallel_case(seed=5, g=80)
    seq = _launch(fn, bufs, params, sc, grid=True, workers=1)
    monkeypatch.setenv("VOLT_WORKERS", "4")
    par = _launch(fn, bufs, params, sc, grid=True)
    _assert_same("spmv_csr VOLT_WORKERS=4", seq, par)
    monkeypatch.setenv("VOLT_WORKERS", "not-a-number")
    with pytest.raises(ValueError, match="VOLT_WORKERS"):
        _launch(fn, bufs, params, sc, grid=True)


def test_backend_and_interleaving_invariance(monkeypatch):
    """Same worker count, different SCHEDULES: serial backend (zero
    concurrency, same chunk plan) vs thread backend under reversed and
    shuffled submission orders.  Results, stats AND grid telemetry must
    be identical — the chunk plan and merge order are functions of the
    launch alone, never of scheduling."""
    from repro.core import parallel
    fn, bufs, sc, params = _parallel_case(seed=11, g=64)
    runs = {}
    orders = {
        "fifo": None,
        "reversed": lambda n: list(range(n))[::-1],
        "shuffled": lambda n: list(
            np.random.default_rng(13).permutation(n)),
    }
    for backend in ("thread", "serial"):
        monkeypatch.setenv("VOLT_PAR_BACKEND", backend)
        for oname, fnorder in orders.items():
            monkeypatch.setattr(parallel, "SUBMIT_ORDER", fnorder)
            interp.GRID_TELEMETRY.reset()
            runs[(backend, oname)] = (
                _launch(fn, bufs, params, sc, grid=True, workers=4),
                _tel_snapshot())
    base = runs[("thread", "fifo")]
    for key, (res, tel) in runs.items():
        _assert_same(f"spmv_csr {key}", base[0], res)
        assert tel == base[1], f"telemetry diverged under {key}"


def test_parallel_chunks_off_at_one_worker(monkeypatch):
    """workers=1 must not touch the pool at all — it is the exact
    historical sequential dispatch (the `1 = today's path` contract)."""
    from repro.core import parallel

    def _boom(*a, **k):
        raise AssertionError("worker pool touched at VOLT_WORKERS=1")

    monkeypatch.setattr(parallel, "get_pool", _boom)
    fn, bufs, sc, params = _parallel_case(seed=2, g=48)
    oracle = _launch(fn, bufs, params, sc, decoded=False)
    seq = _launch(fn, bufs, params, sc, grid=True, workers=1)
    _assert_same("spmv_csr workers=1", oracle, seq)


# --------------------------------------------------------------------------
# hypothesis fuzzing
# --------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not _HAVE_HYPOTHESIS,
    reason="property tests need hypothesis "
           "(pip install -r requirements-dev.txt)")

_H_EXAMPLES = int(os.environ.get("VOLT_HYPOTHESIS_MAX_EXAMPLES", "25"))


if _HAVE_HYPOTHESIS:
    # monkeypatch is function-scoped but every example re-sets the same
    # module attributes, so sharing it across examples is safe
    _FIXTURE_OK = dict(
        suppress_health_check=[HealthCheck.function_scoped_fixture])

    @needs_hypothesis
    @settings(max_examples=min(25, _H_EXAMPLES), deadline=None,
              **_FIXTURE_OK)
    @given(n_warps=st.sampled_from([1, 2, 4]),
           grid=st.integers(2, 10),
           chunk=st.sampled_from([1, 3, 5, 64]),
           fraction=st.sampled_from([0.0, 0.25, 0.6, 1.0]),
           max_trip=st.integers(0, 40),
           seed=st.integers(0, 2**31 - 1))
    def test_grid_config_invariance_random(monkeypatch, n_warps, grid,
                                           chunk, fraction, max_trip,
                                           seed):
        """Random ragged trips x grid shape x chunk size x compaction
        threshold: the grid executor must match the oracle bit for bit
        under every configuration."""
        monkeypatch.setattr(interp, "_GRID_BATCH_MAX", chunk)
        monkeypatch.setattr(interp, "_COMPACT_FRACTION", fraction)
        monkeypatch.setattr(interp, "_COMPACT_MIN_WGS", 2)
        rng = np.random.default_rng(seed)
        W = 32
        local = n_warps * W
        total = grid * local
        params = interp.LaunchParams(grid=grid, local_size=local,
                                     warp_size=W)
        fn = _compiled(K.ragged_nested, "ragged_nested")
        bufs = {"trip": rng.integers(0, max_trip + 1,
                                     total).astype(np.int32),
                "x": (rng.standard_normal(total) * 2).astype(np.float32),
                "out": np.zeros(total, np.float32)}
        sc = {"n": total}
        oracle = _launch(fn, bufs, params, sc, decoded=False)
        got = _launch(fn, bufs, params, sc, grid=True)
        _assert_same(f"cfg{(n_warps, grid, chunk, fraction, seed)}",
                     oracle, got)

    @needs_hypothesis
    @settings(max_examples=min(25, _H_EXAMPLES), deadline=None,
              **_FIXTURE_OK)
    @given(workers=st.integers(2, 8),
           chunk=st.sampled_from([1, 3, 8, 64]),
           par_cap=st.sampled_from([8, 64, 512]),
           grid=st.integers(2, 12),
           max_trip=st.integers(0, 24),
           seed=st.integers(0, 2**31 - 1))
    def test_parallel_worker_chunk_invariance_random(monkeypatch,
                                                     workers, chunk,
                                                     par_cap, grid,
                                                     max_trip, seed):
        """Worker count x base chunk size x widening cap x grid shape,
        over random ragged trip vectors: parallel dispatch must match
        the oracle bit for bit wherever the chunk plan boundaries land
        (including degenerate one-wg chunks and caps below the base
        chunk size)."""
        monkeypatch.setattr(interp, "_GRID_BATCH_MAX", chunk)
        monkeypatch.setattr(interp, "_GRID_PAR_ROWS_MAX", par_cap)
        rng = np.random.default_rng(seed)
        W = 32
        total = grid * W
        params = interp.LaunchParams(grid=grid, local_size=W,
                                     warp_size=W)
        fn = _compiled(K.ragged_nested, "ragged_nested")
        bufs = {"trip": rng.integers(0, max_trip + 1,
                                     total).astype(np.int32),
                "x": (rng.standard_normal(total) * 2).astype(np.float32),
                "out": np.zeros(total, np.float32)}
        sc = {"n": total}
        oracle = _launch(fn, bufs, params, sc, decoded=False)
        got = _launch(fn, bufs, params, sc, grid=True, workers=workers)
        _assert_same(f"par{(workers, chunk, par_cap, grid, seed)}",
                     oracle, got)

    @needs_hypothesis
    @settings(max_examples=min(20, _H_EXAMPLES), deadline=None,
              **_FIXTURE_OK)
    @given(n_warps=st.sampled_from([2, 4]),
           grid=st.integers(2, 8),
           chunk=st.sampled_from([1, 3, 64]),
           seed=st.integers(0, 2**31 - 1))
    def test_grid_barrier_remerge_random(monkeypatch, n_warps, grid,
                                         chunk, seed):
        """Multi-warp grids of the barrier-in-loop kernel with random
        per-workgroup trips: per-wg barrier groups + re-merge must never
        fabricate or drop an arrival (stats count every barrier issue)."""
        monkeypatch.setattr(interp, "_GRID_BATCH_MAX", chunk)
        rng = np.random.default_rng(seed)
        W = 32
        local = n_warps * W
        total = grid * local
        params = interp.LaunchParams(grid=grid, local_size=local,
                                     warp_size=W)
        fn = _compiled(K.ragged_barrier_loop, "ragged_barrier_loop")
        trips = np.repeat(rng.integers(0, 6, grid), local)
        bufs = {"trip": trips.astype(np.int32),
                "x": rng.standard_normal(total).astype(np.float32),
                "out": np.zeros(total, np.float32)}
        sc = {"n": total}
        oracle = _launch(fn, bufs, params, sc, decoded=False)
        got = _launch(fn, bufs, params, sc, grid=True)
        _assert_same(f"barrier{(n_warps, grid, chunk, seed)}",
                     oracle, got)

    @needs_hypothesis
    @settings(max_examples=min(50, _H_EXAMPLES), deadline=None)
    @given(w=st.sampled_from([1, 2, 7, 31, 32]),
           rows=st.integers(1, 6),
           hi=st.integers(1, 512),
           density=st.floats(0.0, 1.0),
           wide=st.booleans(),
           seed=st.integers(0, 2**31 - 1))
    def test_jax_line_count_matches_reference(w, rows, hi, density,
                                              wide, seed):
        """The jax rung's traced distinct-cache-line counter (sentinel
        sort over (R, W) index matrices) vs the exact np.unique oracle
        in ``interp_mem.reference_counting`` mode, per warp AND over
        already-gathered active-lane indices — fuzzing warp width, row
        count, index range/dtype and mask density (incl. all-dead and
        all-live warps)."""
        rng = np.random.default_rng(seed)
        dt = np.int64 if wide else np.int32
        idx = rng.integers(0, hi, (rows, w)).astype(dt)
        mask = rng.uniform(0, 1, (rows, w)) < density
        got = int(jaxgen.count_lines_traced(
            jnp.asarray(idx.astype(np.int32)), jnp.asarray(mask), w))
        with interp_mem.reference_counting():
            per_warp = sum(int(interp_mem.count_warp(idx[r], mask[r]))
                           for r in range(rows))
            gathered = sum(int(interp_mem.count_gathered(idx[r][mask[r]]))
                           for r in range(rows))
        assert got == per_warp == gathered
else:
    @needs_hypothesis
    def test_grid_config_invariance_random():
        pass

    @needs_hypothesis
    def test_parallel_worker_chunk_invariance_random():
        pass

    @needs_hypothesis
    def test_grid_barrier_remerge_random():
        pass

    @needs_hypothesis
    def test_jax_line_count_matches_reference():
        pass
