"""Tests for the workgroup-batched lockstep executor, decode-level slot
fusion, and the persistent disk compile cache.

Parity contract: for multi-warp workgroups the batched executor must be
bit-identical to the ``decoded=False`` instruction-at-a-time oracle —
dynamic instruction counts, per-op counters, coalesced memory requests,
atomic serialization, IPDOM depth, prints, and every output buffer."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent / "kernels"))

from repro.core import diskcache, interp, runtime
from repro.core.passes.pipeline import (ABLATION_LADDER, PassConfig,
                                        run_pipeline)
from repro.core.vir import Op
from repro.volt_bench import BENCHES

import volt_kernels as K

FULL = ABLATION_LADDER[-1]

# benches whose semantics survive a multi-warp workgroup reshape: thread
# behavior depends only on global_id (plus intra-warp collectives, which
# a wider workgroup leaves untouched).  Excluded: reduce0/psum/vote_sw/
# shuffle_sw (bodies hard-code local_size==32 shared tiles), shuffle_hw /
# gc_like (one output cell per warp/workgroup), bfs (benign write races
# whose masks, and so dynamic instruction counts, depend on the warp
# schedule).
MULTI_WARP_BENCHES = [
    "vecadd", "saxpy", "dotproduct", "transpose", "psort", "sfilter",
    "sgemm", "blackscholes", "pathfinder", "kmeans", "nearn", "stencil",
    "spmv", "spmv_csr", "bfs_frontier", "cfd_like", "srad_flag",
    "vote_hw", "bscan_hw", "atomic_naive", "atomic_agg",
]


_multi_warp = interp.fold_warps


def _assert_parity(name, fn, bufs0, params, scalars, **kw):
    ref = {k: v.copy() for k, v in bufs0.items()}
    st_ref = interp.launch(fn, ref, params, scalar_args=scalars,
                           decoded=False)
    bat = {k: v.copy() for k, v in bufs0.items()}
    st_bat = interp.launch(fn, bat, params, scalar_args=scalars,
                           decoded=True, batched=True, **kw)
    assert st_ref.instrs == st_bat.instrs, name
    assert st_ref.by_op == st_bat.by_op, name
    assert st_ref.mem_requests == st_bat.mem_requests, name
    assert st_ref.mem_insts == st_bat.mem_insts, name
    assert st_ref.shared_requests == st_bat.shared_requests, name
    assert st_ref.atomic_serial == st_bat.atomic_serial, name
    assert st_ref.max_ipdom_depth == st_bat.max_ipdom_depth, name
    assert st_ref.prints == st_bat.prints, name
    for k in ref:
        np.testing.assert_array_equal(ref[k], bat[k],
                                      err_msg=f"{name}: buffer {k}")
    return bat, st_bat


# -------------------------------------------------------------------------
# batched-vs-oracle parity across the volt_bench suite
# -------------------------------------------------------------------------

@pytest.mark.parametrize("name", MULTI_WARP_BENCHES)
@pytest.mark.parametrize("cfg_i", [0, len(ABLATION_LADDER) - 1],
                         ids=["base", "full"])
def test_batched_parity_suite(name, cfg_i):
    b = BENCHES[name]
    rng = np.random.default_rng(7)
    bufs0, scalars, params = b.make(rng)
    mod = b.handle.build(None)
    ck = run_pipeline(mod, b.handle.name, ABLATION_LADDER[cfg_i])
    _assert_parity(name, ck.fn, bufs0, _multi_warp(params), scalars)


@pytest.mark.parametrize("factor", [2, 4, 8])
def test_batched_parity_warp_factors(factor):
    """Different workgroup widths (2/4/8 warps) stay parity-exact."""
    for name in ("psort", "cfd_like", "dotproduct"):
        b = BENCHES[name]
        rng = np.random.default_rng(11)
        bufs0, scalars, params = b.make(rng)
        mod = b.handle.build(None)
        ck = run_pipeline(mod, b.handle.name, FULL)
        _assert_parity(f"{name}/x{factor}", ck.fn, bufs0,
                       _multi_warp(params, factor), scalars)


def test_batched_barriers_shared_memory():
    """Barriers inside a uniform loop with cross-warp shared traffic:
    the workgroup re-merges into lockstep after every desync."""
    mod = K.wg_reduce128.build(None)
    ck = run_pipeline(mod, "wg_reduce128", FULL)
    rng = np.random.default_rng(3)
    x = rng.standard_normal(256).astype(np.float32)
    params = interp.LaunchParams(grid=2, local_size=128, warp_size=32)
    bufs0 = {"x": x, "out": np.zeros(2, np.float32)}
    bat, st = _assert_parity("wg_reduce128", ck.fn, bufs0, params,
                             {"n": 250})
    xm = x.copy()
    xm[250:] = 0
    np.testing.assert_allclose(bat["out"], xm.reshape(2, 128).sum(1),
                               atol=1e-3)
    assert st.shared_requests > 0


def test_batched_divergence_barrier_atomic_mix():
    """Lockstep -> desync (atomics) -> re-merge (barrier) end to end."""
    mod = K.wg_mixed.build(None)
    ck = run_pipeline(mod, "wg_mixed", FULL)
    rng = np.random.default_rng(5)
    x = rng.standard_normal(256).astype(np.float32)
    params = interp.LaunchParams(grid=2, local_size=128, warp_size=32)
    bufs0 = {"x": x, "y": np.zeros(256, np.float32),
             "count": np.zeros(1, np.int32)}
    bat, st = _assert_parity("wg_mixed", ck.fn, bufs0, params, {"n": 240})
    assert st.atomic_serial > 0 and st.shared_requests > 0
    assert int(bat["count"][0]) > 0


def test_batched_device_function_calls():
    """Pure device functions run in lockstep; results match the per-thread
    scalar oracle."""
    rng = np.random.default_rng(5)
    coefs = rng.standard_normal(4).astype(np.float32)
    x = rng.standard_normal(128).astype(np.float32)
    params = interp.LaunchParams(grid=1, local_size=128, warp_size=32)
    scalars = {"deg": 4, "n": 120}
    mod = K.uses_helper.build(None)
    ck = run_pipeline(mod, "uses_helper", FULL)
    bufs0 = {"coefs": coefs, "x": x, "out": np.zeros(128, np.float32)}
    _assert_parity("uses_helper", ck.fn, bufs0, params, scalars)


def test_single_warp_workgroups_unaffected():
    """batched=True on single-warp workgroups must take the per-warp
    decoded path (identical to batched=False)."""
    b = BENCHES["cfd_like"]
    rng = np.random.default_rng(7)
    bufs0, scalars, params = b.make(rng)
    assert params.warps_per_wg == 1
    mod = b.handle.build(None)
    ck = run_pipeline(mod, b.handle.name, FULL)
    a = {k: v.copy() for k, v in bufs0.items()}
    st_a = interp.launch(ck.fn, a, params, scalar_args=scalars,
                         batched=True)
    bb = {k: v.copy() for k, v in bufs0.items()}
    st_b = interp.launch(ck.fn, bb, params, scalar_args=scalars,
                         batched=False)
    assert st_a.instrs == st_b.instrs
    for k in a:
        np.testing.assert_array_equal(a[k], bb[k])


def test_barrier_divergence_error_names_warps():
    """The barrier-divergence ExecError names waiting vs exited warps, in
    both the oracle and the batched desync scheduler."""
    mod = K.wg_warp0_barrier.build(None)
    ck = run_pipeline(mod, "wg_warp0_barrier", FULL)
    params = interp.LaunchParams(grid=1, local_size=128, warp_size=32)
    for kw in (dict(decoded=False), dict(decoded=True, batched=True)):
        bufs = {"x": np.zeros(128, np.float32)}
        with pytest.raises(interp.ExecError) as ei:
            interp.launch(ck.fn, bufs, params, scalar_args={"n": 128},
                          **kw)
        msg = str(ei.value)
        assert "barrier divergence" in msg
        assert "workgroup (0, 0)" in msg
        assert "[0]" in msg, f"waiting warp not named: {msg}"
        assert "[1, 2, 3]" in msg, f"exited warps not named: {msg}"


# -------------------------------------------------------------------------
# vx_pred loop ride-along + grid-level batching
# -------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["spmv_csr", "bfs_frontier"])
def test_ragged_loop_ride_along_parity(name):
    """Mixed loop-exit decisions stay in lockstep (ride-along) and remain
    bit-identical to the oracle."""
    b = BENCHES[name]
    rng = np.random.default_rng(7)
    bufs0, scalars, params = b.make(rng)
    mod = b.handle.build(None)
    ck = run_pipeline(mod, b.handle.name, FULL)
    _assert_parity(name, ck.fn, bufs0, _multi_warp(params), scalars)


def test_grid_batchable_gate():
    """The grid-level batcher refuses kernels with a buffer both read
    and written, accepts pure-gather kernels, and accepts __shared__
    tiles used directly by the kernel body (each batched workgroup gets
    a private tile row — the PR 5 extension)."""
    expected = {
        "spmv": True,          # loads row_ptr/cols/vals/x, stores y
        "spmv_csr": True,
        "bfs_frontier": True,  # pull-style: never reads a written buffer
        "vecadd": True,
        "stencil": True,       # multi-site stores desync, not refuse
        "bfs": False,          # reads AND writes visited[] (top-down)
        "saxpy": False,        # y read+written (conservative refusal)
        "reduce0": True,       # __shared__ tile -> private per-row slice
        "psum": True,          # tile + barriers: lockstep rows
        "vote_sw": True,       # tile + shared atomic (desync node)
        "dotproduct": False,   # atomic RMW counts as read+write
    }
    for name, want in expected.items():
        b = BENCHES[name]
        mod = b.handle.build(None)
        ck = run_pipeline(mod, b.handle.name, FULL)
        rng = np.random.default_rng(0)
        bufs0, _, _ = b.make(rng)
        argmap = {id(p): bufs0.get(p.name) for p in ck.fn.params}
        got = interp._grid_batchable(ck.fn, argmap)
        assert got == want, f"{name}: _grid_batchable={got}, want {want}"


def test_grid_multi_store_conflict_ordered():
    """Two static stores clashing on one cell from different workgroups
    (reviewer repro): in grid mode stores to multi-site buffers are
    desync nodes, so the clash executes in workgroup order — the later
    workgroup's write must win exactly as in the oracle, bit-identical
    stats included."""
    mod = K.two_store_conflict.build(None)
    ck = run_pipeline(mod, "two_store_conflict", FULL)
    bufs0 = {"out": np.zeros(65, np.float32)}
    prog = interp._decode_batched(ck.fn, 32, False, 2, grid_mode=True)
    assert prog._hazard_stores, "conflicting stores must be flagged"
    params = interp.LaunchParams(grid=2, local_size=32, warp_size=32)
    bat, _ = _assert_parity("two_store_conflict", ck.fn, bufs0, params,
                            {"n": 63})
    assert bat["out"][0] == 1.0    # the later workgroup's write wins


def test_grid_aliased_param_stores_refused():
    """One ndarray bound to two pointer params, each with a single-site
    store (reviewer repro): the per-pointer _hazard_stores count cannot
    see the clash, so the launch gate must refuse — and the executors
    must stay bit-identical via the per-workgroup fallback.  (Buffers
    are NOT copied per run here: copying would silently un-alias them.)"""
    mod = K.alias_two_params.build(None)
    ck = run_pipeline(mod, "alias_two_params", FULL)
    shared = np.zeros(2, np.float32)
    argmap = {id(pp): shared for pp in ck.fn.params if pp.name in "pq"}
    assert not interp._grid_batchable(ck.fn, argmap)
    params = interp.LaunchParams(grid=2, local_size=32, warp_size=32)
    outs = {}
    for label, kw in (("oracle", dict(decoded=False)),
                      ("batched", dict(decoded=True, batched=True))):
        arr = np.zeros(2, np.float32)
        st = interp.launch(ck.fn, {"p": arr, "q": arr}, params,
                           scalar_args={"n": 63}, **kw)
        outs[label] = (st, arr)
    assert outs["oracle"][0].instrs == outs["batched"][0].instrs
    np.testing.assert_array_equal(outs["oracle"][1], outs["batched"][1])
    assert outs["batched"][1][0] == 1.0    # later workgroup's write wins


def test_grid_callee_store_conflict_ordered():
    """Caller store + callee store to the same buffer (reviewer repro):
    the flat site count cannot attribute the callee's store, so a
    store-containing callee makes every caller store a grid-mode desync
    node — the clash must resolve in workgroup order."""
    mod = K.callee_store_conflict.build(None)
    ck = run_pipeline(mod, "callee_store_conflict", FULL)
    prog = interp._decode_batched(ck.fn, 32, False, 2, grid_mode=True)
    assert prog._hazard_stores, "caller store must be flagged hazardous"
    bufs0 = {"out": np.zeros(1, np.float32)}
    params = interp.LaunchParams(grid=2, local_size=32, warp_size=32)
    bat, _ = _assert_parity("callee_store_conflict", ck.fn, bufs0,
                            params, {"n": 64})
    assert bat["out"][0] == 1.0    # wg1's top-level write wins


def test_grid_loop_store_conflict_ordered():
    """A SINGLE static store site inside a ragged loop (reviewer repro):
    rows writing the same cell at different trip counts must resolve in
    workgroup order, not trip order — grid mode flags stores in cyclic
    blocks as desync nodes."""
    mod = K.loop_store_conflict.build(None)
    ck = run_pipeline(mod, "loop_store_conflict", FULL)
    prog = interp._decode_batched(ck.fn, 32, False, 2, grid_mode=True)
    assert prog._hazard_stores, "loop store must be flagged hazardous"
    trip = np.zeros(64, np.int32)
    trip[0] = 5      # wg0 keeps writing longest...
    trip[32] = 2     # ...but wg1 is the LATER workgroup and must win
    bufs0 = {"trip": trip, "out": np.zeros(1, np.float32)}
    params = interp.LaunchParams(grid=2, local_size=32, warp_size=32)
    bat, _ = _assert_parity("loop_store_conflict", ck.fn, bufs0, params,
                            {"n": 64})
    assert bat["out"][0] == 32.0


def test_grid_view_alias_refused():
    """Overlapping numpy views of one base array must not evade the
    read-write-hazard refusal (distinct id()s, shared memory)."""
    b = BENCHES["vecadd"]           # loads x, y; stores z — batchable
    mod = b.handle.build(None)
    ck = run_pipeline(mod, b.handle.name, FULL)
    base = np.zeros(512, np.float32)
    bufs = {"x": base[0:256], "y": np.zeros(256, np.float32),
            "z": base[128:384]}     # z overlaps x in the base array
    argmap = {id(p): bufs.get(p.name) for p in ck.fn.params}
    assert not interp._grid_batchable(ck.fn, argmap)
    bufs["z"] = np.zeros(256, np.float32)   # disjoint again: accepted
    argmap = {id(p): bufs.get(p.name) for p in ck.fn.params}
    assert interp._grid_batchable(ck.fn, argmap)


def test_grid_fuel_tracks_oracle():
    """Batched fuel burn must stay aligned with the oracle: a grid batch
    where one ragged row loops long while sibling rows ride along empty
    must not exhaust a budget the oracle completes within."""
    b = BENCHES["spmv_csr"]
    rng = np.random.default_rng(3)
    bufs0, scalars, params = b.make(rng)
    mod = b.handle.build(None)
    ck = run_pipeline(mod, b.handle.name, FULL)
    ref = {k: v.copy() for k, v in bufs0.items()}
    st = interp.launch(ck.fn, ref, params, scalar_args=scalars,
                       decoded=False)
    tight = interp.LaunchParams(grid=params.grid,
                                local_size=params.local_size,
                                warp_size=params.warp_size,
                                fuel=3 * st.instrs + 1000)
    bat = {k: v.copy() for k, v in bufs0.items()}
    st_bat = interp.launch(ck.fn, bat, tight, scalar_args=scalars,
                           decoded=True, batched=True)
    assert st_bat.instrs == st.instrs


def test_grid_batching_parity_large_grid():
    """A grid larger than one batch chunk (> _GRID_BATCH_MAX workgroups)
    splits into several (chunk, W) activations, all parity-exact."""
    b = BENCHES["spmv_csr"]
    rng = np.random.default_rng(13)
    bufs0, scalars, params = b.make(rng)
    # stretch to 80 single-warp workgroups (> _GRID_BATCH_MAX = 64) by
    # tiling the CSR inputs
    n = 80 * 32
    reps = (n + len(bufs0["y"]) - 1) // len(bufs0["y"])
    deg = np.tile(np.diff(bufs0["row_ptr"]), reps)[:n]
    rp = np.zeros(n + 1, np.int32)
    rp[1:] = np.cumsum(deg)
    cols = rng.integers(0, n, int(rp[-1])).astype(np.int32)
    bufs0 = {"row_ptr": rp, "cols": cols,
             "vals": rng.standard_normal(int(rp[-1])).astype(np.float32),
             "x": rng.standard_normal(n).astype(np.float32),
             "y": np.zeros(n, np.float32)}
    params = interp.LaunchParams(grid=80, local_size=32, warp_size=32)
    assert params.grid > interp._GRID_BATCH_MAX
    ck = run_pipeline(b.handle.build(None), b.handle.name, FULL)
    _assert_parity("spmv_csr/grid80", ck.fn, bufs0, params, {"n": n})


# -------------------------------------------------------------------------
# multi-warp grid batching: per-workgroup barrier groups + the
# desync-ordering repros at 2 and 4 warps per workgroup
# -------------------------------------------------------------------------

def _compiled_k(handle, name):
    return run_pipeline(handle.build(None), name, FULL).fn


@pytest.mark.parametrize("factor", [2, 4])
def test_grid_multiwarp_engages_and_parity(factor):
    """Multi-warp folds of a grid-eligible ragged launch must take the
    grid path (not silently fall back to per-workgroup dispatch) and
    stay bit-identical to the oracle."""
    b = BENCHES["spmv_csr"]
    rng = np.random.default_rng(7)
    bufs0, scalars, params = b.make(rng)
    mp = _multi_warp(params, factor)
    assert mp.warps_per_wg == factor and mp.grid > 1
    fn = _compiled_k(b.handle, b.handle.name)
    t = interp.GRID_TELEMETRY
    t.reset()
    _assert_parity(f"spmv_csr/grid_x{factor}", fn, bufs0, mp, scalars,
                   grid=True)
    assert t.batches > 0, "multi-warp launch must engage grid batching"


@pytest.mark.parametrize("factor", [2, 4])
def test_grid_multiwarp_two_store_conflict(factor):
    """Reviewer repro under MULTI-warp grid mode: the two clashing
    static stores now sit in different WARPS of one workgroup (and in
    different workgroups at wider grids); hazard-store desync must drain
    whole workgroups with intra-workgroup oracle scheduling, so the
    clash resolves exactly as the per-warp schedule does."""
    fn = _compiled_k(K.two_store_conflict, "two_store_conflict")
    params = _multi_warp(
        interp.LaunchParams(grid=4, local_size=32, warp_size=32), factor)
    if params.grid == 1:
        pytest.skip("fold left a single workgroup: grid mode ineligible")
    t = interp.GRID_TELEMETRY
    t.reset()
    bat, _ = _assert_parity(f"two_store/grid_x{factor}", fn,
                            {"out": np.zeros(130, np.float32)}, params,
                            {"n": 120}, grid=True)
    assert t.desyncs > 0, "hazard stores must desync the batch"


@pytest.mark.parametrize("factor", [2])
def test_grid_multiwarp_loop_store_conflict(factor):
    """Cross-trip single-site clash under multi-warp grid mode: trip
    order must never beat workgroup order."""
    fn = _compiled_k(K.loop_store_conflict, "loop_store_conflict")
    trip = np.zeros(128, np.int32)
    trip[0] = 5      # wg0/warp0 writes longest...
    trip[64] = 2     # ...but wg1 is the later workgroup and must win
    params = _multi_warp(
        interp.LaunchParams(grid=4, local_size=32, warp_size=32), factor)
    bat, _ = _assert_parity(f"loop_store/grid_x{factor}", fn,
                            {"trip": trip, "out": np.zeros(1, np.float32)},
                            params, {"n": 128}, grid=True)
    assert bat["out"][0] == 64.0


@pytest.mark.parametrize("factor", [1, 2])
def test_grid_multiwarp_callee_store_refused(factor):
    """The callee-store repro reaches one buffer through two distinct
    root pointers, so the launch gate refuses it at EVERY warps/wg; the
    grid=True launch must behave exactly like the fallback executor it
    lands on (per-workgroup decoded at 1 warp — oracle-exact; the
    wg-batched executor and its documented PR 2 contract at >1)."""
    fn = _compiled_k(K.callee_store_conflict, "callee_store_conflict")
    bufs0 = {"out": np.zeros(1, np.float32)}
    argmap = {id(p): bufs0["out"] for p in fn.params
              if p.ty is not None and p.name == "out"}
    assert not interp._grid_batchable(fn, argmap)
    params = _multi_warp(
        interp.LaunchParams(grid=4, local_size=32, warp_size=32), factor)
    if factor == 1:
        _assert_parity("callee_store/grid_x1", fn, bufs0, params,
                       {"n": 128}, grid=True)
        return
    for_g = {k: v.copy() for k, v in bufs0.items()}
    st_g = interp.launch(fn, for_g, params, scalar_args={"n": 128},
                         grid=True)
    for_w = {k: v.copy() for k, v in bufs0.items()}
    st_w = interp.launch(fn, for_w, params, scalar_args={"n": 128},
                         grid=False)
    assert st_g.instrs == st_w.instrs and st_g.by_op == st_w.by_op
    np.testing.assert_array_equal(for_g["out"], for_w["out"])


@pytest.mark.parametrize("factor", [1, 2])
def test_grid_multiwarp_alias_refused(factor):
    """Aliased-param stores stay refused at every warps/wg and the
    grid=True launch matches its fallback executor bit for bit."""
    fn = _compiled_k(K.alias_two_params, "alias_two_params")
    shared = np.zeros(2, np.float32)
    argmap = {id(p): shared for p in fn.params if p.name in "pq"}
    assert not interp._grid_batchable(fn, argmap)
    params = _multi_warp(
        interp.LaunchParams(grid=2, local_size=32, warp_size=32), factor)
    outs = {}
    for label, kw in (("grid", dict(grid=True)), ("wg", dict(grid=False))):
        arr = np.zeros(2, np.float32)
        st = interp.launch(fn, {"p": arr, "q": arr}, params,
                           scalar_args={"n": 63}, **kw)
        outs[label] = (st, arr)
    assert outs["grid"][0].instrs == outs["wg"][0].instrs
    np.testing.assert_array_equal(outs["grid"][1], outs["wg"][1])


@pytest.mark.parametrize("factor", [2, 4])
def test_grid_multiwarp_barrier_groups(factor):
    """Barrier-in-loop under multi-warp grid mode: per-workgroup barrier
    groups must neither fabricate nor drop arrivals.  Per-wg-uniform
    trips (ragged ACROSS workgroups) are legal and must be bit-identical
    — the by_op barrier count in _assert_parity proves every arrival;
    trips ragged WITHIN a workgroup are barrier divergence and must
    raise the oracle's exact error class."""
    fn = _compiled_k(K.ragged_barrier_loop, "ragged_barrier_loop")
    rng = np.random.default_rng(23)
    W = 32
    grid = 5
    local = factor * W
    total = grid * local
    params = interp.LaunchParams(grid=grid, local_size=local, warp_size=W)
    trips = np.repeat(rng.integers(0, 5, grid), local).astype(np.int32)
    bufs0 = {"trip": trips,
             "x": rng.standard_normal(total).astype(np.float32),
             "out": np.zeros(total, np.float32)}
    _assert_parity(f"barrier_loop/grid_x{factor}", fn, bufs0, params,
                   {"n": total}, grid=True)

    # ragged within a workgroup: same error class as the oracle
    bad = trips.copy()
    bad[:W] += 1                    # warp 0 of wg 0 loops one trip more
    bufs_bad = {"trip": bad, "x": bufs0["x"],
                "out": np.zeros(total, np.float32)}
    errs = {}
    for label, kw in (("oracle", dict(decoded=False)),
                      ("grid", dict(grid=True))):
        try:
            interp.launch(fn, {k: v.copy() for k, v in bufs_bad.items()},
                          params, scalar_args={"n": total}, **kw)
            errs[label] = None
        except interp.ExecError as e:
            errs[label] = type(e).__name__
    assert errs["oracle"] is not None
    assert errs["grid"] == errs["oracle"]


# -------------------------------------------------------------------------
# hypothesis: random warp / workgroup shapes
# -------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:       # keep the rest of this module runnable
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(warp_size=st.sampled_from([4, 8, 16, 32]),
           n_warps=st.integers(1, 4),
           ragged=st.integers(0, 3),
           grid=st.integers(1, 3),
           seed=st.integers(0, 2**31 - 1))
    def test_batched_parity_random_shapes(warp_size, n_warps, ragged,
                                          grid, seed):
        """Batched == oracle for arbitrary (warp size, warps/wg, grid)
        shapes, including ragged workgroups (wg_threads % W != 0)."""
        local = max(1, n_warps * warp_size - ragged)
        params = interp.LaunchParams(grid=grid, local_size=local,
                                     warp_size=warp_size)
        total = grid * local
        rng = np.random.default_rng(seed)
        mod = K.loop_break_continue.build(None)
        ck = run_pipeline(mod, "loop_break_continue", FULL)
        n = 4
        bufs0 = {"x": rng.standard_normal(total * n).astype(np.float32),
                 "out": np.zeros(total, np.float32)}
        _assert_parity(f"shapes{(warp_size, n_warps, ragged, grid)}",
                       ck.fn, bufs0, params, {"n": n})
else:
    @pytest.mark.skip(reason="property tests need hypothesis "
                             "(pip install -r requirements-dev.txt)")
    def test_batched_parity_random_shapes():
        pass


# -------------------------------------------------------------------------
# decode-level slot fusion
# -------------------------------------------------------------------------

def test_slot_fusion_shrinks_handler_table():
    """Fusion drops/merges slot traffic handlers while ExecStats count the
    original instruction mix (parity is covered by the suite tests)."""
    b = BENCHES["cfd_like"]
    mod = b.handle.build(None)
    ck = run_pipeline(mod, b.handle.name, FULL)
    prog = interp._decode(ck.fn, 32, False)
    assert prog.n_run_handlers < prog.n_run_instrs, \
        "slot fusion should eliminate at least one handler in cfd_like"
    # the fused program still reports the full dynamic instruction count
    rng = np.random.default_rng(7)
    bufs0, scalars, params = b.make(rng)
    ref = {k: v.copy() for k, v in bufs0.items()}
    st_ref = interp.launch(ck.fn, ref, params, scalar_args=scalars,
                           decoded=False)
    dec = {k: v.copy() for k, v in bufs0.items()}
    st_dec = interp.launch(ck.fn, dec, params, scalar_args=scalars,
                           decoded=True)
    assert st_ref.instrs == st_dec.instrs
    assert st_ref.by_op == st_dec.by_op


def test_dead_slot_store_dropped():
    """Stores to slots never loaded anywhere in the function are decoded
    away entirely."""
    mod = K.saxpy.build(None)
    fn = mod.functions["saxpy"]
    from repro.core.vir import Const, Instr, Slot, Ty
    dead = fn.new_slot("dead", Ty.F32)
    # two dead stores right before the terminator of the entry block
    term = fn.entry.instrs[-1]
    assert term.is_terminator()
    fn.entry.insert(len(fn.entry.instrs) - 1,
                    Instr(Op.SLOT_STORE, [dead, Const(1.0, Ty.F32)]))
    fn.entry.insert(len(fn.entry.instrs) - 1,
                    Instr(Op.SLOT_STORE, [dead, Const(2.0, Ty.F32)]))
    prog = interp._decode(fn, 32, False)
    assert prog.n_run_instrs - prog.n_run_handlers >= 2
    # ... but the dynamic instruction count still includes them
    rng = np.random.default_rng(0)
    x = rng.standard_normal(64).astype(np.float32)
    y = rng.standard_normal(64).astype(np.float32)
    params = interp.LaunchParams(grid=2, local_size=32, warp_size=32)
    scalars = {"a": 2.0, "n": 64}
    ref = {"x": x.copy(), "y": y.copy()}
    st_ref = interp.launch(fn, ref, params, scalar_args=scalars,
                           decoded=False)
    dec = {"x": x.copy(), "y": y.copy()}
    st_dec = interp.launch(fn, dec, params, scalar_args=scalars,
                           decoded=True)
    assert st_ref.instrs == st_dec.instrs
    assert st_ref.by_op == st_dec.by_op
    np.testing.assert_array_equal(ref["y"], dec["y"])


# -------------------------------------------------------------------------
# persistent disk compile cache
# -------------------------------------------------------------------------

_SUBPROC = """
import json, sys
from repro.core import diskcache, runtime
from repro.volt_bench import BENCHES
ck = runtime.compile_kernel(BENCHES[sys.argv[1]].handle)
print(json.dumps({**diskcache.DISK_CACHE_STATS,
                  "blocks": len(ck.fn.blocks)}))
"""


def _compile_in_subprocess(cache_dir, name="sgemm"):
    import json
    env = dict(os.environ)
    env["VOLT_CACHE_DIR"] = str(cache_dir)
    env["VOLT_DISK_CACHE"] = "1"
    src = str(Path(runtime.__file__).resolve().parents[2])
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _SUBPROC, name], env=env,
                         capture_output=True, text=True, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_disk_cache_second_process_hits(tmp_path):
    """A second process compiling an identical kernel must hit the
    persistent cache."""
    first = _compile_in_subprocess(tmp_path)
    assert first == {**first, "hits": 0, "misses": 1}
    second = _compile_in_subprocess(tmp_path)
    assert second["hits"] == 1 and second["misses"] == 0
    assert second["blocks"] == first["blocks"]


def test_disk_cache_stale_invalidation(tmp_path, monkeypatch):
    """Different kernels never collide; corrupt entries fall back to a
    fresh compile (and are removed) instead of returning stale IR."""
    monkeypatch.setenv("VOLT_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("VOLT_DISK_CACHE", "1")
    runtime.clear_compile_cache()
    stats0 = dict(diskcache.DISK_CACHE_STATS)
    ck1 = runtime.compile_kernel(BENCHES["vecadd"].handle, use_cache=False)
    # a DIFFERENT kernel body hashes to a different key: no false hit
    ck2 = runtime.compile_kernel(BENCHES["saxpy"].handle, use_cache=False)
    assert diskcache.DISK_CACHE_STATS["misses"] == stats0["misses"] + 2
    files = sorted(tmp_path.glob("*.vck"))
    assert len(files) == 2
    # same kernel again: disk hit with equivalent compiled IR
    ck1b = runtime.compile_kernel(BENCHES["vecadd"].handle,
                                  use_cache=False)
    assert diskcache.DISK_CACHE_STATS["hits"] == stats0["hits"] + 1
    assert len(ck1b.fn.blocks) == len(ck1.fn.blocks)
    # corrupt every entry: loads must fail soft and recompile
    for f in files:
        f.write_bytes(b"not a pickle")
    err0 = diskcache.DISK_CACHE_STATS["errors"]
    ck1c = runtime.compile_kernel(BENCHES["vecadd"].handle,
                                  use_cache=False)
    assert diskcache.DISK_CACHE_STATS["errors"] == err0 + 1
    assert len(ck1c.fn.blocks) == len(ck1.fn.blocks)
    # the unpickled-compile path executes correctly end to end
    rng = np.random.default_rng(3)
    x = rng.standard_normal(64).astype(np.float32)
    y = rng.standard_normal(64).astype(np.float32)
    bufs = {"x": x.copy(), "y": y.copy(),
            "z": np.zeros(64, np.float32)}
    params = interp.LaunchParams(grid=2, local_size=32, warp_size=32)
    runtime.clear_compile_cache()
    ck = runtime.compile_kernel(BENCHES["vecadd"].handle)  # disk hit
    interp.launch(ck.fn, bufs, params, scalar_args={"n": 64})
    np.testing.assert_allclose(bufs["z"], x + y, atol=1e-6)
    runtime.clear_compile_cache()


def test_ir_normalization_is_injective():
    """The content-hash normalizer alpha-renames tokens by first
    appearance: id-counter shifts across processes normalize away, but
    operand swaps (defs precede uses in a dump) and retargeted branches
    must keep distinct kernels distinct."""
    from repro.core.vir import Function, IRBuilder, Op, Param, Ty

    def build(swap: bool) -> str:
        fn = Function("k", [Param("p", Ty.PTR), Param("q", Ty.PTR)])
        bld = IRBuilder(fn)
        a = bld.load(fn.params[0], bld.intr("global_id"))
        b = bld.load(fn.params[1], bld.intr("global_id"))
        r = bld.binop(Op.SUB, b, a) if swap else bld.binop(Op.SUB, a, b)
        bld.store(fn.params[0], bld.intr("global_id"), r)
        bld.ret()
        return fn.dump()

    d1 = diskcache.normalize_ir(build(False))
    d2 = diskcache.normalize_ir(build(False))
    assert d1 == d2, "fresh builds (shifted id counters) must normalize " \
                     "to identical text"
    d3 = diskcache.normalize_ir(build(True))
    assert d1 != d3, "operand swap must survive normalization"
    # swapped branch targets: blocks keep their bodies, so the label
    # lines re-associate and the normalized text differs
    def build_cbr(swap: bool) -> str:
        fn = Function("k", [Param("p", Ty.PTR)])
        bld = IRBuilder(fn)
        c = bld.binop(Op.GT, bld.intr("global_id"),
                      bld.load(fn.params[0], bld.intr("global_id")))
        t_bb, e_bb = fn.new_block("t"), fn.new_block("e")
        bld.cbr(c, e_bb, t_bb) if swap else bld.cbr(c, t_bb, e_bb)
        bld.set_block(t_bb)
        bld.store(fn.params[0], bld.intr("global_id"),
                  bld.intr("global_id"))
        bld.ret()
        bld.set_block(e_bb)
        bld.ret()
        return fn.dump()

    assert diskcache.normalize_ir(build_cbr(False)) != \
        diskcache.normalize_ir(build_cbr(True))


def test_disk_cache_key_includes_compiler_fingerprint(tmp_path,
                                                      monkeypatch):
    """Entries compiled by a different pipeline version never hit."""
    monkeypatch.setenv("VOLT_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("VOLT_DISK_CACHE", "1")
    runtime.clear_compile_cache()
    runtime.compile_kernel(BENCHES["vecadd"].handle, use_cache=False)
    assert len(list(tmp_path.glob("*.vck"))) == 1
    monkeypatch.setattr(diskcache, "_COMPILER_FP", "different-compiler")
    hits0 = diskcache.DISK_CACHE_STATS["hits"]
    runtime.compile_kernel(BENCHES["vecadd"].handle, use_cache=False)
    assert diskcache.DISK_CACHE_STATS["hits"] == hits0, \
        "changed compiler fingerprint must miss"
    assert len(list(tmp_path.glob("*.vck"))) == 2
    runtime.clear_compile_cache()


def test_disk_cache_disabled_by_env(tmp_path, monkeypatch):
    monkeypatch.setenv("VOLT_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("VOLT_DISK_CACHE", "0")
    runtime.clear_compile_cache()
    runtime.compile_kernel(BENCHES["vecadd"].handle, use_cache=False)
    assert list(tmp_path.glob("*.vck")) == []
    runtime.clear_compile_cache()


def test_interp_imports_neither_the_jax_rung_nor_the_runtime():
    """Layering: the host executors sit under the runtime and the jax
    rung, so importing interp loads neither, and nothing installs hooks
    into it."""
    code = ("import sys\n"
            "from repro.core import interp\n"
            "print([m for m in ('repro.core.backends.jaxgen',\n"
            "                   'repro.core.runtime') if m in sys.modules])\n"
            "print([a for a in dir(interp) if '_HOOK' in a])\n")
    env = dict(os.environ)
    src = str(Path(interp.__file__).resolve().parents[2])
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, check=True)
    assert out.stdout.split() == ["[]", "[]"], out.stdout
