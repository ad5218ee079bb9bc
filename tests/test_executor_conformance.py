"""Cross-executor differential conformance suite.

With five executor configurations coexisting (instruction-at-a-time
oracle, per-warp pre-decoded, workgroup-batched lockstep, grid-batched —
now including MULTI-warp grids with per-workgroup barrier groups,
desync re-merge and row compaction — and the jitted JAX codegen rung)
the repo needs a systematic parity net rather than parity asserts
sprinkled through benchmarks.  This suite runs EVERY kernel — the whole
volt_bench registry plus the shared test kernels — through all five
executors at 1, 2 and 4 warps per workgroup and demands they agree
bit-for-bit:

  * identical ExecStats (dynamic instruction counts, per-op counters,
    coalesced memory requests, shared requests, atomic serialization,
    IPDOM depth, prints);
  * identical bytes in every output buffer;
  * or, for launches that are erroneous at that shape (e.g. a 32-wide
    shared tile under a 128-thread workgroup, barrier divergence), the
    SAME error class from every executor — the executors must agree on
    what they reject, not just on what they accept.

Kernels whose dynamic masks depend on the warp schedule (the top-down
``bfs``: threads read ``visited`` cells other threads write) are compared
oracle-vs-decoded at every shape, but batched-vs-oracle only at one warp
per workgroup where the batched path provably falls back to the per-warp
schedule; the grid-level batcher refuses them via its read-write-hazard
scan.

The jax column runs through ``rungs.launch`` (tests/kernels/rungs.py):
the rung self-licenses and self-certifies, and the grid rung serves
what it declines or faults on — so parity holds on EVERY kernel, and a
separate ENGAGEMENT test (telemetry) proves the rung truly executed
each licence-admitted kernel rather than vacuously falling back.  Each jax run is preceded by
a warm-up launch on scratch buffer copies so the differential
certification verdict is already recorded and the compared launch is
the jitted primary.

A hypothesis section fuzzes ragged trip-count vectors and divergence
patterns (nested vx_split inside vx_pred loops, divergent early returns,
barrier-in-loop) against the oracle, and checks the vx_pred ride-along
never fabricates barrier arrivals for warps that already left a loop.
"""
import sys
from pathlib import Path
from typing import Any, Callable, Dict, Tuple

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent / "kernels"))

from repro.core import interp
from repro.core.passes.pipeline import ABLATION_LADDER, run_pipeline
from repro.volt_bench import BENCHES

import rungs
import volt_kernels as K

FULL = ABLATION_LADDER[-1]

WARP_FACTORS = [1, 2, 4]

#: kernels with schedule-dependent masks or cross-warp write-write
#: clashes between different static stores: batched compared only where
#: it provably falls back to the per-warp schedule (see module
#: docstring).  two_store_conflict is the documented PR 2 wg-batching
#: limitation (lockstep orders clashing stores by static instruction,
#: the oracle by warp) — the grid-level batcher decodes its stores as
#: desync nodes (_hazard_stores), so single-warp launches of it stay
#: bit-identical.
#: loop_store_conflict is the cross-TRIP variant of the same clash (one
#: static store site, executed at different trip counts by different
#: warps) — grid mode desyncs it via the cyclic-block hazard rule, the
#: wg-batched mode keeps the PR 2 contract.
SCHEDULE_SENSITIVE = {"bfs", "tk_two_store_conflict",
                      "tk_loop_store_conflict",
                      "tk_callee_store_conflict"}

#: the GRID executor handles most of those exactly at EVERY warp factor:
#: its launch gate accepts two_store/loop_store (single root pointer)
#: and decodes their stores as desync nodes, draining rows in workgroup
#: order.  The ones it REFUSES (bfs: read-write hazard;
#: callee_store_conflict: one buffer stored through two distinct root
#: pointers) fall back to the wg-batched executor, so they inherit the
#: PR 2 multi-warp contract and are excluded at factor > 1 like it.
GRID_SCHEDULE_SENSITIVE = {"bfs", "tk_callee_store_conflict"}

EXECUTORS = {
    "oracle": dict(decoded=False),
    "decoded": dict(decoded=True, batched=False),
    "wg_batched": dict(decoded=True, batched=True, grid=False),
    "grid": dict(decoded=True, batched=True, grid=True),
    "jax": rungs.JAX,
}


_fold_warps = interp.fold_warps


# --------------------------------------------------------------------------
# case registry: volt_bench entries + makers for the shared test kernels
# --------------------------------------------------------------------------

Case = Tuple[Any, Callable]      # (front-end handle, make(rng) -> inputs)

CASES: Dict[str, Case] = {
    name: (b.handle, b.make) for name, b in BENCHES.items()
}


def _tk(handle, make):
    CASES[f"tk_{handle.name}"] = (handle, make)


def _p(grid: int = 4) -> interp.LaunchParams:
    return interp.LaunchParams(grid=grid, local_size=32, warp_size=32)


_tk(K.saxpy, lambda rng: (
    {"x": rng.standard_normal(128).astype(np.float32),
     "y": rng.standard_normal(128).astype(np.float32)},
    {"a": 1.5, "n": 120}, _p()))

_tk(K.loop_break_continue, lambda rng: (
    {"x": rng.standard_normal(128 * 4).astype(np.float32),
     "out": np.zeros(128, np.float32)}, {"n": 4}, _p()))

_tk(K.nested_return, lambda rng: (
    {"x": (rng.standard_normal(128) * 3).astype(np.float32),
     "out": np.zeros(128, np.float32)}, {"n": 6}, _p()))

_tk(K.ternary_mix, lambda rng: (
    {"x": rng.standard_normal(128).astype(np.float32),
     "y": rng.standard_normal(128).astype(np.float32),
     "out": np.zeros(128, np.float32)}, {"n": 120}, _p()))

_tk(K.shared_reduce, lambda rng: (
    {"x": rng.standard_normal(128).astype(np.float32),
     "out": np.zeros(4, np.float32)}, {"n": 120}, _p()))

_tk(K.uses_helper, lambda rng: (
    {"coefs": rng.standard_normal(4).astype(np.float32),
     "x": rng.standard_normal(128).astype(np.float32),
     "out": np.zeros(128, np.float32)}, {"deg": 4, "n": 120}, _p()))

_tk(K.warp_ops, lambda rng: (
    {"x": rng.standard_normal(128).astype(np.float32),
     "out": np.zeros(128, np.float32),
     "ballots": np.zeros(128, np.int32)}, {"n": 120}, _p()))

_tk(K.atomics_kernel, lambda rng: (
    {"x": rng.standard_normal(128).astype(np.float32),
     "hist": np.zeros(2, np.int32),
     "total": np.zeros(1, np.float32)}, {"n": 120}, _p()))

_tk(K.wg_reduce128, lambda rng: (
    {"x": rng.standard_normal(128).astype(np.float32),
     "out": np.zeros(4, np.float32)}, {"n": 120}, _p()))

_tk(K.wg_mixed, lambda rng: (
    {"x": rng.standard_normal(128).astype(np.float32),
     "y": np.zeros(128, np.float32),
     "count": np.zeros(1, np.int32)}, {"n": 120}, _p()))

_tk(K.wg_warp0_barrier, lambda rng: (
    {"x": np.zeros(128, np.float32)}, {"n": 128}, _p()))

_tk(K.two_store_conflict, lambda rng: (
    {"out": np.zeros(130, np.float32)}, {"n": 120}, _p()))

_tk(K.loop_store_conflict, lambda rng: (
    {"trip": rng.integers(0, 6, 128).astype(np.int32),
     "out": np.zeros(1, np.float32)}, {"n": 128}, _p()))

_tk(K.callee_store_conflict, lambda rng: (
    {"out": np.zeros(1, np.float32)}, {"n": 128}, _p()))


def _mk_csr_inputs(rng, n):
    deg = rng.integers(0, 10, n)
    rp = np.zeros(n + 1, np.int32)
    rp[1:] = np.cumsum(deg)
    cols = rng.integers(0, n, int(rp[-1])).astype(np.int32)
    return rp, cols


def _mk_tk_spmv(rng):
    n = 128
    rp, cols = _mk_csr_inputs(rng, n)
    return ({"row_ptr": rp, "cols": cols,
             "vals": rng.standard_normal(len(cols)).astype(np.float32),
             "x": rng.standard_normal(n).astype(np.float32),
             "y": np.zeros(n, np.float32)}, {"n": n}, _p())


def _mk_tk_bfs(rng):
    n = 128
    rp, cols = _mk_csr_inputs(rng, n)
    return ({"row_ptr": rp, "cols": cols,
             "frontier": (rng.uniform(0, 1, n) < 0.2).astype(np.int32),
             "next_frontier": np.zeros(n, np.int32),
             "visited": (rng.uniform(0, 1, n) < 0.3).astype(np.int32)},
            {"n": n}, _p())


_tk(K.spmv_csr, _mk_tk_spmv)
_tk(K.bfs_frontier, _mk_tk_bfs)

_tk(K.ragged_nested, lambda rng: (
    {"trip": rng.integers(0, 9, 128).astype(np.int32),
     "x": (rng.standard_normal(128) * 2).astype(np.float32),
     "out": np.zeros(128, np.float32)}, {"n": 128}, _p()))

# 2-D linear-id stores (the widened store-privacy licence); under the
# conformance harness's 1-D folds gid_y is 0 and the chain degenerates
# to gid_x — the dedicated 2-D launch lives in test_grid_metamorphic
_tk(K.ragged2d, lambda rng: (
    {"trip": rng.integers(0, 9, 128).astype(np.int32),
     "x": (rng.standard_normal(128) * 2).astype(np.float32),
     "out": np.zeros(128, np.float32)}, {"n": 128}, _p()))

# private-shared tile + shared-tile atomic (tile-sliced grid batching
# with the tile-aware per-warp desync fallback)
_tk(K.shared_hist, lambda rng: (
    {"x": rng.standard_normal(128).astype(np.float32),
     "out": np.zeros(16, np.int32)}, {"n": 120}, _p()))

# ragged loop reading a private shared tile (compaction tile gathering)
_tk(K.shared_tail, lambda rng: (
    {"trip": rng.integers(0, 6, 128).astype(np.int32),
     "x": rng.standard_normal(128).astype(np.float32),
     "out": np.zeros(128, np.float32)}, {"n": 128}, _p()))

# uniform trips: legal at every warp factor (ragged trips are exercised by
# the hypothesis section below, where the expected outcome is an error)
_tk(K.ragged_barrier_loop, lambda rng: (
    {"trip": np.full(128, 3, np.int32),
     "x": rng.standard_normal(128).astype(np.float32),
     "out": np.zeros(128, np.float32)}, {"n": 128}, _p()))


# --------------------------------------------------------------------------
# differential harness
# --------------------------------------------------------------------------

_CK_CACHE: Dict[str, Any] = {}


def _compiled(name: str):
    fn = _CK_CACHE.get(name)
    if fn is None:
        handle = CASES[name][0]
        mod = handle.build(None)
        fn = run_pipeline(mod, handle.name, FULL).fn
        _CK_CACHE[name] = fn
    return fn


def _run_one(fn, bufs0, params, scalars, kw):
    if kw.get("rung") == "jax":
        # warm-up on scratch copies: the first licensed launch is the
        # differential certification run; after it the recorded verdict
        # lets the compared launch below run as the jitted primary
        warm = {k: v.copy() for k, v in bufs0.items()}
        try:
            rungs.launch(fn, warm, params, scalar_args=scalars, **kw)
        except interp.ExecError:
            pass
    bufs = {k: v.copy() for k, v in bufs0.items()}
    try:
        st = rungs.launch(fn, bufs, params, scalar_args=scalars, **kw)
    except interp.ExecError as e:
        return ("error", type(e).__name__, None, None)
    return ("ok", None, st, bufs)


def _stats_tuple(st: interp.ExecStats):
    return (st.instrs, dict(st.by_op), st.mem_requests, st.mem_insts,
            st.shared_requests, st.atomic_serial, st.max_ipdom_depth,
            st.prints)


@pytest.mark.parametrize("factor", WARP_FACTORS)
@pytest.mark.parametrize("name", sorted(CASES))
def test_executor_conformance(name, factor):
    handle, make = CASES[name]
    rng = np.random.default_rng(7)
    bufs0, scalars, params = make(rng)
    params = _fold_warps(params, factor)
    fn = _compiled(name)

    results = {label: _run_one(fn, bufs0, params, scalars, kw)
               for label, kw in EXECUTORS.items()}
    compared = ["decoded", "wg_batched", "grid", "jax"]
    if factor > 1 and name in SCHEDULE_SENSITIVE:
        compared.remove("wg_batched")
        # the grid executor stays compared where it truly engages: a
        # gate-refused kernel, or a fold that left a single workgroup
        # (grid batching needs n_wg > 1), falls back to the wg-batched
        # executor and inherits its PR 2 contract.  The jax rung
        # REFUSES every schedule-sensitive kernel (they are not
        # order-free), so its column degenerates to the grid column and
        # inherits exactly the grid exclusions.
        if (name in GRID_SCHEDULE_SENSITIVE
                or params.grid * params.grid_y == 1):
            compared.remove("grid")
            compared.remove("jax")

    oracle = results["oracle"]
    for label in compared:
        r = results[label]
        assert r[0] == oracle[0], \
            f"{name} x{factor}: {label} {r[0]} but oracle {oracle[0]}"
        if oracle[0] == "error":
            assert r[1] == oracle[1], \
                f"{name} x{factor}: {label} raised {r[1]}, " \
                f"oracle {oracle[1]}"
            continue
        assert _stats_tuple(r[2]) == _stats_tuple(oracle[2]), \
            f"{name} x{factor}: {label} ExecStats diverged"
        for k in bufs0:
            np.testing.assert_array_equal(
                oracle[3][k], r[3][k],
                err_msg=f"{name} x{factor}: {label} buffer {k}")


def test_conformance_covers_whole_bench_registry():
    """The net must widen automatically: every registered bench is a
    conformance case."""
    for name in BENCHES:
        assert name in CASES


@pytest.mark.parametrize("name", ["reduce0", "psum", "shuffle_sw",
                                  "vote_sw", "tk_shared_hist"])
def test_private_shared_kernels_truly_take_the_grid_path(name):
    """The shared-kernel rows of the conformance sweep must not be
    vacuous: at their native single-warp-workgroup launches the grid
    batcher must actually ENGAGE (telemetry batches > 0) — these
    kernels fell back to per-workgroup dispatch before the private
    tile slicing — and stay bit-identical to the oracle."""
    handle, make = CASES[name]
    rng = np.random.default_rng(7)
    bufs0, scalars, params = make(rng)
    fn = _compiled(name)
    t = interp.GRID_TELEMETRY
    t.reset()
    got = _run_one(fn, bufs0, params, scalars,
                   dict(decoded=True, batched=True, grid=True))
    assert t.batches > 0, f"{name}: grid batching did not engage"
    oracle = _run_one(fn, bufs0, params, scalars, EXECUTORS["oracle"])
    assert got[0] == oracle[0] == "ok"
    assert _stats_tuple(got[2]) == _stats_tuple(oracle[2])
    assert got[2].shared_requests > 0, \
        f"{name}: expected shared-memory traffic"
    for k in bufs0:
        np.testing.assert_array_equal(oracle[3][k], got[3][k])


def test_jax_rung_engages_on_every_licensed_kernel():
    """The jax column of the sweep must not be vacuous: for every
    (kernel, warp factor) the licence admits, the telemetry must show
    the jitted program actually produced the results (engaged >= 1
    after the warm-up certified it) — a silently-falling-back rung
    would pass every parity assert while testing nothing."""
    from repro.core.backends import jaxgen

    admitted, failures = [], []
    for name in sorted(CASES):
        handle, make = CASES[name]
        fn = _compiled(name)
        for factor in WARP_FACTORS:
            rng = np.random.default_rng(7)
            bufs0, scalars, params = make(rng)
            params = _fold_warps(params, factor)
            ok, _why = jaxgen.licence_check(fn, params, bufs0,
                                            scalars, {})
            if not ok:
                continue
            admitted.append((name, factor))
            jaxgen.reset_jax_telemetry()
            r = _run_one(fn, bufs0, params, scalars, EXECUTORS["jax"])
            t = jaxgen.JAX_TELEMETRY
            if r[0] != "ok" or t["engaged"] < 1:
                failures.append((name, factor, r[0], dict(t)))
    assert admitted, "licence admitted no kernel at all — vacuous sweep"
    # the licence must keep admitting a healthy slice of the registry
    # (order-free store-private kernels at multi-workgroup shapes);
    # shrinkage here means a licence regression, not test drift
    assert len(admitted) >= 20, admitted
    assert not failures, f"licensed but not engaged: {failures}"


@pytest.mark.parametrize("label", sorted(EXECUTORS))
def test_exec_errors_carry_context(label):
    """Error-class conformance extends to error CONTEXT: every
    executor's semantic errors name the kernel and the workgroup they
    died in (the barrier-divergence error's format), so a production
    out-of-fuel or bad-binop report is actionable."""
    handle, make = CASES["tk_saxpy"]
    rng = np.random.default_rng(7)
    bufs0, scalars, params = make(rng)
    params = interp.LaunchParams(grid=params.grid,
                                 local_size=params.local_size,
                                 warp_size=params.warp_size, fuel=50)
    fn = _compiled("tk_saxpy")
    bufs = {k: v.copy() for k, v in bufs0.items()}
    with pytest.raises(interp.ExecError) as ei:
        rungs.launch(fn, bufs, params, scalar_args=scalars,
                     **EXECUTORS[label])
    msg = str(ei.value)
    assert "in @saxpy" in msg, (label, msg)
    assert "workgroup" in msg, (label, msg)


# --------------------------------------------------------------------------
# hypothesis: ragged trip counts and divergence patterns vs the oracle
# --------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

import os

# CI caps the example budget (VOLT_HYPOTHESIS_MAX_EXAMPLES=10) so the
# hypothesis-enabled job stays fast while local runs keep full coverage
_H_EXAMPLES = int(os.environ.get("VOLT_HYPOTHESIS_MAX_EXAMPLES", "25"))

needs_hypothesis = pytest.mark.skipif(
    not _HAVE_HYPOTHESIS,
    reason="property tests need hypothesis "
           "(pip install -r requirements-dev.txt)")


def _parity_or_same_error(name, fn, bufs0, params, scalars,
                          kw=dict(decoded=True, batched=True)):
    """Default kw = the production default (auto wg/grid batching)."""
    oracle = _run_one(fn, bufs0, params, scalars, EXECUTORS["oracle"])
    batched = _run_one(fn, bufs0, params, scalars, kw)
    assert batched[0] == oracle[0], \
        f"{name}: batched {batched[0]} but oracle {oracle[0]}"
    if oracle[0] == "error":
        assert batched[1] == oracle[1], name
        return "error"
    assert _stats_tuple(batched[2]) == _stats_tuple(oracle[2]), \
        f"{name}: ExecStats diverged"
    for k in bufs0:
        np.testing.assert_array_equal(oracle[3][k], batched[3][k],
                                      err_msg=f"{name}: buffer {k}")
    return "ok"


if _HAVE_HYPOTHESIS:
    @needs_hypothesis
    @settings(max_examples=min(25, _H_EXAMPLES), deadline=None)
    @given(warp_size=st.sampled_from([4, 8, 16, 32]),
           n_warps=st.integers(1, 4),
           grid=st.integers(1, 2),
           max_trip=st.integers(0, 12),
           seed=st.integers(0, 2**31 - 1))
    def test_ride_along_ragged_loop_parity(warp_size, n_warps, grid,
                                           max_trip, seed):
        """Random ragged trip-count vectors through a loop with a nested
        vx_split diamond and a divergent early return: lockstep (with
        vx_pred ride-along) must match the oracle bit for bit."""
        rng = np.random.default_rng(seed)
        local = n_warps * warp_size
        total = grid * local
        params = interp.LaunchParams(grid=grid, local_size=local,
                                     warp_size=warp_size)
        fn = _compiled("tk_ragged_nested")
        bufs0 = {"trip": rng.integers(0, max_trip + 1,
                                      total).astype(np.int32),
                 "x": (rng.standard_normal(total) * 2).astype(np.float32),
                 "out": np.zeros(total, np.float32)}
        _parity_or_same_error(
            f"ragged_nested{(warp_size, n_warps, grid, seed)}",
            fn, bufs0, params, {"n": total})

    @needs_hypothesis
    @settings(max_examples=min(25, _H_EXAMPLES), deadline=None)
    @given(n_warps=st.integers(1, 4),
           grid=st.integers(1, 2),
           uniform=st.booleans(),
           seed=st.integers(0, 2**31 - 1))
    def test_ride_along_never_fabricates_barrier_arrivals(n_warps, grid,
                                                          uniform, seed):
        """Barrier inside a data-dependent loop.  Per-workgroup-uniform
        trip counts must execute in parity; ragged trip counts are
        barrier divergence — the batched executor must reproduce the
        oracle's error instead of letting exited warps ride along and
        silently 'arrive' at barriers they never reach per-warp."""
        rng = np.random.default_rng(seed)
        W = 32
        local = n_warps * W
        total = grid * local
        params = interp.LaunchParams(grid=grid, local_size=local,
                                     warp_size=W)
        fn = _compiled("tk_ragged_barrier_loop")
        if uniform:
            trips = np.repeat(rng.integers(0, 5, grid), local)
        else:
            # per-warp trip counts; ragged across warps iff n_warps > 1
            per_warp = rng.integers(0, 5, grid * n_warps)
            trips = np.repeat(per_warp, W)
        bufs0 = {"trip": trips.astype(np.int32),
                 "x": rng.standard_normal(total).astype(np.float32),
                 "out": np.zeros(total, np.float32)}
        outcome = _parity_or_same_error(
            f"ragged_barrier{(n_warps, grid, uniform, seed)}",
            fn, bufs0, params, {"n": total})
        wg_trips = trips.reshape(grid, local)
        wg_uniform = bool((wg_trips == wg_trips[:, :1]).all())
        if wg_uniform:
            assert outcome == "ok"
        else:
            assert outcome == "error", \
                "ragged barrier loop must fail in BOTH executors"

    @needs_hypothesis
    @settings(max_examples=min(15, _H_EXAMPLES), deadline=None)
    @given(n_warps=st.integers(2, 4),
           seed=st.integers(0, 2**31 - 1))
    def test_ride_along_grid_mode_barrier_loop(n_warps, seed):
        """Grid-level batching: ragged barrier loops over INDEPENDENT
        single-warp workgroups are legal (a barrier synchronizes one
        warp) and must stay in parity even though rows exit the loop at
        different trips."""
        rng = np.random.default_rng(seed)
        W = 32
        grid = n_warps            # several single-warp workgroups
        total = grid * W
        params = interp.LaunchParams(grid=grid, local_size=W, warp_size=W)
        fn = _compiled("tk_ragged_barrier_loop")
        bufs0 = {"trip": np.repeat(rng.integers(0, 5, grid),
                                   W).astype(np.int32),
                 "x": rng.standard_normal(total).astype(np.float32),
                 "out": np.zeros(total, np.float32)}
        outcome = _parity_or_same_error(
            f"grid_barrier{(n_warps, seed)}", fn, bufs0, params,
            {"n": total})
        assert outcome == "ok"
else:
    @needs_hypothesis
    def test_ride_along_ragged_loop_parity():
        pass

    @needs_hypothesis
    def test_ride_along_never_fabricates_barrier_arrivals():
        pass

    @needs_hypothesis
    def test_ride_along_grid_mode_barrier_loop():
        pass
