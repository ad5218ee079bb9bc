"""Launch-service conformance: continuous launch batching + pooled
device memory (docs/performance.md "Serve side").

The tentpole contract under test: coalescing compatible launches of one
compiled kernel into shared grid chunks must be BIT-INVISIBLE — every
tenant's buffers and ExecStats identical to running its launch alone —
and every failure mode (injected faults, deadlines, memory budgets,
open breakers) must stay PER-LAUNCH, never per-coalesced-chunk.

Sections:

  * engine sweep — every coalescible registry kernel x {1, 2, 4}
    warps/workgroup x mixed-tenant queues (different data, scalars and
    grids per tenant), solo vs ``interp.launch_coalesced``;
  * service — grouping, mixed-kernel queues, EngineBusy backpressure,
    cross-tenant aliasing fallback, breaker interplay, abort-streak
    pause;
  * pooled allocator — zero-fill preserved across reuse (stale bytes
    from a previous tenant never observable), capacity bound,
    double-release guard, steady-state reuse;
  * fault/deadline/budget isolation — a group member's fault demotes or
    fails ONLY that member's launch; everyone else's results stay
    bit-identical to the fault-free reference.
"""
import sys
import threading
from pathlib import Path
from typing import Dict

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent / "kernels"))

from repro.core import diskcache, faults, governor, interp, runtime
from repro.core.faults import DeadlineExceeded, EngineBusy
from repro.core.passes.pipeline import ABLATION_LADDER, run_pipeline
from repro.core.runtime import LaunchService, Runtime
from repro.volt_bench import BENCHES

import volt_kernels as K

FULL = ABLATION_LADDER[-1]
WARP_FACTORS = [1, 2, 4]

_CK: Dict[str, object] = {}


def _compiled(handle):
    fn = _CK.get(handle.name)
    if fn is None:
        fn = run_pipeline(handle.build(None), handle.name, FULL).fn
        _CK[handle.name] = fn
    return fn


def _stats_sig(st: interp.ExecStats):
    return (st.instrs, dict(st.by_op), st.mem_requests, st.mem_insts,
            st.shared_requests, st.atomic_serial, st.max_ipdom_depth,
            st.prints)


def _assert_tenant_parity(name, solo_bufs, solo_stats, co_bufs,
                          co_stats):
    for j, (sb, cb) in enumerate(zip(solo_bufs, co_bufs)):
        for k in sb:
            np.testing.assert_array_equal(
                sb[k], cb[k],
                err_msg=f"{name}: tenant {j} buffer {k} diverged")
    for j, (ss, cs) in enumerate(zip(solo_stats, co_stats)):
        assert _stats_sig(ss) == _stats_sig(cs), \
            f"{name}: tenant {j} stats diverged\n" \
            f"  solo: {_stats_sig(ss)}\n  coal: {_stats_sig(cs)}"


# --------------------------------------------------------------------------
# engine sweep: every coalescible kernel x warp factors x mixed tenants
# --------------------------------------------------------------------------

@pytest.mark.parametrize("factor", WARP_FACTORS)
def test_coalesced_bit_identity_sweep(factor):
    """Solo-vs-coalesced differential over the whole bench registry.
    Kernels the licence refuses (read+write params, hazard stores at
    the folded shape, shared-tile kernels the fold makes erroneous) must
    abort with tenant buffers untouched — never silently diverge."""
    coalesced_any = 0
    for name in sorted(BENCHES):
        b = BENCHES[name]
        fn = _compiled(b.handle)
        tenants = []
        for seed in (11, 12, 13):
            rng = np.random.default_rng(seed)
            bufs, scalars, params = b.make(rng)
            tenants.append((bufs, scalars,
                            interp.fold_warps(params, factor)))
        # solo reference on copies (solo may legitimately error at the
        # folded shape, e.g. 32-wide shared tiles under 128 threads —
        # then the coalesced run must refuse, not invent an answer)
        solo_bufs, solo_stats = [], []
        solo_err = None
        for bufs, scalars, params in tenants:
            bb = {k: v.copy() for k, v in bufs.items()}
            try:
                st = interp.launch(fn, bb, params, scalar_args=scalars)
            except faults.KernelFault as e:
                solo_err = e
                break
            solo_bufs.append(bb)
            solo_stats.append(st)
        co_bufs = [{k: v.copy() for k, v in bufs.items()}
                   for bufs, _, _ in tenants]
        frozen = [{k: v.copy() for k, v in cb.items()} for cb in co_bufs]
        co_tenants = [(cb, scal, p) for cb, (_, scal, p)
                      in zip(co_bufs, tenants)]
        try:
            co_stats = interp.launch_coalesced(fn, co_tenants)
        except interp._CoalesceAbort:
            # group-abort contract: nothing written
            for cb, fz in zip(co_bufs, frozen):
                for k in cb:
                    np.testing.assert_array_equal(
                        cb[k], fz[k],
                        err_msg=f"{name} x{factor}: aborted group "
                                f"wrote tenant buffer {k}")
            continue
        assert solo_err is None, \
            f"{name} x{factor}: solo errored ({solo_err}) but the " \
            f"coalesced run did not abort"
        coalesced_any += 1
        _assert_tenant_parity(f"{name} x{factor}", solo_bufs,
                              solo_stats, co_bufs, co_stats)
    # the sweep must not go vacuous: a healthy slice of the registry
    # coalesces at every factor
    assert coalesced_any >= 8, \
        f"only {coalesced_any} kernels coalesced at factor {factor}"


def test_coalesced_mixed_grids_and_scalars():
    """Tenants may differ in grid size AND scalar args — grid-dependent
    intrinsics go row-uniform, scalars broadcast per tenant row."""
    fn = _compiled(K.ternary_mix)
    tenants = []
    for j, (grid, n) in enumerate([(4, 120), (7, 200), (2, 40)]):
        rng = np.random.default_rng(20 + j)
        # buffer shapes must agree across tenants (licence); grids and
        # scalars may not — out-of-range rows simply stay untouched
        tenants.append((
            {"x": rng.standard_normal(256).astype(np.float32),
             "y": rng.standard_normal(256).astype(np.float32),
             "out": np.zeros(256, np.float32)},
            {"n": n},
            interp.LaunchParams(grid=grid, local_size=32, warp_size=32)))
    solo_bufs, solo_stats = [], []
    for bufs, scalars, params in tenants:
        bb = {k: v.copy() for k, v in bufs.items()}
        solo_stats.append(interp.launch(fn, bb, params,
                                        scalar_args=scalars))
        solo_bufs.append(bb)
    co_bufs = [{k: v.copy() for k, v in bufs.items()}
               for bufs, _, _ in tenants]
    co_stats = interp.launch_coalesced(
        fn, [(cb, scal, p) for cb, (_, scal, p)
             in zip(co_bufs, tenants)])
    _assert_tenant_parity("ternary_mix mixed", solo_bufs, solo_stats,
                          co_bufs, co_stats)


# --------------------------------------------------------------------------
# service behaviour
# --------------------------------------------------------------------------

def _mk_vecadd(seed):
    rng = np.random.default_rng(seed)
    bufs, scalars, params = BENCHES["vecadd"].make(rng)
    return bufs, scalars, params


def _stream_solo(fn, tenant_inputs, rounds=1):
    rt = Runtime()
    stats = []
    for _ in range(rounds):
        for bufs, scalars, params in tenant_inputs:
            stats.append(rt.launch(fn, grid=params.grid,
                                   block=params.local_size,
                                   scalar_args=scalars, buffers=bufs))
    return stats


def test_service_coalesces_and_matches_solo():
    fn = _compiled(BENCHES["vecadd"].handle)
    solo_in = [_mk_vecadd(s) for s in range(4)]
    solo_stats = _stream_solo(fn, solo_in, rounds=2)

    svc_in = [_mk_vecadd(s) for s in range(4)]
    rt = Runtime()
    svc = LaunchService(rt)
    handles = []
    for _ in range(2):
        for j, (bufs, scalars, params) in enumerate(svc_in):
            handles.append(svc.submit(fn, grid=params.grid,
                                      block=params.local_size,
                                      buffers=bufs, scalar_args=scalars,
                                      tenant=j))
        svc.flush()
    assert all(h.mode == "coalesced" for h in handles), \
        [h.mode for h in handles]
    assert svc.telemetry["groups"] == 2
    for (sb, _, _), (cb, _, _) in zip(solo_in, svc_in):
        for k in sb:
            np.testing.assert_array_equal(sb[k], cb[k])
    for ss, h in zip(solo_stats, handles):
        assert _stats_sig(ss) == _stats_sig(h.result())
    # per-tenant reports pushed, executor = grid (shared chunks)
    assert all(h.report is not None and h.report.executor == "grid"
               for h in handles)
    # second flush reused the first flush's staging tables
    assert rt.pool.hits > 0


def test_service_mixed_kernel_queue():
    """A queue holding several kernels: compatible ones fuse per group,
    non-coalescible ones (saxpy reads+writes y) run solo — results all
    bit-identical to sequential execution."""
    fn_v = _compiled(BENCHES["vecadd"].handle)
    fn_s = _compiled(K.saxpy)

    def mk_saxpy(seed):
        rng = np.random.default_rng(seed)
        return ({"x": rng.standard_normal(128).astype(np.float32),
                 "y": rng.standard_normal(128).astype(np.float32)},
                {"a": 1.5, "n": 120},
                interp.LaunchParams(grid=4, local_size=32, warp_size=32))

    plan = [(fn_v, _mk_vecadd(1)), (fn_s, mk_saxpy(2)),
            (fn_v, _mk_vecadd(3)), (fn_s, mk_saxpy(4)),
            (fn_v, _mk_vecadd(5))]
    ref = [(fn, ({k: v.copy() for k, v in bufs.items()}, scal, p))
           for fn, (bufs, scal, p) in plan]
    for fn, (bufs, scal, p) in ref:
        interp.launch(fn, bufs, p, scalar_args=scal)

    rt = Runtime()
    svc = LaunchService(rt)
    handles = [svc.submit(fn, grid=p.grid, block=p.local_size,
                          buffers=bufs, scalar_args=scal)
               for fn, (bufs, scal, p) in plan]
    out = svc.flush()
    assert out == handles      # submission order preserved
    modes = [h.mode for h in handles]
    assert modes == ["coalesced", "solo", "coalesced", "solo",
                     "coalesced"], modes
    for (_, (rb, _, _)), (_, (lb, _, _)) in zip(ref, plan):
        for k in rb:
            np.testing.assert_array_equal(rb[k], lb[k])
    assert svc.telemetry["no_licence"] >= 1     # saxpy group refused


def test_service_busy_and_pending():
    fn = _compiled(BENCHES["vecadd"].handle)
    rt = Runtime()
    svc = LaunchService(rt, max_pending=2)
    bufs, scal, p = _mk_vecadd(0)
    svc.submit(fn, grid=p.grid, block=p.local_size, buffers=bufs,
               scalar_args=scal)
    svc.submit(fn, grid=p.grid, block=p.local_size, buffers=bufs,
               scalar_args=scal)
    assert svc.pending() == 2
    with pytest.raises(EngineBusy):
        svc.submit(fn, grid=p.grid, block=p.local_size, buffers=bufs,
                   scalar_args=scal)
    assert svc.telemetry["busy_rejections"] == 1
    svc.flush()
    assert svc.pending() == 0


def test_service_cross_tenant_alias_runs_solo():
    """Two queued launches sharing a buffer are sequentially dependent
    (launch 2 reads launch 1's output) — the service must NOT stage
    them into last-wins table rows."""
    fn = _compiled(BENCHES["vecadd"].handle)
    bufs, scal, p = _mk_vecadd(0)
    ref = {k: v.copy() for k, v in bufs.items()}
    interp.launch(fn, ref, p, scalar_args=scal)
    interp.launch(fn, ref, p, scalar_args=scal)

    rt = Runtime()
    svc = LaunchService(rt)
    h1 = svc.submit(fn, grid=p.grid, block=p.local_size, buffers=bufs,
                    scalar_args=scal)
    h2 = svc.submit(fn, grid=p.grid, block=p.local_size, buffers=bufs,
                    scalar_args=scal)
    svc.flush()
    assert h1.mode == "solo" and h2.mode == "solo"
    assert svc.telemetry["alias_solo"] == 1
    for k in ref:
        np.testing.assert_array_equal(ref[k], bufs[k])


def test_service_open_breaker_disables_coalescing():
    """An open breaker means the kernel is demoting — its launches need
    the per-launch chain (pin, probes), so the service must not fuse
    them."""
    fn = _compiled(BENCHES["vecadd"].handle)
    rt = Runtime()
    svc = LaunchService(rt)
    key = diskcache.plan_key(fn)
    entry = rt.breaker.entry(key, fn.name)
    entry.state = "open"
    entry.pinned_rung = "decoded"
    entry._probe_countdown = 100
    ins = [_mk_vecadd(s) for s in range(3)]
    hs = [svc.submit(fn, grid=p.grid, block=p.local_size, buffers=b,
                     scalar_args=s) for b, s, p in ins]
    svc.flush()
    assert all(h.mode == "solo" for h in hs)
    assert svc.telemetry["breaker_solo"] == 1
    assert all(h.report.pinned_rung == "decoded" for h in hs)


def test_service_abort_streak_pauses_coalescing():
    fn = _compiled(BENCHES["vecadd"].handle)
    rt = Runtime()
    svc = LaunchService(rt)

    def one_flush():
        ins = [_mk_vecadd(s) for s in range(2)]
        for b, s, p in ins:
            svc.submit(fn, grid=p.grid, block=p.local_size, buffers=b,
                       scalar_args=s)
        return svc.flush()

    with faults.inject("coalesce.exec", prob=1.0):
        for _ in range(LaunchService.ABORT_STREAK):
            hs = one_flush()
            assert all(h.mode == "solo" and h.error is None
                       for h in hs)
    assert svc.telemetry["group_aborts"] == LaunchService.ABORT_STREAK
    # streak reached: the next flushes skip the staging attempt...
    hs = one_flush()
    assert all(h.mode == "solo" for h in hs)
    assert svc.telemetry["abort_paused"] == 1
    # ...until the cooldown elapses, then a clean probe re-enables
    for _ in range(LaunchService.RETRY_EVERY - 1):
        one_flush()
    hs = one_flush()
    assert all(h.mode == "coalesced" for h in hs)


# --------------------------------------------------------------------------
# pooled allocator
# --------------------------------------------------------------------------

def test_pool_zero_fill_never_leaks_stale_bytes():
    pool = interp.DevicePool()
    a = pool.take((64,), np.float32)
    assert not a.any()
    a[:] = 7.0
    assert pool.release(a) is True
    b = pool.take((64,), np.float32)
    assert pool.hits == 1
    assert not b.any(), "pooled reuse leaked a previous tenant's bytes"
    # a smaller take rounding up to the same pow2 class is zeroed too
    b[:] = 3.0
    pool.release(b)
    c = pool.take((40,), np.float32)     # 160 B -> 256 B class
    assert pool.hits == 2 and not c.any()


def test_pool_capacity_and_double_release():
    pool = interp.DevicePool(capacity=256)
    a = pool.take((64,), np.float32)     # 256-byte class
    b = pool.take((64,), np.float32)
    assert pool.release(a) is True
    assert pool.release(a) is False      # double release guarded
    assert pool.release(b) is False      # over capacity: dropped
    assert pool.dropped == 1
    assert pool.held_bytes == 256
    # foreign arrays are never pooled
    assert pool.release(np.zeros(64, np.float32)) is False


def test_pool_steady_state_no_fresh_allocation():
    """Second identical coalesced flush serves every staging table and
    shared tile from the free lists."""
    fn = _compiled(BENCHES["sfilter"].handle)
    rt = Runtime()
    svc = LaunchService(rt)

    def one_round():
        ins = [BENCHES["sfilter"].make(np.random.default_rng(s))
               for s in range(3)]
        hs = [svc.submit(fn, grid=p.grid, block=p.local_size,
                         buffers=b, scalar_args=s) for b, s, p in ins]
        svc.flush()
        assert all(h.mode == "coalesced" for h in hs)

    one_round()
    misses0 = rt.pool.misses
    one_round()
    assert rt.pool.misses == misses0, \
        "steady-state flush allocated fresh backing arrays"
    assert rt.pool.hits > 0


def test_pool_budget_env(monkeypatch):
    monkeypatch.setenv("VOLT_POOL_BUDGET", "1k")
    assert governor.env_pool_budget() == 1024
    rt = Runtime()
    assert rt.pool.capacity == 1024
    rt2 = Runtime(governor=governor.GovernorConfig(pool_budget=2048))
    assert rt2.pool.capacity == 2048


# --------------------------------------------------------------------------
# fault / deadline / budget isolation
# --------------------------------------------------------------------------

def test_injected_group_fault_falls_back_bit_identical():
    fn = _compiled(BENCHES["vecadd"].handle)
    solo_in = [_mk_vecadd(s) for s in range(3)]
    solo_stats = _stream_solo(fn, solo_in)

    svc_in = [_mk_vecadd(s) for s in range(3)]
    rt = Runtime()
    svc = LaunchService(rt)
    hs = [svc.submit(fn, grid=p.grid, block=p.local_size, buffers=b,
                     scalar_args=s) for b, s, p in svc_in]
    with faults.inject("coalesce.exec", prob=1.0):
        svc.flush()
    assert all(h.mode == "solo" and h.error is None for h in hs)
    assert svc.telemetry["group_aborts"] == 1
    assert runtime.LAUNCH_TELEMETRY["coalesce_aborts"] >= 1
    for (sb, _, _), (cb, _, _) in zip(solo_in, svc_in):
        for k in sb:
            np.testing.assert_array_equal(sb[k], cb[k])
    for ss, h in zip(solo_stats, hs):
        assert _stats_sig(ss) == _stats_sig(h.result())


def test_deadline_fails_only_the_affected_tenant():
    """One tenant with an already-expired deadline: the group aborts
    untouched, the solo reruns fail THAT tenant (rolled back) and
    complete everyone else bit-identically."""
    fn = _compiled(BENCHES["vecadd"].handle)
    solo_in = [_mk_vecadd(s) for s in range(3)]
    solo_stats = _stream_solo(fn, solo_in)

    svc_in = [_mk_vecadd(s) for s in range(3)]
    frozen1 = {k: v.copy() for k, v in svc_in[1][0].items()}
    rt = Runtime()
    svc = LaunchService(rt)
    hs = []
    for j, (b, s, p) in enumerate(svc_in):
        hs.append(svc.submit(
            fn, grid=p.grid, block=p.local_size, buffers=b,
            scalar_args=s, deadline_ms=0.0 if j == 1 else None,
            tenant=j))
    svc.flush()
    assert hs[1].error is not None
    with pytest.raises(DeadlineExceeded):
        hs[1].result()
    assert hs[1].report is not None and hs[1].report.deadline_expired
    # the timed-out tenant is bit-invisible (rollback)
    for k in frozen1:
        np.testing.assert_array_equal(frozen1[k], svc_in[1][0][k])
    # the others completed exactly as solo
    for j in (0, 2):
        for k in solo_in[j][0]:
            np.testing.assert_array_equal(solo_in[j][0][k],
                                          svc_in[j][0][k])
        assert _stats_sig(solo_stats[j]) == _stats_sig(hs[j].result())


def test_grid_fault_demotes_per_launch_not_per_group():
    """A persistent fast-rung outage: the coalesced attempt aborts, each
    solo rerun demotes below the faulted rungs INDIVIDUALLY and still
    completes — results bit-identical, per-tenant reports record the
    demotion (never one shared demotion for the whole chunk)."""
    fn = _compiled(BENCHES["vecadd"].handle)
    solo_ref = [_mk_vecadd(s) for s in range(3)]
    for bufs, scalars, params in solo_ref:
        interp.launch(fn, bufs, params, scalar_args=scalars)

    svc_in = [_mk_vecadd(s) for s in range(3)]
    rt = Runtime()
    svc = LaunchService(rt)
    hs = [svc.submit(fn, grid=p.grid, block=p.local_size, buffers=b,
                     scalar_args=s) for b, s, p in svc_in]
    try:
        faults.install_spec("coalesce.exec:1.0:1, jax.exec:1.0:2, "
                            "grid.exec:1.0:3")
        svc.flush()
    finally:
        faults.clear()
    assert all(h.error is None and h.mode == "solo" for h in hs)
    assert svc.telemetry["group_aborts"] == 1
    for h in hs:
        assert h.report.demotions >= 1
        assert h.report.executor not in ("jax", "grid")
    for (rb, _, _), (lb, _, _) in zip(solo_ref, svc_in):
        for k in rb:
            np.testing.assert_array_equal(rb[k], lb[k])


def test_mem_budget_aborts_staging_to_solo():
    """Staging tables over VOLT_MEM_BUDGET: the group refuses up front
    and the launches run solo (whose own allocations fit)."""
    fn = _compiled(BENCHES["vecadd"].handle)
    rt = Runtime(governor=governor.GovernorConfig(mem_budget=1024))
    svc = LaunchService(rt)
    ins = [_mk_vecadd(s) for s in range(3)]
    hs = [svc.submit(fn, grid=p.grid, block=p.local_size, buffers=b,
                     scalar_args=s) for b, s, p in ins]
    svc.flush()
    assert all(h.mode == "solo" and h.error is None for h in hs)
    assert svc.telemetry["group_aborts"] == 1
    assert "budget" in svc.last_abort


# --------------------------------------------------------------------------
# small-launch router (jax rung dispatch floor)
# --------------------------------------------------------------------------

def test_small_launch_router_prefers_grid_when_measured_faster(monkeypatch):
    """Schema-3 verdicts carry measured (jax_ms, grid_ms); when the grid
    walk measured decisively faster, the jax rung declines the launch so
    the ~0.5 ms dispatch floor never taxes small kernels.  Timings are
    seeded deterministically so the test doesn't depend on the host."""
    from repro.core.backends import jaxgen

    b = BENCHES["vecadd"]
    fn = run_pipeline(b.handle.build(None), b.handle.name, FULL).fn
    rt = Runtime(jax=True)
    bufs, scalars, params = b.make(np.random.default_rng(0))
    # cert + certified primary populate the timed verdicts
    rt.launch(fn, grid=params.grid, block=params.local_size,
              scalar_args=scalars, buffers=bufs)
    rt.launch(fn, grid=params.grid, block=params.local_size,
              scalar_args=scalars, buffers=bufs)
    certs = jaxgen._certs(fn)
    assert certs, "cert store never populated"

    # pin decisive measurements in-memory only: the disk cache is off,
    # so the fake timings never reach the shared .vjc store
    monkeypatch.setenv("VOLT_DISK_CACHE", "0")
    for sig, entry in list(certs.items()):
        verdict, jax_ms, grid_ms = jaxgen._verdict_of(entry)
        assert verdict in ("pass", "pass-exact")
        assert grid_ms is not None, "cert run did not measure grid_ms"
        jaxgen._record(fn, sig, verdict, jax_ms=10.0, grid_ms=1.0)

    before = jaxgen.JAX_TELEMETRY["routed_small"]
    routed = runtime.LAUNCH_TELEMETRY["routed_small"]
    ref = {k: v.copy() for k, v in bufs.items()}
    interp.launch(fn, ref, params, scalar_args=scalars)
    rt.launch(fn, grid=params.grid, block=params.local_size,
              scalar_args=scalars, buffers=bufs)
    assert jaxgen.JAX_TELEMETRY["routed_small"] == before + 1
    assert runtime.LAUNCH_TELEMETRY["routed_small"] == routed + 1
    assert rt.last_report.executor == "grid"
    for k in ref:
        np.testing.assert_array_equal(ref[k], bufs[k])

    # flip the measurement: jax decisively faster -> jax serves again
    for sig, entry in list(certs.items()):
        verdict, _, _ = jaxgen._verdict_of(entry)
        jaxgen._record(fn, sig, verdict, jax_ms=1.0, grid_ms=10.0)
    rt.launch(fn, grid=params.grid, block=params.local_size,
              scalar_args=scalars, buffers=bufs)
    assert rt.last_report.executor == "jax"
    assert jaxgen.JAX_TELEMETRY["routed_small"] == before + 1


# --------------------------------------------------------------------------
# concurrency: shared Runtime, per-tenant buffers
# --------------------------------------------------------------------------

def test_concurrent_submitters_and_solo_launches():
    fn = _compiled(BENCHES["vecadd"].handle)
    ref = [_mk_vecadd(s) for s in range(8)]
    for bufs, scalars, params in ref:
        interp.launch(fn, bufs, params, scalar_args=scalars)

    rt = Runtime()
    svc = LaunchService(rt, max_pending=64)
    live = [_mk_vecadd(s) for s in range(8)]
    runtime.reset_launch_telemetry()
    errs = []

    def submit_two(j):
        try:
            for b, s, p in live[2 * j: 2 * j + 2]:
                svc.submit(fn, grid=p.grid, block=p.local_size,
                           buffers=b, scalar_args=s, tenant=j)
        except Exception as e:          # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=submit_two, args=(j,))
               for j in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    hs = svc.flush()
    assert len(hs) == 8
    assert all(h.error is None for h in hs)
    for (rb, _, _), (lb, _, _) in zip(ref, live):
        for k in rb:
            np.testing.assert_array_equal(rb[k], lb[k])
    t = runtime.LAUNCH_TELEMETRY
    assert t["launches"] == 8
    assert len(rt.last_reports()) == 8


# --------------------------------------------------------------------------
# latency-bounded flush: deadline pressure drains the queue from submit()
# --------------------------------------------------------------------------

def test_pressure_flush_drains_on_submit():
    """When the oldest queued launch has burned over `pressure` of its
    deadline budget waiting, the next submit() drains the queue —
    batching never turns a deadline miss into a queueing artifact."""
    import time
    fn = _compiled(BENCHES["vecadd"].handle)
    rt = Runtime()
    svc = LaunchService(rt, pressure=0.5)
    b1, s1, p = _mk_vecadd(0)
    h1 = svc.submit(fn, grid=p.grid, block=p.local_size, buffers=b1,
                    scalar_args=s1, deadline_ms=40.0)
    # fresh entry: far under 50% of its 40ms budget — no drain
    assert svc.pending() == 1
    time.sleep(0.03)               # 30ms queued > 0.5 * 40ms
    b2, s2, _ = _mk_vecadd(1)
    h2 = svc.submit(fn, grid=p.grid, block=p.local_size, buffers=b2,
                    scalar_args=s2, deadline_ms=40.0)
    assert svc.pending() == 0, "pressure submit must drain the queue"
    assert svc.telemetry["pressure_flushes"] == 1
    assert h1.error is None and h2.error is None
    assert h1.stats is not None and h2.stats is not None


def test_pressure_none_disables_auto_flush():
    import time
    fn = _compiled(BENCHES["vecadd"].handle)
    rt = Runtime()
    svc = LaunchService(rt, pressure=None)
    for seed in (0, 1):
        b, s, p = _mk_vecadd(seed)
        svc.submit(fn, grid=p.grid, block=p.local_size, buffers=b,
                   scalar_args=s, deadline_ms=5.0)
        time.sleep(0.02)
    assert svc.pending() == 2      # explicit flush() only
    assert svc.telemetry["pressure_flushes"] == 0
    svc.flush()


def test_pressure_ignores_deadlineless_entries():
    """Entries with no deadline (and no governor default) exert no
    pressure — there is no budget to burn."""
    import time
    fn = _compiled(BENCHES["vecadd"].handle)
    rt = Runtime()
    assert rt.gov_cfg.deadline_ms is None
    svc = LaunchService(rt, pressure=0.0)
    for seed in (0, 1):
        b, s, p = _mk_vecadd(seed)
        svc.submit(fn, grid=p.grid, block=p.local_size, buffers=b,
                   scalar_args=s)
        time.sleep(0.005)
    assert svc.pending() == 2
    assert svc.telemetry["pressure_flushes"] == 0
    svc.flush()


# --------------------------------------------------------------------------
# parallel workers x coalescing (the multiplicative serve-side win)
# --------------------------------------------------------------------------

def _mk_big_spmv(seed, g=96):
    """Coalescible large-grid spmv tenants: the CSR skeleton (and with
    it every buffer SHAPE) is shared — the group key requires matching
    signatures — while values, x and the seed-varying data differ."""
    from repro.volt_bench.suite import _params, _ragged_csr
    n = g * 32
    row_ptr, cols = _ragged_csr(np.random.default_rng(5), n)
    rng = np.random.default_rng(seed)
    return ({"row_ptr": row_ptr.copy(), "cols": cols.copy(),
             "vals": rng.standard_normal(len(cols)).astype(np.float32),
             "x": rng.standard_normal(n).astype(np.float32),
             "y": np.zeros(n, np.float32)},
            {"n": n}, _params(g))


def test_coalesced_parallel_parity():
    """Parallel chunk dispatch inside a coalesced group: demixed
    per-tenant stats and written buffers bit-identical to the
    sequential coalesced drain AND to each tenant running solo."""
    fn = _compiled(BENCHES["spmv_csr"].handle)
    tenants = [_mk_big_spmv(s) for s in (21, 22, 23)]
    solo = []
    for bufs, scal, p in tenants:
        bb = {k: v.copy() for k, v in bufs.items()}
        st = interp.launch(fn, bb, p, scalar_args=scal)
        solo.append((st, bb))

    def run(workers):
        cb = [{k: v.copy() for k, v in bufs.items()}
              for bufs, _, _ in tenants]
        ct = [(cb[j], tenants[j][1], tenants[j][2])
              for j in range(len(tenants))]
        return interp.launch_coalesced(fn, ct, workers=workers), cb

    seq_stats, seq_bufs = run(1)
    par_stats, par_bufs = run(4)
    for j, (sst, sb) in enumerate(solo):
        assert _stats_sig(seq_stats[j]) == _stats_sig(sst)
        assert _stats_sig(par_stats[j]) == _stats_sig(sst), \
            f"tenant {j}: parallel coalesced stats diverged"
        for k in sb:
            np.testing.assert_array_equal(seq_bufs[j][k], sb[k])
            np.testing.assert_array_equal(
                par_bufs[j][k], sb[k],
                err_msg=f"tenant {j} buffer {k} (parallel coalesced)")


def test_service_parallel_workers_end_to_end():
    """LaunchService over Runtime(workers=4): groups still coalesce
    (mode == 'coalesced') and every tenant's results match a
    single-worker service run bit for bit."""
    fn = _compiled(BENCHES["spmv_csr"].handle)

    def serve(workers):
        ins = [_mk_big_spmv(s) for s in (31, 32, 33)]
        rt = Runtime(workers=workers)
        svc = LaunchService(rt)
        hs = [svc.submit(fn, grid=p.grid, block=p.local_size,
                         buffers=b, scalar_args=s, tenant=j)
              for j, (b, s, p) in enumerate(ins)]
        svc.flush()
        assert all(h.error is None for h in hs)
        return ins, hs, svc

    ins1, hs1, svc1 = serve(1)
    ins4, hs4, svc4 = serve(4)
    assert [h.mode for h in hs4] == [h.mode for h in hs1]
    assert svc4.telemetry["groups"] == svc1.telemetry["groups"] >= 1
    for (b1, _, _), (b4, _, _) in zip(ins1, ins4):
        for k in b1:
            np.testing.assert_array_equal(b1[k], b4[k])
    for h1, h4 in zip(hs1, hs4):
        assert _stats_sig(h1.result()) == _stats_sig(h4.result())
