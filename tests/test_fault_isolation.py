"""Fault-isolation matrix: every injection site x every registered
kernel, asserting the degradation contract of core/runtime.py
(docs/robustness.md):

  * an injected fast-path ``EngineFault`` demotes the launch to a
    slower executor and the final result — ``ExecStats`` AND buffers —
    is bit-identical to the oracle's (rollback leaves no partial
    stores);
  * every demotion is visible in ``LaunchReport`` / process telemetry;
  * semantic ``KernelFault``s surface unchanged (same class as the
    oracle raises) and are never retried;
  * injections are deterministic per seed; disabling transactional
    buffers disables retry (an un-rolled-back retry would be unsound).

Kernels ride the same case registry as the executor-conformance suite.
Schedule-sensitive kernels run at warp factor 1 (where all executors
conform bit-identically); everything else runs folded to 2 warps so the
wg-batched rung is exercised too.
"""
import os

import numpy as np
import pytest

import test_executor_conformance as conf
from repro.core import faults, interp
from repro.core.runtime import (LAUNCH_TELEMETRY, Runtime,
                                reset_launch_telemetry)


def _factor(name: str) -> int:
    return 1 if name in conf.SCHEDULE_SENSITIVE else 2


def _case(name: str, factor: int):
    handle, make = conf.CASES[name]
    rng = np.random.default_rng(7)
    bufs0, scalars, params = make(rng)
    params = interp.fold_warps(params, factor)
    return conf._compiled(name), bufs0, scalars, params


_ORACLE = {}


def _oracle(name: str):
    """(outcome, error-class, stats, bufs) of the plain oracle run."""
    key = (name, _factor(name))
    if key not in _ORACLE:
        fn, bufs0, scalars, params = _case(name, _factor(name))
        _ORACLE[key] = conf._run_one(fn, bufs0, params, scalars,
                                     dict(decoded=False))
    return _ORACLE[key]


def _rt_launch(name: str, **rt_kw):
    """Launch through the Runtime degradation chain; same result tuple
    shape as conf._run_one plus the Runtime itself."""
    fn, bufs0, scalars, params = _case(name, _factor(name))
    assert params.grid_y == 1 and params.warp_size == 32
    rt = Runtime(**rt_kw)
    for k, v in bufs0.items():
        rt.create_buffer(k, v.copy())
    try:
        st = rt.launch(fn, grid=params.grid, block=params.local_size,
                       scalar_args=scalars)
    except interp.ExecError as e:
        return ("error", type(e).__name__, None, None), rt
    return ("ok", None, st, rt.buffers), rt


# --------------------------------------------------------------------------
# the matrix
# --------------------------------------------------------------------------

@pytest.mark.parametrize("site", sorted(faults.SITES))
@pytest.mark.parametrize("name", sorted(conf.CASES))
def test_fault_matrix(name, site):
    oracle = _oracle(name)
    with faults.inject(site) as inj:
        got, rt = _rt_launch(name)
    rep = rt.last_report

    # recovery-to-oracle-equivalence: outcome, error class, stats and
    # every buffer bit-identical — whether or not the site fired
    assert got[0] == oracle[0], \
        f"{name}/{site}: {got[0]} but oracle {oracle[0]}"
    if oracle[0] == "error":
        assert got[1] == oracle[1]
    else:
        assert conf._stats_tuple(got[2]) == conf._stats_tuple(oracle[2]), \
            f"{name}/{site}: ExecStats diverged through demotion"
        for k in oracle[3]:
            np.testing.assert_array_equal(
                oracle[3][k], got[3][k],
                err_msg=f"{name}/{site}: buffer {k}")

    # telemetry contract: every engine-fault attempt was rolled back
    # and demoted, and the final attempt succeeded (or surfaced the
    # same semantic error as the oracle)
    eng = [a for a in rep.attempts if a.outcome == "engine_fault"]
    assert rep.demotions == len(eng) == rep.rolled_back
    if inj.fired and faults.SITES[site]["scoped"]:
        assert rep.demotions >= 1, \
            f"{name}/{site}: fired {inj.fired}x but no demotion recorded"
        assert any(a.reason.startswith("injected fault") for a in eng)
    if got[0] == "ok":
        assert rep.attempts[-1].outcome == "ok"
        assert rep.executor is not None


# --------------------------------------------------------------------------
# targeted contracts
# --------------------------------------------------------------------------

def test_decode_fault_walks_the_whole_chain_to_oracle():
    """prob=1.0 at a site present in every demotable rung demotes all
    the way to the oracle floor, which cannot be injected."""
    oracle = _oracle("vecadd")
    with faults.inject("decode") as inj:
        got, rt = _rt_launch("vecadd")
    rep = rt.last_report
    assert inj.fired >= 1
    assert rep.executor == "oracle"
    assert rep.attempts[0].rung == "grid"
    assert [a.outcome for a in rep.attempts][-1] == "ok"
    assert conf._stats_tuple(got[2]) == conf._stats_tuple(oracle[2])


def test_partial_store_rollback_across_grid_chunks():
    """A fault AFTER the first grid chunk committed its stores must
    roll the written-root buffer back before the retry — the retried
    launch sees pristine inputs and produces the oracle's bytes."""
    fn = conf._compiled("vecadd")
    n = 130 * 32                       # 130 wgs -> 3 chunks of <=64
    rng = np.random.default_rng(3)
    x = rng.standard_normal(n).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    params = interp.LaunchParams(grid=130, local_size=32, warp_size=32)
    bo = {"x": x.copy(), "y": y.copy(), "z": np.zeros(n, np.float32)}
    st_o = interp.launch(fn, bo, params, scalar_args={"n": n},
                         decoded=False)

    rt = Runtime()
    rt.create_buffer("x", x.copy())
    rt.create_buffer("y", y.copy())
    rt.create_buffer("z", np.zeros(n, np.float32))
    with faults.inject("chunk.dispatch", after=1) as inj:
        st = rt.launch(fn, grid=130, block=32, scalar_args={"n": n})
    rep = rt.last_report
    assert inj.fired == 1              # chunk 0 committed, chunk 1 died
    assert rep.attempts[0] .rung == "grid"
    assert rep.attempts[0].outcome == "engine_fault"
    assert rep.demotions == rep.rolled_back == 1
    # only the written root (z) was snapshotted, not the read-only x/y
    assert rep.snapshot_bytes == n * 4
    assert conf._stats_tuple(st) == conf._stats_tuple(st_o)
    np.testing.assert_array_equal(rt.buffers["z"], bo["z"])
    np.testing.assert_array_equal(rt.buffers["x"], x)


def test_injection_is_deterministic_per_seed():
    """Same seed -> same hits/fired and the same attempt rung sequence;
    a different seed may differ but must still recover."""
    def run(seed):
        with faults.inject("grid.exec", prob=0.5, seed=seed) as inj:
            got, rt = _rt_launch("vecadd")
        assert got[0] == "ok"
        return (inj.hits, inj.fired,
                [(a.rung, a.outcome) for a in rt.last_report.attempts])

    a = run(42)
    b = run(42)
    assert a == b
    for seed in (0, 1, 2, 3):
        run(seed)                      # always recovers, any seed


def test_nontransactional_runtime_surfaces_engine_faults():
    """transactional=False: no snapshot means a retry could replay on
    partially-written buffers, so the chain is disabled and the
    EngineFault surfaces to the caller."""
    with faults.inject("grid.exec"):
        fn, bufs0, scalars, params = _case("tk_shared_reduce", 1)
        rt = Runtime(transactional=False)
        for k, v in bufs0.items():
            rt.create_buffer(k, v.copy())
        with pytest.raises(faults.EngineFault):
            rt.launch(fn, grid=params.grid, block=params.local_size,
                      scalar_args=scalars)
    rep = rt.last_report
    assert rep.demotions == 0 and rep.rolled_back == 0
    assert rep.attempts[-1].outcome == "engine_fault"


def test_degrade_false_surfaces_engine_faults():
    with faults.inject("grid.exec"):
        fn, bufs0, scalars, params = _case("tk_shared_reduce", 1)
        rt = Runtime(degrade=False)
        for k, v in bufs0.items():
            rt.create_buffer(k, v.copy())
        with pytest.raises(faults.EngineFault):
            rt.launch(fn, grid=params.grid, block=params.local_size,
                      scalar_args=scalars)


def test_kernel_faults_are_never_retried():
    """A semantic error (out of fuel) surfaces from the first attempt;
    no demotion, no rollback, class matches the oracle's."""
    fn, bufs0, scalars, params = _case("tk_saxpy", 1)
    params = interp.LaunchParams(grid=params.grid,
                                 local_size=params.local_size,
                                 warp_size=params.warp_size, fuel=50)
    errs = {}
    for label, kw in conf.EXECUTORS.items():
        bufs = {k: v.copy() for k, v in bufs0.items()}
        with pytest.raises(interp.ExecError) as ei:
            conf.rungs.launch(fn, bufs, params, scalar_args=scalars,
                              **kw)
        errs[label] = ei.value
        assert isinstance(ei.value, faults.KernelFault)
    assert len({type(e).__name__ for e in errs.values()}) == 1


def test_exec_errors_carry_kernel_and_workgroup_context():
    """Satellite: every executor's out-of-fuel error names the kernel
    and the workgroup it died in (barrier-divergence format)."""
    fn, bufs0, scalars, params = _case("tk_saxpy", 1)
    params = interp.LaunchParams(grid=params.grid,
                                 local_size=params.local_size,
                                 warp_size=params.warp_size, fuel=50)
    for label, kw in conf.EXECUTORS.items():
        bufs = {k: v.copy() for k, v in bufs0.items()}
        with pytest.raises(interp.ExecError) as ei:
            conf.rungs.launch(fn, bufs, params, scalar_args=scalars,
                              **kw)
        msg = str(ei.value)
        assert "in @saxpy" in msg, (label, msg)
        assert "workgroup" in msg, (label, msg)


def test_launch_telemetry_counters():
    reset_launch_telemetry()
    _rt_launch("tk_saxpy")
    assert LAUNCH_TELEMETRY["launches"] == 1
    assert LAUNCH_TELEMETRY["demotions"] == 0
    with faults.inject("decode"):
        _rt_launch("tk_saxpy")
    assert LAUNCH_TELEMETRY["launches"] == 2
    assert LAUNCH_TELEMETRY["demotions"] >= 1
    assert LAUNCH_TELEMETRY["rollbacks"] == LAUNCH_TELEMETRY["demotions"]
    assert LAUNCH_TELEMETRY["engine_faults"] >= 1
    assert LAUNCH_TELEMETRY["by_executor"]["oracle"] >= 1
    assert LAUNCH_TELEMETRY["demotion_reasons"]["decode"] >= 1
    reset_launch_telemetry()


def test_launch_report_summary_is_descriptive():
    with faults.inject("decode"):
        got, rt = _rt_launch("tk_saxpy")
    s = rt.last_report.summary()
    assert "@saxpy" in s and "engine_fault" in s and "demotion" in s


def test_env_spec_round_trip():
    """VOLT_FAULT-format specs arm the same deterministic injections
    as the context manager."""
    try:
        injs = faults.install_spec("decode:1.0:7, handler.mem::3")
        assert faults.ACTIVE
        assert [i.pattern for i in injs] == ["decode", "handler.mem"]
        assert [i.prob for i in injs] == [1.0, 1.0]
        assert [i.seed for i in injs] == [7, 3]
        got, rt = _rt_launch("tk_saxpy")
        assert got[0] == "ok"
        assert rt.last_report.demotions >= 1
    finally:
        faults.clear()
    assert not faults.ACTIVE


def test_unknown_site_rejected():
    with pytest.raises(ValueError, match="unknown fault site"):
        with faults.inject("no.such.site"):
            pass


def test_scoped_sites_never_fire_in_the_oracle():
    """The recovery floor: a scoped injection armed during a plain
    oracle launch never fires, so demotion always terminates."""
    fn, bufs0, scalars, params = _case("tk_saxpy", 1)
    bufs = {k: v.copy() for k, v in bufs0.items()}
    with faults.inject("handler.mem") as inj:
        interp.launch(fn, bufs, params, scalar_args=scalars,
                      decoded=False)
    assert inj.fired == 0


# --------------------------------------------------------------------------
# jax rung recovery (the chain's TOP rung; off by default, so the fixed
# matrix exercises its sites vacuously — these arm Runtime(jax=True))
# --------------------------------------------------------------------------

from repro.core.backends import jaxgen

_JAX_SITES = ("jax.trace", "jax.exec", "jax.cache.load")


def _jax_case(name: str):
    """A licence-admitted case at this suite's standard factor, with
    jax trace/cert caches dropped for a deterministic cold start."""
    fn, bufs0, scalars, params = _case(name, _factor(name))
    ok, why = jaxgen.licence_check(fn, params, bufs0, scalars or {}, {})
    assert ok, f"{name} must stay jax-licensed for this test: {why}"
    for attr in ("_jaxgen_cache", "_jax_certs"):
        if hasattr(fn, attr):
            delattr(fn, attr)
    return fn, bufs0, scalars, params


@pytest.mark.parametrize("site", _JAX_SITES)
@pytest.mark.parametrize("name", ["vecadd", "spmv_tail"])
def test_jax_fault_demotes_to_grid(monkeypatch, name, site):
    """Every jax fault site, injected cold: the top rung dies, the
    runtime rolls back (nothing was written — the jax rung stages all
    stores device-side) and the grid rung reproduces the oracle's
    bytes and stats exactly."""
    monkeypatch.setenv("VOLT_DISK_CACHE", "0")
    _jax_case(name)                    # assert licence + drop caches
    oracle = _oracle(name)
    jaxgen.reset_jax_telemetry()
    with faults.inject(site) as inj:
        got, rt = _rt_launch(name, jax=True)
    rep = rt.last_report
    assert inj.fired >= 1, f"{site} must fire on a jax=True launch"
    assert got[0] == "ok"
    assert conf._stats_tuple(got[2]) == conf._stats_tuple(oracle[2]), \
        f"{name}/{site}: ExecStats diverged through jax demotion"
    for k in oracle[3]:
        np.testing.assert_array_equal(oracle[3][k], got[3][k],
                                      err_msg=f"{name}/{site}: buffer {k}")
    assert rep.attempts[0].rung == "jax"
    assert rep.attempts[0].outcome == "engine_fault"
    assert rep.demotions >= 1 and rep.rolled_back == rep.demotions
    assert rep.executor == "grid", \
        "jax rung must hand off to the grid rung, not skip it"
    assert jaxgen.JAX_TELEMETRY["demotions"] >= 1
    assert jaxgen.JAX_TELEMETRY["engaged"] == 0


def test_jax_cert_run_fault_records_no_verdict(monkeypatch):
    """An injected infra fault DURING a certification run must leave
    the (kernel, shape) pair uncertified — not pinned to a permanent
    "fail" — so the next clean launch re-runs certification and then
    promotes to the jitted primary."""
    monkeypatch.setenv("VOLT_DISK_CACHE", "0")
    name = "vecadd"
    fn, _, _, _ = _jax_case(name)
    oracle = _oracle(name)
    jaxgen.reset_jax_telemetry()
    with faults.inject("jax.exec") as inj:
        got, rt = _rt_launch(name, jax=True)
    assert inj.fired >= 1
    assert got[0] == "ok"
    certs = getattr(fn, "_jax_certs", (None, {}))[1]
    assert not certs, \
        f"faulted cert run must record no verdict, got {certs}"
    t = dict(jaxgen.JAX_TELEMETRY)
    assert t["cert_runs"] == 1 and t["certified"] == 0

    # clean launches: #1 re-certifies (pass), #2 runs the jitted primary
    jaxgen.reset_jax_telemetry()
    got1, _ = _rt_launch(name, jax=True)
    got2, rt2 = _rt_launch(name, jax=True)
    t = dict(jaxgen.JAX_TELEMETRY)
    assert t["cert_runs"] == 1 and t["certified"] == 1
    assert t["engaged"] == 1
    assert rt2.last_report.executor == "jax"
    assert rt2.last_report.demotions == 0
    for g in (got1, got2):
        assert conf._stats_tuple(g[2]) == conf._stats_tuple(oracle[2])
        for k in oracle[3]:
            np.testing.assert_array_equal(oracle[3][k], g[3][k])


def test_jax_certified_primary_fault_demotes_bit_exactly(monkeypatch):
    """The warm path: certify cleanly first, THEN kill the jitted
    primary mid-chunk-loop.  The staged device buffers are discarded,
    host buffers stay pristine, and the grid retry is bit-exact."""
    monkeypatch.setenv("VOLT_DISK_CACHE", "0")
    # several host-loop chunks so the fault can land AFTER one ran
    monkeypatch.setattr(jaxgen, "_CHUNK_WGS", 8)
    name = "spmv_tail"
    _jax_case(name)
    oracle = _oracle(name)
    got0, _ = _rt_launch(name, jax=True)       # certification launch
    assert got0[0] == "ok"
    jaxgen.reset_jax_telemetry()
    with faults.inject("jax.exec", after=1) as inj:
        got, rt = _rt_launch(name, jax=True)
    rep = rt.last_report
    assert inj.fired == 1, "fault must hit after the first chunk ran"
    assert rep.attempts[0].rung == "jax"
    assert rep.attempts[0].outcome == "engine_fault"
    assert rep.executor == "grid"
    assert conf._stats_tuple(got[2]) == conf._stats_tuple(oracle[2])
    for k in oracle[3]:
        np.testing.assert_array_equal(oracle[3][k], got[3][k],
                                      err_msg=f"warm demotion buffer {k}")


def test_jax_rung_skipped_entirely_when_disabled():
    """Runtime() default (VOLT_JAX unset/0): the jax sites are dead
    code — armed injections never fire and no jax attempt appears."""
    for site in _JAX_SITES:
        with faults.inject(site) as inj:
            got, rt = _rt_launch("vecadd")
        assert got[0] == "ok"
        assert inj.fired == 0, f"{site} fired with the jax rung disabled"
        assert all(a.rung != "jax" for a in rt.last_report.attempts)


# --------------------------------------------------------------------------
# parallel dispatch sites (core/parallel.py): the matrix above runs them
# at the default worker count, where small conformance grids never widen
# past one chunk and the sites stay dead code — exactly the workers=1
# contract.  Here large licensed grids at VOLT_WORKERS=4 force every
# site to FIRE, and the chain must demote with bit-exact rollback.
# --------------------------------------------------------------------------

_PAR_SITES = ("parallel.submit", "parallel.worker.exec", "parallel.merge")
_PAR_ORACLE = {}


def _par_case(bench: str):
    """Large-grid licensed launches (store-private stores, several
    widened chunks at 4 workers)."""
    from repro.core.passes.pipeline import ABLATION_LADDER, run_pipeline
    from repro.volt_bench import BENCHES
    from repro.volt_bench.suite import _params, _ragged_csr
    rng = np.random.default_rng(3)
    g = 96
    if bench == "spmv_csr":
        n = g * 32
        row_ptr, cols = _ragged_csr(rng, n)
        bufs = {"row_ptr": row_ptr, "cols": cols,
                "vals": rng.standard_normal(len(cols)).astype(np.float32),
                "x": rng.standard_normal(n).astype(np.float32),
                "y": np.zeros(n, np.float32)}
        sc = {"n": n}
    else:
        bufs = {"x": rng.standard_normal(g * 32).astype(np.float32),
                "out": np.zeros(g, np.float32)}
        sc = {"n": g * 32 - 13}
    handle = BENCHES[bench].handle
    fn = run_pipeline(handle.build(None), handle.name,
                      ABLATION_LADDER[-1]).fn
    return fn, bufs, sc, _params(g)


def _par_oracle(bench: str):
    if bench not in _PAR_ORACLE:
        fn, bufs0, sc, params = _par_case(bench)
        bufs = {k: v.copy() for k, v in bufs0.items()}
        st = interp.launch(fn, bufs, params, scalar_args=sc,
                           decoded=False)
        _PAR_ORACLE[bench] = (conf._stats_tuple(st), bufs)
    return _PAR_ORACLE[bench]


def _par_rt_launch(bench: str, **rt_kw):
    fn, bufs0, sc, params = _par_case(bench)
    rt = Runtime(workers=4, **rt_kw)
    for k, v in bufs0.items():
        rt.create_buffer(k, v.copy())
    st = rt.launch(fn, grid=params.grid, block=params.local_size,
                   scalar_args=sc)
    return st, rt


@pytest.mark.parametrize("site", _PAR_SITES)
@pytest.mark.parametrize("bench", ["spmv_csr", "reduce0"])
def test_parallel_site_recovers_to_oracle(bench, site):
    """Every parallel fault site actually fires at 4 workers on these
    grids, and the launch recovers to oracle equivalence through the
    ordinary demote-with-rollback chain (a worker crash is just another
    EngineFault)."""
    ostats, obufs = _par_oracle(bench)
    with faults.inject(site) as inj:
        st, rt = _par_rt_launch(bench)
    rep = rt.last_report
    assert inj.fired >= 1, f"{site} never fired at 4 workers"
    assert rep.demotions >= 1 and rep.rolled_back == rep.demotions
    eng = [a for a in rep.attempts if a.outcome == "engine_fault"]
    assert any(a.reason.startswith("injected fault") for a in eng)
    assert conf._stats_tuple(st) == ostats, \
        f"{bench}/{site}: ExecStats diverged through demotion"
    for k in obufs:
        np.testing.assert_array_equal(obufs[k], rt.buffers[k],
                                      err_msg=f"{bench}/{site}: {k}")


def test_parallel_worker_fault_surfaces_when_nontransactional():
    """transactional=False disables the retry chain: a worker-injected
    EngineFault must surface to the caller, not be silently retried
    over partially-written buffers."""
    with faults.inject("parallel.worker.exec") as inj:
        with pytest.raises(faults.EngineFault):
            _par_rt_launch("spmv_csr", transactional=False)
    assert inj.fired >= 1


def test_parallel_sites_dead_at_one_worker():
    """workers=1 is today's exact sequential dispatch: the parallel
    sites are dead code and armed injections never fire."""
    for site in _PAR_SITES:
        fn, bufs0, sc, params = _par_case("spmv_csr")
        rt = Runtime(workers=1)
        for k, v in bufs0.items():
            rt.create_buffer(k, v.copy())
        with faults.inject(site) as inj:
            rt.launch(fn, grid=params.grid, block=params.local_size,
                      scalar_args=sc)
        assert inj.fired == 0, f"{site} fired at workers=1"
        assert rt.last_report.demotions == 0


def test_parallel_disabled_when_other_sites_armed():
    """Deterministic injection bookkeeping requires the exact
    sequential site order: arming any non-parallel site forces the
    sequential path (faults.parallel_safe), so chunk.dispatch fires in
    its historical order even at 4 workers."""
    ostats, obufs = _par_oracle("spmv_csr")
    with faults.inject("chunk.dispatch", after=1) as inj:
        st, rt = _par_rt_launch("spmv_csr")
    assert inj.fired >= 1
    rep = rt.last_report
    assert rep.demotions >= 1 and rep.rolled_back == rep.demotions
    assert conf._stats_tuple(st) == ostats
    for k in obufs:
        np.testing.assert_array_equal(obufs[k], rt.buffers[k],
                                      err_msg=f"parallel-safe {k}")


# --------------------------------------------------------------------------
# randomized sweep (CI's second job leg; seed from the environment)
# --------------------------------------------------------------------------

def test_randomized_sweep():
    """Random (site, kernel, prob, seed) draws — same invariants as
    the fixed matrix.  VOLT_FAULT_SWEEP_SEED / _EXAMPLES parameterize
    the CI randomized leg."""
    seed = int(os.environ.get("VOLT_FAULT_SWEEP_SEED", "0"))
    n = int(os.environ.get("VOLT_FAULT_SWEEP_EXAMPLES", "6"))
    rng = np.random.default_rng(seed)
    sites = sorted(faults.SITES)
    names = sorted(conf.CASES)
    for i in range(n):
        site = sites[int(rng.integers(len(sites)))]
        name = names[int(rng.integers(len(names)))]
        prob = float(rng.choice([0.3, 0.7, 1.0]))
        inj_seed = int(rng.integers(1 << 16))
        oracle = _oracle(name)
        with faults.inject(site, prob=prob, seed=inj_seed):
            got, rt = _rt_launch(name)
        assert got[0] == oracle[0], (site, name, prob, inj_seed)
        if oracle[0] == "ok":
            assert conf._stats_tuple(got[2]) == \
                conf._stats_tuple(oracle[2]), (site, name, prob, inj_seed)
            for k in oracle[3]:
                np.testing.assert_array_equal(
                    oracle[3][k], got[3][k],
                    err_msg=f"sweep {site}/{name} p={prob} s={inj_seed}")
        rep = rt.last_report
        eng = [a for a in rep.attempts if a.outcome == "engine_fault"]
        assert rep.demotions == len(eng) == rep.rolled_back
