"""Launch-governor contracts (core/governor.py, docs/robustness.md):

  * **Neutrality** — with the governor armed (generous deadline, huge
    memory budget, breaker watching) but nothing tripping, every
    executor produces bit-identical ``ExecStats`` and buffers to the
    disarmed run, across all four executors x {1,2,4} warps/wg.
  * **Deadlines** — expiry raises ``faults.DeadlineExceeded`` carrying
    the partial stats, and the runtime rolls written buffers back
    bit-exactly (a timed-out launch is bit-invisible).
  * **Circuit breaker** — N demoting launches open it (subsequent
    launches pinned at the last-good rung, no demotion walk), a
    half-open probe re-promotes once the fault clears; every state
    visible in LaunchReport / LAUNCH_TELEMETRY.
  * **Memory budget** — lazy-allocation overruns demote to a
    smaller-footprint rung; over-budget snapshots degrade to
    oracle-first execution; at the floor the EngineFault surfaces with
    the LaunchReport summary attached.
  * ``install_spec`` hardening and the last-32 report ring.
"""
import os

import numpy as np
import pytest

import test_executor_conformance as conf
from repro.core import diskcache, faults, governor, interp, runtime
from repro.core.frontends import opencl
from repro.core.runtime import (LAUNCH_TELEMETRY, Runtime,
                                reset_launch_telemetry)

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:             # keep the rest of this module runnable
    _HAVE_HYPOTHESIS = False

_H_EXAMPLES = int(os.environ.get("VOLT_HYPOTHESIS_MAX_EXAMPLES", "25"))

#: armed-but-untrippable governor kwargs for interp.launch
ARMED = dict(deadline_ms=600_000.0, mem_budget=1 << 40)


def _case(name: str, factor: int):
    handle, make = conf.CASES[name]
    rng = np.random.default_rng(7)
    bufs0, scalars, params = make(rng)
    return conf._compiled(name), bufs0, scalars, \
        interp.fold_warps(params, factor)


def _same(a, b, label):
    assert a[0] == b[0], f"{label}: outcome {a[0]} vs {b[0]}"
    if a[0] == "error":
        assert a[1] == b[1], f"{label}: error class diverged"
        return
    assert conf._stats_tuple(a[2]) == conf._stats_tuple(b[2]), \
        f"{label}: ExecStats diverged with governor armed"
    for k in b[3]:
        np.testing.assert_array_equal(
            b[3][k], a[3][k], err_msg=f"{label}: buffer {k}")


# --------------------------------------------------------------------------
# neutrality: armed-but-untripped == disarmed, bit for bit
# --------------------------------------------------------------------------

@pytest.mark.parametrize("factor", conf.WARP_FACTORS)
@pytest.mark.parametrize("executor", sorted(conf.EXECUTORS))
@pytest.mark.parametrize("name", sorted(conf.CASES))
def test_governor_neutrality(name, executor, factor):
    fn, bufs0, scalars, params = _case(name, factor)
    kw = dict(conf.EXECUTORS[executor])
    plain = conf._run_one(fn, bufs0, params, scalars, kw)
    armed = conf._run_one(fn, bufs0, params, scalars, {**kw, **ARMED})
    _same(armed, plain, f"{name}/{executor}/x{factor}")


if _HAVE_HYPOTHESIS:
    @settings(max_examples=_H_EXAMPLES, deadline=None)
    @given(name=st.sampled_from(["vecadd", "tk_shared_reduce",
                                 "tk_ragged_nested",
                                 "tk_atomics_kernel"]),
           deadline_ms=st.floats(min_value=10_000.0, max_value=1e9),
           budget_mb=st.integers(min_value=64, max_value=1 << 20),
           threshold=st.integers(min_value=1, max_value=10),
           probe_every=st.integers(min_value=1, max_value=64))
    def test_governor_neutrality_fuzz(name, deadline_ms, budget_mb,
                                      threshold, probe_every):
        """Runtime-level: any untripped governor config is invisible."""
        fn, bufs0, scalars, params = _case(name, 1)
        outs = []
        for rt in (Runtime(govern=False),
                   Runtime(governor=governor.GovernorConfig(
                       deadline_ms=deadline_ms,
                       mem_budget=budget_mb << 20,
                       breaker_threshold=threshold,
                       breaker_probe_every=probe_every))):
            for k, v in bufs0.items():
                rt.create_buffer(k, v.copy())
            st_ = rt.launch(fn, grid=params.grid,
                            block=params.local_size,
                            scalar_args=scalars)
            assert rt.last_report.demotions == 0
            outs.append(("ok", None, st_, rt.buffers))
        _same(outs[1], outs[0], name)


# --------------------------------------------------------------------------
# deadlines
# --------------------------------------------------------------------------

@opencl.kernel
def busy_loop(x: "ptr_f32 const", out: "ptr_f32", n: "i32 uniform"):
    gid = get_global_id(0)
    out[gid] = 1.0          # early store the rollback must undo
    acc = 0.0
    i = 0
    while i < n:
        acc += x[gid] * 0.5
        i += 1
    out[gid] = acc


def _busy(n=200_000, grid=2):
    ck = runtime.compile_kernel(busy_loop)
    bufs0 = {"x": np.ones(64 * grid, np.float32),
             "out": np.zeros(64 * grid, np.float32)}
    return ck.fn, bufs0, {"n": n}, grid


@pytest.mark.parametrize("executor", sorted(conf.EXECUTORS))
def test_expired_deadline_raises_in_every_executor(executor):
    """deadline_ms=0 expires at the very first checkpoint of every
    executor — before any store commits."""
    fn, bufs0, scalars, _ = _busy(n=4)
    params = interp.LaunchParams(grid=2, local_size=64, warp_size=32)
    bufs = {k: v.copy() for k, v in bufs0.items()}
    with pytest.raises(faults.DeadlineExceeded) as ei:
        conf.rungs.launch(fn, bufs, params, scalar_args=scalars,
                          deadline_ms=0.0, **conf.EXECUTORS[executor])
    assert ei.value.deadline_ms == 0.0
    assert ei.value.elapsed_ms is not None


def test_deadline_expiry_rolls_back_bit_exact():
    fn, bufs0, scalars, grid = _busy()
    rt = Runtime()
    for k, v in bufs0.items():
        rt.create_buffer(k, v.copy())
    reset_launch_telemetry()
    with pytest.raises(faults.DeadlineExceeded) as ei:
        rt.launch(fn, grid=grid, block=64, scalar_args=scalars,
                  deadline_ms=15.0)
    e = ei.value
    # partial progress really happened and is reported...
    assert e.stats is not None and e.stats.instrs > 0
    assert e.report is rt.last_report
    assert e.report.deadline_expired
    assert e.report.attempts[-1].outcome == "deadline"
    assert e.report.rolled_back >= 1
    assert any(n.startswith("launch report:") for n in e.__notes__)
    assert LAUNCH_TELEMETRY["deadline_expired"] == 1
    # ...but the buffers are bit-identical to pre-launch (the early
    # out[gid]=1.0 store is undone)
    for k, v in bufs0.items():
        np.testing.assert_array_equal(rt.buffers[k], v,
                                      err_msg=f"buffer {k}")
    # the same runtime still serves the kernel under a workable budget
    st_ = rt.launch(fn, grid=grid, block=64,
                    scalar_args={"n": 4}, deadline_ms=60_000.0)
    assert st_.instrs > 0 and not rt.last_report.deadline_expired


def test_generous_deadline_is_neutral():
    fn, bufs0, scalars, grid = _busy(n=16)
    outs = []
    for dl in (None, 600_000.0):
        rt = Runtime()
        for k, v in bufs0.items():
            rt.create_buffer(k, v.copy())
        st_ = rt.launch(fn, grid=grid, block=64, scalar_args=scalars,
                        deadline_ms=dl)
        outs.append(("ok", None, st_, rt.buffers))
    _same(outs[1], outs[0], "busy_loop")


def test_jax_deadline_expiry_is_bit_invisible_and_leaves_no_verdict(
        monkeypatch):
    """Deadline death on a Runtime(jax=True) launch: buffers roll back
    to pre-launch bytes and the (kernel, shape) pair records NO
    certification verdict — a timed-out certification must not pin the
    pair to "fail".  The same runtime then certifies and serves the
    kernel under a workable deadline, bit-identically to the oracle."""
    monkeypatch.setenv("VOLT_DISK_CACHE", "0")
    from repro.core.backends import jaxgen
    monkeypatch.setattr(jaxgen, "_CHUNK_WGS", 1)   # one check per wg
    fn, bufs0, scalars, params = _case("spmv_tail", 1)
    for attr in ("_jaxgen_cache", "_jax_certs"):
        if hasattr(fn, attr):
            delattr(fn, attr)
    oracle = conf._run_one(fn, bufs0, params, scalars,
                           dict(decoded=False))
    rt = Runtime(jax=True)
    for k, v in bufs0.items():
        rt.create_buffer(k, v.copy())
    jaxgen.reset_jax_telemetry()
    with pytest.raises(faults.DeadlineExceeded):
        rt.launch(fn, grid=params.grid, block=params.local_size,
                  scalar_args=scalars, deadline_ms=0.0)
    assert rt.last_report.deadline_expired
    assert jaxgen.JAX_TELEMETRY["engaged"] == 0
    certs = getattr(fn, "_jax_certs", (None, {}))[1]
    assert not certs, f"timed-out launch recorded a verdict: {certs}"
    for k, v in bufs0.items():
        np.testing.assert_array_equal(rt.buffers[k], v,
                                      err_msg=f"buffer {k}")
    # recovery: certify + promote under a generous deadline
    st_ = rt.launch(fn, grid=params.grid, block=params.local_size,
                    scalar_args=scalars, deadline_ms=600_000.0)
    st2 = rt.launch(fn, grid=params.grid, block=params.local_size,
                    scalar_args=scalars, deadline_ms=600_000.0)
    assert jaxgen.JAX_TELEMETRY["certified"] == 1
    assert jaxgen.JAX_TELEMETRY["engaged"] == 1
    assert rt.last_report.executor == "jax"
    for s in (st_, st2):
        assert conf._stats_tuple(s) == conf._stats_tuple(oracle[2])
    for k in oracle[3]:
        np.testing.assert_array_equal(oracle[3][k], rt.buffers[k])


def test_default_deadline_from_governor_config():
    fn, bufs0, scalars, grid = _busy()
    rt = Runtime(governor=governor.GovernorConfig(deadline_ms=10.0))
    for k, v in bufs0.items():
        rt.create_buffer(k, v.copy())
    with pytest.raises(faults.DeadlineExceeded):
        rt.launch(fn, grid=grid, block=64, scalar_args=scalars)
    assert rt.last_report.deadline_ms == 10.0


def test_deadline_polls_are_strided():
    """The armed clean path pays ~1 clock read per CHECK_STRIDE
    checkpoints, not one per node."""
    fn, bufs0, scalars, grid = _busy(n=64)
    governor.TELEMETRY["deadline_polls"] = 0
    rt = Runtime()
    for k, v in bufs0.items():
        rt.create_buffer(k, v.copy())
    st_ = rt.launch(fn, grid=grid, block=64, scalar_args=scalars,
                    deadline_ms=600_000.0)
    polls = governor.TELEMETRY["deadline_polls"]
    assert 1 <= polls < max(4, st_.instrs)
    assert polls <= st_.instrs // governor.CHECK_STRIDE + 4


# --------------------------------------------------------------------------
# circuit breaker
# --------------------------------------------------------------------------

def _breaker_rt(threshold=2, probe_every=3):
    fn, bufs0, scalars, params = _case("vecadd", 1)
    rt = Runtime(governor=governor.GovernorConfig(
        breaker_threshold=threshold, breaker_probe_every=probe_every))
    for k, v in bufs0.items():
        rt.create_buffer(k, v.copy())
    kw = dict(grid=params.grid, block=params.local_size,
              scalar_args=scalars)
    oracle = conf._run_one(fn, bufs0, params, scalars,
                           dict(decoded=False))

    def hit():
        st_ = rt.launch(fn, **kw)
        assert conf._stats_tuple(st_) == conf._stats_tuple(oracle[2])
        for k in oracle[3]:
            np.testing.assert_array_equal(oracle[3][k], rt.buffers[k])
        return rt.last_report
    return rt, hit


def test_breaker_opens_pins_probes_and_repromotes():
    """The deterministic open -> pinned -> half-open -> closed walk,
    with results bit-identical to the oracle at every stage."""
    rt, hit = _breaker_rt(threshold=2, probe_every=3)
    reset_launch_telemetry()
    with faults.inject("grid.exec"):
        r = hit()
        assert r.demotions == 1 and r.breaker == "closed"
        clean_rung = r.executor          # the last-good rung
        r = hit()                        # second trip: breaker opens
        assert r.demotions == 1 and r.breaker == "open"
        for _ in range(2):               # pinned: no demotion walk
            r = hit()
            assert r.pinned_rung == clean_rung and r.demotions == 0
            assert r.attempts[0].rung == clean_rung
        r = hit()                        # probe while still faulty
        assert r.probe and r.demotions == 1 and r.breaker == "open"
    # fault cleared: pinned until the next probe, which re-promotes
    seen_probe = None
    for _ in range(4):
        r = hit()
        if r.probe:
            seen_probe = r
            break
        assert r.pinned_rung == clean_rung
    assert seen_probe is not None and seen_probe.breaker == "closed"
    assert seen_probe.demotions == 0
    assert seen_probe.executor == "grid"     # full fast path is back
    r = hit()
    assert r.breaker == "closed" and r.pinned_rung is None
    t = LAUNCH_TELEMETRY
    assert t["breaker_trips"] >= 2           # open + probe re-pin
    assert t["breaker_pinned"] >= 3
    assert t["breaker_probes"] >= 2
    assert t["breaker_promotions"] == 1
    reset_launch_telemetry()


def test_breaker_is_keyed_by_kernel_content():
    rt, hit = _breaker_rt(threshold=1)
    with faults.inject("grid.exec"):
        hit()
    fn2 = conf._compiled("transpose")
    key1 = diskcache.plan_key(conf._compiled("vecadd"))
    key2 = diskcache.plan_key(fn2)
    assert key1 != key2
    assert rt.breaker.entries[key1].state == "open"
    assert key2 not in rt.breaker.entries


def test_breaker_pins_below_faulty_jax_rung(monkeypatch):
    """Runtime(jax=True) with the jitted executor faulting: the first
    launch demotes jax -> grid and opens the breaker; subsequent
    launches are pinned at grid and never attempt the jax rung at all
    (no retrace, no re-certification, no demotion walk)."""
    monkeypatch.setenv("VOLT_DISK_CACHE", "0")
    from repro.core.backends import jaxgen
    fn, bufs0, scalars, params = _case("vecadd", 1)
    for attr in ("_jaxgen_cache", "_jax_certs"):
        if hasattr(fn, attr):
            delattr(fn, attr)
    ok, why = jaxgen.licence_check(fn, params, bufs0, scalars or {}, {})
    assert ok, why
    oracle = conf._run_one(fn, bufs0, params, scalars,
                           dict(decoded=False))
    rt = Runtime(jax=True, governor=governor.GovernorConfig(
        breaker_threshold=1, breaker_probe_every=64))
    for k, v in bufs0.items():
        rt.create_buffer(k, v.copy())
    kw = dict(grid=params.grid, block=params.local_size,
              scalar_args=scalars)

    def hit():
        st_ = rt.launch(fn, **kw)
        assert conf._stats_tuple(st_) == conf._stats_tuple(oracle[2])
        for k in oracle[3]:
            np.testing.assert_array_equal(oracle[3][k], rt.buffers[k])
        return rt.last_report

    with faults.inject("jax.exec"):
        r = hit()
        assert r.attempts[0].rung == "jax"
        assert r.attempts[0].outcome == "engine_fault"
        assert r.executor == "grid" and r.demotions == 1
        assert r.breaker == "open"
        for _ in range(2):
            r = hit()
            assert r.pinned_rung == "grid" and r.demotions == 0
            assert all(a.rung != "jax" for a in r.attempts), \
                "pinned launches must not touch the faulty jax rung"


def test_breaker_disabled_when_ungoverned():
    fn, bufs0, scalars, params = _case("vecadd", 1)
    rt = Runtime(govern=False)
    assert rt.breaker is None
    for k, v in bufs0.items():
        rt.create_buffer(k, v.copy())
    with faults.inject("grid.exec"):
        for _ in range(5):
            rt.launch(fn, grid=params.grid, block=params.local_size,
                      scalar_args=scalars)
            # every launch re-walks the demotion chain: no pinning
            assert rt.last_report.demotions == 1
            assert rt.last_report.breaker is None


# --------------------------------------------------------------------------
# memory budget
# --------------------------------------------------------------------------

def test_parse_mem_budget():
    p = governor.parse_mem_budget
    assert p(None) is None and p("") is None and p("0") is None
    assert p("65536") == 65536
    assert p("64k") == 64 << 10
    assert p("16m") == 16 << 20
    assert p("2g") == 2 << 30
    assert p("1.5k") == 1536
    with pytest.raises(ValueError, match="VOLT_MEM_BUDGET"):
        p("lots")
    with pytest.raises(ValueError, match="VOLT_MEM_BUDGET"):
        p("-4k")


def test_mem_budget_env_var(monkeypatch):
    monkeypatch.setenv("VOLT_MEM_BUDGET", "64k")
    assert Runtime().mem_budget == 64 << 10
    monkeypatch.delenv("VOLT_MEM_BUDGET")
    assert Runtime().mem_budget is None
    # explicit config wins over the environment
    monkeypatch.setenv("VOLT_MEM_BUDGET", "64k")
    rt = Runtime(governor=governor.GovernorConfig(mem_budget=123))
    assert rt.mem_budget == 123


def test_mem_budget_demotes_grid_tile_table():
    """shared_reduce's grid rung allocates an (n_wg, 32) f32 tile
    table; a budget that only fits one workgroup's 128-byte tile
    demotes to the per-workgroup rung, bit-identically."""
    fn, bufs0, scalars, params = _case("tk_shared_reduce", 1)
    oracle = conf._run_one(fn, bufs0, params, scalars,
                           dict(decoded=False))
    rt = Runtime(governor=governor.GovernorConfig(mem_budget=384))
    for k, v in bufs0.items():
        rt.create_buffer(k, v.copy())
    st_ = rt.launch(fn, grid=params.grid, block=params.local_size,
                    scalar_args=scalars)
    r = rt.last_report
    assert r.demotions == 1
    assert r.attempts[0].outcome == "engine_fault"
    assert "memory budget" in r.attempts[0].reason
    assert conf._stats_tuple(st_) == conf._stats_tuple(oracle[2])
    for k in oracle[3]:
        np.testing.assert_array_equal(oracle[3][k], rt.buffers[k])


def test_mem_budget_exhausts_chain_with_report_attached():
    """A budget too small for even one workgroup's tile fails every
    rung; the surfaced EngineFault names the exhausted chain."""
    fn, bufs0, scalars, params = _case("tk_shared_reduce", 1)
    rt = Runtime(governor=governor.GovernorConfig(mem_budget=64))
    for k, v in bufs0.items():
        rt.create_buffer(k, v.copy())
    with pytest.raises(faults.EngineFault) as ei:
        rt.launch(fn, grid=params.grid, block=params.local_size,
                  scalar_args=scalars)
    e = ei.value
    assert getattr(e, "site", None) == "mem.alloc"
    assert e.report is rt.last_report
    assert any(n.startswith("launch report:") for n in e.__notes__)
    assert rt.last_report.attempts[-1].outcome == "engine_fault"
    # rollback happened for every demotion: buffers are pre-launch
    for k, v in bufs0.items():
        np.testing.assert_array_equal(rt.buffers[k], v)


def test_snapshot_over_budget_degrades_to_oracle_first():
    """vecadd has no lazy allocations, but its write-root snapshot
    exceeds a tiny budget: the chain skips the snapshot and runs
    oracle-first (the floor needs no retry snapshot)."""
    fn, bufs0, scalars, params = _case("vecadd", 1)
    oracle = conf._run_one(fn, bufs0, params, scalars,
                           dict(decoded=False))
    reset_launch_telemetry()
    rt = Runtime(governor=governor.GovernorConfig(mem_budget=64))
    for k, v in bufs0.items():
        rt.create_buffer(k, v.copy())
    st_ = rt.launch(fn, grid=params.grid, block=params.local_size,
                    scalar_args=scalars)
    r = rt.last_report
    assert r.snapshot_skipped == "mem-budget"
    assert r.executor == "oracle" and r.demotions == 0
    assert r.snapshot_bytes == 0
    assert LAUNCH_TELEMETRY["snapshot_budget_skips"] == 1
    assert conf._stats_tuple(st_) == conf._stats_tuple(oracle[2])
    for k in oracle[3]:
        np.testing.assert_array_equal(oracle[3][k], rt.buffers[k])
    reset_launch_telemetry()


def test_deadline_outranks_snapshot_budget():
    """With a deadline armed the snapshot is forced despite the budget
    — the rollback contract is what makes a timed-out launch
    bit-invisible."""
    fn, bufs0, scalars, grid = _busy()
    rt = Runtime(governor=governor.GovernorConfig(mem_budget=64))
    for k, v in bufs0.items():
        rt.create_buffer(k, v.copy())
    with pytest.raises(faults.DeadlineExceeded):
        rt.launch(fn, grid=grid, block=64, scalar_args=scalars,
                  deadline_ms=15.0)
    assert rt.last_report.snapshot_bytes > 0
    assert rt.last_report.rolled_back == 1
    for k, v in bufs0.items():
        np.testing.assert_array_equal(rt.buffers[k], v)


# --------------------------------------------------------------------------
# install_spec hardening
# --------------------------------------------------------------------------

@pytest.mark.parametrize("spec,needle", [
    ("nosuchsite", "unknown site"),
    ("zz.*", "matches no registered site"),
    ("decode:2.0", "prob must be in [0, 1]"),
    ("decode:abc", "not a number"),
    ("decode:0.5:-1", "seed must be >= 0"),
    ("decode:0.5:x", "not an integer"),
    ("decode:1.0:0:9", "got 4"),
    (":", "empty site name"),
])
def test_install_spec_rejects_malformed(spec, needle):
    faults.clear()
    with pytest.raises(faults.FaultSpecError) as ei:
        faults.install_spec(f"decode:1.0, {spec}")
    msg = str(ei.value)
    assert needle in msg
    assert spec in msg          # the offending component is named
    # validation is all-or-nothing: the good leading component was
    # NOT armed
    assert not faults.ACTIVE


def test_install_spec_accepts_legacy_forms():
    try:
        injs = faults.install_spec("decode, grid.*:0.5, handler.mem::3")
        assert [i.pattern for i in injs] == ["decode", "grid.*",
                                             "handler.mem"]
        assert [i.prob for i in injs] == [1.0, 0.5, 1.0]
        assert [i.seed for i in injs] == [0, 0, 3]
    finally:
        faults.clear()


# --------------------------------------------------------------------------
# report ring
# --------------------------------------------------------------------------

def test_last_reports_ring_keeps_most_recent_32():
    fn, bufs0, scalars, params = _case("vecadd", 1)
    rt = Runtime()
    for k, v in bufs0.items():
        rt.create_buffer(k, v.copy())
    for _ in range(runtime.REPORT_RING + 8):
        rt.launch(fn, grid=params.grid, block=params.local_size,
                  scalar_args=scalars)
    reps = rt.last_reports()
    assert len(reps) == runtime.REPORT_RING
    assert reps[-1] is rt.last_report
    assert all(r.kernel == "vecadd" for r in reps)


def test_nontransactional_surface_attaches_report():
    fn, bufs0, scalars, params = _case("vecadd", 1)
    rt = Runtime(transactional=False)
    for k, v in bufs0.items():
        rt.create_buffer(k, v.copy())
    with faults.inject("decode"), pytest.raises(faults.EngineFault) as ei:
        rt.launch(fn, grid=params.grid, block=params.local_size,
                  scalar_args=scalars)
    assert ei.value.report is rt.last_report
    assert any(n.startswith("launch report:") for n in ei.value.__notes__)
