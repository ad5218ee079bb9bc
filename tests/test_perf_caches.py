"""Tests for the performance layer: decoded-interpreter parity, decode-cache
invalidation on IR mutation, AnalysisManager version-keyed memoization, and
the runtime compile cache."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent / "kernels"))

from repro.core import diskcache, graph, interp, runtime
from repro.core.vir import Const, Instr, Op, Reg, Ty
from repro.core.passes.analysis import AnalysisManager
from repro.core.passes.pipeline import (ABLATION_LADDER, PassConfig,
                                        run_pipeline)
from repro.core.passes.uniformity import VortexTTI, run_uniformity
from repro.volt_bench import BENCHES

import volt_kernels as K


# a cross-section of execution features: guards, barriers+shared memory,
# data-dependent loops, deep CFGs, warp collectives + atomics, vx_pred loops
PARITY_BENCHES = ["vecadd", "reduce0", "psort", "cfd_like", "atomic_agg",
                  "spmv", "vote_sw"]


def _launch_both(fn, bufs0, params, scalars):
    ref = {k: v.copy() for k, v in bufs0.items()}
    st_ref = interp.launch(fn, ref, params, scalar_args=scalars,
                           decoded=False)
    dec = {k: v.copy() for k, v in bufs0.items()}
    st_dec = interp.launch(fn, dec, params, scalar_args=scalars,
                           decoded=True)
    return ref, st_ref, dec, st_dec


@pytest.mark.parametrize("name", PARITY_BENCHES)
@pytest.mark.parametrize("cfg_i", [0, len(ABLATION_LADDER) - 1],
                         ids=["base", "full"])
def test_decoded_execstats_parity(name, cfg_i):
    """Decoded executor == instruction-at-a-time executor: identical
    outputs AND identical dynamic instruction counts / memory stats."""
    b = BENCHES[name]
    rng = np.random.default_rng(7)
    bufs0, scalars, params = b.make(rng)
    mod = b.handle.build(None)
    ck = run_pipeline(mod, b.handle.name, ABLATION_LADDER[cfg_i])
    ref, st_ref, dec, st_dec = _launch_both(ck.fn, bufs0, params, scalars)
    assert st_ref.instrs == st_dec.instrs
    assert st_ref.by_op == st_dec.by_op
    assert st_ref.mem_requests == st_dec.mem_requests
    assert st_ref.mem_insts == st_dec.mem_insts
    assert st_ref.shared_requests == st_dec.shared_requests
    assert st_ref.atomic_serial == st_dec.atomic_serial
    assert st_ref.max_ipdom_depth == st_dec.max_ipdom_depth
    assert st_ref.prints == st_dec.prints
    for k in ref:
        np.testing.assert_array_equal(ref[k], dec[k],
                                      err_msg=f"buffer {k}")


def test_decoded_matches_scalar_oracle():
    """Decoded SIMT execution of transformed IR == per-thread scalar
    reference on untransformed IR (device-function calls included)."""
    rng = np.random.default_rng(5)
    coefs = rng.standard_normal(4).astype(np.float32)
    x = rng.standard_normal(128).astype(np.float32)
    params = interp.LaunchParams(grid=4, local_size=32, warp_size=32)
    scalars = {"deg": 4, "n": 128}
    mod = K.uses_helper.build(None)
    ck = run_pipeline(mod, "uses_helper", ABLATION_LADDER[-1])
    simt = {"coefs": coefs.copy(), "x": x.copy(),
            "out": np.zeros(128, np.float32)}
    interp.launch(ck.fn, simt, params, scalar_args=scalars, decoded=True)
    mod2 = K.uses_helper.build(None)
    ref = {"coefs": coefs.copy(), "x": x.copy(),
           "out": np.zeros(128, np.float32)}
    interp.reference_launch(mod2.functions["uses_helper"], ref, params,
                            scalar_args=scalars)
    np.testing.assert_allclose(simt["out"], ref["out"], atol=1e-4)


def test_decode_cache_hit_and_stale_invalidation():
    """The decoded program is cached on the function keyed by ir_version;
    mutating the IR after a launch must trigger a re-decode (stale-cache
    regression: both executors must see the MUTATED semantics)."""
    b = BENCHES["saxpy"]
    rng = np.random.default_rng(0)
    bufs0, scalars, params = b.make(rng)
    mod = b.handle.build(None)
    ck = run_pipeline(mod, b.handle.name, PassConfig())
    fn = ck.fn

    interp.launch(fn, {k: v.copy() for k, v in bufs0.items()}, params,
                  scalar_args=scalars)
    cache = fn._decode_cache
    assert len(cache) == 1
    prog0 = next(iter(cache.values()))
    interp.launch(fn, {k: v.copy() for k, v in bufs0.items()}, params,
                  scalar_args=scalars)
    assert next(iter(cache.values())) is prog0, "same IR must hit cache"

    # hazard-style mutation: invert the branch without repairing the split
    # (Fig 5a) — the interpreter must now execute the *corrupted* program
    split_block = None
    for blk in fn.blocks:
        if any(i.op is Op.SPLIT for i in blk.instrs):
            split_block = blk
            break
    assert split_block is not None
    cbr = split_block.terminator
    notc = Reg(Ty.BOOL, "inv")
    split_block.insert(len(split_block.instrs) - 2,
                       Instr(Op.NOT, [cbr.operands[0]], notc))
    cbr.operands = [notc, cbr.operands[2], cbr.operands[1]]

    ref, st_ref, dec, st_dec = _launch_both(fn, bufs0, params, scalars)
    assert next(iter(cache.values())) is not prog0, \
        "IR mutation must invalidate the decode cache"
    # both executors agree on the (corrupted) semantics
    assert st_ref.instrs == st_dec.instrs
    for k in ref:
        np.testing.assert_array_equal(ref[k], dec[k])
    # ... and the corruption is real (we are not silently running stale IR)
    n = scalars["n"]
    expect = bufs0["y"].copy()
    expect[:n] = scalars["a"] * bufs0["x"][:n] + bufs0["y"][:n]
    assert not np.allclose(dec["y"], expect)


def test_analysis_manager_invalidates_on_cfg_mutation():
    """Cached dominators/loops/control-deps drop when the CFG changes."""
    mod = K.loop_break_continue.build(None)
    fn = mod.functions["loop_break_continue"]
    am = AnalysisManager()
    dom1 = am.dominators(fn)
    loops1 = am.loops(fn)
    cdeps1 = am.control_deps(fn)
    assert am.dominators(fn) is dom1, "unchanged CFG must be a cache hit"
    assert am.loops(fn) is loops1
    assert am.control_deps(fn) is cdeps1

    # CFG mutation: new block spliced in front of a successor edge
    old_entry_term = fn.entry.terminator
    target = old_entry_term.successors()[0]
    mid = fn.new_block("mid")
    mid.append(Instr(Op.BR, [target]))
    old_entry_term.replace_operand(target, mid)

    dom2 = am.dominators(fn)
    assert dom2 is not dom1, "CFG mutation must invalidate dominators"
    assert any(b is mid for b in dom2.order)
    assert am.loops(fn) is not loops1
    assert am.control_deps(fn) is not cdeps1


def test_analysis_manager_uniformity_memoized_and_invalidated():
    mod = K.saxpy.build(None)
    fn = mod.functions["saxpy"]
    from repro.core.passes.simplify import run_simplify
    from repro.core.passes.structurize import run_structurize
    run_simplify(fn)
    run_structurize(fn)
    am = AnalysisManager()
    tti = VortexTTI(uni_hw=True, uni_ann=True)
    info1 = am.uniformity(fn, tti)
    assert am.uniformity(fn, tti) is info1, "unchanged IR: exact reuse"
    # different TTI configuration: distinct cache line
    info_other = am.uniformity(fn, VortexTTI(uni_hw=False, uni_ann=False))
    assert info_other is not info1
    # attrs-only bump keeps uniformity warm but invalidates decode
    v0 = fn.ir_version
    fn.bump_version(cfg=False, dataflow=False)
    assert fn.ir_version == v0 + 1
    assert am.uniformity(fn, tti) is info1
    # a dataflow bump forces recomputation
    fn.bump_version(cfg=False)
    assert am.uniformity(fn, tti) is not info1


def test_uniformity_seed_warm_start_is_conservative():
    """Seeding from a previous lattice re-converges to the same result on
    unchanged IR (monotone fixpoint)."""
    mod = K.saxpy.build(None)
    fn = mod.functions["saxpy"]
    tti = VortexTTI()
    a = run_uniformity(fn, tti)
    b = run_uniformity(fn, tti, seed=a)
    assert a.divergent_values == b.divergent_values
    assert a.divergent_slots == b.divergent_slots
    assert a.divergent_branches == b.divergent_branches


def test_pipeline_ir_identical_with_and_without_analysis_cache():
    import re
    from repro.core.backends.asm import emit_asm

    def norm(s):
        return re.sub(r"\.[0-9]+", "", re.sub(r"%v[0-9]+", "%v", s))

    for name in ("cfd_like", "srad_flag"):
        b = BENCHES[name]
        for cfg in (ABLATION_LADDER[0], ABLATION_LADDER[-1]):
            m1 = b.handle.build(None)
            c1 = run_pipeline(m1, name, cfg, use_analysis_cache=True)
            m2 = b.handle.build(None)
            c2 = run_pipeline(m2, name, cfg, use_analysis_cache=False)
            assert norm(emit_asm(c1.fn)) == norm(emit_asm(c2.fn)), \
                f"{name}/{cfg.label}: cached pipeline changed the IR"


def test_decode_plan_disk_cache(tmp_path, monkeypatch):
    """The persistent decode-plan cache: a FRESH build of an identical
    kernel (new Function objects, same content) must hit the on-disk
    plan instead of recomputing the static decode analysis — and the
    loaded plan must produce identical decode classifications and
    identical execution."""
    monkeypatch.setenv("VOLT_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("VOLT_DISK_CACHE", "1")
    b = BENCHES["spmv_csr"]
    rng = np.random.default_rng(0)
    bufs0, scalars, params = b.make(rng)

    def fresh_fn():
        mod = b.handle.build(None)
        return run_pipeline(mod, b.handle.name, ABLATION_LADDER[-1]).fn

    base = dict(diskcache.DISK_CACHE_STATS)
    fn1 = fresh_fn()
    prog1 = interp._decode_batched(fn1, 32, False, 1, grid_mode=True)
    assert diskcache.DISK_CACHE_STATS["decode_misses"] > base["decode_misses"]
    hits0 = diskcache.DISK_CACHE_STATS["decode_hits"]
    assert list(tmp_path.glob("*.vdp")), "plan must persist to disk"

    fn2 = fresh_fn()                 # same content, new objects
    prog2 = interp._decode_batched(fn2, 32, False, 1, grid_mode=True)
    assert diskcache.DISK_CACHE_STATS["decode_hits"] > hits0, \
        "identical kernel must hit the decode-plan cache"
    # loaded-plan decode classifications match the computed ones
    assert (prog1.order_free, prog1.private_stores,
            prog1.private_stores_2d) == \
           (prog2.order_free, prog2.private_stores,
            prog2.private_stores_2d)
    assert len(prog1._hazard_stores) == len(prog2._hazard_stores)
    f1 = {k.kind for k in prog1.mem_facts.index_fact.values()}
    f2 = {k.kind for k in prog2.mem_facts.index_fact.values()}
    assert f1 == f2
    # ... and execution through the loaded plan stays bit-identical
    ref = {k: v.copy() for k, v in bufs0.items()}
    st_ref = interp.launch(fn2, ref, params, scalar_args=scalars,
                           decoded=False)
    dec = {k: v.copy() for k, v in bufs0.items()}
    st_dec = interp.launch(fn2, dec, params, scalar_args=scalars)
    assert st_ref.instrs == st_dec.instrs
    assert st_ref.mem_requests == st_dec.mem_requests
    for k in ref:
        np.testing.assert_array_equal(ref[k], dec[k])


def test_decode_plan_corrupt_and_content_invalidation(tmp_path,
                                                      monkeypatch):
    """Corrupt plan payloads fall back to a fresh computation (and the
    bad entry is deleted); editing the kernel body changes the content
    hash so the old plan can never be returned."""
    monkeypatch.setenv("VOLT_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("VOLT_DISK_CACHE", "1")

    def fresh_fn(handle, name):
        return run_pipeline(handle.build(None), name,
                            ABLATION_LADDER[-1]).fn

    fn1 = fresh_fn(K.saxpy, "saxpy")
    interp._decode_batched(fn1, 32, False, 1, grid_mode=True)
    paths = list(tmp_path.glob("*.vdp"))
    assert len(paths) == 1
    # corrupt it: the next fresh decode must recompute, not crash
    paths[0].write_bytes(b"garbage")
    errs0 = diskcache.DISK_CACHE_STATS["decode_errors"]
    fn2 = fresh_fn(K.saxpy, "saxpy")
    prog = interp._decode_batched(fn2, 32, False, 1, grid_mode=True)
    assert diskcache.DISK_CACHE_STATS["decode_errors"] > errs0
    assert not paths[0].exists() or \
        paths[0].read_bytes() != b"garbage"
    assert prog.private_stores      # recomputed facts, not garbage
    # different kernel content -> different key (no false sharing)
    fn3 = fresh_fn(K.loop_break_continue, "loop_break_continue")
    k_a = diskcache.plan_key(fn2)
    k_b = diskcache.plan_key(fn3)
    assert k_a != k_b
    # ... and an in-place IR mutation changes the key too
    v0 = diskcache.plan_key(fn2)
    blk = fn2.entry
    from repro.core.vir import Const
    blk.insert(0, Instr(Op.ADD, [Const(Ty.I32, 1), Const(Ty.I32, 2)],
                        Reg(Ty.I32, "dead")))
    assert diskcache.plan_key(fn2) != v0


def test_runtime_compile_cache():
    runtime.clear_compile_cache()
    h = BENCHES["vecadd"].handle
    ck1 = runtime.compile_kernel(h)
    assert runtime.compile_kernel(h) is ck1, "same (kernel, config): hit"
    ck2 = runtime.compile_kernel(h, PassConfig(uni_hw=True))
    assert ck2 is not ck1, "different PassConfig: separate entry"
    assert runtime.compile_kernel(h, warp_size=16) is not ck1, \
        "different warp config: separate entry"
    # end-to-end through the Runtime wrapper
    rt = runtime.Runtime()
    rng = np.random.default_rng(3)
    x = rng.standard_normal(64).astype(np.float32)
    y = rng.standard_normal(64).astype(np.float32)
    rt.create_buffer("x", x)
    rt.create_buffer("y", y)
    rt.create_buffer("z", np.zeros(64, np.float32))
    rt.launch_kernel(h, grid=2, block=32, scalar_args={"n": 64})
    np.testing.assert_allclose(rt.read_buffer("z"), x + y, atol=1e-6)
    runtime.clear_compile_cache()


def test_compile_cache_crash_mid_write_leaves_no_truncated_entry(
        tmp_path, monkeypatch):
    """A crash between the tmp write and the rename (the cache.commit
    injection site inside _atomic_write) must never leave a partial
    .vck a later process could load: only tmp debris, the compile still
    succeeds, and a clean recompile persists a loadable entry."""
    from repro.core import faults

    monkeypatch.setenv("VOLT_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("VOLT_DISK_CACHE", "1")
    runtime.clear_compile_cache()
    h = BENCHES["vecadd"].handle
    errs0 = diskcache.DISK_CACHE_STATS["errors"]
    with faults.inject("cache.commit"):
        ck = runtime.compile_kernel(h, use_cache=False)
    assert ck is not None, "cache-write failure must never fail compile"
    assert diskcache.DISK_CACHE_STATS["errors"] > errs0
    assert not list(tmp_path.glob("*.vck")), \
        "crash mid-write must not commit an entry"
    # the clean retry commits, and the entry actually loads
    runtime.compile_kernel(h, use_cache=False)
    paths = list(tmp_path.glob("*.vck"))
    assert len(paths) == 1
    hits0 = diskcache.DISK_CACHE_STATS["hits"]
    runtime.compile_kernel(h, use_cache=False)
    assert diskcache.DISK_CACHE_STATS["hits"] > hits0
    runtime.clear_compile_cache()


def test_decode_plan_crash_mid_write_leaves_no_truncated_entry(
        tmp_path, monkeypatch):
    """Same contract for the decode-plan cache (.vdp)."""
    from repro.core import faults

    monkeypatch.setenv("VOLT_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("VOLT_DISK_CACHE", "1")

    def fresh_fn():
        return run_pipeline(K.saxpy.build(None), "saxpy",
                            ABLATION_LADDER[-1]).fn

    with faults.inject("cache.commit"):
        prog = interp._decode_batched(fresh_fn(), 32, False, 1,
                                      grid_mode=True)
    assert prog is not None
    assert not list(tmp_path.glob("*.vdp")), \
        "crash mid-write must not commit a plan"
    # clean rerun persists a loadable plan
    interp._decode_batched(fresh_fn(), 32, False, 1, grid_mode=True)
    assert list(tmp_path.glob("*.vdp"))
    hits0 = diskcache.DISK_CACHE_STATS["decode_hits"]
    interp._decode_batched(fresh_fn(), 32, False, 1, grid_mode=True)
    assert diskcache.DISK_CACHE_STATS["decode_hits"] > hits0
