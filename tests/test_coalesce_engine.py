"""Parity suite for the shared coalescing/stats engine (core/interp_mem).

The engine replaced six-plus per-access ``np.unique`` sites across the
four executors with one counting kernel plus a decode-time analytic
fast path.  Its contract is bit-exactness: every path — generic
sort/diff, monotone run-count, uniform closed form, and the
``reference_counting()`` np.unique mode — must agree with the
``np.unique`` oracle on EVERY input, and the executors must produce
identical ``ExecStats`` whichever counting implementation is active.

The counting RULE is pinned here too (the cross-executor consistency
audit): line counts are taken over the IN-BOUNDS indices of active
lanes — loads clamp out-of-bounds lanes to the buffer edge first,
stores/atomics have already validated theirs — and every executor
agrees on it (regression: a kernel with OOB-clipped load indices runs
through all five executors with identical ``mem_requests``).

The jax-codegen rung re-implements the rule a third way — a traced
sentinel sort over the gathered (R, W) index matrix
(``jaxgen.count_lines_traced``) instead of the engine's analytic
closed forms or np.unique — so this suite also pins traced counts ==
analytic fast path == oracle on the same OOB-clipped affine families.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent / "kernels"))

from repro.core import interp, interp_mem
from repro.core.interp_mem import AffineFact
from repro.core.frontends import opencl
from repro.core.passes.analysis import affine_mem_facts, window_classes
from repro.core.passes.pipeline import ABLATION_LADDER, run_pipeline
from repro.core.vir import Op
from repro.volt_bench import BENCHES

import rungs
import volt_kernels as K

FULL = ABLATION_LADDER[-1]

_CK = {}


def _compiled(handle, name):
    fn = _CK.get(name)
    if fn is None:
        fn = run_pipeline(handle.build(None), handle.name, FULL).fn
        _CK[name] = fn
    return fn


class _Ctx:
    """Stand-in for _WarpCtx in direct engine tests."""

    def __init__(self, ok=True, span=1 << 20):
        self.affine_ok = ok
        self.affine_span = span


def _oracle_rows(ix, mask):
    """Per-row distinct lines summed — the definitional oracle."""
    return sum(len(np.unique(ix[r][mask[r]] // interp_mem.CACHE_LINE_ELEMS))
               for r in range(ix.shape[0]))


# --------------------------------------------------------------------------
# deterministic engine-level parity
# --------------------------------------------------------------------------

def test_generic_paths_match_unique_oracle():
    rng = np.random.default_rng(0)
    for _ in range(200):
        R = int(rng.integers(1, 70))
        W = int(rng.choice([1, 4, 16, 32]))
        n = int(rng.integers(1, 3000))
        ix = rng.integers(0, n, (R, W)).astype(np.int64)
        mask = rng.uniform(0, 1, (R, W)) < rng.uniform(0, 1)
        want = _oracle_rows(ix, mask)
        n_act = int(mask.any(axis=1).sum())
        assert interp_mem.count_rows(ix, mask, n_act, n) == want
        with interp_mem.reference_counting():
            assert interp_mem.count_rows(ix, mask, n_act, n) == want
        if mask[0].any():
            w1 = len(np.unique(ix[0][mask[0]]
                               // interp_mem.CACHE_LINE_ELEMS))
            assert interp_mem.count_warp(ix[0].copy(), mask[0]) == w1
            a = ix[0][mask[0]]
            assert interp_mem.count_gathered(a.copy()) == w1


def test_monotone_and_uniform_facts_match_oracle():
    """Affine fast paths across stride signs, bases and ragged masks,
    including clip saturation at both buffer edges (clip is monotone,
    so the licence survives it)."""
    rng = np.random.default_rng(1)
    ctx = _Ctx()
    for _ in range(200):
        R = int(rng.integers(1, 70))
        W = int(rng.choice([1, 8, 32]))
        n = int(rng.integers(1, 2000))
        s = int(rng.choice([-7, -2, -1, 1, 2, 5, 16, 33]))
        base = rng.integers(-50, n + 50, (R, 1))
        aff = np.clip(base + s * np.arange(W), 0, n - 1).astype(np.int64)
        mask = rng.uniform(0, 1, (R, W)) < rng.uniform(0, 1)
        fact = AffineFact("inc" if s > 0 else "dec", False, abs(s),
                          int(np.abs(base).max()) + 1)
        want = _oracle_rows(aff, mask)
        assert interp_mem.count_rows(aff, mask, 0, n, fact, ctx) == want
        uni = np.broadcast_to(base % n, (R, W)).astype(np.int64).copy()
        ufact = AffineFact("uni", False)
        n_act = int(mask.any(axis=1).sum())
        assert interp_mem.count_rows(uni, mask, n_act, n, ufact,
                                     ctx) == _oracle_rows(uni, mask)
        if mask[0].any():
            assert interp_mem.count_warp(aff[0], mask[0], fact,
                                         ctx) == len(
                np.unique(aff[0][mask[0]] // 16))
            assert interp_mem.count_gathered(
                aff[0][mask[0]], fact, ctx) == len(
                np.unique(aff[0][mask[0]] // 16))


def test_invalid_licence_falls_back_exactly():
    """A fact whose launch-layout / wrap preconditions fail must take
    the generic path — same answer on non-affine data (where trusting
    the fact would miscount)."""
    rng = np.random.default_rng(2)
    ix = rng.integers(0, 500, (8, 32)).astype(np.int64)  # NOT monotone
    mask = rng.uniform(0, 1, (8, 32)) < 0.7
    want = _oracle_rows(ix, mask)
    bad_layout = AffineFact("inc", True, 1, 0)
    assert interp_mem.count_rows(ix, mask, 8, 500, bad_layout,
                                 _Ctx(ok=False)) == want
    bad_span = AffineFact("inc", False, 1 << 40, 0)
    assert interp_mem.count_rows(ix, mask, 8, 500, bad_span,
                                 _Ctx()) == want
    # and a VALID monotone fact on monotone data under the same ctxs
    aff = np.clip(7 + np.arange(32), 0, 499).astype(np.int64)
    aff = np.broadcast_to(aff, (8, 32)).copy()
    good = AffineFact("inc", False, 1, 7)
    assert interp_mem.count_rows(aff, mask, 8, 500, good,
                                 _Ctx()) == _oracle_rows(aff, mask)


def test_fact_ok_gates():
    f = AffineFact("inc", True, 1, 0)
    assert f.ok(_Ctx(ok=True))
    assert not f.ok(_Ctx(ok=False))
    assert not AffineFact("inc", False, 1 << 31, 0).ok(_Ctx())
    assert AffineFact("uni", False).ok(_Ctx(ok=False))
    assert not AffineFact("uni", True).ok(_Ctx(ok=False))


# --------------------------------------------------------------------------
# decode-time classification sanity on real compiled kernels
# --------------------------------------------------------------------------

def test_affine_facts_on_compiled_benches():
    """The guarded-stream pattern must classify (vecadd's accesses are
    stride-1 affine; dotproduct's atomic hits one cell), and
    data-dependent gathers must NOT."""
    fn = _compiled(BENCHES["vecadd"].handle, "vecadd")
    facts = affine_mem_facts(fn)
    kinds = [facts.index_fact[id(i)].kind
             for i in fn.instructions()
             if i.op in (Op.LOAD, Op.STORE) and id(i) in facts.index_fact]
    assert kinds and all(k == "inc" for k in kinds)
    assert all(p == "1d" for p in facts.store_privacy.values())

    fn = _compiled(BENCHES["dotproduct"].handle, "dotproduct")
    facts = affine_mem_facts(fn)
    at = [i for i in fn.instructions() if i.op is Op.ATOMIC]
    assert facts.index_fact[id(at[0])].kind == "uni"

    fn = _compiled(BENCHES["spmv_csr"].handle, "spmv_csr")
    facts = affine_mem_facts(fn)
    loads = [i for i in fn.instructions() if i.op is Op.LOAD]
    # row_ptr[gid]/row_ptr[gid+1] classify; vals[e]/x[cols[e]] must not
    classified = sum(id(i) in facts.index_fact for i in loads)
    assert 0 < classified < len(loads)


@opencl.kernel
def div_by_lane(x: "ptr_f32 const", y: "ptr_f32", n: "i32 uniform"):
    gid = get_global_id(0)  # noqa: F821 - an intrinsic of the dialect
    if gid < n:
        y[gid] = x[gid // (get_local_id(0) + 1)]  # noqa: F821


def _window_class_by_buffer(fn):
    """{buffer param name: window class or None} over the loads and
    stores of ``fn`` (one access per buffer in these kernels)."""
    cls = window_classes(fn)
    return {i.operands[0].name: cls.get(id(i))
            for i in fn.instructions() if i.op in (Op.LOAD, Op.STORE)}


@pytest.mark.parametrize("name, want", [
    # gid: lane stride 1 everywhere
    ("vecadd", {"x": "window", "y": "window", "z": "window"}),
    # a[row*k + i]: row = gid // n is a per-row term and the counter i is
    # only ever assigned launch-uniform values; b[i*n + col]: col is
    # gid less that term
    ("sgemm", {"a": "row_uniform", "b": "window", "c": "window"}),
    # vals[e], cols[e], x[cols[e]]: e is loaded from row_ptr
    ("spmv_csr", {"row_ptr": "window", "vals": None, "cols": None,
                  "x": None, "y": "window"}),
])
def test_window_classes_on_compiled_benches(name, want):
    fn = _compiled(BENCHES[name].handle, name)
    assert _window_class_by_buffer(fn) == want


def test_window_class_needs_a_uniform_divisor():
    fn = _compiled(div_by_lane, "div_by_lane")
    assert _window_class_by_buffer(fn) == {"x": None, "y": "window"}
    # the wide rules are for candidates only: the interpreter's facts
    # keep sgemm's loads unclassified
    fn = _compiled(BENCHES["sgemm"].handle, "sgemm")
    facts = affine_mem_facts(fn)
    assert not any(id(i) in facts.index_fact for i in fn.instructions()
                   if i.op is Op.LOAD)


def test_2d_linear_id_store_privacy():
    """gid_x + gid_y * global_size(0) chains earn the 2-D privacy level
    (the widened licence); bare gid_x chains stay 1-D."""
    fn2 = _compiled(K.ragged2d, "ragged2d")
    prog = interp._decode_batched(fn2, 32, False, 4, grid_mode=True,
                                  wg_rows=1)
    assert prog.order_free and prog.private_stores
    assert prog.private_stores_2d
    fn1 = _compiled(BENCHES["spmv_csr"].handle, "spmv_csr")
    prog1 = interp._decode_batched(fn1, 32, False, 4, grid_mode=True,
                                   wg_rows=1)
    assert prog1.private_stores and not prog1.private_stores_2d


# --------------------------------------------------------------------------
# executor-level: the counting rule + reference-mode invariance
# --------------------------------------------------------------------------

def _stats_tuple(st):
    return (st.instrs, dict(st.by_op), st.mem_requests, st.mem_insts,
            st.shared_requests, st.atomic_serial, st.max_ipdom_depth)


EXECUTORS = {
    "oracle": dict(decoded=False),
    "decoded": dict(decoded=True, batched=False),
    "wg_batched": dict(decoded=True, batched=True, grid=False),
    "grid": dict(decoded=True, batched=True, grid=True),
    "jax": rungs.JAX,
}


def test_oob_clip_rule_consistent_across_executors():
    """The audit's regression: a transpose load reads x[col*n + row]
    for every thread of over-provisioned warps, so tail threads clamp
    OOB indices — all five executors must count the clamped lines
    identically (the one rule: in-bounds indices of active lanes), in
    both counting modes.  For the jax rung that pins the traced
    gathered-index counts against the engine's analytic fast path on
    a real OOB-clip kernel, not just synthetic index matrices."""
    from repro.core.backends import jaxgen
    b = BENCHES["transpose"]          # gid >= n*n lanes load OOB
    rng = np.random.default_rng(3)
    bufs0, sc, params = b.make(rng)
    fn = _compiled(b.handle, "transpose")
    for factor in (1, 2, 4):
        p = interp.fold_warps(params, factor)
        stats = {}
        for label, kw in EXECUTORS.items():
            if label == "jax":        # certification warm-up launch
                jaxgen.reset_jax_telemetry()
                bufs = {k: v.copy() for k, v in bufs0.items()}
                rungs.launch(fn, bufs, p, scalar_args=sc, **kw)
            bufs = {k: v.copy() for k, v in bufs0.items()}
            stats[label] = _stats_tuple(rungs.launch(
                fn, bufs, p, scalar_args=sc, **kw))
            bufs = {k: v.copy() for k, v in bufs0.items()}
            with interp_mem.reference_counting():
                ref = _stats_tuple(rungs.launch(fn, bufs, p,
                                                scalar_args=sc, **kw))
            assert ref == stats[label], \
                f"{label} x{factor}: counting mode changed ExecStats"
        assert jaxgen.JAX_TELEMETRY["engaged"] >= 1, \
            f"x{factor}: jax rung must engage on the OOB-clip kernel"
        for label in ("decoded", "wg_batched", "grid", "jax"):
            assert stats[label] == stats["oracle"], \
                f"{label} x{factor}: executors disagree on " \
                f"clipped-line counts"


def test_jax_traced_counts_match_analytic_fast_path():
    """Engine-level pin: the jax rung's traced sentinel-sort counter
    over gathered (R, W) indices == the analytic affine fast path ==
    the np.unique oracle, on OOB-clipped affine families across stride
    signs, warp widths and ragged masks (the exact shape the licence
    admits: clip is monotone, so the affine fact survives while the
    traced counter sees the already-clipped gather indices)."""
    import jax.numpy as jnp

    from repro.core.backends import jaxgen
    rng = np.random.default_rng(9)
    ctx = _Ctx()
    for _ in range(60):
        R = int(rng.integers(1, 40))
        W = int(rng.choice([1, 8, 32]))
        n = int(rng.integers(1, 2000))
        s = int(rng.choice([-7, -2, -1, 1, 2, 5, 16, 33]))
        base = rng.integers(-50, n + 50, (R, 1))
        aff = np.clip(base + s * np.arange(W), 0, n - 1).astype(np.int64)
        mask = rng.uniform(0, 1, (R, W)) < rng.uniform(0, 1)
        fact = AffineFact("inc" if s > 0 else "dec", False, abs(s),
                          int(np.abs(base).max()) + 1)
        analytic = interp_mem.count_rows(aff, mask, 0, n, fact, ctx)
        traced = int(jaxgen.count_lines_traced(
            jnp.asarray(aff.astype(np.int32)), jnp.asarray(mask), W))
        assert traced == analytic == _oracle_rows(aff, mask)


@pytest.mark.parametrize("name", ["vecadd", "reduce0", "spmv_csr",
                                  "atomic_agg", "cfd_like"])
def test_reference_counting_invariant(name):
    """Flipping the engine to the historical np.unique implementation
    must change nothing observable (stats + buffers) on the default
    executor."""
    b = BENCHES[name]
    rng = np.random.default_rng(5)
    bufs0, sc, params = b.make(rng)
    fn = _compiled(b.handle, name)
    fast = {k: v.copy() for k, v in bufs0.items()}
    st_fast = interp.launch(fn, fast, params, scalar_args=sc)
    ref = {k: v.copy() for k, v in bufs0.items()}
    with interp_mem.reference_counting():
        st_ref = interp.launch(fn, ref, params, scalar_args=sc)
    assert _stats_tuple(st_fast) == _stats_tuple(st_ref)
    for k in bufs0:
        np.testing.assert_array_equal(fast[k], ref[k])


# --------------------------------------------------------------------------
# hypothesis: random masks / strides / dtypes / OOB clip vs the oracle
# --------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

import os

_H_EXAMPLES = int(os.environ.get("VOLT_HYPOTHESIS_MAX_EXAMPLES", "50"))

needs_hypothesis = pytest.mark.skipif(
    not _HAVE_HYPOTHESIS,
    reason="property tests need hypothesis "
           "(pip install -r requirements-dev.txt)")


if _HAVE_HYPOTHESIS:
    @needs_hypothesis
    @settings(max_examples=min(50, _H_EXAMPLES), deadline=None)
    @given(rows=st.integers(1, 80),
           w=st.sampled_from([1, 4, 8, 16, 32]),
           buflen=st.integers(1, 5000),
           dtype=st.sampled_from(["int32", "int64"]),
           density=st.floats(0.0, 1.0),
           seed=st.integers(0, 2**31 - 1))
    def test_engine_random_rows_vs_oracle(rows, w, buflen, dtype,
                                          density, seed):
        """Generic + reference counting on arbitrary (possibly OOB,
        then clipped) indices of either integer dtype."""
        rng = np.random.default_rng(seed)
        raw = rng.integers(-buflen, 2 * buflen, (rows, w)).astype(dtype)
        safe = np.clip(raw.astype(np.int64), 0, buflen - 1)
        mask = rng.uniform(0, 1, (rows, w)) < density
        want = _oracle_rows(safe, mask)
        n_act = int(mask.any(axis=1).sum())
        assert interp_mem.count_rows(safe.copy(), mask, n_act,
                                     buflen) == want
        with interp_mem.reference_counting():
            assert interp_mem.count_rows(safe.copy(), mask, n_act,
                                         buflen) == want
        if mask[0].any():
            w1 = len(np.unique(safe[0][mask[0]] // 16))
            assert interp_mem.count_warp(safe[0].copy(), mask[0]) == w1

    @needs_hypothesis
    @settings(max_examples=min(50, _H_EXAMPLES), deadline=None)
    @given(rows=st.integers(1, 80),
           w=st.sampled_from([1, 8, 32]),
           buflen=st.integers(1, 5000),
           stride=st.integers(-40, 40).filter(lambda s: s != 0),
           base_span=st.integers(1, 6000),
           density=st.floats(0.0, 1.0),
           seed=st.integers(0, 2**31 - 1))
    def test_engine_affine_facts_vs_oracle(rows, w, buflen, stride,
                                           base_span, density, seed):
        """The analytic licence: any affine-in-lane index family, any
        stride sign, OOB-clipped at both edges, arbitrary masks."""
        rng = np.random.default_rng(seed)
        base = rng.integers(-base_span, base_span, (rows, 1))
        aff = np.clip(base + stride * np.arange(w), 0,
                      buflen - 1).astype(np.int64)
        mask = rng.uniform(0, 1, (rows, w)) < density
        fact = AffineFact("inc" if stride > 0 else "dec", False,
                          abs(stride), base_span)
        ctx = _Ctx(span=1 << 18)
        want = _oracle_rows(aff, mask)
        n_act = int(mask.any(axis=1).sum())
        assert interp_mem.count_rows(aff, mask, n_act, buflen, fact,
                                     ctx) == want
        if mask[0].any():
            assert interp_mem.count_warp(
                aff[0], mask[0], fact, ctx) == len(
                np.unique(aff[0][mask[0]] // 16))
            assert interp_mem.count_gathered(
                aff[0][mask[0]], fact, ctx) == len(
                np.unique(aff[0][mask[0]] // 16))
else:
    @needs_hypothesis
    def test_engine_random_rows_vs_oracle():
        pass

    @needs_hypothesis
    def test_engine_affine_facts_vs_oracle():
        pass
