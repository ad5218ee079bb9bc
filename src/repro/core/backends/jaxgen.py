"""JAX codegen executor — the fifth (top) rung of the launch chain.

The decoder already proves, per kernel, everything a real code generator
needs: order-freedom (no cross-workgroup read/write hazard), store
privacy (every store index injective across the launch), structured
control flow (post-``structurize`` every loop is a ``vx_pred``/uniform
header loop and every divergent branch a ``vx_split``/``vx_join``
diamond).  This module consumes those licences and emits ONE traced,
``jax.jit``-compiled chunk function over ``(rows, W)`` activation
arrays — rows are warps, ``n_warps`` consecutive rows per workgroup,
exactly the grid executor's row layout — instead of walking one Python
handler per decoded node:

  * masks become ``jnp.where`` / masked scatters (``.at[...].set(...,
    mode="drop")``);
  * ``vx_split`` diamonds trace both sides sequentially under sub-masks
    (the oracle's own execution order for a warp that takes both);
  * ``vx_pred`` and uniform header loops become ``lax.while_loop`` with
    a carry of (written slots, written buffers, header-defined regs,
    live mask, stat counters);
  * lockstep barriers are no-ops (the rung only licenses barriers at
    ``n_warps == 1``, where a row IS the whole workgroup);
  * loads/stores lower to gathers/scatters; store injectivity comes
    from ``passes.analysis.export_codegen_facts`` (the same
    ``affine_mem_facts`` privacy classes that license run-ahead).

``ExecStats`` are not sampled — they are *computed in the trace*, to
the oracle's exact counting rules (per-op counts under ``mask.any()``,
distinct-cache-line requests per access, IPDOM depth at two-sided
splits), so certification can demand bit-identical stats, not just
bit-identical buffers.

Certification gate (the promotion state machine, docs/performance.md
"Execute side 5"): a (kernel ir_version, launch shape class) pair starts
UNKNOWN.  The first licensed launch runs BOTH the jitted program and the
normal executor chain, compares buffers byte-for-byte and stats
field-for-field, and records "pass"/"fail" — in memory and, when the
runtime installed ``interp.JAX_CERT_HOOKS``, in a ``.vjc`` file next to
the ``.vck``/``.vdp`` caches.  Only a recorded "pass" lets later
launches run JAX as the primary; any recorded "fail" pins the pair to
the normal chain forever (until the kernel IR changes).  Evidence
promotes the fast path, not static analysis alone.

Failure model: the trace never raises mid-chunk.  Semantic errors the
oracle would raise (OOB store, uniformity violation, fuel exhaustion)
set bits in a traced ``err`` scalar; any nonzero bit after the chunk
loop raises ``EngineFault(site="jax.exec")`` with the buffers untouched
(results are staged device-side and only copied back on full success),
so the runtime chain demotes to the grid rung, which reproduces the
exact ``ExecError`` with full context.  ``DeadlineExceeded`` and
injected faults at ``jax.trace`` / ``jax.exec`` / ``jax.cache.load``
follow the PR 6/7 contracts unchanged.
"""
from __future__ import annotations

from time import perf_counter

import numpy as np

import jax
import jax.numpy as jnp

from ..vir import (AddrSpace, BINOPS, Const, Function, GlobalVar, Instr,
                   Op, Param, Reg, Slot, Ty, UNOPS, Value)
from .. import graph
from .. import faults as _faults
from .. import governor as _gov
from .. import interp as _interp
from ..interp_mem import CACHE_LINE_ELEMS
from ..passes.analysis import export_codegen_facts

_TY_DTYPE = {Ty.I32: jnp.int32, Ty.F32: jnp.float32, Ty.BOOL: jnp.bool_}
_TY_NP = {Ty.I32: np.int32, Ty.F32: np.float32, Ty.BOOL: np.bool_}

#: workgroups per jitted chunk (module attribute so the metamorphic
#: suite can vary it; the compiled-record key includes the value)
_CHUNK_WGS = 256

#: sorts-after-everything sentinel for masked-out line keys (valid line
#: keys are element_index // CACHE_LINE_ELEMS <= 2**27)
_SENT = 2**31 - 1

#: error bits accumulated in the traced err scalar — any nonzero bit
#: demotes; the grid rung then reproduces the oracle's exact exception
ERR_OOB_STORE = 1
ERR_UNIFORM = 2
ERR_FUEL = 4
ERR_OVERFLOW = 8     # an int32 line-request counter wrapped

#: every counter of the trace is int32 (JAX runs without x64 on the
#: TPU).  Instruction counts never exceed the fuel spent, so a budget
#: below this bound keeps them exact; line-request counters can still
#: outgrow it and raise ERR_OVERFLOW instead of wrapping silently
FUEL_MAX = 2**30

#: host-libm vs XLA transcendentals differ in ulps — certification
#: would catch the mismatch anyway, but refusing up front keeps the
#: cert cache free of foreseeable "fail" entries
_REFUSED_OPS = {Op.EXP, Op.LOG, Op.SIN, Op.COS, Op.POW}

JAX_TELEMETRY = {
    "engaged": 0,        # launches served by the jitted program
    "certified": 0,      # (kernel, shape) pairs newly certified "pass"
    "cert_runs": 0,      # differential certification launches
    "refusals": 0,       # licence/trace refusals (silent fallthrough)
    "compile_errors": 0, # backend compile failures: demoted, reported
                         # in the LaunchReport, retried next launch
    "cert_errors": 0,    # exceptions of the jitted program while it
                         # was being certified (kept with the verdict)
    "demotions": 0,      # certified launches that faulted -> grid
    "trace_cache_hits": 0,
    "routed_small": 0,   # certified but sent to the grid rung: the
                         # measured grid time beats the jitted-dispatch
                         # floor at this launch-shape class
}

#: route a certified launch to the grid rung when the measured grid
#: time is below this fraction of the measured jax time — the margin
#: keeps borderline shape classes on the certified primary (timing
#: noise must not flap the route)
_ROUTE_MARGIN = 0.9


def reset_jax_telemetry() -> None:
    for k in JAX_TELEMETRY:
        JAX_TELEMETRY[k] = 0


class LowerError(Exception):
    """Kernel/launch outside this rung's licence — silent fallthrough
    (NOT a demotion: nothing was attempted, nothing can have failed)."""


# --------------------------------------------------------------------------
# transitive static scan (memoized per ir_version)
# --------------------------------------------------------------------------

def _scan_fn(fn: Function) -> dict:
    cached = getattr(fn, "_jaxgen_scan", None)
    if cached is not None and cached[0] == fn.ir_version:
        return cached[1]
    out = {"refused": set(), "barrier": False, "shared": False,
           "global": False, "recursive": False}

    def visit(f: Function, stack: tuple) -> None:
        if f in stack:
            out["recursive"] = True
            return
        for i in f.instructions():
            op = i.op
            if op in _REFUSED_OPS or op in (Op.ATOMIC, Op.PRINT):
                out["refused"].add(op)
            if op is Op.BARRIER:
                out["barrier"] = True
            for o in i.operands:
                if isinstance(o, GlobalVar):
                    if o.space is AddrSpace.SHARED:
                        out["shared"] = True
                    else:
                        out["global"] = True
            if op is Op.CALL:
                visit(i.operands[0], stack + (f,))

    visit(fn, ())
    fn._jaxgen_scan = (fn.ir_version, out)  # type: ignore[attr-defined]
    return out


# --------------------------------------------------------------------------
# trace context: stat counters + error bits as traced scalars
# --------------------------------------------------------------------------

class _TraceCtx:
    """Counter state threaded through one chunk trace.  All members are
    int32 device scalars with a FIXED structure (``cnt`` keys are the
    sorted op values reachable from the kernel), so the whole context
    packs into a stable pytree for loop carries — and across chunks:
    each chunk starts from the previous chunk's packed counters, so the
    launch totals never leave the device until the end.

    Packed members: per-op counts, coalesced global line requests
    (``mem``), coalesced shared-tile line requests (``shm``), load/store
    instructions issued (``minst``), max two-sided IPDOM depth
    (``maxd``), fuel spent, error bits."""

    __slots__ = ("cnt_keys", "cnt", "mem", "shm", "minst", "maxd",
                 "fuel", "err", "fuel_limit", "_live")

    def __init__(self, cnt_keys: tuple, fuel_limit: int, acc: tuple) -> None:
        self.cnt_keys = cnt_keys
        self.fuel_limit = int(fuel_limit)
        self.unpack(acc)

    def live(self, mask):
        """Rows with any active lane — the oracle's per-warp
        ``mask.any()`` stat gate, batched.  Memoized per mask object
        (strong refs held so ids cannot recycle mid-trace)."""
        hit = self._live.get(id(mask))
        if hit is not None and hit[0] is mask:
            return hit[1]
        n = mask.any(axis=1).sum(dtype=jnp.int32)
        self._live[id(mask)] = (mask, n)
        return n

    def charge(self, opval: str, mask) -> None:
        n = self.live(mask)
        self.cnt[opval] = self.cnt[opval] + n
        self.fuel = self.fuel + n

    def add_lines(self, tile: bool, lines) -> None:
        old = self.shm if tile else self.mem
        new = old + lines
        self.err = self.err | jnp.where(new < old, jnp.int32(ERR_OVERFLOW),
                                        jnp.int32(0))
        if tile:
            self.shm = new
        else:
            self.mem = new

    def pack(self) -> tuple:
        return (tuple(self.cnt[k] for k in self.cnt_keys), self.mem,
                self.shm, self.minst, self.maxd, self.fuel, self.err)

    def unpack(self, t: tuple) -> None:
        cnt_t, self.mem, self.shm, self.minst, self.maxd, self.fuel, \
            self.err = t
        self.cnt = dict(zip(self.cnt_keys, cnt_t))
        self._live = {}     # masks from another trace scope are stale


_FUEL_IN_PACK = 5           # index of ``fuel`` in _TraceCtx.pack()


def _zero_acc(n_ops: int) -> tuple:
    """The packed counters a launch starts from."""
    z = np.int32(0)
    return ((z,) * n_ops,) + (z,) * 6


class _State:
    """Functional slice of executor state threaded through the walk."""

    __slots__ = ("slots", "bufs", "mask")

    def __init__(self, slots: dict, bufs: dict, mask) -> None:
        self.slots = slots   # id(Slot) -> (R, W)
        self.bufs = bufs     # name -> (N,) global | (R, S) private tile
        self.mask = mask     # (R, W) bool

    def copy(self) -> "_State":
        return _State(dict(self.slots), dict(self.bufs), self.mask)


# --------------------------------------------------------------------------
# arithmetic: numpy-parity versions of the oracle's op tables
# --------------------------------------------------------------------------

def _jx_binop(op: Op, a, b):
    if op is Op.ADD: return a + b
    if op is Op.SUB: return a - b
    if op is Op.MUL: return a * b
    if op is Op.DIV:
        if jnp.issubdtype(a.dtype, jnp.integer):
            return jnp.where(b != 0, a // jnp.where(b == 0, 1, b), 0)
        return jnp.where(b != 0, a / jnp.where(b == 0, 1, b),
                         jnp.zeros((), a.dtype))
    if op is Op.MOD:
        return jnp.where(b != 0, a % jnp.where(b == 0, 1, b),
                         jnp.zeros((), a.dtype))
    if op is Op.AND:
        # oracle _and_fn: float32 operands compare as booleans
        if a.dtype == jnp.float32:
            return a.astype(jnp.bool_) & b.astype(jnp.bool_)
        return a & b
    if op is Op.OR: return a | b
    if op is Op.XOR: return a ^ b
    if op is Op.SHL: return a << b
    if op is Op.SHR: return a >> b
    if op is Op.MIN: return jnp.minimum(a, b)
    if op is Op.MAX: return jnp.maximum(a, b)
    if op is Op.EQ: return a == b
    if op is Op.NE: return a != b
    if op is Op.LT: return a < b
    if op is Op.LE: return a <= b
    if op is Op.GT: return a > b
    if op is Op.GE: return a >= b
    raise LowerError(f"binop {op} unsupported on the jax rung")


def _jx_unop(op: Op, a):
    if op is Op.NEG: return -a
    if op is Op.NOT: return ~a
    if op is Op.ABS: return jnp.abs(a)
    if op is Op.SQRT:
        return jnp.sqrt(jnp.maximum(a, 0).astype(jnp.float32))
    if op is Op.ITOF: return a.astype(jnp.float32)
    if op is Op.FTOI: return a.astype(jnp.int32)
    if op is Op.POPC:
        return jax.lax.population_count(
            a.astype(jnp.uint32)).astype(jnp.int32)
    if op is Op.FFS:
        au = a.astype(jnp.uint32)
        low = au & (~au + jnp.uint32(1))
        idx = 32 - jax.lax.clz(low).astype(jnp.int32)
        return jnp.where(au == 0, 0, idx)
    raise LowerError(f"unop {op} unsupported on the jax rung")


def count_lines_traced(clip, mask, W: int):
    """Oracle line counting, batched and traceable: distinct cache lines
    among ACTIVE lanes, summed over rows (``interp_mem.count_gathered``
    per warp).  ``clip`` is an (R, W) int32 index array, ``mask`` the
    matching activation mask; W is the static warp width."""
    key = jnp.where(mask, clip // CACHE_LINE_ELEMS,
                    jnp.int32(_SENT))
    skey = jnp.sort(key, axis=1)
    distinct = (skey[:, 0] != _SENT).astype(jnp.int32)
    if W > 1:
        neq = skey[:, 1:] != skey[:, :-1]
        distinct = distinct + (
            neq & (skey[:, 1:] != _SENT)).sum(axis=1,
                                              dtype=jnp.int32)
    return distinct.sum(dtype=jnp.int32)


# --------------------------------------------------------------------------
# the (rows, W) walker
# --------------------------------------------------------------------------

class _RowLowering:
    """Traces one function over (R, W) activations with oracle-exact
    stat counting.  ``walk`` mirrors ``interp._exec_warp``'s control
    loop at trace time; all R rows take all paths under row sub-masks,
    which the order-free / store-private licences make equivalent to
    the oracle's per-warp sequential order."""

    def __init__(self, fn: Function, R: int, W: int, intr: dict,
                 argmap: dict, tc: _TraceCtx, tiles: set,
                 shape_1d: bool, facts: dict | None) -> None:
        self.fn = fn
        self.R = R
        self.W = W
        self.intr = intr       # (name, dim) -> (R, W) int32
        self.argmap = argmap   # id(Param) -> buffer name | (R, W) value
        self.tc = tc
        self.tiles = tiles     # buffer names that are (R, S) tiles
        self.shape_1d = shape_1d
        self.facts = facts     # export_codegen_facts or None (callees)
        self.iidx = {id(i): (bi, ii)
                     for bi, b in enumerate(fn.blocks)
                     for ii, i in enumerate(b.instrs)}
        self.env: dict = {}
        self.tokens: dict = {}          # id(token Reg) -> (R, W) mask
        self.loops = graph.natural_loops(fn)
        self.headers = {id(l.header): l for l in self.loops}
        self.pdom = graph.postdominators(fn)
        self.depth = 0                  # static enclosing-split count
        self.pending = None             # SPLIT awaiting its CBR
        self.ret_val = None
        # static cross-lane patterns shared by tile-store dedup
        self._rowix = jnp.arange(R, dtype=jnp.int32)[:, None]
        self._later = jnp.asarray(
            np.triu(np.ones((W, W), dtype=bool), k=1))[None]

    # -- values ------------------------------------------------------------
    def val(self, v: Value):
        if isinstance(v, Const):
            return jnp.full((self.R, self.W), v.value,
                            dtype=_TY_DTYPE.get(v.ty, jnp.float32))
        if isinstance(v, Reg):
            a = self.env.get(id(v))
            if a is None:
                raise LowerError(f"undefined reg %{v.name}")
            return a
        if isinstance(v, Param):
            a = self.argmap.get(id(v))
            if a is None:
                raise LowerError(f"unbound param {v.name}")
            if isinstance(a, str):
                raise LowerError(f"pointer param {v.name} used as value")
            return a
        raise LowerError(f"cannot lower value {v!r}")

    def buf_name(self, ptr: Value) -> str:
        if isinstance(ptr, Param):
            a = self.argmap.get(id(ptr))
            if isinstance(a, str):
                return a
            raise LowerError(f"pointer param {ptr.name} not bound")
        if isinstance(ptr, GlobalVar):
            if ptr.space is AddrSpace.SHARED:
                return f"@{ptr.name}"
            raise LowerError(f"non-shared global @{ptr.name}")
        raise LowerError(f"bad pointer {ptr!r}")

    # -- walk --------------------------------------------------------------
    def walk(self, block, pos: int, st: _State, stop_block):
        """Returns ("ret", None, st) | ("join", (block, pos), st) |
        ("stop", (block, 0), st)."""
        tc = self.tc
        while True:
            if stop_block is not None and block is stop_block and pos == 0:
                return ("stop", (block, 0), st)
            i = block.instrs[pos]
            op = i.op
            if op is Op.BR:
                tc.charge(op.value, st.mask)
                self.pending = None
                block, pos = i.operands[0], 0
                continue
            if op is Op.RET:
                tc.charge(op.value, st.mask)
                if i.operands:
                    self.ret_val = self.val(i.operands[0])
                return ("ret", None, st)
            if op is Op.JOIN:
                # charged by the enclosing _lower_split under the
                # side-exit mask
                return ("join", (block, pos), st)
            if op is Op.SPLIT:
                tc.charge(op.value, st.mask)
                self.pending = i
                pos += 1
                continue
            if op is Op.PRED:
                st, block = self._lower_pred_loop(block, i, st)
                pos = 0
                continue
            if op is Op.CBR:
                if self.pending is not None:
                    st, block, pos = self._lower_split(i, st)
                    continue
                loop = self.headers.get(id(block))
                if loop is not None and any(
                        not loop.contains(s) for s in block.successors()):
                    st, block = self._lower_uniform_loop(block, i, st,
                                                         loop)
                else:
                    st, block = self._lower_uniform_branch(block, i, st)
                pos = 0
                continue
            st = self._lower_straight(i, st)
            pos += 1

    # -- straight-line ops -------------------------------------------------
    def _lower_straight(self, i: Instr, st: _State) -> _State:
        op = i.op
        tc = self.tc
        tc.charge(op.value, st.mask)
        if op is Op.TMC_SAVE:
            self.tokens[id(i.result)] = st.mask
            return st
        if op is Op.TMC_RESTORE:
            tok = self.tokens.get(id(i.operands[0]))
            if tok is None:
                raise LowerError("tmc_restore of unsaved token")
            st = st.copy()
            st.mask = tok
            return st
        if op is Op.BARRIER:
            return st      # licensed only at n_warps == 1: trivially met
        if op is Op.SLOT_LOAD:
            s = i.operands[0]
            arr = st.slots.get(id(s))
            if arr is None:
                arr = jnp.zeros((self.R, self.W), dtype=_TY_DTYPE[s.ty])
            self.env[id(i.result)] = arr
            return st
        if op is Op.SLOT_STORE:
            s, v = i.operands
            nv = self.val(v)
            arr = st.slots.get(id(s))
            if arr is None:
                arr = jnp.zeros((self.R, self.W), dtype=nv.dtype)
            st = st.copy()
            st.slots[id(s)] = jnp.where(st.mask, nv, arr)
            return st
        if op is Op.LOAD:
            return self._lower_load(i, st)
        if op is Op.STORE:
            return self._lower_store(i, st)
        if op is Op.INTR:
            key = (i.operands[0], i.operands[1])
            a = self.intr.get(key)
            if a is None:
                raise LowerError(f"intrinsic {key} not provided")
            self.env[id(i.result)] = a
            return st
        if op is Op.VOTE:
            return self._lower_vote(i, st)
        if op is Op.SHFL:
            v = self.val(i.operands[0])
            src = self.val(i.operands[1]).astype(jnp.int32) % self.W
            self.env[id(i.result)] = jnp.take_along_axis(v, src, axis=1)
            return st
        if op is Op.CALL:
            return self._lower_call(i, st)
        if op in (Op.CMOV, Op.SELECT):
            c = self.val(i.operands[0]).astype(jnp.bool_)
            self.env[id(i.result)] = jnp.where(
                c, self.val(i.operands[1]), self.val(i.operands[2]))
            return st
        if op in _REFUSED_OPS:
            raise LowerError(f"op {op} refused on the jax rung")
        if op in BINOPS:
            self.env[id(i.result)] = _jx_binop(
                op, self.val(i.operands[0]), self.val(i.operands[1]))
            return st
        if op in UNOPS:
            self.env[id(i.result)] = _jx_unop(op,
                                              self.val(i.operands[0]))
            return st
        raise LowerError(f"op {op} unsupported on the jax rung")

    # -- memory ------------------------------------------------------------
    def _count_lines(self, clip, mask):
        return count_lines_traced(clip, mask, self.W)

    def _lower_load(self, i: Instr, st: _State) -> _State:
        nm = self.buf_name(i.operands[0])
        buf = st.bufs.get(nm)
        if buf is None:
            raise LowerError(f"no buffer {nm}")
        ix = self.val(i.operands[1]).astype(jnp.int32)
        n = buf.shape[-1]
        clip = jnp.clip(ix, 0, n - 1)
        tc = self.tc
        tc.add_lines(nm in self.tiles, self._count_lines(clip, st.mask))
        if nm in self.tiles:
            v = jnp.take_along_axis(buf, clip, axis=1)
        else:
            v = buf[clip]
        tc.minst = tc.minst + tc.live(st.mask)
        self.env[id(i.result)] = v
        return st

    def _lower_store(self, i: Instr, st: _State) -> _State:
        nm = self.buf_name(i.operands[0])
        buf = st.bufs.get(nm)
        if buf is None:
            raise LowerError(f"no buffer {nm}")
        ix = self.val(i.operands[1]).astype(jnp.int32)
        v = self.val(i.operands[2])
        m = st.mask
        n = buf.shape[-1]
        tc = self.tc
        oob = (ix < 0) | (ix >= n)
        bad = (m & oob).any()
        tc.err = tc.err | jnp.where(bad, jnp.int32(ERR_OOB_STORE),
                                    jnp.int32(0))
        clip = jnp.clip(ix, 0, n - 1)
        tile = nm in self.tiles
        tc.add_lines(tile, self._count_lines(clip, m))
        tc.minst = tc.minst + tc.live(m)
        wm = m & ~oob
        vv = v.astype(buf.dtype)
        st = st.copy()
        if tile:
            # XLA scatter leaves duplicate-index order unspecified, so
            # enforce numpy's last-active-lane-wins within each row
            eq = clip[:, :, None] == clip[:, None, :]
            dup = (wm[:, None, :] & eq & self._later).any(axis=2)
            wm = wm & ~dup
            safe = jnp.where(wm, clip, jnp.int32(n))
            st.bufs[nm] = buf.at[self._rowix, safe].set(vv, mode="drop")
        else:
            # global stores need NO dedup: the launch runs this rung
            # only under the store-privacy licence, and this per-site
            # check confirms THIS store's index chain is injective
            # across the whole launch (no within-row or cross-row
            # collisions exist to order)
            if self.facts is None:
                raise LowerError("store inside a callee")
            priv = self.facts["store_private"].get(self.iidx[id(i)])
            if not (priv == "2d" or (priv == "1d" and self.shape_1d)):
                raise LowerError("store not proven injective at this "
                                 "launch shape")
            safe = jnp.where(wm, clip, jnp.int32(n))
            st.bufs[nm] = buf.at[safe.reshape(-1)].set(
                vv.reshape(-1), mode="drop")
        return st

    # -- collectives -------------------------------------------------------
    def _lower_vote(self, i: Instr, st: _State) -> _State:
        mode = i.operands[0]
        v = self.val(i.operands[1]).astype(jnp.bool_)
        m = st.mask
        act = v & m
        R, W = self.R, self.W
        if mode == "any":
            r = jnp.broadcast_to(act.any(axis=1)[:, None], (R, W))
        elif mode == "all":
            # oracle: all(v | ~mask) over active lanes; True when empty
            r = jnp.broadcast_to((v | ~m).all(axis=1)[:, None], (R, W))
        elif mode == "ballot":
            if W > 32:
                raise LowerError("ballot with W > 32")
            bits = (act.astype(jnp.uint32)
                    << jnp.arange(W, dtype=jnp.uint32)[None, :]).sum(
                        axis=1, dtype=jnp.uint32)
            r = jnp.broadcast_to(
                jax.lax.bitcast_convert_type(bits, jnp.int32)[:, None],
                (R, W))
        else:
            raise LowerError(f"unknown vote mode {mode}")
        self.env[id(i.result)] = r
        return st

    def _lower_call(self, i: Instr, st: _State) -> _State:
        callee: Function = i.operands[0]
        cargs: dict = {}
        for p, a in zip(callee.params, i.operands[1:]):
            if p.ty is Ty.PTR:
                if not isinstance(a, (Param, GlobalVar)):
                    raise LowerError("pointer arg must be param/global")
                cargs[id(p)] = self.buf_name(a)
            else:
                cargs[id(p)] = self.val(a)
        sub = _RowLowering(callee, self.R, self.W, self.intr, cargs,
                           self.tc, self.tiles, self.shape_1d,
                           facts=None)
        sst = _State({}, st.bufs, st.mask)
        kind, _, out = sub.walk(callee.entry, 0, sst, None)
        if kind != "ret":
            raise LowerError(f"callee @{callee.name} did not return")
        st = st.copy()
        st.bufs = out.bufs
        if i.result is not None:
            rv = sub.ret_val
            if rv is None:
                rv = jnp.zeros((self.R, self.W), dtype=_TY_DTYPE.get(
                    callee.ret_ty, jnp.float32))
            # oracle short-circuits empty-mask warps to typed zeros
            live = st.mask.any(axis=1)
            self.env[id(i.result)] = jnp.where(
                live[:, None], rv, jnp.zeros((), rv.dtype))
        return st

    # -- split diamonds ----------------------------------------------------
    def _lower_split(self, cbr: Instr, st: _State):
        """Handle the CBR that consumes ``self.pending``.  Both sides
        trace sequentially under sub-masks (the oracle's own order);
        resumes after the else side's JOIN under the entry mask."""
        tc = self.tc
        split = self.pending
        self.pending = None
        tc.charge(cbr.op.value, st.mask)
        sp = self.val(split.operands[0]).astype(jnp.bool_)
        if split.attrs.get("negate", False):
            sp = ~sp
        m = st.mask
        then_bb, else_bb = cbr.operands[1], cbr.operands[2]
        tok = split.result
        # oracle: max_ipdom_depth updates only at TWO-SIDED pushes, at
        # len(stack) == the static split-nesting depth (every split
        # pushes exactly one entry)
        d = self.depth + 1
        two = ((m & sp).any(axis=1) & (m & ~sp).any(axis=1)).any()
        tc.maxd = jnp.maximum(tc.maxd, jnp.where(two, jnp.int32(d),
                                                 jnp.int32(0)))
        self.depth = d
        st1 = st.copy()
        st1.mask = m & sp
        kind, where1, st1 = self.walk(then_bb, 0, st1, None)
        self._expect_join(kind, where1, tok)
        tc.charge(Op.JOIN.value, st1.mask)
        st2 = st1.copy()
        st2.mask = m & ~sp
        kind, where2, st2 = self.walk(else_bb, 0, st2, None)
        self._expect_join(kind, where2, tok)
        tc.charge(Op.JOIN.value, st2.mask)
        self.depth = d - 1
        out = st2.copy()
        out.mask = m
        # resume past the else side's JOIN: the next instr is the BR to
        # the ipdom block, charged by the walk under the restored mask
        jb, jp = where2
        return out, jb, jp + 1

    def _expect_join(self, kind, where, tok) -> None:
        if kind != "join":
            raise LowerError("split side did not reach a join")
        jb, jp = where
        if jb.instrs[jp].operands[0] is not tok:
            raise LowerError("vx_join token mismatch in trace")

    # -- uniform branches --------------------------------------------------
    def _uniform_err(self, m, c) -> None:
        viol = ((m & c).any(axis=1) & (m & ~c).any(axis=1)).any()
        self.tc.err = self.tc.err | jnp.where(
            viol, jnp.int32(ERR_UNIFORM), jnp.int32(0))

    def _lower_uniform_branch(self, block, cbr: Instr, st: _State):
        tc = self.tc
        tc.charge(cbr.op.value, st.mask)
        merge = self.pdom.immediate(block)
        if merge is None:
            raise LowerError("uniform branch without a post-dominator")
        c = self.val(cbr.operands[0]).astype(jnp.bool_)
        m = st.mask
        # rows where active lanes disagree would raise
        # UniformityViolation in the oracle
        self._uniform_err(m, c)
        then_bb, else_bb = cbr.operands[1], cbr.operands[2]
        st1 = st.copy()
        st1.mask = m & c
        kind, _, st1 = self.walk(then_bb, 0, st1, merge)
        if kind != "stop":
            raise LowerError("then side escaped its merge block")
        st2 = st1.copy()
        # oracle sends empty-mask warps down the THEN side; both sides
        # count zero under an empty row, so routing them to the else
        # side here changes nothing
        st2.mask = m & ~c
        kind, _, st2 = self.walk(else_bb, 0, st2, merge)
        if kind != "stop":
            raise LowerError("else side escaped its merge block")
        out = st2.copy()
        out.mask = m
        return out, merge

    # -- loops -------------------------------------------------------------
    def _loop_carried(self, loop):
        """What a while_loop carry must thread: slots touched in the
        loop, buffers stored in the loop, header-defined regs (the only
        regs that may dominate the exit), tokens saved in the loop."""
        slots: dict = {}
        bufs: list = []
        tok_ids: list = []
        for b in self.fn.blocks:
            if not loop.contains(b):
                continue
            for i in b.instrs:
                if i.op in (Op.SLOT_STORE, Op.SLOT_LOAD):
                    slots[id(i.operands[0])] = i.operands[0]
                elif i.op is Op.STORE:
                    nm = self.buf_name(i.operands[0])
                    if nm not in bufs:
                        bufs.append(nm)
                elif i.op is Op.TMC_SAVE:
                    tok_ids.append(id(i.result))
                elif i.op is Op.CALL:
                    # callees are store-free under the licence; their
                    # slots/tokens are call-local
                    if _interp._contains_store(i.operands[0]):
                        raise LowerError("storing callee in loop")
        hdr_regs = [i.result for i in loop.header.instrs[:-1]
                    if i.result is not None]
        return slots, bufs, hdr_regs, tok_ids

    def _lower_pred_loop(self, block, pred: Instr, st: _State):
        loop = self.headers.get(id(block))
        if loop is None:
            raise LowerError("vx_pred outside a natural-loop header")
        tok = pred.operands[1]
        exit_mask = self.tokens.get(id(tok))
        if exit_mask is None:
            raise LowerError("vx_pred token not saved")
        inside, outside = pred.operands[2], pred.operands[3]
        neg = bool(pred.attrs.get("negate", False))
        final = self._lower_loop(block, pred, st, loop, inside,
                                 pred_mode=True, negate=neg)
        final.mask = exit_mask
        return final, outside

    def _lower_uniform_loop(self, block, cbr: Instr, st: _State, loop):
        then_bb, else_bb = cbr.operands[1], cbr.operands[2]
        if loop.contains(then_bb):
            inside, outside, neg = then_bb, else_bb, False
        else:
            inside, outside, neg = else_bb, then_bb, True
        final = self._lower_loop(block, cbr, st, loop, inside,
                                 pred_mode=False, negate=neg)
        # every row leaves a uniform loop with its entry mask intact
        final.mask = st.mask
        return final, outside

    def _lower_loop(self, header, term: Instr, st: _State, loop,
                    inside, pred_mode: bool, negate: bool) -> _State:
        """Shared per-row loop lowering.  Called AT the header
        terminator of the already-traced entry visit (visit #0: the
        header prefix was charged by the normal walk).  Charges the
        terminator, narrows each row's mask by its continue-condition,
        then runs [body walk + next counted header visit + narrow] under
        ``lax.while_loop`` while any row stays live.  Count-exact per
        row: the visit where a row exits was charged under its
        then-live mask, and an exited row's mask is empty ever after.
        """
        tc = self.tc

        def cond_val(s):
            c = self.val(term.operands[0]).astype(jnp.bool_)
            if negate:
                c = ~c
            if not pred_mode:
                self._uniform_err(s.mask, c)
            return c

        tc.charge(term.op.value, st.mask)
        c0 = cond_val(st)
        st0 = st.copy()
        st0.mask = st.mask & c0

        slots, buf_names, hdr_regs, tok_ids = self._loop_carried(loop)
        slot_ids = sorted(slots, key=lambda sid: slots[sid].name)
        snap_env = dict(self.env)
        snap_tokens = dict(self.tokens)
        zmask = jnp.zeros((self.R, self.W), dtype=jnp.bool_)

        def pack_state(s: _State) -> tuple:
            svals = []
            for sid in slot_ids:
                a = s.slots.get(sid)
                if a is None:
                    a = jnp.zeros((self.R, self.W),
                                  dtype=_TY_DTYPE[slots[sid].ty])
                svals.append(a)
            return (tuple(svals),
                    tuple(s.bufs[nm] for nm in buf_names),
                    tuple(self.env[id(r)] for r in hdr_regs),
                    tuple(self.tokens.get(t, zmask) for t in tok_ids),
                    s.mask, tc.pack())

        def unpack_state(carry) -> _State:
            svals, bvals, rvals, tvals, mask, tcp = carry
            s = st0.copy()
            for sid, a in zip(slot_ids, svals):
                s.slots[sid] = a
            for nm, a in zip(buf_names, bvals):
                s.bufs[nm] = a
            self.env = dict(snap_env)
            for r, a in zip(hdr_regs, rvals):
                self.env[id(r)] = a
            self.tokens = dict(snap_tokens)
            for t, a in zip(tok_ids, tvals):
                self.tokens[t] = a
            s.mask = mask
            tc.unpack(tcp)
            return s

        def cond_fn(carry):
            return carry[4].any() & (
                carry[5][_FUEL_IN_PACK] < jnp.int32(tc.fuel_limit))

        def body_fn(carry):
            s = unpack_state(carry)
            kind, _, s = self.walk(inside, 0, s, header)
            if kind != "stop":
                raise LowerError("loop body escaped its header")
            # the next counted header visit (the back-edge BR was
            # charged by the walk)
            for hi in header.instrs[:-1]:
                if hi.op in (Op.SPLIT, Op.CBR, Op.PRED, Op.BR, Op.RET,
                             Op.JOIN):
                    raise LowerError("control op in loop-header prefix")
                s = self._lower_straight(hi, s)
            tc.charge(term.op.value, s.mask)
            c = cond_val(s)
            s = s.copy()
            s.mask = s.mask & c
            return pack_state(s)

        out = jax.lax.while_loop(cond_fn, body_fn, pack_state(st0))
        final = unpack_state(out)
        return final


# --------------------------------------------------------------------------
# chunk compilation
# --------------------------------------------------------------------------

#: Two executable tiers per traced chunk program.  XLA's CPU backend
#: contracts mul+add chains inside fused loop bodies into FMAs at every
#: optimization level >= 1 — a few-ulp divergence from the oracle's
#: separately-rounded numpy arithmetic on float-accumulation kernels.
#: No HLO-level construct suppresses it: ``optimization_barrier`` is
#: expanded away before fusion, fast-math/excess-precision flags don't
#: reach the decision, and second-use tricks die to recomputation in
#: multi-output fusions.  So certification picks the tier per
#: (kernel, shape) pair: the "fast" tier (full pipeline) is certified
#: first, and only when its float bits diverge does the pair fall back
#: to the "exact" tier (backend level 0, every float op separately
#: rounded) and re-certify — FMA-free kernels keep the optimized
#: executable, accumulation kernels trade speed for bit-exactness.
#: The TPU compiler accepts both levels; what they do to its float
#: rounding is whatever certification on the chip finds.
_TIER_OPTIONS = {
    "fast": {"xla_backend_optimization_level": 3},
    "exact": {"xla_backend_optimization_level": 0},
}


class _Compiled:
    """One traced chunk program + everything the host loop needs.
    ``_trace`` fills in the chunk function and its abstract arguments;
    ``_prepare`` lowers it once; each executable tier is compiled from
    the lowering on first use (the fast tier eagerly, so compile errors
    surface before anything runs)."""

    __slots__ = ("sig", "chunk_fn", "jitted", "abstract", "lowered", "tiers",
                 "cnt_keys", "buf_names", "scalar_names", "scalar_dtypes",
                 "cw", "trace_s", "compile_s", "cert_s")

    def executable(self, tier: str):
        """The compiled program of ``tier``.  A backend compile error is
        an ``EngineFault`` at site ``jax.compile`` — the launch demotes
        with the compiler's text in its report — and nothing is cached,
        so the next launch compiles again."""
        exe = self.tiers.get(tier)
        if exe is None:
            t0 = perf_counter()
            try:
                exe = self.lowered.compile(
                    compiler_options=_TIER_OPTIONS[tier])
            except Exception as e:
                JAX_TELEMETRY["compile_errors"] += 1
                raise _faults.EngineFault(
                    f"jax {tier}-tier compile failed on "
                    f"{'/'.join(_device_key()[:2])}: "
                    f"{type(e).__name__}: {e}",
                    site="jax.compile", rung="jax") from e
            self.compile_s[tier] = perf_counter() - t0
            self.tiers[tier] = exe
        return exe


def _licence(fn: Function, params, n_wg: int, argmap: dict,
             globals_mem) -> None:
    """Static gates — raises LowerError on any licence miss."""
    if params.warp_size > 32:
        raise LowerError("warp size > 32")
    if params.strict_oob_loads:
        raise LowerError("strict OOB loads")
    if n_wg <= 1:
        raise LowerError("single-workgroup launch")
    if params.fuel > FUEL_MAX:
        raise LowerError("fuel budget beyond the int32 counters")
    plan = _interp._decode_plan(fn)
    if plan["ordering_sensitive"]:
        raise LowerError("ordering-sensitive kernel")
    if plan["callee_stores"]:
        raise LowerError("callee stores")
    n_warps = params.warps_per_wg
    cw = min(_CHUNK_WGS, n_wg)
    gprog = _interp._decode_batched(fn, params.warp_size, False,
                                    cw * n_warps, grid_mode=True,
                                    wg_rows=n_warps)
    if not gprog.order_free:
        raise LowerError("not order-free")
    shape_1d = params.grid_y == 1 and params.local_size_y == 1
    if not (gprog.private_stores if shape_1d
            else gprog.private_stores_2d):
        raise LowerError("stores not private at this launch shape")
    if not _interp._grid_batchable(fn, argmap, globals_mem):
        raise LowerError("not grid-batchable under these bindings")
    scan = _scan_fn(fn)
    if scan["recursive"]:
        raise LowerError("recursive call")
    if scan["refused"]:
        raise LowerError(f"refused ops {sorted(o.value for o in scan['refused'])}")
    if scan["global"]:
        raise LowerError("non-shared module global")
    if n_warps > 1 and (scan["barrier"] or scan["shared"]):
        raise LowerError("barrier/shared tile with multi-warp rows")


def _device_key() -> tuple:
    """What a verdict and its timings hold for: the platform and kind of
    the device the program runs on, and the JAX version that compiled
    it.  A verdict certified on one never promotes a launch on another."""
    d = jax.devices()[0]
    return (d.platform, d.device_kind, jax.__version__)


def _shape_sig(params, buffers: dict, scalar_args: dict,
               cw: int) -> str:
    """The launch SHAPE CLASS a certification verdict covers: every
    static input of the trace (grid, warp geometry, fuel, chunk width,
    buffer shapes/dtypes, scalar names) — buffer/scalar VALUES excluded
    — on one device (``_device_key``).
    """
    return repr((_device_key(),
                 params.grid, params.grid_y, params.local_size,
                 params.local_size_y, params.warp_size, params.fuel,
                 bool(params.strict_oob_loads), cw,
                 tuple(sorted((nm, tuple(b.shape), b.dtype.name)
                              for nm, b in buffers.items())),
                 tuple(sorted(scalar_args))))


def _collect_ops(fn: Function, acc: set, seen: set) -> None:
    if id(fn) in seen:
        return
    seen.add(id(fn))
    for i in fn.instructions():
        acc.add(i.op.value)
        if i.op is Op.CALL:
            _collect_ops(i.operands[0], acc, seen)


def _trace(fn: Function, params, buffers: dict, scalar_args: dict,
           cw: int) -> _Compiled:
    """The chunk function of one (kernel, launch shape) and its abstract
    arguments: ``chunk_fn(bufs, scalars, c0, acc) -> (bufs, c0 + cw,
    acc)`` runs workgroups ``c0 .. c0 + cw - 1``; ``acc`` is the packed
    ``_TraceCtx`` counters carried from chunk to chunk.  ``rec.jitted``
    donates the buffers, ``c0`` and ``acc``, so each chunk updates them
    in place and the host loop passes nothing but what the previous
    chunk returned.  Nothing is traced by JAX yet: lowering
    ``rec.jitted`` on ``rec.abstract`` does that."""
    W = params.warp_size
    n_warps = params.warps_per_wg
    R = cw * n_warps
    shape_1d = params.grid_y == 1 and params.local_size_y == 1
    facts = export_codegen_facts(fn)

    lanes = np.arange(W, dtype=np.int32)
    rows_w = (np.arange(R, dtype=np.int32) % n_warps)      # warp per row
    tid = rows_w[:, None] * W + lanes[None, :]
    wact = tid < params.wg_threads
    lx = (tid % params.local_size).astype(np.int32)
    ly = (tid // params.local_size).astype(np.int32)

    buf_names = tuple(sorted(buffers))
    scalar_names = tuple(sorted(scalar_args))
    scalar_dtypes = {}
    for p in fn.params:
        if p.ty is not Ty.PTR:
            if p.name not in scalar_args:
                raise LowerError(f"no scalar bound for {p.name}")
            scalar_dtypes[p.name] = _TY_NP[p.ty]
    tiles = {f"@{g.name}": (g.size, _TY_DTYPE[g.elem_ty])
             for g in fn.shared}
    ops: set = set()
    _collect_ops(fn, ops, set())
    cnt_keys = tuple(sorted(ops))
    fuel_limit = int(params.fuel)
    n_wg = params.grid * params.grid_y

    def chunk_fn(bufs, scalars, c0, acc):
        tc = _TraceCtx(cnt_keys, fuel_limit, acc)

        def full(v):
            return jnp.broadcast_to(jnp.int32(v), (R, W))

        ks = c0 + jnp.arange(cw, dtype=jnp.int32)     # workgroup ids
        valid = jnp.repeat(ks < n_wg, n_warps, total_repeat_length=R)
        ksc = jnp.where(ks < n_wg, ks, 0)
        gxr = jnp.repeat(ksc % params.grid, n_warps, total_repeat_length=R)
        gyr = jnp.repeat(ksc // params.grid, n_warps,
                         total_repeat_length=R)
        gx2 = jnp.broadcast_to(gxr[:, None], (R, W))
        gy2 = jnp.broadcast_to(gyr[:, None], (R, W))
        intr = {
            ("local_id", 0): jnp.asarray(lx),
            ("local_id", 1): jnp.asarray(ly),
            ("lane_id", 0): jnp.broadcast_to(jnp.asarray(lanes)[None, :],
                                             (R, W)),
            ("warp_id", 0): jnp.broadcast_to(
                jnp.asarray(rows_w)[:, None], (R, W)),
            ("group_id", 0): gx2,
            ("group_id", 1): gy2,
            ("core_id", 0): gx2 % jnp.int32(4),
            ("global_id", 0): gx2 * jnp.int32(params.local_size)
            + jnp.asarray(lx),
            ("global_id", 1): gy2 * jnp.int32(params.local_size_y)
            + jnp.asarray(ly),
            ("local_size", 0): full(params.local_size),
            ("local_size", 1): full(params.local_size_y),
            ("num_groups", 0): full(params.grid),
            ("num_groups", 1): full(params.grid_y),
            ("global_size", 0): full(params.grid * params.local_size),
            ("global_size", 1): full(params.grid_y
                                     * params.local_size_y),
            ("num_threads", 0): full(W),
            ("num_warps", 0): full(n_warps),
            ("grid_dim", 0): full(params.grid),
        }
        argmap = {}
        for p in fn.params:
            if p.ty is Ty.PTR:
                argmap[id(p)] = p.name
            else:
                k = scalar_names.index(p.name)
                argmap[id(p)] = jnp.broadcast_to(
                    scalars[k].astype(_TY_DTYPE[p.ty]), (R, W))
        bufd = dict(zip(buf_names, bufs))
        for nm, (size, dt) in tiles.items():
            bufd[nm] = jnp.zeros((R, size), dtype=dt)
        mask0 = jnp.asarray(wact) & valid[:, None]
        low = _RowLowering(fn, R, W, intr, argmap, tc,
                           tiles=set(tiles), shape_1d=shape_1d,
                           facts=facts)
        stt = _State({}, bufd, mask0)
        kind, _, out = low.walk(fn.entry, 0, stt, None)
        if kind != "ret":
            raise LowerError("kernel did not return")
        tc.err = tc.err | jnp.where(
            tc.fuel >= jnp.int32(fuel_limit), jnp.int32(ERR_FUEL),
            jnp.int32(0))
        return (tuple(out.bufs[nm] for nm in buf_names),
                c0 + jnp.int32(cw), tc.pack())

    i32 = jax.ShapeDtypeStruct((), np.int32)
    rec = _Compiled()
    rec.chunk_fn = chunk_fn
    rec.jitted = jax.jit(chunk_fn, donate_argnums=(0, 2, 3))
    rec.abstract = (
        tuple(jax.ShapeDtypeStruct(buffers[nm].shape,
                                   buffers[nm].dtype)
              for nm in buf_names),
        tuple(jax.ShapeDtypeStruct((), np.dtype(scalar_dtypes[nm]))
              for nm in scalar_names if nm in scalar_dtypes),
        i32,
        ((i32,) * len(cnt_keys),) + (i32,) * 6)
    rec.lowered = None
    rec.tiers = {}
    rec.cnt_keys = cnt_keys
    rec.buf_names = buf_names
    rec.scalar_names = tuple(nm for nm in scalar_names
                             if nm in scalar_dtypes)
    rec.scalar_dtypes = scalar_dtypes
    rec.cw = cw
    rec.trace_s = None
    rec.compile_s = {}
    rec.cert_s = None
    return rec


def _chunk_width(params) -> int:
    return min(int(_CHUNK_WGS), params.grid * params.grid_y)


def _prepare(fn: Function, params, buffers: dict, scalar_args: dict,
             argmap: dict, globals_mem) -> _Compiled:
    """The traced, lowered program of this (kernel, launch shape), with
    its fast tier compiled.  A licence miss or a trace failure is a
    ``LowerError`` and is cached for the kernel's IR version; a backend
    compile error is an ``EngineFault`` and is not cached."""
    if _faults.ACTIVE:
        _faults.maybe_fault("jax.trace")
    cw = _chunk_width(params)
    sig = _shape_sig(params, buffers, scalar_args, cw)
    cache = getattr(fn, "_jaxgen_cache", None)
    if cache is None or cache[0] != fn.ir_version:
        cache = (fn.ir_version, {})
        fn._jaxgen_cache = cache  # type: ignore[attr-defined]
    rec = cache[1].get(sig)
    if isinstance(rec, str):
        raise LowerError(rec)
    if rec is not None:
        JAX_TELEMETRY["trace_cache_hits"] += 1
    else:
        try:
            _licence(fn, params, params.grid * params.grid_y, argmap,
                     globals_mem)
            rec = _trace(fn, params, buffers, scalar_args, cw)
            # every LowerError surfaces here, at trace time, before
            # anything runs or any verdict is recorded
            t0 = perf_counter()
            rec.lowered = rec.jitted.lower(*rec.abstract)
            rec.trace_s = perf_counter() - t0
        except _faults.KernelFault:
            raise
        except _faults.InjectedFault:
            raise
        except Exception as e:
            reason = (str(e) if isinstance(e, LowerError)
                      else f"trace failed: {type(e).__name__}: {e}")
            cache[1][sig] = reason
            raise LowerError(reason) from e
        rec.sig = sig
        cache[1][sig] = rec
    rec.executable("fast")
    return rec


# --------------------------------------------------------------------------
# host loop
# --------------------------------------------------------------------------

def _run(rec: _Compiled, fn: Function, buffers: dict,
         scalar_args: dict, params, tier: str = "fast") -> tuple:
    """Run every chunk on the given executable tier; returns
    (host_bufs, jstats dict).  Never mutates ``buffers`` — results are
    staged device-side and converted at the end, so a faulted launch
    costs nothing to roll back."""
    n_wg = params.grid * params.grid_y
    dev_bufs = tuple(jnp.asarray(buffers[nm]) for nm in rec.buf_names)
    scal = tuple(np.asarray(scalar_args[nm],
                            dtype=rec.scalar_dtypes[nm])
                 for nm in rec.scalar_names)
    # under jax.disable_jit() run the traced function eagerly — the
    # metamorphic contract: op-by-op eager execution, the AOT-compiled
    # executable and the oracle all agree bit-for-bit
    run = (rec.chunk_fn if jax.config.jax_disable_jit
           else rec.executable(tier))
    c0, acc = np.int32(0), _zero_acc(len(rec.cnt_keys))
    for _ in range(0, n_wg, rec.cw):
        if _gov.ACTIVE:
            _gov.deadline_check()
        if _faults.ACTIVE:
            _faults.maybe_fault("jax.exec")
        dev_bufs, c0, acc = run(dev_bufs, scal, c0, acc)
    cnt, mem_, shm, minst, maxd, _fuel, err = jax.device_get(acc)
    err_v = int(err)
    if err_v:
        names = [nm for bit, nm in ((ERR_OOB_STORE, "oob-store"),
                                    (ERR_UNIFORM, "uniformity"),
                                    (ERR_FUEL, "fuel"),
                                    (ERR_OVERFLOW, "counter-overflow"))
                 if err_v & bit]
        raise _faults.EngineFault(
            f"jax rung semantic-error bits [{', '.join(names)}] in "
            f"@{fn.name} — demoting so the grid rung reproduces the "
            f"exact outcome", site="jax.exec", rung="jax")
    host_bufs = {nm: np.asarray(b)
                 for nm, b in zip(rec.buf_names, dev_bufs)}
    by_op = {k: int(v) for k, v in zip(rec.cnt_keys, cnt) if int(v)}
    jstats = {
        "instrs": sum(by_op.values()),
        "by_op": by_op,
        "mem_requests": int(mem_),
        "mem_insts": int(minst),
        "shared_requests": int(shm),
        "max_ipdom_depth": int(maxd),
    }
    return host_bufs, jstats


def _apply(host_bufs: dict, jstats: dict, buffers: dict,
           stats) -> None:
    for nm, arr in host_bufs.items():
        np.copyto(buffers[nm], arr)
    stats.instrs += jstats["instrs"]
    stats.by_op.update(jstats["by_op"])
    stats.mem_requests += jstats["mem_requests"]
    stats.mem_insts += jstats["mem_insts"]
    stats.shared_requests += jstats["shared_requests"]
    stats.max_ipdom_depth = max(stats.max_ipdom_depth,
                                jstats["max_ipdom_depth"])


def _ulps(a, b) -> int:
    """Distance between two float32 values in units in the last place."""
    i = np.array([a, b], np.float32).view(np.int32).astype(np.int64)
    i = np.where(i < 0, -(i & 0x7FFFFFFF), i)
    return int(abs(i[0] - i[1]))


def _disagreement(host_bufs: dict, jstats: dict, buffers: dict,
                  stats) -> str | None:
    """None when the jitted run agrees with the oracle bit for bit;
    otherwise the first difference — a buffer element, with its
    distance in ulps for float32, or a stat."""
    for nm in sorted(host_bufs):
        got, want = host_bufs[nm].reshape(-1), buffers[nm].reshape(-1)
        if got.tobytes() == want.tobytes():
            continue
        u = f"u{got.itemsize}"
        bad = np.flatnonzero(got.view(u) != want.view(u))
        i = int(bad[0])
        ulp = (f", {_ulps(got[i], want[i])} ulp"
               if got.dtype == np.float32 else "")
        return (f"{nm}[{i}]: jax {got[i]!r} vs oracle {want[i]!r}{ulp} "
                f"({bad.size} of {got.size} elements differ)")
    want_stats = {
        "instrs": stats.instrs,
        "by_op": {k: v for k, v in stats.by_op.items() if v},
        "mem_requests": stats.mem_requests,
        "mem_insts": stats.mem_insts,
        "shared_requests": stats.shared_requests,
        "max_ipdom_depth": stats.max_ipdom_depth,
    }
    for k, v in want_stats.items():
        if jstats[k] != v:
            return f"stat {k}: jax {jstats[k]} vs oracle {v}"
    if stats.atomic_serial or stats.prints:
        return "the oracle serialized atomics or printed"
    return None


# --------------------------------------------------------------------------
# certification store
# --------------------------------------------------------------------------

def _certs(fn: Function) -> dict:
    c = getattr(fn, "_jax_certs", None)
    if c is not None and c[0] == fn.ir_version:
        return c[1]
    d = None
    hooks = _interp.JAX_CERT_HOOKS
    if hooks is not None:
        try:
            d = hooks[0](fn)
        except Exception:
            d = None
    if not isinstance(d, dict):
        d = {}
    fn._jax_certs = (fn.ir_version, d)  # type: ignore[attr-defined]
    return d


def _verdict_of(entry) -> tuple:
    """Normalize a cert-store entry to ``(verdict, jax_ms, grid_ms)``.
    Entries are ``(verdict, jax_ms, grid_ms, detail)`` (docs/performance.md
    "Serve side"): the differential certification run measures the
    normal chain anyway, so its wall time rides along with the verdict,
    and the first certified primary fills in the warm jitted time —
    together they let the dispatch router send launches whose grid time
    beats the jitted-dispatch floor straight to the grid rung.
    ``detail`` says why a verdict is not a plain "pass": the first
    difference from the oracle, or an exception's text.  Plain-string
    entries (legacy in-memory) mean "no timings yet"."""
    if isinstance(entry, tuple):
        return entry[:3]
    return (entry, None, None)


def _detail_of(entry) -> str:
    return entry[3] if isinstance(entry, tuple) and len(entry) > 3 else ""


def _record(fn: Function, sig: str, verdict: str,
            jax_ms: float | None = None,
            grid_ms: float | None = None,
            detail: str | None = None) -> None:
    """Store a verdict; ``detail=None`` keeps the entry's detail (a
    timing update of a verdict already recorded)."""
    certs = _certs(fn)
    if detail is None:
        detail = _detail_of(certs.get(sig))
    certs[sig] = (verdict, jax_ms, grid_ms, detail)
    hooks = _interp.JAX_CERT_HOOKS
    if hooks is not None:
        try:
            hooks[1](fn, certs)
        except Exception:
            pass


# --------------------------------------------------------------------------
# public entry points
# --------------------------------------------------------------------------

def describe(fn: Function, params, buffers: dict,
             scalar_args: dict | None = None) -> dict:
    """What this rung holds for one (kernel, launch shape) on this
    device, without running anything: ``refused`` (the licence or trace
    refusal, else None), ``verdict`` ("pass", "pass-exact", "fail",
    "error" or None), its ``tier`` and ``detail``, seconds spent in
    ``trace_s``, ``compile_s`` (per tier) and ``cert_s``, and the
    router's ``jax_ms`` / ``grid_ms``."""
    sig = _shape_sig(params, buffers, scalar_args or {},
                     _chunk_width(params))
    cache = getattr(fn, "_jaxgen_cache", None)
    rec = cache[1].get(sig) if cache and cache[0] == fn.ir_version \
        else None
    entry = _certs(fn).get(sig)
    verdict, jax_ms, grid_ms = _verdict_of(entry)
    return {"refused": rec if isinstance(rec, str) else None,
            "verdict": verdict,
            "tier": {"pass": "fast", "pass-exact": "exact"}.get(verdict),
            "detail": _detail_of(entry),
            "trace_s": getattr(rec, "trace_s", None),
            "compile_s": dict(getattr(rec, "compile_s", None) or {}),
            "cert_s": getattr(rec, "cert_s", None),
            "jax_ms": jax_ms, "grid_ms": grid_ms}


def licence_check(fn: Function, params, buffers: dict,
                  scalar_args: dict | None = None,
                  globals_mem: dict | None = None) -> tuple:
    """(admitted, reason) — does this (kernel, launch) pass the static
    licence AND trace cleanly?  Used by the conformance suite's
    engagement assertions; performs no execution and records no
    verdicts."""
    scalar_args = scalar_args or {}
    argmap: dict = {}
    for p in fn.params:
        if p.ty is Ty.PTR:
            if p.name not in buffers:
                return (False, f"no buffer bound for {p.name}")
            argmap[id(p)] = buffers[p.name]
        else:
            if p.name not in scalar_args:
                return (False, f"no scalar bound for {p.name}")
            argmap[id(p)] = np.full(params.warp_size,
                                    scalar_args[p.name],
                                    dtype=_TY_NP[p.ty])
    try:
        _prepare(fn, params, buffers, scalar_args, argmap,
                 globals_mem or {})
    except _faults.InjectedFault:
        raise
    except (LowerError, _faults.EngineFault) as e:
        return (False, str(e))
    return (True, "")


def _certify(rec: _Compiled, fn: Function, buffers: dict,
             scalar_args: dict, params, stats, mode, run_normal) -> bool:
    """The differential certification run of one (kernel, launch shape):
    the launch's results come from the normal chain (``run_normal``),
    and the jitted program's run beside it decides the verdict —
    "pass" (fast tier bit-exact), "pass-exact" (only the exact tier
    is), "fail" (the exact tier differs too; the detail names the first
    difference) or "error" (the jitted program raised; the detail holds
    the text).  Raises what the normal chain raises, and an infra fault
    of the jitted run before the normal chain has run (in "fallback"
    mode it returns False instead: nothing ran)."""
    JAX_TELEMETRY["cert_runs"] += 1
    t_cert = perf_counter()
    # run_normal mutates buffers in place below; the exact tier (tried
    # only when the fast tier's float bits diverge) replays from the
    # original inputs, so snapshot them first
    snap = {nm: buffers[nm].copy() for nm in rec.buf_names}
    notes = []

    def attempt(tier: str, inputs: dict):
        try:
            return _run(rec, fn, inputs, scalar_args, params, tier=tier)
        except (_faults.KernelFault, _faults.InjectedFault):
            raise
        except Exception as e:
            JAX_TELEMETRY["cert_errors"] += 1
            notes.append(f"{tier} tier raised {type(e).__name__}: {e}")
            return None

    try:
        # reads buffers before run_normal can mutate them; never writes
        fast = attempt("fast", buffers)
    except _faults.InjectedFault:
        # an INFRA fault interrupted the certification — record no
        # verdict (the pair stays unknown and re-certifies later)
        JAX_TELEMETRY["demotions"] += 1
        if mode == "fallback":
            return False
        raise
    try:
        t0 = perf_counter()
        run_normal(stats)
        grid_ms = (perf_counter() - t0) * 1e3
    except Exception as e:
        # outcome parity: the caller sees exactly the normal chain's
        # exception; the pair is pinned to it from now on
        _record(fn, rec.sig, "fail",
                detail=f"the normal chain raised {type(e).__name__}: {e}")
        raise
    miss = fast and _disagreement(*fast, buffers, stats)
    if fast and miss is None:
        # grid_ms rides along with the verdict; jax_ms stays None until
        # the first certified primary measures the WARM dispatch (the
        # cert run's timing is polluted by jit compilation)
        _record(fn, rec.sig, "pass", grid_ms=grid_ms, detail="")
        JAX_TELEMETRY["certified"] += 1
        rec.cert_s = perf_counter() - t_cert
        return True
    if miss:
        notes.append(f"fast tier: {miss}")
    # ---- exact-tier retry -------------------------------------------
    # the optimized executable diverged (typically FMA-contracted float
    # accumulation) or raised; replay the snapshot on the separately-
    # rounded tier against the same oracle results
    try:
        exact = attempt("exact", snap)
    except _faults.InjectedFault:
        # infra fault mid-retry: the launch's results already came from
        # the normal chain; leave the pair unknown so a later launch
        # re-certifies
        JAX_TELEMETRY["demotions"] += 1
        return True
    miss = exact and _disagreement(*exact, buffers, stats)
    if exact and miss is None:
        verdict = "pass-exact"
        JAX_TELEMETRY["certified"] += 1
    elif exact:
        verdict = "fail"
        notes.append(f"exact tier: {miss}")
    else:
        verdict = "error"
    _record(fn, rec.sig, verdict,
            grid_ms=grid_ms if verdict == "pass-exact" else None,
            detail="; ".join(notes))
    rec.cert_s = perf_counter() - t_cert
    return True


def orchestrate(fn: Function, buffers: dict, params, scalar_args: dict,
                mem, argmap: dict, stats, mode, run_normal,
                route: bool = False) -> bool:
    """The jax rung's launch entry, called from ``interp._launch_impl``
    with the "jax" rung pushed.  Returns True when THIS call produced
    the launch's results (either the jitted program ran as the
    certified primary, or a certification run drove ``run_normal``);
    False means nothing happened and the caller falls through to the
    normal executor selection.

    ``mode``: True (chain rung — failures raise EngineFault so the
    runtime demotes + rolls back) or "fallback" (standalone — failures
    silently fall through, buffers untouched either way).

    ``route``: enable the small-launch dispatch router (the Runtime
    chain's ``jax="route"`` mode) — pairs whose measured grid time
    beats the jitted dispatch floor are declined so they land on the
    grid rung.  Direct ``jax=True`` calls (conformance sweeps, the
    jax-vs-grid benchmarks) keep unconditional engagement.
    """
    try:
        rec = _prepare(fn, params, buffers, scalar_args, argmap,
                       mem.globals_mem)
    except LowerError:
        JAX_TELEMETRY["refusals"] += 1
        return False
    except _faults.KernelFault:
        raise
    except _faults.EngineFault:
        JAX_TELEMETRY["demotions"] += 1
        if mode == "fallback":
            return False
        raise

    if _faults.ACTIVE:
        try:
            _faults.maybe_fault("jax.cache.load")
        except _faults.InjectedFault:
            JAX_TELEMETRY["demotions"] += 1
            if mode == "fallback":
                return False
            raise
    verdict, v_jax_ms, v_grid_ms = _verdict_of(_certs(fn).get(rec.sig))

    if verdict in ("fail", "error"):
        return False

    # ---- small-launch dispatch router --------------------------------
    # A certified pair whose measured grid time beats the measured
    # jitted time (dominated by the per-dispatch jit-call floor for
    # small launches) is SERVED BY THE GRID RUNG: falling through here
    # lands exactly there, with the verdict untouched — a bigger shape
    # class of the same kernel still takes the jitted primary.
    if (route and verdict is not None and v_jax_ms is not None
            and v_grid_ms is not None
            and v_grid_ms < v_jax_ms * _ROUTE_MARGIN):
        JAX_TELEMETRY["routed_small"] += 1
        hook = getattr(_interp, "ROUTED_SMALL_HOOK", None)
        if hook is not None:
            hook()
        return False

    if verdict is None:
        return _certify(rec, fn, buffers, scalar_args, params, stats,
                        mode, run_normal)

    # ---- certified primary ------------------------------------------
    tier = "exact" if verdict == "pass-exact" else "fast"
    t0 = perf_counter()
    try:
        host_bufs, jstats = _run(rec, fn, buffers, scalar_args, params,
                                 tier=tier)
    except _faults.KernelFault:
        raise
    except _faults.EngineFault:
        JAX_TELEMETRY["demotions"] += 1
        if mode == "fallback":
            return False
        raise
    except Exception as e:
        JAX_TELEMETRY["demotions"] += 1
        if mode == "fallback":
            return False
        raise _faults.EngineFault(
            f"jax executor failure: {type(e).__name__}: {e}",
            site="jax.exec", rung="jax") from e
    _apply(host_bufs, jstats, buffers, stats)
    JAX_TELEMETRY["engaged"] += 1
    if v_jax_ms is None:
        # first warm primary at this shape class: measure the jitted
        # wall (dispatch floor included) so the router has both sides
        _record(fn, rec.sig, verdict,
                jax_ms=(perf_counter() - t0) * 1e3,
                grid_ms=v_grid_ms)
    return True
