"""JAX codegen executor — the fifth (top) rung of the launch chain.

The decoder already proves, per kernel, everything a real code generator
needs: order-freedom (no cross-workgroup read/write hazard), store
privacy (every store index injective across the launch), structured
control flow (post-``structurize`` every loop is a ``vx_pred``/uniform
header loop and every divergent branch a ``vx_split``/``vx_join``
diamond).  This module consumes those licences and emits ONE traced,
``jax.jit``-compiled program per launch shape instead of walking one
Python handler per decoded node.  The program loops over chunks of
workgroups on the device (``lax.while_loop``); each iteration runs one
chunk over ``(rows, W)`` activation arrays — rows are warps,
``n_warps`` consecutive rows per workgroup, exactly the grid executor's
row layout:

  * masks become ``jnp.where`` / masked scatters (``.at[...].set(...,
    mode="drop")``);
  * ``vx_split`` diamonds trace both sides sequentially under sub-masks
    (the oracle's own execution order for a warp that takes both);
  * ``vx_pred`` and uniform header loops become ``lax.while_loop`` with
    a carry of (written slots, written buffers, header-defined regs,
    live mask, stat counters, count of live rows).  A ``vx_pred`` loop
    compacts its rows, the device counterpart of the grid executor's
    ``interp._compact_grid`` under the same licence (private stores, no
    buffer both loaded and stored): it runs in stages on fewer and
    fewer rows (``_ladder``), gathering the live rows, whole warps, into
    each next stage and scattering them back after the last, so the
    body is not paid for on rows that have left the loop;
  * lockstep barriers are no-ops (the rung only licenses barriers at
    ``n_warps == 1``, where a row IS the whole workgroup);
  * loads/stores of private tiles lower to per-row gathers/scatters;
    global loads/stores whose index the middle end classed warp-affine
    (``passes.analysis.window_classes``) are served as a slice of the
    chunk, a window a row or an element a row broadcast, wherever a
    device check confirms the form on every lane, and as element
    gathers/scatters otherwise; store injectivity comes from
    ``passes.analysis.export_codegen_facts`` (the same
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
field-for-field, and records "pass"/"fail" — in memory and in a
``.vjc`` file next to the ``.vck``/``.vdp`` caches (``core/diskcache``).
Only a recorded "pass" lets later launches run JAX as the primary; any
recorded "fail" pins the pair to the normal chain forever (until the
kernel IR changes).  Evidence promotes the fast path, not static
analysis alone.

Entry: ``serve`` runs one launch on this rung, or declines it and says
why, for ``runtime.interp_launch`` to run the grid rung instead.

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

from contextlib import contextmanager
from time import perf_counter

import numpy as np

import jax
import jax.numpy as jnp

from ..vir import (AddrSpace, BINOPS, Const, Function, GlobalVar, Instr,
                   Op, Param, Reg, Slot, Ty, UNOPS, Value)
from .. import graph
from .. import diskcache as _diskcache
from .. import faults as _faults
from .. import governor as _gov
from .. import interp as _interp
from ..interp_mem import CACHE_LINE_ELEMS
from ..passes.analysis import export_codegen_facts, launch_uniform
from ..spans import span

_TY_DTYPE = {Ty.I32: jnp.int32, Ty.F32: jnp.float32, Ty.BOOL: jnp.bool_}
_TY_NP = {Ty.I32: np.int32, Ty.F32: np.float32, Ty.BOOL: np.bool_}

#: workgroups per chunk, one iteration of the program's device-side
#: loop (module attribute so the metamorphic suite can vary it; the
#: compiled-record key includes the value)
_CHUNK_WGS = 256

#: row compaction of ``vx_pred`` loops: each stage of a compacted loop
#: runs on ``1 / _COMPACT_STEP`` of the rows of the stage before it
#: (``_ladder``)
_COMPACT_STEP = 4

#: the layout of the compiled program, part of every shape signature:
#: the chunk loop runs inside one executable, and warp-affine global
#: accesses are served as row windows under a device check.  A verdict
#: certified for another layout (one executable call per chunk, or
#: element gathers only) never promotes this one
_PROGRAM = "device-chunk-loop, row windows"

#: the width of the rows a global buffer is viewed as when each row of
#: a chunk reads its own window: one (8, 128) tile of the chip is 1024
#: consecutive elements, so the view is a bitcast and the row gather
#: moves whole tile rows
_VIEW = 128

#: the name scope of every element gather and scatter of a global
#: buffer, in the lowered program's op metadata
_GATHER_SCOPE = "volt_element_gather"

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
    "upload_bytes": 0,   # bound buffers copied to the device and back
    "download_bytes": 0, # by the launches counted in "engaged"
    "dispatches": 0,     # executable calls made by those launches: one
                         # each, one per chunk while a deadline is armed
    # device-side loops of those launches, from the counter pack:
    "loop_trips": 0,     # loop iterations executed (every stage, chunk)
    "loop_rows_paid": 0, # rows the loop body was computed for
    "loop_rows_live": 0, # of those, rows with a live lane
    "loop_compactions": 0,  # live rows gathered into a narrower stage
    # global-buffer accesses executed, per chunk and loop trip:
    "mem_window": 0,     # served by a window, broadcast or update slice
    "mem_gather": 0,     # served by an element gather or scatter
}

#: the loop counters of the counter pack, in pack order (the pack holds
#: them only for kernels with a loop)
LOOP_KEYS = ("loop_trips", "loop_rows_paid", "loop_rows_live",
             "loop_compactions")

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
           "global": False, "recursive": False, "loops": False}

    def visit(f: Function, stack: tuple) -> None:
        if f in stack:
            out["recursive"] = True
            return
        if graph.natural_loops(f):
            out["loops"] = True
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
    (``maxd``), fuel spent, error bits, global-buffer accesses served
    by a window form (``mwin``) and by an element gather or scatter
    (``mgat``), and the loop counters (``lp``, ``LOOP_KEYS`` order;
    empty for a kernel without loops)."""

    __slots__ = ("cnt_keys", "cnt", "mem", "shm", "minst", "maxd",
                 "fuel", "err", "mwin", "mgat", "lp", "fuel_limit",
                 "_live")

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

    def knows_live(self, mask, n) -> None:
        """``n`` is ``live(mask)``, already counted (a loop carries it)."""
        self._live[id(mask)] = (mask, n)

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
                self.shm, self.minst, self.maxd, self.fuel, self.err,
                self.mwin, self.mgat, tuple(self.lp))

    def unpack(self, t: tuple) -> None:
        cnt_t, self.mem, self.shm, self.minst, self.maxd, self.fuel, \
            self.err, self.mwin, self.mgat, lp = t
        self.cnt = dict(zip(self.cnt_keys, cnt_t))
        self.lp = list(lp)
        self._live = {}     # masks from another trace scope are stale


_FUEL_IN_PACK = 5           # index of ``fuel`` in _TraceCtx.pack()
_N_SCALARS = 8              # scalars between the op counts and ``lp``
_TRIPS, _PAID, _LIVE, _COMPACTIONS = range(len(LOOP_KEYS))


def _zero_acc(n_ops: int, n_loop: int) -> tuple:
    """The packed counters a launch starts from."""
    z = np.int32(0)
    return ((z,) * n_ops,) + (z,) * _N_SCALARS + ((z,) * n_loop,)


def _ladder(rows: int) -> tuple:
    """The row counts of a compacted loop's stages: ``rows``, then each
    ``1 / _COMPACT_STEP`` of the last while that is a row or more."""
    out = [rows]
    while out[-1] // _COMPACT_STEP >= 1:
        out.append(out[-1] // _COMPACT_STEP)
    return tuple(out)


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
        self.loop_depth = 0             # loops whose body is being traced
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

    def _window_class(self, i: Instr, nm: str) -> str | None:
        """The middle end's window candidate class of global access
        ``i`` ("window", "row_uniform"), or None: tiles, callees and
        accesses whose index did not classify."""
        if self.facts is None or nm in self.tiles:
            return None
        return self.facts["window"].get(self.iidx[id(i)])

    def _run_ok(self, ix, n: int):
        """``ix`` is one run ``ix[0, 0] + W * row + lane`` in bounds."""
        R, W = self.R, self.W
        x0 = ix[0, 0]
        flat = jnp.arange(R * W, dtype=jnp.int32).reshape(R, W)
        return ((x0 >= 0) & (x0 <= n - R * W) & (ix == x0 + flat).all())

    def _served(self, forms: list, fallback):
        """The value of the first of ``forms`` (``(check, thunk)``) whose
        check holds on the device, else of ``fallback``, the element
        gather or scatter; one ``lax.switch``.  Counts the access in
        ``mwin`` or ``mgat``."""
        tc = self.tc
        if not forms:
            tc.mgat = tc.mgat + 1
            return fallback()
        k = jnp.int32(len(forms))
        for j in reversed(range(len(forms))):
            k = jnp.where(forms[j][0], jnp.int32(j), k)
        hit = (k < len(forms)).astype(jnp.int32)
        tc.mwin = tc.mwin + hit
        tc.mgat = tc.mgat + 1 - hit
        return jax.lax.switch(k, [f for _, f in forms] + [fallback])

    def _row_windows(self, buf, base):
        """Row ``r`` of the result is ``buf[base[r]:base[r] + W]``, each
        ``base[r]`` a multiple of W: a gather of the ``_VIEW``-wide rows
        that hold the windows, then each row's window picked out of its
        ``_VIEW // W`` places."""
        W = self.W
        wide = buf.reshape(-1, _VIEW).at[base // _VIEW].get(
            mode="promise_in_bounds")
        at = (base % _VIEW) // W
        out = wide[:, :W]
        for j in range(1, _VIEW // W):
            out = jnp.where((at == j)[:, None], wide[:, j * W:(j + 1) * W],
                            out)
        return out

    def _global_load(self, i: Instr, nm: str, buf, ix, clip):
        """``buf[clip]``, served as windows where the index is a
        candidate and the device check confirms its form on every lane,
        active or not, so every form reads exactly what the gather would:
        one slice of the whole chunk (a run), one window of W a row
        (each starting at a multiple of W), or one element a row,
        broadcast along its lanes."""
        R, W = self.R, self.W
        n = buf.shape[0]
        cls = self._window_class(i, nm)
        forms = []
        base = ix[:, 0] if cls else None
        if cls == "window":
            if R * W <= n:
                forms.append((self._run_ok(ix, n), lambda: jax.lax.
                              dynamic_slice(buf, (ix[0, 0],), (R * W,))
                              .reshape(R, W)))
            if n % _VIEW == 0 and _VIEW % W == 0:
                ok = ((base >= 0) & (base <= n - W) & (base % W == 0)
                      ).all() & (ix == base[:, None] + jnp.arange(
                          W, dtype=jnp.int32)).all()
                forms.append((ok, lambda: self._row_windows(buf, base)))
        elif cls == "row_uniform":
            ok = ((base >= 0) & (base < n)).all() \
                & (ix == base[:, None]).all()
            forms.append((ok, lambda: jnp.broadcast_to(
                buf.at[base].get(mode="promise_in_bounds")[:, None],
                (R, W))))

        def gather():
            with jax.named_scope(_GATHER_SCOPE):
                return buf[clip]

        return self._served(forms, gather)

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
            v = self._global_load(i, nm, buf, ix, clip)
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
            forms = []
            if self._window_class(i, nm) == "window" and \
                    self.R * self.W <= n:
                # the whole chunk is one run: its lanes not written keep
                # the buffer's values, as the scatter leaves them
                def put():
                    x0 = ix[0, 0]
                    old = jax.lax.dynamic_slice(
                        buf, (x0,), (self.R * self.W,)).reshape(ix.shape)
                    return jax.lax.dynamic_update_slice(
                        buf, jnp.where(wm, vv, old).reshape(-1), (x0,))
                forms.append((self._run_ok(ix, n), put))

            def scatter():
                safe = jnp.where(wm, clip, jnp.int32(n))
                with jax.named_scope(_GATHER_SCOPE):
                    return buf.at[safe.reshape(-1)].set(
                        vv.reshape(-1), mode="drop")

            st.bufs[nm] = self._served(forms, scatter)
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

    def _compaction_plan(self, loop, term: Instr) -> dict | None:
        """What a compacted ``vx_pred`` loop gathers beyond its carry, or
        None where the loop keeps one stage: the registers, intrinsics,
        scalar arguments and tokens its body reads from before the loop.
        Compacted loops are outermost loops of the kernel's own body with
        no call, no tile access and no buffer both loaded and stored in
        the loop, whose exit test ``term`` is not launch-uniform
        (``analysis.launch_uniform``): such a loop makes the same trips
        in every row that enters it (unless a branch on other values
        skips one of its slot stores), so compaction would cost it
        without ever firing.  Only the choice of lowering rests on this;
        both lowerings are exact.  The
        rung's licence (private stores, no buffer both read and written)
        already holds for the launch; the buffer rule restates it for the
        loop, so an exited row's header registers are the same whichever
        stage last computed them."""
        if (self.facts is None or self.loop_depth
                or len(_ladder(self.R)) < 2
                or launch_uniform(self.fn, term.operands[0])):
            return None
        defined, regs, intr, params, toks = set(), {}, set(), set(), set()
        loaded, stored = set(), set()
        for b in self.fn.blocks:
            if not loop.contains(b):
                continue
            for i in b.instrs:
                if i.result is not None:
                    defined.add(id(i.result))
                if i.op is Op.CALL:
                    return None
                if i.op in (Op.LOAD, Op.STORE):
                    nm = self.buf_name(i.operands[0])
                    if nm in self.tiles:
                        return None
                    (loaded if i.op is Op.LOAD else stored).add(nm)
                if i.op is Op.INTR:
                    intr.add((i.operands[0], i.operands[1]))
                if i.op is Op.TMC_RESTORE:
                    toks.add(id(i.operands[0]))
                for o in i.operands:
                    if isinstance(o, Reg):
                        regs[id(o)] = o
                    elif isinstance(o, Param) and o.ty is not Ty.PTR:
                        params.add(id(o))
        if loaded & stored:
            return None
        return {"regs": [r for r in regs if r not in defined
                         and r in self.env],
                "intr": [k for k in sorted(intr) if k in self.intr],
                "params": [p for p in sorted(params)
                           if not isinstance(self.argmap.get(p, ""), str)],
                "tokens": [t for t in toks if t not in defined
                           and t in self.tokens]}

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

        The carry holds the count of live rows (rows with a live lane),
        and the loop's test compares it with a threshold.  A
        ``vx_pred`` loop with a ``_compaction_plan`` runs in stages on
        ``_ladder`` row counts: stage ``k`` loops while more than the
        next stage's rows are live, then gathers the live rows, in
        order, into the next stage's rows (padding rows have no live
        lane), and the stage's result is scattered back to the rows'
        places.  Rows stay whole, and each row's trips stay where they
        were, so counts, buffers and errors are those of one stage.
        """
        tc = self.tc

        def cond_val(s):
            c = self.val(term.operands[0]).astype(jnp.bool_)
            if negate:
                c = ~c
            if not pred_mode:
                self._uniform_err(s.mask, c)
            return c

        def n_live(mask):
            return mask.any(axis=1).sum(dtype=jnp.int32)

        tc.charge(term.op.value, st.mask)
        c0 = cond_val(st)
        st0 = st.copy()
        st0.mask = st.mask & c0

        slots, buf_names, hdr_regs, tok_ids = self._loop_carried(loop)
        slot_ids = sorted(slots, key=lambda sid: slots[sid].name)
        plan = self._compaction_plan(loop, term) if pred_mode else None
        ladder = _ladder(self.R) if plan is not None else (self.R,)

        def pack_state(s: _State) -> tuple:
            zmask = jnp.zeros((self.R, self.W), dtype=jnp.bool_)
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

        def unpack_state(carry, base: _State, env: dict,
                         tokens: dict) -> _State:
            svals, bvals, rvals, tvals, mask, tcp = carry[:6]
            s = base.copy()
            for sid, a in zip(slot_ids, svals):
                s.slots[sid] = a
            for nm, a in zip(buf_names, bvals):
                s.bufs[nm] = a
            self.env = dict(env)
            for r, a in zip(hdr_regs, rvals):
                self.env[id(r)] = a
            self.tokens = dict(tokens)
            for t, a in zip(tok_ids, tvals):
                self.tokens[t] = a
            s.mask = mask
            tc.unpack(tcp)
            return s

        def stage(k: int, carry: tuple, base: _State, env: dict,
                  tokens: dict) -> tuple:
            """Stage ``k`` of the ladder, and the stages after it."""
            rows = ladder[k]
            floor = ladder[k + 1] if k + 1 < len(ladder) else 0

            def cond_fn(c):
                return (c[6] > floor) & (
                    c[5][_FUEL_IN_PACK] < jnp.int32(tc.fuel_limit))

            def body_fn(c):
                s = unpack_state(c, base, env, tokens)
                tc.knows_live(s.mask, c[6])
                tc.lp[_TRIPS] = tc.lp[_TRIPS] + 1
                tc.lp[_LIVE] = tc.lp[_LIVE] + c[6]
                self.loop_depth += 1
                kind, _, s = self.walk(inside, 0, s, header)
                self.loop_depth -= 1
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
                return pack_state(s) + (n_live(s.mask),)

            trips = carry[5][-1][_TRIPS]
            out = jax.lax.while_loop(cond_fn, body_fn, carry)
            tc.unpack(out[5])
            tc.lp[_PAID] = tc.lp[_PAID] + jnp.int32(rows) * (
                tc.lp[_TRIPS] - trips)
            out = out[:5] + (tc.pack(), out[6])
            if not floor:
                return out

            def compact(c):
                svals, bvals, rvals, tvals, mask, tcp, n = c
                idx = jnp.nonzero(mask.any(axis=1), size=floor,
                                  fill_value=rows)[0]
                carried = (*svals, *rvals, *tvals, mask)
                picked = iter(_take_rows(
                    carried + tuple(env[r] for r in plan["regs"])
                    + tuple(tokens[t] for t in plan["tokens"])
                    + tuple(self.intr[key] for key in plan["intr"])
                    + tuple(self.argmap[p] for p in plan["params"]), idx))

                def nxt(keys):
                    return {key: next(picked) for key in keys}

                tc.unpack(tcp)
                tc.lp[_COMPACTIONS] = tc.lp[_COMPACTIONS] + 1
                sub = (tuple(next(picked) for _ in svals), bvals,
                       tuple(next(picked) for _ in rvals),
                       tuple(next(picked) for _ in tvals),
                       next(picked), tc.pack(), n)
                sub_env, sub_tokens = nxt(plan["regs"]), nxt(plan["tokens"])
                with self._narrowed(floor, nxt(plan["intr"]),
                                    nxt(plan["params"])):
                    got = stage(k + 1, sub, _State({}, base.bufs, None),
                                sub_env, sub_tokens)
                back = iter(_put_rows(carried,
                                      (*got[0], *got[2], *got[3], got[4]),
                                      idx))
                return (tuple(next(back) for _ in svals), got[1],
                        tuple(next(back) for _ in rvals),
                        tuple(next(back) for _ in tvals),
                        next(back), got[5], got[6])

            return jax.lax.cond(out[6] > 0, compact, lambda c: c, out)

        snap_env, snap_tokens = dict(self.env), dict(self.tokens)
        out = stage(0, pack_state(st0) + (n_live(st0.mask),), st0,
                    snap_env, snap_tokens)
        return unpack_state(out, st0, snap_env, snap_tokens)

    @contextmanager
    def _narrowed(self, rows: int, intr: dict, params: dict):
        """Trace on ``rows`` rows: the row count, and the intrinsics and
        scalar arguments a compacted loop reads, already gathered."""
        saved = self.R, self.intr, self.argmap
        self.R = rows
        self.intr = intr
        self.argmap = {**self.argmap, **params}
        try:
            yield
        finally:
            self.R, self.intr, self.argmap = saved


def _take_rows(arrs: tuple, idx) -> list:
    """The rows ``idx`` of each (R, W) array, gathered at once; an index
    past the last row reads zeros (False)."""
    got = jnp.take(_stack_rows(arrs), idx, axis=1, mode="fill",
                   fill_value=0)
    return _unstack_rows(got, arrs)


def _put_rows(arrs: tuple, subs: tuple, idx) -> list:
    """``arrs`` with rows ``idx`` replaced by the rows of ``subs``,
    scattered at once; an index past the last row is dropped."""
    got = _stack_rows(arrs).at[:, idx].set(_stack_rows(subs), mode="drop")
    return _unstack_rows(got, arrs)


def _stack_rows(arrs: tuple):
    """(R, W) arrays of any of the rung's dtypes as one (n, R, W) int32."""
    return jnp.stack([jax.lax.bitcast_convert_type(a, jnp.int32)
                      if a.dtype == jnp.float32 else a.astype(jnp.int32)
                      for a in arrs])


def _unstack_rows(stacked, like: tuple) -> list:
    return [jax.lax.bitcast_convert_type(stacked[j], jnp.float32)
            if a.dtype == jnp.float32 else stacked[j].astype(a.dtype)
            for j, a in enumerate(like)]


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
    """One traced program + everything the host side of a launch needs.
    ``_trace`` fills in the looped function and its abstract arguments;
    ``_prepare`` lowers it once; each executable tier is compiled from
    the lowering on first use (the fast tier eagerly, so compile errors
    surface before anything runs)."""

    __slots__ = ("sig", "program", "jitted", "abstract", "lowered", "tiers",
                 "cnt_keys", "loop_keys", "buf_names", "scalar_names", "scalar_dtypes",
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


def _licence(fn: Function, params, buffers: dict, scalar_args: dict,
             globals_mem) -> None:
    """Static gates — raises LowerError on any licence miss."""
    try:
        argmap = _interp.kernel_args(fn, buffers, scalar_args,
                                     params.warp_size)
    except _interp.ExecError as e:
        raise LowerError(str(e)) from e
    n_wg = params.grid * params.grid_y
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
    — on one device (``_device_key``), for one program layout
    (``_PROGRAM``).
    """
    return repr((_device_key(), _PROGRAM,
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
    """The program of one (kernel, launch shape) and its abstract
    arguments: ``program(bufs, scalars, c_lo, c_hi, acc) -> (bufs,
    acc)`` runs the chunks of workgroups ``c_lo, c_lo + cw, ...`` below
    ``c_hi`` in a device-side loop, one ``chunk_fn`` per iteration;
    ``acc`` is the packed ``_TraceCtx`` counters carried from chunk to
    chunk.  The loop carries every buffer: XLA turns the ones no chunk
    writes into loop-invariant operands itself.  ``rec.jitted`` donates
    the buffers and ``acc``, so one call updates them in place, and a
    shorter ``[c_lo, c_hi)`` steps the same executable.  Nothing is
    traced by JAX yet: lowering ``rec.jitted`` on ``rec.abstract`` does
    that."""
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
    scalar_dtypes = {p.name: _TY_NP[p.ty] for p in fn.params
                     if p.ty is not Ty.PTR}
    tiles = {f"@{g.name}": (g.size, _TY_DTYPE[g.elem_ty])
             for g in fn.shared}
    ops: set = set()
    _collect_ops(fn, ops, set())
    cnt_keys = tuple(sorted(ops))
    loop_keys = LOOP_KEYS if _scan_fn(fn)["loops"] else ()
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

    def program(bufs, scalars, c_lo, c_hi, acc):
        bufs, _, acc = jax.lax.while_loop(
            lambda carry: carry[1] < c_hi,
            lambda carry: chunk_fn(carry[0], scalars, carry[1], carry[2]),
            (bufs, c_lo, acc))
        return bufs, acc

    i32 = jax.ShapeDtypeStruct((), np.int32)
    rec = _Compiled()
    rec.program = program
    rec.jitted = jax.jit(program, donate_argnums=(0, 4))
    rec.abstract = (
        tuple(jax.ShapeDtypeStruct(buffers[nm].shape,
                                   buffers[nm].dtype)
              for nm in buf_names),
        tuple(jax.ShapeDtypeStruct((), np.dtype(scalar_dtypes[nm]))
              for nm in scalar_names if nm in scalar_dtypes),
        i32, i32,
        ((i32,) * len(cnt_keys),) + (i32,) * _N_SCALARS
        + ((i32,) * len(loop_keys),))
    rec.lowered = None
    rec.tiers = {}
    rec.cnt_keys = cnt_keys
    rec.loop_keys = loop_keys
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
             globals_mem) -> _Compiled:
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
            _licence(fn, params, buffers, scalar_args, globals_mem)
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
# host side of a launch
# --------------------------------------------------------------------------

def _run(rec: _Compiled, fn: Function, buffers: dict,
         scalar_args: dict, params, tier: str = "fast") -> tuple:
    """Run every chunk on the given executable tier; returns
    (host_bufs, jstats dict), ``jstats["dispatches"]`` the executable
    calls made, ``jstats["loops"]`` the loop counters (``LOOP_KEYS``;
    empty for a kernel without loops).  One call runs the whole grid; while a deadline is armed
    the same executable is stepped a chunk at a time, with the deadline
    checked before each call.  Site ``jax.exec`` is checked before each
    call and once after the last.  Never mutates ``buffers`` — results
    are staged device-side and converted at the end, so a faulted
    launch costs nothing to roll back."""
    n_wg = params.grid * params.grid_y
    with span("volt.jax.upload"):
        dev_bufs = tuple(jnp.asarray(buffers[nm]) for nm in rec.buf_names)
        scal = tuple(np.asarray(scalar_args[nm],
                                dtype=rec.scalar_dtypes[nm])
                     for nm in rec.scalar_names)
    # under jax.disable_jit() run the traced function eagerly — the
    # metamorphic contract: op-by-op eager execution, the AOT-compiled
    # executable and the oracle all agree bit-for-bit
    run = (rec.program if jax.config.jax_disable_jit
           else rec.executable(tier))
    acc = _zero_acc(len(rec.cnt_keys), len(rec.loop_keys))
    step = rec.cw if _gov.ACTIVE else n_wg
    calls = 0
    with span("volt.jax.dispatch"):
        for c in range(0, n_wg, step):
            if _gov.ACTIVE:
                _gov.deadline_check()
            if _faults.ACTIVE:
                _faults.maybe_fault("jax.exec")
            dev_bufs, acc = run(dev_bufs, scal, np.int32(c),
                                np.int32(min(c + step, n_wg)), acc)
            calls += 1
        if _faults.ACTIVE:
            _faults.maybe_fault("jax.exec")
    with span("volt.jax.sync"):
        cnt, mem_, shm, minst, maxd, _fuel, err, mwin, mgat, loops = \
            jax.device_get(acc)
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
    with span("volt.jax.download"):
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
        "dispatches": calls,
        "loops": {k: int(v) for k, v in zip(rec.loop_keys, loops)},
        "windows": {"mem_window": int(mwin), "mem_gather": int(mgat)},
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
    d = _diskcache.load_verdicts(fn)
    if d is None:
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
    _diskcache.save_verdicts(fn, certs)


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
    try:
        _prepare(fn, params, buffers, scalar_args or {}, globals_mem or {})
    except _faults.InjectedFault:
        raise
    except (LowerError, _faults.EngineFault) as e:
        return (False, str(e))
    return (True, "")


def _certify(rec: _Compiled, fn: Function, buffers: dict,
             scalar_args: dict, params, stats, run_normal) -> None:
    """The differential certification run of one (kernel, launch shape):
    the launch's results come from the normal chain (``run_normal``),
    and the jitted program's run beside it decides the verdict —
    "pass" (fast tier bit-exact), "pass-exact" (only the exact tier
    is), "fail" (the exact tier differs too; the detail names the first
    difference) or "error" (the jitted program raised; the detail holds
    the text).  Raises what the normal chain raises, and an infra fault
    of the jitted run before the normal chain has run (nothing ran)."""
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
        return
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
        return
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


def serve(fn: Function, buffers: dict, params, scalar_args: dict,
          globals_mem, stats, run_normal) -> str:
    """The jax rung's entry for one launch, called by
    ``runtime.interp_launch`` with the "jax" rung pushed.  Returns
    "served" when this call produced the launch's results in ``buffers``
    and ``stats``: the jitted program ran as the certified primary, or a
    differential certification run drove ``run_normal(stats)``, the grid
    rung.  Otherwise it declines, with ``buffers`` and ``stats``
    untouched, and returns why:

      * "refused": outside the licence, or the trace failed;
      * "routed": the small-launch router — the pair is certified, but
        its measured grid time beats the jitted dispatch floor
        (``_ROUTE_MARGIN``), so the grid rung serves it;
      * "fail" / "error": the pair's recorded verdict.

    A failure raises: a ``KernelFault`` as the normal chain raises it,
    or an ``EngineFault`` with the buffers untouched, for the caller to
    demote.  Any other exception inside the rung becomes an
    ``EngineFault`` here.  Like the host executors, the rung records
    itself in ``interp.LAST_EXECUTOR[0]``."""
    _interp.LAST_EXECUTOR[0] = "jax"
    try:
        return _serve(fn, buffers, params, scalar_args, globals_mem, stats,
                      run_normal)
    except (_faults.KernelFault, _faults.EngineFault):
        raise
    except Exception as e:
        JAX_TELEMETRY["demotions"] += 1
        raise _faults.EngineFault(
            f"jax executor failure: {type(e).__name__}: {e}",
            site="jax.exec", rung="jax") from e


def _serve(fn: Function, buffers: dict, params, scalar_args: dict,
           globals_mem, stats, run_normal) -> str:
    if params.grid * params.grid_y <= 1 or params.strict_oob_loads:
        return "refused"       # outside the licence without a scan
    try:
        with span("volt.jax.prepare"):
            rec = _prepare(fn, params, buffers, scalar_args,
                           globals_mem or {})
    except LowerError:
        JAX_TELEMETRY["refusals"] += 1
        return "refused"
    except _faults.EngineFault:
        JAX_TELEMETRY["demotions"] += 1
        raise

    if _faults.ACTIVE:
        try:
            _faults.maybe_fault("jax.cache.load")
        except _faults.InjectedFault:
            JAX_TELEMETRY["demotions"] += 1
            raise
    verdict, v_jax_ms, v_grid_ms = _verdict_of(_certs(fn).get(rec.sig))

    if verdict in ("fail", "error"):
        return verdict

    # ---- small-launch dispatch router --------------------------------
    # A certified pair whose measured grid time beats the measured
    # jitted time (dominated by the per-dispatch jit-call floor for
    # small launches) is SERVED BY THE GRID RUNG, with the verdict
    # untouched — a bigger shape class of the same kernel still takes
    # the jitted primary.
    if (verdict is not None and v_jax_ms is not None
            and v_grid_ms is not None
            and v_grid_ms < v_jax_ms * _ROUTE_MARGIN):
        JAX_TELEMETRY["routed_small"] += 1
        return "routed"

    if verdict is None:
        with span("volt.jax.certify"):
            _certify(rec, fn, buffers, scalar_args, params, stats,
                     run_normal)
        return "served"

    # ---- certified primary ------------------------------------------
    tier = "exact" if verdict == "pass-exact" else "fast"
    t0 = perf_counter()
    try:
        host_bufs, jstats = _run(rec, fn, buffers, scalar_args, params,
                                 tier=tier)
    except _faults.EngineFault:
        JAX_TELEMETRY["demotions"] += 1
        raise
    with span("volt.jax.apply"):
        _apply(host_bufs, jstats, buffers, stats)
    JAX_TELEMETRY["engaged"] += 1
    JAX_TELEMETRY["dispatches"] += jstats["dispatches"]
    for k, v in (*jstats["loops"].items(), *jstats["windows"].items()):
        JAX_TELEMETRY[k] += v
    JAX_TELEMETRY["upload_bytes"] += sum(buffers[nm].nbytes
                                         for nm in rec.buf_names)
    JAX_TELEMETRY["download_bytes"] += sum(a.nbytes
                                           for a in host_bufs.values())
    if v_jax_ms is None:
        # first warm primary at this shape class: measure the jitted
        # wall (dispatch floor included) so the router has both sides
        _record(fn, rec.sig, verdict,
                jax_ms=(perf_counter() - t0) * 1e3,
                grid_ms=v_grid_ms)
    return "served"
