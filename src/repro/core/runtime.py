"""Host runtime (paper §4.2 host compilation flow + Case Study 2).

The front-end rewrites host-side API calls into operations against this
device runtime.  We expose both dialect flavors:

  OpenCL-ish:  create_buffer / enqueue_nd_range / read_buffer
  CUDA-ish:    cuda_malloc / cuda_memcpy / cuda_memcpy_to_symbol /
               cuda_launch_kernel

Case Study 2 — ``cudaMemcpyToSymbol``: CuPBoP maps CUDA constant memory to
Vortex global memory but lacks the host API, so constant initialization is
impossible.  VOLT buffers the host data and *materializes it just before
kernel launch*, after global addresses are resolved.  ``Runtime.launch``
below does exactly that (``_pending_symbols``).

Case Study 2 — shared-memory mapping: ``shared_in_local`` selects whether
__shared__ arrays map to per-core local memory or global memory; it flows
into the cycle model (simx.CycleModel) and reproduces the Fig 10 trade-off.

The grid computation in ``launch`` is the runtime half of ``vx_wspawn``:
a single control thread computes #warps/#cores from launch arguments, then
spawns the grid (here: schedules the interpreter or the JAX backend).
"""
from __future__ import annotations

import dataclasses
import os
import threading
from collections import Counter, deque
from dataclasses import dataclass, field
from time import perf_counter
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import diskcache as _diskcache
from . import faults as _faults
from . import governor as _gov
from . import interp as _interp
from . import parallel as _parallel
from .faults import DeadlineExceeded, EngineBusy, EngineFault, KernelFault
from .interp import ExecStats, LaunchParams
from .passes.pipeline import CompiledKernel, PassConfig, run_pipeline
from .simx import CycleModel
from .spans import span
from .vir import Function, Module, Ty

_TY_DTYPE = {Ty.I32: np.int32, Ty.F32: np.float32, Ty.BOOL: np.bool_}


# --------------------------------------------------------------------------
# Compile cache: repeated launches of the same @kernel under the same
# PassConfig + warp configuration skip the front-end build AND the whole
# pass pipeline.  Two tiers:
#
#   * in-memory, keyed by (handle identity, PassConfig fields, warp size);
#     values keep a strong reference to the handle so its id() can never
#     be recycled;
#   * on disk (``.vck``, core/diskcache.py), keyed by the CONTENT hash of
#     the normalized pre-pipeline IR — a second process compiling an
#     identical kernel deserializes the compiled module instead of
#     re-running the pass pipeline.
# --------------------------------------------------------------------------

_COMPILE_CACHE: Dict[Tuple, Tuple[Any, CompiledKernel]] = {}


def use_jax_compile_cache() -> None:
    """Point JAX's persistent compilation cache at a fixed path: the
    directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX
    reads that itself, so nothing is set here), else
    ``<checkout>/.cache/jax``.  Called by the programs that reach the
    device, never on import, so a test run leaves the checkout alone."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(_diskcache.CHECKOUT_CACHE / "jax"))


def compile_kernel(kernel_handle, config: Optional[PassConfig] = None,
                   *, warp_size: int = 32, use_cache: bool = True,
                   use_disk_cache: Optional[bool] = None) -> CompiledKernel:
    """Build + run the pass pipeline for a front-end @kernel handle,
    memoized on (kernel, PassConfig, warp config) in memory and — keyed
    by IR content hash — on disk across processes."""
    config = config or PassConfig()
    key = (id(kernel_handle), kernel_handle.name,
           dataclasses.astuple(config), warp_size)
    if use_cache:
        hit = _COMPILE_CACHE.get(key)
        if hit is not None:
            return hit[1]
    module = kernel_handle.build(None)
    disk_key = None
    if use_disk_cache is not False and _diskcache.cache_dir() is not None:
        disk_key = _diskcache.kernel_key(module, kernel_handle.name,
                                         config, warp_size)
        ck = _diskcache.load_kernel(disk_key, kernel_handle.name, config)
        if ck is not None:
            if use_cache:
                _COMPILE_CACHE[key] = (kernel_handle, ck)
            return ck
    ck = run_pipeline(module, kernel_handle.name, config)
    if disk_key is not None:
        _diskcache.store_kernel(disk_key, ck)
    if use_cache:
        _COMPILE_CACHE[key] = (kernel_handle, ck)
    return ck


def clear_compile_cache(*, disk: bool = False) -> None:
    _COMPILE_CACHE.clear()
    if disk:
        _diskcache.clear()


@dataclass
class Buffer:
    name: str
    data: np.ndarray


# --------------------------------------------------------------------------
# Executor degradation chain (docs/robustness.md).
#
# The executors form rungs of a ladder, fastest first; an
# ``EngineFault`` (internal fast-path failure — injected or real)
# demotes the launch to the rung BELOW the executor that actually ran,
# after rolling written buffers back to their pre-launch snapshot, so a
# demotion is semantically invisible: the surviving attempt produces
# bit-identical ExecStats and buffers to a launch that had taken the
# slower path from the start.  ``KernelFault``s (semantic errors)
# surface immediately — every rung would raise the same class.
# --------------------------------------------------------------------------

_RUNG_ORDER = ("jax", "grid", "wg", "decoded", "oracle")

#: interp.launch kwargs of the host rungs.  "grid" is the production
#: default (auto-selects grid / wg-batched / decoded by eligibility);
#: pinning grid=False / batched=False peels one fast path per rung.
#: "jax", the top rung when the Runtime enables it (jax=True /
#: VOLT_JAX=1), is no mode of interp.launch: ``interp_launch`` runs it.
_RUNG_KWARGS: Dict[str, Dict[str, Any]] = {
    "grid":    dict(decoded=True, batched=True),
    "wg":      dict(decoded=True, batched=True, grid=False),
    "decoded": dict(decoded=True, batched=False),
    "oracle":  dict(decoded=False, batched=False),
}


def interp_launch(fn: Function, buffers: Dict[str, np.ndarray],
                  params: LaunchParams,
                  scalar_args: Optional[Dict[str, Any]] = None, *,
                  rung: str = "grid",
                  globals_mem: Optional[Dict[str, np.ndarray]] = None,
                  deadline_t: Optional[float] = None,
                  deadline_ms: Optional[float] = None,
                  mem_budget: Optional[int] = None,
                  pool: Optional[_interp.DevicePool] = None,
                  workers: Optional[object] = None) -> ExecStats:
    """One attempt of one rung of the chain; every attempt of
    ``Runtime.launch`` goes through this one name.  A host rung is
    ``interp.launch`` under its ``_RUNG_KWARGS``.  The "jax" rung asks
    ``jaxgen.serve`` first, which either serves the launch (the jitted
    program as the certified primary, or a certification run whose
    results come from the grid rung) or declines it; a declined launch
    runs on the grid rung in the same attempt.  The executor that
    served is left in ``interp.LAST_EXECUTOR[0]``."""
    kw = dict(globals_mem=globals_mem, mem_budget=mem_budget, pool=pool,
              workers=workers)
    if rung != "jax":
        return _interp.launch(fn, buffers, params, scalar_args,
                              deadline_t=deadline_t,
                              deadline_ms=deadline_ms, **kw,
                              **_RUNG_KWARGS[rung])
    from .backends import jaxgen   # the numpy chain never loads JAX
    if deadline_t is None and deadline_ms is not None:
        # one absolute deadline for the rung and a grid run after it
        deadline_t = perf_counter() + deadline_ms * 1e-3
    kw.update(deadline_t=deadline_t, deadline_ms=deadline_ms,
              **_RUNG_KWARGS["grid"])

    def run_grid(stats: ExecStats) -> None:
        stats.merge(_interp.launch(fn, buffers, params, scalar_args,
                                   **kw))

    stats = ExecStats()
    if deadline_t is not None:
        _gov.arm_deadline(deadline_t, deadline_ms, stats)
    try:
        with _faults.rung("jax"):
            outcome = jaxgen.serve(fn, buffers, params, scalar_args or {},
                                   globals_mem, stats, run_grid)
    finally:
        if deadline_t is not None:
            _gov.disarm_deadline()
    if outcome == "served":
        return stats
    if outcome == "routed":
        _tel("routed_small")
    return _interp.launch(fn, buffers, params, scalar_args, **kw)


@dataclass
class LaunchAttempt:
    rung: str                      # rung configuration requested
    executor: Optional[str]        # executor interp actually selected
    outcome: str      # "ok" | "engine_fault" | "kernel_fault" | "deadline"
    reason: str = ""
    wall_ms: float = 0.0


@dataclass
class LaunchReport:
    """Per-launch degradation record (``Runtime.last_report``; the last
    ``REPORT_RING`` live in ``Runtime.last_reports()``)."""
    kernel: str
    attempts: List[LaunchAttempt] = field(default_factory=list)
    executor: Optional[str] = None     # executor that produced the result
    demotions: int = 0
    rolled_back: int = 0
    snapshot_bytes: int = 0
    wall_ms: float = 0.0
    # governor context (core/governor.py)
    breaker: Optional[str] = None      # breaker state when planned
    pinned_rung: Optional[str] = None  # open breaker: chain started here
    probe: bool = False                # half-open probe of the full chain
    deadline_ms: Optional[float] = None
    deadline_expired: bool = False
    snapshot_skipped: Optional[str] = None   # e.g. "mem-budget"

    def summary(self) -> str:
        steps = " -> ".join(
            f"{a.executor or a.rung}:{a.outcome}" for a in self.attempts)
        gov = ""
        if self.pinned_rung:
            gov += f", pinned @{self.pinned_rung}"
        if self.probe:
            gov += ", probe"
        if self.deadline_expired:
            gov += f", deadline {self.deadline_ms:.3g} ms expired"
        if self.snapshot_skipped:
            gov += f", snapshot skipped ({self.snapshot_skipped})"
        return (f"@{self.kernel}: {steps} ({self.demotions} demotion(s), "
                f"{self.rolled_back} rollback(s), "
                f"{self.wall_ms:.2f} ms{gov})")


#: ring depth of Runtime.last_reports() (post-mortem debugging)
REPORT_RING = 32


def _attach_report(e: BaseException, report: LaunchReport) -> None:
    """Attach the degradation history to a SURFACING exception:
    ``e.report`` for programmatic use, plus the one-line summary as an
    exception note (``e.__notes__``) so a traceback shows which rungs
    were tried."""
    e.report = report                       # type: ignore[attr-defined]
    e.add_note("launch report: " + report.summary())


#: process-lifetime launch/degradation counters (GRID_TELEMETRY's
#: pattern: NOT part of ExecStats — stats stay bit-identical across
#: executors by contract).  Mutate through ``_tel``/``_tel_ctr`` — the
#: launch service drains queues from concurrent submitter threads, and
#: bare ``+=`` on a module dict is a read-modify-write race.
LAUNCH_TELEMETRY: Dict[str, Any] = {}

_TEL_LOCK = threading.Lock()


def _tel(key: str, n: int = 1) -> None:
    with _TEL_LOCK:
        LAUNCH_TELEMETRY[key] += n


def _tel_ctr(key: str, sub: Any, n: int = 1) -> None:
    with _TEL_LOCK:
        LAUNCH_TELEMETRY[key][sub] += n


def reset_launch_telemetry() -> None:
    with _TEL_LOCK:
        LAUNCH_TELEMETRY.clear()
        LAUNCH_TELEMETRY.update(
            launches=0, demotions=0, rollbacks=0, engine_faults=0,
            kernel_faults=0, by_executor=Counter(),
            demotion_reasons=Counter(),
            # launch governor (core/governor.py)
            deadline_expired=0, snapshot_budget_skips=0,
            breaker_trips=0, breaker_pinned=0, breaker_probes=0,
            breaker_promotions=0,
            # launch service (continuous batching + small-launch router)
            coalesced_groups=0, coalesced_launches=0, coalesce_aborts=0,
            routed_small=0)


reset_launch_telemetry()


class Runtime:
    """A Vortex device-runtime stand-in with CUDA/OpenCL host APIs.

    ``degrade=True`` (default) arms the executor degradation chain: an
    ``EngineFault`` in a fast path rolls written buffers back to their
    pre-launch snapshot and retries one rung down (jax-codegen when
    enabled -> grid -> wg-batched -> decoded -> oracle), recording
    every attempt in ``self.last_report``.  Each attempt is one call of
    ``interp_launch``; a jax attempt the rung declines (licence, router
    or verdict) is served by the grid rung within that attempt.
    ``transactional=False`` disables the write-root snapshots — and with
    them the chain, since retrying over partially-committed stores (or
    re-applied atomics) would be unsound; an EngineFault then surfaces
    to the caller.

    ``govern=True`` (default) arms the launch governor
    (core/governor.py, docs/robustness.md): per-launch wall-clock
    deadlines (``launch(..., deadline_ms=)``), a per-kernel circuit
    breaker that pins repeatedly-demoting kernels at their last-good
    rung, and the ``VOLT_MEM_BUDGET`` memory budget; ``governor=``
    overrides the knobs per Runtime."""

    def __init__(self, *, warp_size: int = 32,
                 shared_in_local: bool = True,
                 batched: bool = True,
                 jax: Optional[bool] = None,
                 degrade: bool = True,
                 transactional: bool = True,
                 govern: bool = True,
                 governor: Optional[_gov.GovernorConfig] = None,
                 workers: Optional[object] = None) -> None:
        self.warp_size = warp_size
        self.batched = batched     # workgroup-batched lockstep executor
        # host-parallel grid dispatch (core/parallel.py): resolved ONCE
        # here so a malformed VOLT_WORKERS fails at construction, not
        # mid-launch; 1 = today's exact sequential dispatch
        self.workers = _parallel.resolve_workers(workers)
        # jax codegen rung: opt-in (jax=True or VOLT_JAX=1) — default
        # OFF so the numpy chain stays the reference behaviour
        self.jax = bool(jax) if jax is not None \
            else os.environ.get("VOLT_JAX", "0") not in ("", "0")
        self.degrade = degrade
        self.transactional = transactional
        self.govern = govern
        self.gov_cfg = governor or _gov.GovernorConfig()
        mb = self.gov_cfg.mem_budget
        self.mem_budget = mb if mb is not None else _gov.env_mem_budget()
        self.breaker: Optional[_gov.CircuitBreaker] = \
            _gov.CircuitBreaker(self.gov_cfg.breaker_threshold,
                                self.gov_cfg.breaker_probe_every) \
            if govern else None
        pb = self.gov_cfg.pool_budget
        if pb is None:
            pb = _gov.env_pool_budget()
        #: pooled device allocator (interp.DevicePool): shared tiles,
        #: tile tables and the launch service's coalesced staging tables
        #: reuse backing arrays across launches instead of allocating —
        #: bounded by GovernorConfig.pool_budget / VOLT_POOL_BUDGET
        self.pool = _interp.DevicePool(
            capacity=pb if pb is not None else 64 << 20)
        self.buffers: Dict[str, np.ndarray] = {}
        self.globals_mem: Dict[str, np.ndarray] = {}
        self._pending_symbols: Dict[str, np.ndarray] = {}
        self.cycle_model = CycleModel(shared_in_local=shared_in_local)
        self.last_stats: Optional[ExecStats] = None
        self.last_report: Optional[LaunchReport] = None
        self._reports: deque = deque(maxlen=REPORT_RING)
        # the launch service drains tenant queues from submitter
        # threads; the ring and last_report are shared post-mortem state
        self._report_lock = threading.Lock()

    def last_reports(self) -> List[LaunchReport]:
        """The last ``REPORT_RING`` LaunchReports, oldest first — the
        post-mortem trail when a failure is noticed after the fact."""
        with self._report_lock:
            return list(self._reports)

    def _push_report(self, report: LaunchReport) -> None:
        with self._report_lock:
            self.last_report = report
            self._reports.append(report)

    # -- OpenCL-ish -----------------------------------------------------------
    def create_buffer(self, name: str, data: np.ndarray) -> Buffer:
        arr = np.array(data, copy=True)
        self.buffers[name] = arr
        return Buffer(name, arr)

    def read_buffer(self, name: str) -> np.ndarray:
        return self.buffers[name]

    def enqueue_nd_range(self, kernel_fn: Function, global_size: int,
                         local_size: int,
                         scalar_args: Optional[Dict[str, Any]] = None
                         ) -> ExecStats:
        grid = max(1, (global_size + local_size - 1) // local_size)
        return self.launch(kernel_fn, grid=grid, block=local_size,
                           scalar_args=scalar_args)

    # -- CUDA-ish ---------------------------------------------------------------
    def cuda_malloc(self, name: str, size: int,
                    dtype=np.float32) -> Buffer:
        arr = np.zeros(size, dtype=dtype)
        self.buffers[name] = arr
        return Buffer(name, arr)

    def cuda_memcpy(self, dst: str, src: np.ndarray) -> None:
        self.buffers[dst][:] = src

    def cuda_memcpy_from(self, src: str) -> np.ndarray:
        return self.buffers[src].copy()

    def cuda_memcpy_to_symbol(self, module: Module, symbol: str,
                              data: np.ndarray) -> None:
        """Deferred constant initialization (Case Study 2): stage host data;
        it is materialized into the symbol's global storage at launch."""
        if symbol not in module.globals:
            raise KeyError(f"no such device symbol {symbol!r}")
        g = module.globals[symbol]
        arr = np.asarray(data, dtype=_TY_DTYPE[g.elem_ty])
        if len(arr) > g.size:
            raise ValueError(f"symbol {symbol} overflow: {len(arr)} > {g.size}")
        self._pending_symbols[symbol] = arr

    # -- launch ------------------------------------------------------------------
    def _snapshot_write_roots(self, kernel_fn: Function,
                              report: LaunchReport,
                              budget: Optional[int] = None,
                              force: bool = False,
                              buffers: Optional[Dict[str, np.ndarray]]
                              = None,
                              globals_mem: Optional[Dict[str, np.ndarray]]
                              = None) -> Optional[Dict[Any, Any]]:
        """Transactional snapshot: copy the buffers this kernel may
        WRITE (interp.write_root_buffers; everything bound when the
        scan cannot resolve a store root).  Read-only buffers are never
        copied, which keeps the clean path cheap.  Also records the
        global names alive now, so a rollback can drop globals the
        launch lazily created.

        With a memory ``budget``, an over-budget snapshot is refused
        (returns None) and the caller degrades to oracle-first
        execution — the floor needs no retry snapshot — instead of
        OOMing mid-chain.  ``force`` overrides the budget: an armed
        deadline's rollback contract outranks the budget (the snapshot
        is the only thing that makes a timed-out launch bit-invisible)."""
        bufs = self.buffers if buffers is None else buffers
        gmem = self.globals_mem if globals_mem is None else globals_mem
        roots = _interp.write_root_buffers(kernel_fn)
        pairs: List[Tuple[Any, np.ndarray]] = []
        if roots is None:
            pairs.extend((("b", n), a) for n, a in bufs.items())
            pairs.extend((("g", n), a) for n, a in gmem.items())
        else:
            params_w, globals_w = roots
            for name in params_w:
                arr = bufs.get(name)
                if arr is not None:
                    pairs.append((("b", name), arr))
            for name in globals_w:
                arr = gmem.get(name)
                if arr is not None:
                    pairs.append((("g", name), arr))
        total = sum(a.nbytes for _, a in pairs)
        if budget is not None and total > budget and not force:
            report.snapshot_skipped = "mem-budget"
            _tel("snapshot_budget_skips")
            return None
        with span("volt.launch.snapshot"):
            snap: Dict[Any, Any] = {k: a.copy() for k, a in pairs}
        snap["__globals_keys__"] = set(gmem)
        report.snapshot_bytes = total
        return snap

    def _rollback(self, snap: Dict[Any, Any],
                  buffers: Optional[Dict[str, np.ndarray]] = None,
                  globals_mem: Optional[Dict[str, np.ndarray]] = None
                  ) -> None:
        bufs = self.buffers if buffers is None else buffers
        gmem = self.globals_mem if globals_mem is None else globals_mem
        with span("volt.launch.rollback"):
            for key, arr in snap.items():
                if not isinstance(key, tuple):
                    continue
                kind, name = key
                dst = bufs[name] if kind == "b" else gmem[name]
                dst[:] = arr
            # globals the failed attempt lazily zero-created: drop them
            # so the retry re-creates them identically
            for name in list(gmem):
                if name not in snap["__globals_keys__"]:
                    del gmem[name]

    def launch(self, kernel_fn: Function, *, grid: int, block: int,
               scalar_args: Optional[Dict[str, Any]] = None,
               deadline_ms: Optional[float] = None,
               buffers: Optional[Dict[str, np.ndarray]] = None,
               globals_mem: Optional[Dict[str, np.ndarray]] = None,
               fuel: Optional[int] = None) -> ExecStats:
        """Run one kernel launch through the full degradation chain.
        ``buffers``/``globals_mem`` override the Runtime-owned dicts —
        the launch service runs each tenant's launch against the
        tenant's own buffer set while sharing this Runtime's breaker
        bank, governor, pool and report ring.  The whole call is the
        ``volt.launch`` span."""
        with span("volt.launch"):
            return self._launch(kernel_fn, grid=grid, block=block,
                                scalar_args=scalar_args,
                                deadline_ms=deadline_ms, buffers=buffers,
                                globals_mem=globals_mem, fuel=fuel)

    def _launch(self, kernel_fn: Function, *, grid: int, block: int,
                scalar_args: Optional[Dict[str, Any]],
                deadline_ms: Optional[float],
                buffers: Optional[Dict[str, np.ndarray]],
                globals_mem: Optional[Dict[str, np.ndarray]],
                fuel: Optional[int]) -> ExecStats:
        bufs = self.buffers if buffers is None else buffers
        gmem = self.globals_mem if globals_mem is None else globals_mem
        # materialize staged symbols now that "addresses are resolved"
        for sym, data in self._pending_symbols.items():
            buf = gmem.get(sym)
            if buf is None or len(buf) < len(data):
                buf = np.zeros(max(len(data), 1), dtype=data.dtype)
            buf[:len(data)] = data
            gmem[sym] = buf
        self._pending_symbols.clear()

        params = LaunchParams(grid=grid, local_size=block,
                              warp_size=self.warp_size)
        if fuel is not None:
            params = dataclasses.replace(params, fuel=fuel)
        chain = list(_RUNG_ORDER) if self.batched \
            else list(_RUNG_ORDER[_RUNG_ORDER.index("decoded"):])
        if not self.jax:
            chain = [r for r in chain if r != "jax"]
        if not (self.degrade and self.transactional):
            chain = chain[:1]      # single attempt, no retry
        report = LaunchReport(kernel=kernel_fn.name)
        self._push_report(report)
        _tel("launches")

        # ---- governor plan (core/governor.py) ------------------------
        if deadline_ms is None and self.govern:
            deadline_ms = self.gov_cfg.deadline_ms
        mem_budget = self.mem_budget if self.govern else None
        deadline_t: Optional[float] = None
        if deadline_ms is not None:
            report.deadline_ms = deadline_ms
            # one absolute deadline shared by every rung of the chain:
            # demotion retries do not refill the budget
            deadline_t = perf_counter() + deadline_ms * 1e-3
        bkey: Optional[str] = None
        probing = False
        if self.breaker is not None and len(chain) > 1:
            bkey = _diskcache.plan_key(kernel_fn)
            pin, probing = self.breaker.plan(bkey, kernel_fn.name)
            report.breaker = self.breaker.entry(
                bkey, kernel_fn.name).state
            report.probe = probing
            if probing:
                _tel("breaker_probes")
            if pin is not None:
                # open breaker: start at the last-good rung, skipping
                # the doomed fast path (and, when pinned at the oracle
                # floor with no deadline, the snapshot too)
                report.pinned_rung = pin
                _tel("breaker_pinned")
                kp = _RUNG_ORDER.index(pin)
                chain = [r for r in chain
                         if _RUNG_ORDER.index(r) >= kp] or [chain[-1]]

        txn: Optional[Dict[Any, Any]] = None
        t_launch = perf_counter()
        i = 0
        while True:
            rung = chain[i]
            # snapshot when further rungs could retry, or to honor the
            # deadline rollback contract (force= overrides the budget)
            if txn is None and self.transactional and \
                    (i + 1 < len(chain) or deadline_t is not None):
                txn = self._snapshot_write_roots(
                    kernel_fn, report, budget=mem_budget,
                    force=deadline_t is not None,
                    buffers=bufs, globals_mem=gmem)
                if txn is None and i + 1 < len(chain):
                    # over-budget snapshot: degrade straight to the
                    # oracle floor, which needs no retry snapshot
                    i = len(chain) - 1
                    rung = chain[i]
            t0 = perf_counter()
            try:
                stats = interp_launch(kernel_fn, bufs, params,
                                      scalar_args=scalar_args,
                                      rung=rung,
                                      globals_mem=gmem,
                                      deadline_t=deadline_t,
                                      deadline_ms=deadline_ms,
                                      mem_budget=mem_budget,
                                      pool=self.pool,
                                      workers=self.workers)
            except DeadlineExceeded as e:
                used = _interp.LAST_EXECUTOR[0] or rung
                report.attempts.append(LaunchAttempt(
                    rung, used, "deadline", str(e),
                    (perf_counter() - t0) * 1e3))
                report.deadline_expired = True
                _tel("deadline_expired")
                if txn is not None:
                    self._rollback(txn, buffers=bufs, globals_mem=gmem)
                    report.rolled_back += 1
                    _tel("rollbacks")
                report.wall_ms = (perf_counter() - t_launch) * 1e3
                if bkey is not None:
                    self.breaker.abort(bkey, kernel_fn.name,
                                       probing=probing)
                _attach_report(e, report)
                raise
            except EngineFault as e:
                used = getattr(e, "rung", None) \
                    or _interp.LAST_EXECUTOR[0] or rung
                report.attempts.append(LaunchAttempt(
                    rung, used, "engine_fault", str(e),
                    (perf_counter() - t0) * 1e3))
                _tel("engine_faults")
                # demote BELOW the executor that actually ran (a
                # gate-refused grid request already fell back before
                # the fault fired)
                k = _RUNG_ORDER.index(used) if used in _RUNG_ORDER \
                    else _RUNG_ORDER.index(rung)
                nxt = None
                for j in range(i + 1, len(chain)):
                    if _RUNG_ORDER.index(chain[j]) > k:
                        nxt = j
                        break
                if nxt is None or txn is None:
                    report.wall_ms = (perf_counter() - t_launch) * 1e3
                    if bkey is not None:
                        self.breaker.abort(bkey, kernel_fn.name,
                                           probing=probing)
                    _attach_report(e, report)
                    raise
                self._rollback(txn, buffers=bufs, globals_mem=gmem)
                report.rolled_back += 1
                report.demotions += 1
                _tel("rollbacks")
                _tel("demotions")
                _tel_ctr("demotion_reasons",
                         getattr(e, "site", None) or "exec")
                i = nxt
                continue
            except KernelFault as e:
                # semantic: deterministic, every rung agrees — surface
                report.attempts.append(LaunchAttempt(
                    rung, _interp.LAST_EXECUTOR[0], "kernel_fault",
                    str(e), (perf_counter() - t0) * 1e3))
                _tel("kernel_faults")
                report.wall_ms = (perf_counter() - t_launch) * 1e3
                if bkey is not None:
                    # never a breaker trip — but a probe that hit a
                    # semantic fault learned nothing: re-pin
                    self.breaker.abort(bkey, kernel_fn.name,
                                       probing=probing)
                e.report = report          # type: ignore[attr-defined]
                raise
            used = _interp.LAST_EXECUTOR[0] or rung
            report.attempts.append(LaunchAttempt(
                rung, used, "ok", "", (perf_counter() - t0) * 1e3))
            report.executor = used
            report.wall_ms = (perf_counter() - t_launch) * 1e3
            _tel_ctr("by_executor", used)
            if bkey is not None:
                demoted = report.demotions > 0
                changed = self.breaker.record(
                    bkey, kernel_fn.name, demoted=demoted,
                    final_rung=used, probing=probing)
                if changed:
                    _tel("breaker_trips" if demoted
                         else "breaker_promotions")
                report.breaker = self.breaker.entry(
                    bkey, kernel_fn.name).state
            self.last_stats = stats
            return stats

    def launch_kernel(self, kernel_handle, *, grid: int, block: int,
                      config: Optional[PassConfig] = None,
                      scalar_args: Optional[Dict[str, Any]] = None,
                      deadline_ms: Optional[float] = None
                      ) -> ExecStats:
        """Compile (memoized via the module compile cache) and launch a
        front-end @kernel handle in one call — the hot path for repeated
        launches of the same kernel."""
        ck = compile_kernel(kernel_handle, config,
                            warp_size=self.warp_size)
        return self.launch(ck.fn, grid=grid, block=block,
                           scalar_args=scalar_args,
                           deadline_ms=deadline_ms)

    def cycles(self, stats: Optional[ExecStats] = None) -> float:
        st = stats or self.last_stats
        if st is None:
            raise RuntimeError("no kernel has been launched")
        return self.cycle_model.cycles(st)


# --------------------------------------------------------------------------
# Launch service: continuous launch batching over the Runtime
# --------------------------------------------------------------------------


class LaunchHandle:
    """One launch submitted to a :class:`LaunchService`.  ``flush()``
    fills in exactly one of ``stats`` / ``error``; ``result()`` replays
    the solo-launch contract (return the ExecStats or raise the stored
    exception, with ``.report`` attached where the solo path attaches
    it)."""

    __slots__ = ("kernel", "tenant", "grid", "block", "stats", "error",
                 "report", "mode")

    def __init__(self, kernel: str, tenant: Any, grid: int,
                 block: int) -> None:
        self.kernel = kernel
        self.tenant = tenant
        self.grid = grid
        self.block = block
        self.stats: Optional[ExecStats] = None
        self.error: Optional[BaseException] = None
        self.report: Optional[LaunchReport] = None
        #: "coalesced" | "solo" | None (not flushed yet)
        self.mode: Optional[str] = None

    def done(self) -> bool:
        return self.stats is not None or self.error is not None

    def result(self) -> ExecStats:
        if self.error is not None:
            raise self.error
        if self.stats is None:
            raise RuntimeError(
                f"launch of @{self.kernel} not flushed yet "
                f"(call LaunchService.flush())")
        return self.stats

    def __repr__(self) -> str:   # pragma: no cover - debugging aid
        state = ("error" if self.error is not None
                 else "ok" if self.stats is not None else "pending")
        return (f"LaunchHandle(@{self.kernel}, tenant={self.tenant!r}, "
                f"grid={self.grid}, {state}, mode={self.mode})")


class LaunchService:
    """Async multi-tenant launch front-end over one :class:`Runtime`.

    Tenants ``submit()`` launches against their OWN buffer dicts into a
    bounded pending queue (overflow raises ``EngineBusy`` — the serve
    engine's backpressure contract); ``flush()`` drains it, coalescing
    compatible launches of the same compiled kernel — same decode-plan
    content hash, same block shape, same buffer signature, coalescing
    licence granted (``interp._coalesce_struct``) — into shared grid
    chunks via :func:`interp.launch_coalesced`.  Results are
    bit-identical to running each launch alone: stats are de-mixed per
    tenant by the striped accounting, buffers write back per tenant
    from the staging tables, and ANY group condition the coalesced
    driver cannot reproduce exactly (licence refusal at decode,
    desync, a kernel error, an injected fault, a deadline) aborts the
    group untouched and reruns every member through the normal
    ``Runtime.launch`` degradation chain — so faults, deadlines and
    breaker trips stay per-launch, never per-chunk.

    The runtime's governor context is shared: coalesced groups run
    against the same ``DevicePool`` and ``VOLT_MEM_BUDGET``, arm the
    tightest member deadline, are skipped while the kernel's circuit
    breaker is open (a demoting kernel must keep its per-launch chain),
    and pause after ``ABORT_STREAK`` consecutive aborts (re-probing
    every ``RETRY_EVERY`` flushes) so a persistently-refusing group
    stops paying the staging cost."""

    #: consecutive group aborts before a group key stops coalescing
    ABORT_STREAK = 3
    #: paused group keys re-probe coalescing every N-th flush
    RETRY_EVERY = 8

    def __init__(self, runtime: Runtime, *, max_pending: int = 256,
                 coalesce: bool = True,
                 pressure: Optional[float] = 0.5) -> None:
        self.rt = runtime
        self.max_pending = max_pending
        self.coalesce = coalesce
        #: latency-bounded flush: when the OLDEST queued launch has
        #: burned this fraction of its deadline budget just waiting in
        #: the queue, the next submit() drains everything — batching
        #: must never turn a deadline miss into a queueing artifact.
        #: None disables (explicit flush() only).
        self.pressure = pressure
        self._lock = threading.Lock()      # queue admission
        self._flush_lock = threading.Lock()  # serializes drains
        self._pending: List[Tuple[Any, ...]] = []
        self._aborts: Dict[Tuple[Any, ...], int] = {}
        self._cooldown: Dict[Tuple[Any, ...], int] = {}
        self.telemetry: Counter = Counter()
        self.last_abort: Optional[str] = None

    # -- admission ----------------------------------------------------------
    def submit(self, kernel_fn: Function, *, grid: int, block: int,
               buffers: Dict[str, np.ndarray],
               scalar_args: Optional[Dict[str, Any]] = None,
               deadline_ms: Optional[float] = None,
               tenant: Any = None) -> LaunchHandle:
        """Queue one launch of ``kernel_fn`` against ``buffers`` (the
        tenant's own dict — mutated in place exactly as
        ``Runtime.launch`` would).  Raises ``EngineBusy`` when the
        pending queue is full."""
        with self._lock:
            if len(self._pending) >= self.max_pending:
                self.telemetry["busy_rejections"] += 1
                raise EngineBusy(
                    f"launch queue full ({len(self._pending)}/"
                    f"{self.max_pending}); flush() or retry later")
            h = LaunchHandle(
                kernel_fn.name,
                tenant if tenant is not None else len(self._pending),
                grid, block)
            self._pending.append(
                (kernel_fn, grid, block, buffers, scalar_args,
                 deadline_ms, h, perf_counter()))
            urgent = self._deadline_pressure()
        if urgent:
            # drain OUTSIDE the admission lock (flush() takes it to
            # swap the queue; holding it here would deadlock)
            self.telemetry["pressure_flushes"] += 1
            self.flush()
        return h

    def _deadline_pressure(self) -> bool:
        """True when any queued launch (the oldest first — entries are
        in submission order) has burned more than ``self.pressure`` of
        its deadline budget waiting (caller holds ``self._lock``)."""
        if self.pressure is None or not self._pending:
            return False
        now = perf_counter()
        default_dl = self.rt.gov_cfg.deadline_ms if self.rt.govern \
            else None
        for entry in self._pending:
            dl = entry[5] if entry[5] is not None else default_dl
            if dl is None:
                continue
            if (now - entry[7]) * 1e3 >= self.pressure * dl:
                return True
        return False

    def pending(self) -> int:
        with self._lock:
            return len(self._pending)

    # -- drain --------------------------------------------------------------
    def flush(self) -> List[LaunchHandle]:
        """Drain the queue: group, coalesce where licensed, solo-run the
        rest.  Returns the drained handles in submission order; errors
        are STORED on their handle (``.result()`` re-raises), never
        raised from flush — one tenant's fault must not block the
        drain."""
        with self._lock:
            batch, self._pending = self._pending, []
        if not batch:
            return []
        with self._flush_lock:
            groups: Dict[Tuple[Any, ...], List[Tuple[Any, ...]]] = {}
            for entry in batch:
                groups.setdefault(self._group_key(entry), []).append(entry)
            for key, entries in groups.items():
                self._run_group(key, entries)
        return [entry[6] for entry in batch]

    def _group_key(self, entry: Tuple[Any, ...]) -> Tuple[Any, ...]:
        fn, grid, block, buffers, _scal, _dl, _h, _t = entry
        sig = []
        for p in fn.params:
            if p.ty is not Ty.PTR:
                continue
            b = buffers.get(p.name)
            if isinstance(b, np.ndarray):
                sig.append((p.name, b.shape, b.dtype.str))
            else:
                sig.append((p.name, None, None))
        return (_diskcache.plan_key(fn), block, self.rt.warp_size,
                tuple(sig))

    def _run_group(self, key: Tuple[Any, ...],
                   entries: List[Tuple[Any, ...]]) -> None:
        fn = entries[0][0]
        if (self.coalesce and len(entries) >= 2
                and self._may_coalesce(key, fn)
                and self._run_coalesced(key, fn, entries)):
            return
        for (fn_, grid, block, bufs, scal, dl, h, _t) in entries:
            self._run_solo(fn_, grid, block, bufs, scal, dl, h)

    def _may_coalesce(self, key: Tuple[Any, ...], fn: Function) -> bool:
        if _interp._coalesce_struct(fn) is None:
            self.telemetry["no_licence"] += 1
            return False
        rt = self.rt
        if rt.breaker is not None:
            # read-only peek: an open/half-open breaker means this
            # kernel is demoting — its launches need the full
            # per-launch chain (and the probe accounting), which only
            # the solo path runs
            st = rt.breaker.entry(key[0], fn.name)
            if st.state != "closed":
                self.telemetry["breaker_solo"] += 1
                return False
        if self._aborts.get(key, 0) >= self.ABORT_STREAK:
            cd = self._cooldown.get(key, self.RETRY_EVERY) - 1
            if cd > 0:
                self._cooldown[key] = cd
                self.telemetry["abort_paused"] += 1
                return False
            self._cooldown[key] = self.RETRY_EVERY
        return True

    def _run_coalesced(self, key: Tuple[Any, ...], fn: Function,
                       entries: List[Tuple[Any, ...]]) -> bool:
        rt = self.rt
        # cross-tenant aliasing: two queued launches sharing a buffer
        # must run sequentially (the second reads the first's output);
        # staged write-back would make them last-wins instead
        arrs = [[a for a in bufs.values() if isinstance(a, np.ndarray)]
                for (_f, _g, _b, bufs, _s, _d, _h, _t) in entries]
        for i in range(len(arrs)):
            for j in range(i + 1, len(arrs)):
                for a in arrs[i]:
                    for b in arrs[j]:
                        if np.shares_memory(a, b):
                            self.telemetry["alias_solo"] += 1
                            return False
        triples = []
        deadlines = []
        for (_f, grid, block, bufs, scal, dl, _h, _t) in entries:
            triples.append((bufs, scal, LaunchParams(
                grid=grid, local_size=block,
                warp_size=rt.warp_size)))
            if dl is None and rt.govern:
                dl = rt.gov_cfg.deadline_ms
            if dl is not None:
                deadlines.append(dl)
        deadline_ms = min(deadlines) if deadlines else None
        mem_budget = rt.mem_budget if rt.govern else None
        armed = False
        t0 = perf_counter()
        try:
            if deadline_ms is not None:
                # tightest member deadline governs the group; a trip
                # aborts it untouched and the solo reruns re-arm each
                # tenant's own budget
                _gov.arm_deadline(perf_counter() + deadline_ms * 1e-3,
                                  deadline_ms)
                armed = True
            with _faults.rung("grid"):
                stats = _interp.launch_coalesced(
                    fn, triples, pool=rt.pool, mem_budget=mem_budget,
                    workers=rt.workers)
        except _interp._CoalesceAbort as e:
            self._aborts[key] = self._aborts.get(key, 0) + 1
            self._cooldown[key] = self.RETRY_EVERY
            self.telemetry["group_aborts"] += 1
            self.last_abort = str(e)
            _tel("coalesce_aborts")
            return False
        finally:
            if armed:
                _gov.disarm_deadline()
        self._aborts.pop(key, None)
        self._cooldown.pop(key, None)
        wall_ms = (perf_counter() - t0) * 1e3
        self.telemetry["groups"] += 1
        self.telemetry["coalesced_launches"] += len(entries)
        _tel("coalesced_groups")
        _tel("coalesced_launches", len(entries))
        for (_f, _g, _b, _bufs, _s, _d, h, _t), st in zip(entries, stats):
            report = LaunchReport(kernel=fn.name)
            report.executor = "grid"
            report.wall_ms = wall_ms    # group wall: shared chunks
            report.attempts.append(LaunchAttempt(
                "grid", "grid", "ok",
                f"coalesced x{len(entries)}", wall_ms))
            rt._push_report(report)
            h.stats = st
            h.report = report
            h.mode = "coalesced"
            rt.last_stats = st
            _tel("launches")
            _tel_ctr("by_executor", "grid")
        if rt.breaker is not None:
            rt.breaker.record(key[0], fn.name, demoted=False,
                              final_rung="grid", probing=False)
        return True

    def _run_solo(self, fn: Function, grid: int, block: int,
                  bufs: Dict[str, np.ndarray],
                  scal: Optional[Dict[str, Any]],
                  dl: Optional[float], h: LaunchHandle) -> None:
        self.telemetry["solo_launches"] += 1
        try:
            h.stats = self.rt.launch(
                fn, grid=grid, block=block, scalar_args=scal,
                deadline_ms=dl, buffers=bufs)
        except Exception as e:
            h.error = e
            h.report = getattr(e, "report", None)
        else:
            h.report = self.rt.last_report
        h.mode = "solo"
