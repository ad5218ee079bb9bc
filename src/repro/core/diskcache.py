"""The on-disk caches, one module under the executors and the runtime.

Three kinds of file live in one directory, ``$VOLT_CACHE_DIR``, else
``<checkout>/.cache/volt``; ``VOLT_DISK_CACHE=0`` turns all three off:

  * ``.vck`` — a compiled kernel (the module after the pass pipeline),
    keyed by a CONTENT hash of the normalized pre-pipeline IR, the
    ``PassConfig`` fields, the warp size and the compiler's own source
    (``runtime.compile_kernel``);
  * ``.vdp`` — the interpreter's decode plan: the per-function static
    analysis (affine index facts, store privacy, hazard/cyclic
    classification, callee purity — ``interp._decode_plan``), keyed by
    ``plan_key``, a content hash of the function, its transitive callees
    and referenced globals, and the decoder's own source.  The decoded
    handler tables are closures and never persist;
  * ``.vjc`` — the jax rung's differential-certification verdicts
    (``{launch-shape-sig: (verdict, jax_ms, grid_ms, detail)}``), keyed
    by the same ``plan_key``.

Any IR edit changes the keys, so a stale entry is never returned; an
unreadable or corrupt entry is deleted and recomputed, and a failed
write never fails the caller.  Every read and write is counted in
``DISK_CACHE_STATS``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import pickle
import re
import tempfile
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

from . import faults as _faults
from .passes.pipeline import CompiledKernel, PassConfig
from .passes.uniformity import UniformityInfo
from .vir import Function, Module, Op

#: caches the program keeps inside its own checkout (git ignores it)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".cache"

#: process-lifetime counters of every disk-cache read and write
DISK_CACHE_STATS = {"hits": 0, "misses": 0, "errors": 0,
                    "decode_hits": 0, "decode_misses": 0,
                    "decode_errors": 0,
                    "cert_hits": 0, "cert_misses": 0, "cert_errors": 0}

_KERNEL_SCHEMA = 1

PLAN_SCHEMA = 1

# schema 2: verdicts gained the "pass-exact" tier — a schema-1 "pass"
# meant "certified at backend level 0" and must not promote a pair onto
# the optimized fast tier, so old files are discarded wholesale.
# schema 3: verdicts carry measured (jax_ms, grid_ms) per launch-shape
# class so the dispatch router can send small launches straight to the
# grid rung (the ~0.5 ms jitted-dispatch floor fix); schema-2 verdicts
# lack the timings and are discarded wholesale.
# schema 4: each verdict carries a detail (the first difference from the
# oracle, or the exception a tier raised), and its shape signature names
# the device it was certified on (platform, device_kind, JAX version)
VERDICT_SCHEMA = 4


def cache_dir() -> Optional[Path]:
    if os.environ.get("VOLT_DISK_CACHE", "1") == "0":
        return None
    d = os.environ.get("VOLT_CACHE_DIR")
    if d:
        return Path(d)
    return CHECKOUT_CACHE / "volt"


def atomic_write(path: Path, payload: bytes) -> None:
    """Crash-safe cache write: the payload lands in a same-directory tmp
    file, then ``os.replace`` commits it atomically — a crash (or an
    injected ``cache.commit`` fault) before the rename leaves only tmp
    debris, NEVER a truncated entry a concurrent reader could
    deserialize."""
    path.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=str(path.parent), suffix=".tmp")
    with os.fdopen(fd, "wb") as f:
        f.write(payload)
    if _faults.ACTIVE:
        _faults.maybe_fault("cache.commit")
    os.replace(tmp, path)


def _drop(path: Path) -> None:
    try:
        path.unlink()
    except OSError:
        pass


# --------------------------------------------------------------------------
# content hashes
# --------------------------------------------------------------------------

_TOKEN_RE = re.compile(r"%[A-Za-z_][\w.]*")


def normalize_ir(dump: str) -> str:
    """Rewrite process-dependent SSA/label tokens (%v123, %for.cond.17,
    %gid, ...) to dense first-appearance indices.  The renaming is
    INJECTIVE within one dump — distinct registers stay distinct — so
    operand swaps or retargeted branches still change the hash, while
    identical kernels built in fresh processes (different absolute id
    counters) normalize to the same text.  Float constants never follow
    a '%', so they survive untouched."""
    mapping: Dict[str, str] = {}

    def renum(m: "re.Match[str]") -> str:
        tok = m.group(0)
        new = mapping.get(tok)
        if new is None:
            new = f"%t{len(mapping)}"
            mapping[tok] = new
        return new

    return _TOKEN_RE.sub(renum, dump)


def _source_hash(files) -> str:
    h = hashlib.sha256()
    for f in files:
        try:
            h.update(f.name.encode())
            h.update(f.read_bytes())
        except OSError:
            pass
    return h.hexdigest()


_COMPILER_FP: Optional[str] = None
_DECODE_PLAN_FP: Optional[str] = None


def _compiler_fingerprint() -> str:
    """Hash of the compiler's own source (passes + IR + front-ends):
    folded into every ``.vck`` key so editing the pipeline invalidates
    entries compiled by the old code."""
    global _COMPILER_FP
    if _COMPILER_FP is None:
        root = Path(__file__).resolve().parent
        _COMPILER_FP = _source_hash(
            sorted((root / "passes").glob("*.py"))
            + sorted((root / "frontends").glob("*.py"))
            + [root / "vir.py", root / "graph.py"])
    return _COMPILER_FP


def _decode_plan_fingerprint() -> str:
    """Hash of the decoder's own source: editing the interpreter, the
    coalescing engine or the affine classifier invalidates plans
    computed by the old code."""
    global _DECODE_PLAN_FP
    if _DECODE_PLAN_FP is None:
        root = Path(__file__).resolve().parent
        _DECODE_PLAN_FP = _source_hash(
            (root / "interp.py", root / "interp_mem.py",
             root / "vir.py", root / "passes" / "analysis.py"))
    return _DECODE_PLAN_FP


def kernel_key(module: Module, kernel_name: str, config: PassConfig,
               warp_size: int) -> str:
    h = hashlib.sha256()
    h.update(repr((_KERNEL_SCHEMA, _compiler_fingerprint(),
                   kernel_name, dataclasses.astuple(config),
                   warp_size)).encode())
    h.update(normalize_ir(module.dump()).encode())
    return h.hexdigest()


def plan_key(fn: Function) -> str:
    """Content hash of ``fn`` + transitive callees + referenced globals
    (name/space/size matter: __shared__-ness changes hazard rules)."""
    cached = getattr(fn, "_decode_plan_key", None)
    if cached is not None and cached[0] == fn.ir_version:
        return cached[1]
    h = hashlib.sha256()
    h.update(repr((PLAN_SCHEMA, _decode_plan_fingerprint())).encode())
    seen = set()
    work = [fn]
    gvars = []
    while work:
        f = work.pop(0)
        if id(f) in seen:
            continue
        seen.add(id(f))
        h.update(normalize_ir(f.dump()).encode())
        for i in f.instructions():
            if i.op is Op.CALL:
                work.append(i.operands[0])
            for o in i.operands:
                if o.__class__.__name__ == "GlobalVar":
                    gvars.append((o.name, str(o.space), o.size,
                                  str(o.elem_ty)))
    h.update(repr(sorted(set(gvars))).encode())
    key = h.hexdigest()
    fn._decode_plan_key = (fn.ir_version, key)  # type: ignore
    return key


# --------------------------------------------------------------------------
# .vck: compiled kernels
# --------------------------------------------------------------------------

def _freeze_info(module: Module, info: UniformityInfo) -> Tuple:
    """id()-keyed divergence sets -> object lists (ids do not survive
    pickling; the objects do, with referential integrity)."""
    id2obj: Dict[int, Any] = {}
    for fn in module.functions.values():
        for b in fn.blocks:
            id2obj[id(b)] = b
            for i in b.instrs:
                id2obj[id(i)] = i
                if i.result is not None:
                    id2obj[id(i.result)] = i.result
                for o in i.operands:
                    id2obj[id(o)] = o
        for s in fn.slots:
            id2obj[id(s)] = s
    return tuple([id2obj[x] for x in ids if x in id2obj] for ids in (
        info.divergent_values, info.divergent_slots,
        info.divergent_exec, info.divergent_branches))


def _thaw_info(frozen: Tuple) -> UniformityInfo:
    dv, ds, de, db = frozen
    return UniformityInfo({id(o) for o in dv}, {id(o) for o in ds},
                          {id(o) for o in de}, {id(o) for o in db})


def load_kernel(key: str, kernel_name: str,
                config: PassConfig) -> Optional[CompiledKernel]:
    """The compiled kernel stored under ``key``, else None (a miss, a
    disabled cache or an unreadable entry, which is deleted)."""
    d = cache_dir()
    if d is None:
        return None
    path = d / (key + ".vck")
    if not path.exists():
        DISK_CACHE_STATS["misses"] += 1
        return None
    try:
        if _faults.ACTIVE:
            _faults.maybe_fault("cache.load")
        with open(path, "rb") as f:
            module, frozen, stats = pickle.load(f)
        ck = CompiledKernel(module, module.functions[kernel_name],
                            _thaw_info(frozen), config, stats)
    except Exception:
        DISK_CACHE_STATS["errors"] += 1
        _drop(path)
        DISK_CACHE_STATS["misses"] += 1
        return None
    DISK_CACHE_STATS["hits"] += 1
    return ck


def store_kernel(key: str, ck: CompiledKernel) -> None:
    d = cache_dir()
    if d is None:
        return
    try:
        if _faults.ACTIVE:
            _faults.maybe_fault("cache.store")
        payload = pickle.dumps(
            (ck.module, _freeze_info(ck.module, ck.info), ck.stats))
        atomic_write(d / (key + ".vck"), payload)
    except Exception:              # cache write failure never fails a
        DISK_CACHE_STATS["errors"] += 1   # compile


def clear() -> None:
    """Delete every ``.vck`` and ``.vdp`` entry of the cache directory."""
    d = cache_dir()
    if d is None or not d.exists():
        return
    for p in list(d.glob("*.vck")) + list(d.glob("*.vdp")):
        _drop(p)


# --------------------------------------------------------------------------
# .vdp: decode plans, .vjc: certification verdicts
# --------------------------------------------------------------------------

def load_plan(fn: Function) -> Optional[dict]:
    d = cache_dir()
    if d is None:
        return None
    path = d / (plan_key(fn) + ".vdp")
    if not path.exists():
        DISK_CACHE_STATS["decode_misses"] += 1
        return None
    try:
        if _faults.ACTIVE:
            _faults.maybe_fault("plan.load")
        with open(path, "rb") as f:
            plan = pickle.load(f)
        if plan.get("schema") != PLAN_SCHEMA:
            raise ValueError("decode plan schema mismatch")
        DISK_CACHE_STATS["decode_hits"] += 1
        return plan
    except Exception:
        DISK_CACHE_STATS["decode_errors"] += 1
        _drop(path)
        return None


def save_plan(fn: Function, plan: dict) -> None:
    d = cache_dir()
    if d is None:
        return
    try:
        if _faults.ACTIVE:
            _faults.maybe_fault("plan.store")
        atomic_write(d / (plan_key(fn) + ".vdp"), pickle.dumps(plan))
    except Exception:              # plan persistence is best-effort
        DISK_CACHE_STATS["decode_errors"] += 1


def load_verdicts(fn: Function) -> Optional[dict]:
    d = cache_dir()
    if d is None:
        return None
    path = d / (plan_key(fn) + ".vjc")
    if not path.exists():
        DISK_CACHE_STATS["cert_misses"] += 1
        return None
    try:
        with open(path, "rb") as f:
            rec = pickle.load(f)
        if rec.get("schema") != VERDICT_SCHEMA:
            raise ValueError("jax cert schema mismatch")
        certs = rec["certs"]
        if not isinstance(certs, dict):
            raise ValueError("jax cert payload is not a dict")
        DISK_CACHE_STATS["cert_hits"] += 1
        return certs
    except Exception:
        DISK_CACHE_STATS["cert_errors"] += 1
        _drop(path)
        return None


def save_verdicts(fn: Function, certs: dict) -> None:
    d = cache_dir()
    if d is None:
        return
    try:
        atomic_write(d / (plan_key(fn) + ".vjc"), pickle.dumps(
            {"schema": VERDICT_SCHEMA, "certs": certs}))
    except Exception:              # cert persistence is best-effort
        DISK_CACHE_STATS["cert_errors"] += 1
