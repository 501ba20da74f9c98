"""The AOT program store: serialized XLA executables keyed by program
signature, so a fresh process dispatches its first batch through an
ALREADY-COMPILED program — zero trace, zero compile, zero cold start.

Three layers (COMPILE.md):

1. an **in-process program table** ``key → jax.stages.Compiled`` — the
   executor consults it at dispatch (``compile.hits`` / ``.misses``);
2. a persisted, checksummed **program manifest**
   (``programs-manifest.json``, atomic tmp+``os.replace`` like every
   durable manifest in this codebase): one entry per observed program
   signature — fn fingerprint + arg shapes/dtypes/shardings + donate +
   mesh topology + backend — each entry carrying a self-crc and, when
   the program is *portable*, the name+crc of a serialized-executable
   file beside it;
3. **serialized executables** (``prog-<key>.bin``:
   ``jax.experimental.serialize_executable`` payload + arg/out
   treedefs, pickled, crc-checked): a fresh process
   :meth:`ProgramStore.ensure_restored`-s them straight into the table
   with NO live function at all — the true zero-cold-start path.

Identity & staleness: the fn fingerprint hashes the function's CODE
(bytecode + consts, recursively through wrapper chains) and its closure
CONTENTS — numpy closures (weights, codec scales) by bounded-sample
crc, so changed weights re-key. A closure holding a live ``jax.Array``
cannot be content-hashed without a device→host fetch (which the warm
path must never issue), so such programs are **non-portable**: their
signatures are still recorded (a relaunch re-lowers them from the live
fn — the trace cost — while the XLA compile rides the persistent
compilation cache), but no executable is serialized, so a stale-weights
program can never be restored. An explicit ``fn.aot_token`` (set it to
a content identity you own, e.g. a weights-artifact checksum) makes
any fn portable.

Misses compile in the background on a small pool (2 threads): the run
that OBSERVES a novel signature pays nothing extra on its hot path; the
NEXT process restores the result. Everything is fail-safe: a corrupt
manifest quarantines and starts empty, a corrupt or backend-mismatched
executable is skipped, a Compiled that refuses its args falls back to
the jitted path — the store can degrade to exactly today's behavior but
never take a run down.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import re
import sys
import time
import weakref
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from tpudl.testing import faults as _faults
from tpudl.testing import tsan as _tsan

__all__ = ["ProgramStore", "get_program_store", "reset_program_store",
           "aot_enabled", "store_dir", "warm_start", "fn_fingerprint",
           "backend_token", "MANIFEST_NAME", "MANIFEST_SCHEMA",
           "MANIFEST_VERSION", "EXE_PREFIX"]

MANIFEST_NAME = "programs-manifest.json"
MANIFEST_SCHEMA = "tpudl-programs"
MANIFEST_VERSION = 1
EXE_PREFIX = "prog-"

_TRUTHY = ("1", "on", "true", "yes")


def aot_enabled(value=None) -> bool:
    """Is the AOT program store armed? An explicit kwarg wins; else
    ``TPUDL_COMPILE_AOT`` — unset/``0``/``off`` = off, ``1`` (or a
    store-directory path) = on."""
    if value is not None:
        return bool(value)
    env = os.environ.get("TPUDL_COMPILE_AOT", "").strip()
    return env != "" and env.lower() not in ("0", "off", "false", "none")


def store_dir() -> str:
    """The program store directory: a path-valued ``TPUDL_COMPILE_AOT``
    names it directly; otherwise ``<compilation cache dir>/programs``
    (the two caches travel together — ``JAX_COMPILATION_CACHE_DIR``
    relocates both, :func:`tpudl.compile.cache.cache_dir`)."""
    env = os.environ.get("TPUDL_COMPILE_AOT", "").strip()
    if env and env.lower() not in _TRUTHY \
            and env.lower() not in ("0", "off", "false", "none"):
        return os.path.expanduser(env)
    from tpudl.compile.cache import cache_dir

    return os.path.join(cache_dir(), "programs")


def backend_token() -> dict:
    """The backend identity a serialized executable is valid for —
    platform + device kind + device count + jax version (a deserialized
    binary is an exact artifact of all four)."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "n_devices": len(devs),
            "jax": jax.__version__}


# -- fn fingerprinting -------------------------------------------------------

_FP_LOCK = _tsan.named_lock("compile.fingerprint_memo")
_FP_MEMO: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

_SAMPLE = 1 << 16  # closure-array crc sample bytes (head + tail)


def _hash_array(h, arr: np.ndarray) -> None:
    h.update(f"&nd{arr.dtype}{arr.shape}".encode())
    flat = arr.reshape(-1) if arr.flags["C_CONTIGUOUS"] \
        else np.ascontiguousarray(arr).reshape(-1)
    head = flat[: _SAMPLE // max(1, arr.itemsize)]
    tail = flat[-(_SAMPLE // max(1, arr.itemsize)):]
    h.update(zlib.crc32(head.tobytes()).to_bytes(4, "little"))
    h.update(zlib.crc32(tail.tobytes()).to_bytes(4, "little"))


def _hash_obj(h, obj, depth: int, seen: set, state: dict) -> None:
    """One closure/const value into the running fingerprint. Bounded
    depth + identity-set so cyclic wrapper graphs terminate. A live
    ``jax.Array`` marks the fingerprint NON-portable (its content
    cannot be hashed without a device fetch)."""
    if depth > 5 or id(obj) in seen:
        h.update(b"&deep")
        return
    seen.add(id(obj))
    tok = getattr(obj, "aot_token", None)
    if tok is not None and not callable(tok):
        h.update(f"&tok{tok}".encode())
        return
    if obj is None or isinstance(obj, (bool, int, float, complex, str,
                                       bytes)):
        h.update(f"&c{obj!r}".encode())
        return
    import types

    if isinstance(obj, types.ModuleType):
        # function-local imports land in closures: a module's identity
        # is its name — walking its namespace would hash half of jax
        # (and per-process object addresses with it)
        h.update(f"&mod{obj.__name__}".encode())
        return
    if isinstance(obj, type):
        h.update(f"&cls{obj.__module__}.{obj.__qualname__}".encode())
        return
    jax = sys.modules.get("jax")
    if jax is not None and isinstance(obj, jax.Array):
        # shape/dtype only — content-blind, so entries over this fn are
        # never serialized (stale weights could otherwise restore)
        h.update(f"&jax{obj.dtype}{obj.shape}".encode())
        state["portable"] = False
        return
    if isinstance(obj, np.ndarray):
        _hash_array(h, obj)
        return
    if jax is not None and isinstance(obj, jax.sharding.Mesh):
        # a Mesh in a closure (the tp generate path closes over it) is
        # topology, not content: hash axis names + grid shape. The
        # generic walk below would reach the `devices` object ndarray
        # and hash per-process POINTERS — a fingerprint that never
        # matches across runs
        h.update(f"&mesh{dict(obj.shape)!r}".encode())
        return
    code = getattr(obj, "__code__", None)
    if code is not None:
        h.update(f"&fn{getattr(obj, '__qualname__', '?')}".encode())
        h.update(hashlib.sha1(code.co_code).digest())
        for const in code.co_consts:
            if hasattr(const, "co_code"):
                h.update(hashlib.sha1(const.co_code).digest())
            else:
                h.update(f"&k{const!r}".encode())
        for cell in (obj.__closure__ or ()):
            _hash_obj(h, cell.cell_contents, depth + 1, seen, state)
        for d in (obj.__defaults__ or ()):
            _hash_obj(h, d, depth + 1, seen, state)
        # a BOUND METHOD's state lives on __self__, not in cells: two
        # models of one class with different weights baked into self
        # must re-key (module GLOBALS remain out of scope — set
        # fn.aot_token for global-state programs, COMPILE.md)
        owner = getattr(obj, "__self__", None)
        if owner is not None:
            _hash_obj(h, owner, depth + 1, seen, state)
        return
    if isinstance(obj, (tuple, list)):
        h.update(f"&seq{len(obj)}".encode())
        for v in obj[:32]:
            _hash_obj(h, v, depth + 1, seen, state)
        return
    if isinstance(obj, dict):
        h.update(f"&map{len(obj)}".encode())
        for k in sorted(obj, key=repr)[:32]:
            h.update(f"&k{k!r}".encode())
            _hash_obj(h, obj[k], depth + 1, seen, state)
        return
    inner = getattr(obj, "__wrapped__", None) or getattr(obj, "func",
                                                         None)
    if inner is not None and inner is not obj:
        # a jit/partial/shim wrapper: identity lives in what it wraps.
        # args/keywords only when they are REAL bound values (a class
        # or slotted object answers getattr with a descriptor)
        _hash_obj(h, inner, depth + 1, seen, state)
        args = getattr(obj, "args", None)
        if isinstance(args, (tuple, list)):
            for a in args:
                _hash_obj(h, a, depth + 1, seen, state)
        kw = getattr(obj, "keywords", None)
        if isinstance(kw, dict):
            for k, v in sorted(kw.items()):
                h.update(f"&k{k}".encode())
                _hash_obj(h, v, depth + 1, seen, state)
        return
    t = type(obj)
    h.update(f"&o{t.__module__}.{t.__qualname__}".encode())
    attrs = getattr(obj, "__dict__", None)
    if isinstance(attrs, dict) and attrs:
        # content-walk instance state (bounded): covers weights held as
        # attributes (a bound method's model), and avoids the default
        # repr's per-process memory address, which would make the key
        # never match across runs
        for k in sorted(attrs)[:32]:
            h.update(f"&k{k}".encode())
            _hash_obj(h, attrs[k], depth + 1, seen, state)
    else:
        # leaf object: repr, with memory addresses stripped (a lock or
        # opaque handle must degrade to type identity, not a value that
        # re-keys every process)
        h.update(re.sub(r"0x[0-9a-fA-F]+", "0x",
                        repr(obj)[:256]).encode())


def fn_fingerprint(fn) -> tuple[str | None, bool]:
    """``(sha1-hex, portable)`` identity of a program's function —
    stable ACROSS processes for the same source + same closure
    contents. ``None`` when no identity is derivable (the store then
    stands aside for this fn). Memoized per live fn object (the warm
    dispatch path calls this per batch)."""
    try:
        with _FP_LOCK:
            cached = _FP_MEMO.get(fn)
    except TypeError:
        cached = None
    if cached is not None:
        return cached
    tok = getattr(fn, "aot_token", None)
    if tok is not None and not callable(tok):
        out: tuple[str | None, bool] = (
            hashlib.sha1(f"token:{tok}".encode()).hexdigest(), True)
    else:
        h = hashlib.sha1()
        state = {"portable": True}
        _hash_obj(h, fn, 0, set(), state)
        digest = h.hexdigest()
        # a fingerprint that saw no code object anywhere is just a
        # type repr — too weak to key a compiled binary on
        found_code = hasattr(fn, "__code__") or \
            getattr(fn, "__wrapped__", None) is not None or \
            getattr(fn, "func", None) is not None
        out = (digest if found_code else None, state["portable"])
    try:
        with _FP_LOCK:
            _FP_MEMO[fn] = out
    except TypeError:
        pass
    return out


# -- program signatures ------------------------------------------------------

def _sharding_token(x) -> str:
    """Sharding identity of one leaf — shared by live arrays AND
    ``ShapeDtypeStruct`` avals so a warmup-declared signature keys
    identically to the dispatch-time one."""
    sh = getattr(x, "sharding", None)
    if sh is not None and hasattr(sh, "spec"):
        mesh = getattr(sh, "mesh", None)
        axes = dict(getattr(mesh, "shape", {}) or {})
        return f"P{tuple(sh.spec)}|{sorted(axes.items())}"
    # single-device jax arrays and host numpy share one token: a
    # host-lowered executable accepts either (the runtime places host
    # args), so a warmup-declared aval must key like the live array
    return "host"


def _mesh_axes_of_token(tok) -> dict | None:
    """Structured ``{axis: size}`` topology parsed back out of a leaf
    sharding token (``"P(...)|[('data', 4), ('model', 2)]"``) — what
    manifest audits (tools/validate_programs.py) compare, so 1-D and
    2-D entries can be told apart without re-parsing token strings."""
    if not tok or "|" not in tok:
        return None
    import ast

    try:
        pairs = ast.literal_eval(tok.split("|", 1)[1])
        return {str(k): int(v) for k, v in pairs}
    except (ValueError, SyntaxError, TypeError):
        return None


def signature_of(args) -> dict:
    """JSON-shippable signature of one positional-arg tuple (live
    arrays or avals): pytree structure + per-leaf (shape, dtype,
    sharding token)."""
    import jax

    leaves, treedef = jax.tree.flatten(tuple(args))
    return {"tree": str(treedef),
            "leaves": [[list(np.shape(x)),
                        str(getattr(x, "dtype", None)
                            if getattr(x, "dtype", None) is not None
                            else np.asarray(x).dtype),
                        _sharding_token(x)] for x in leaves]}


def _avals_of(args):
    """ShapeDtypeStructs (sharding-carrying for sharded leaves) for
    ``fn.lower(*avals)`` — built EAGERLY from live args so the
    background compile retains no batch data."""
    import jax

    def aval(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x  # warmup-declared aval (sharding preserved)
        if isinstance(x, jax.Array) and getattr(x, "sharding", None) \
                is not None and hasattr(x.sharding, "spec"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=x.sharding)
        a = x if hasattr(x, "shape") and hasattr(x, "dtype") \
            else np.asarray(x)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    return jax.tree.map(aval, tuple(args))


def _entry_crc(entry: dict) -> int:
    """Self-checksum over the entry's canonical JSON (sans the crc
    field itself) — the validator's torn-manifest tripwire."""
    body = {k: v for k, v in entry.items() if k != "crc"}
    return zlib.crc32(json.dumps(body, sort_keys=True,
                                 default=str).encode()) & 0xFFFFFFFF


def _crc32_file(path: str, chunk: int = 1 << 20) -> int:
    crc = 0
    with open(path, "rb") as f:
        while True:
            buf = f.read(chunk)
            if not buf:
                break
            crc = zlib.crc32(buf, crc)
    return crc & 0xFFFFFFFF


def _metrics():
    """The obs metrics surface, or None in a minimal subprocess —
    every publication site is best-effort: a broken registry must not
    kill a compile that already succeeded."""
    try:
        from tpudl.obs import metrics as _m

        return _m
    except Exception:  # minimal subprocess without obs: None-checked
        return None


class ProgramStore:
    """One store directory: manifest + serialized executables + the
    live program table. Thread-safe (dispatch pool, prepare pool and
    the background compiler all touch it)."""

    def __init__(self, root: str):
        self.root = str(root)
        self._lock = _tsan.named_lock("compile.program_store")
        self._table: dict = {}          # key -> jax.stages.Compiled
        self._entries: dict = {}        # key -> manifest entry
        self._ladder_meta: dict | None = None
        self._pending: set = set()      # keys queued/compiling
        self._restore_state: str | None = None  # None|"pending"|"done"
        self._pool: ThreadPoolExecutor | None = None
        self._futures: list = []
        os.makedirs(self.root, exist_ok=True)
        self._load_manifest()
        self._sweep_stale_files()

    # -- manifest ----------------------------------------------------------
    def _manifest_path(self) -> str:
        return os.path.join(self.root, MANIFEST_NAME)

    def _load_manifest(self) -> None:
        path = self._manifest_path()
        try:
            with open(path) as f:
                m = json.load(f)
        except FileNotFoundError:
            return
        except (OSError, json.JSONDecodeError):
            m = None
        if not isinstance(m, dict) or m.get("schema") != MANIFEST_SCHEMA:
            # corrupt/foreign: quarantine beside (forensics) and start
            # empty — the store must never take a process down
            mm = _metrics()
            if mm is not None:
                mm.counter("compile.store_corrupt").inc()
            try:
                os.replace(path, path + ".corrupt")
            except OSError:
                pass
            return
        entries = m.get("entries")
        if isinstance(entries, dict):
            self._entries = {k: v for k, v in entries.items()
                             if isinstance(v, dict)}
        lad = m.get("ladder")
        if isinstance(lad, dict):
            self._ladder_meta = lad

    def _sweep_stale_files(self) -> None:
        """Unlink executables and tmp leftovers no manifest entry
        references — the artifact of a crash between a bin's publish
        and its manifest seal (the entry then still reads
        ``exe: null``). Age-guarded: a file younger than a minute may
        be another process's in-flight persist on a shared store."""
        try:
            now = time.time()
            referenced = {e.get("exe") for e in self._entries.values()
                          if e.get("exe")}
            for name in os.listdir(self.root):
                if not name.startswith(EXE_PREFIX) or name in referenced:
                    continue
                if not (name.endswith(".bin") or ".tmp." in name):
                    continue
                path = os.path.join(self.root, name)
                try:
                    if now - os.stat(path).st_mtime < 60:
                        continue
                    os.unlink(path)
                except OSError:
                    pass
        except OSError:  # unreadable dir: the store still works
            pass

    def _write_manifest_locked(self) -> None:
        m = {"schema": MANIFEST_SCHEMA, "version": MANIFEST_VERSION,
             "backend": self._backend_or_none(),
             "ladder": self._ladder_meta,
             "updated_ts": time.time(),
             "entries": self._entries}
        tmp = self._manifest_path() + f".tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(m, f)
            os.replace(tmp, self._manifest_path())
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    @staticmethod
    def _backend_or_none():
        try:
            return backend_token()
        except Exception:  # jax not initialized yet: manifest-only use
            return None

    def note_ladder(self, ladder) -> None:
        """Record the bucket ladder this store's signatures were
        observed under (validator: shapes↔ladder consistency)."""
        meta = ladder.to_meta() if ladder is not None else None
        with self._lock:
            if meta != self._ladder_meta:
                self._ladder_meta = meta
                self._write_manifest_locked()

    def entries(self) -> dict:
        with self._lock:
            return dict(self._entries)

    def programs(self) -> int:
        with self._lock:
            return len(self._table)

    # -- keys --------------------------------------------------------------
    def _key_for(self, fp: str, sig: dict, donate: bool) -> str:
        h = hashlib.sha1()
        h.update(fp.encode())
        h.update(json.dumps(sig, sort_keys=True).encode())
        h.update(b"donate" if donate else b"plain")
        try:
            h.update(json.dumps(backend_token(),
                                sort_keys=True).encode())
        except Exception:  # pre-backend probes: still a stable key
            h.update(b"nobackend")
        return h.hexdigest()

    # -- the dispatch-path hook -------------------------------------------
    def call(self, fn, args, *, donate: bool = False,
             bucketed: bool = False, report=None):
        """Run one dispatch THROUGH the store: a table hit executes the
        precompiled program (no trace possible). The FIRST miss of a
        signature AOT-compiles it inline — the same trace+compile the
        jitted path was about to pay, so the miss costs one compile,
        not two — inserts it into the table, and serializes+persists in
        the background; concurrent misses of the same key (and any
        store trouble) fall back to the jitted ``fn``, behavior-
        identical by construction."""
        fp, portable = fn_fingerprint(fn)
        if fp is None or not hasattr(fn, "lower"):
            return fn(*args)
        sig = signature_of(args)
        key = self._key_for(fp, sig, donate)
        with self._lock:
            exe = self._table.get(key)
        if exe is not None:
            try:
                out = exe(*args)
                mm = _metrics()
                if mm is not None:
                    mm.counter("compile.hits").inc()
                if report is not None:
                    report.count("aot_hits")
                return out
            except Exception:
                # arg/backend drift the key failed to capture: drop the
                # program, run the honest path, count the evidence
                mm = _metrics()
                if mm is not None:
                    mm.counter("compile.exec_failed").inc()
                with self._lock:
                    self._table.pop(key, None)
                if donate:
                    # a DONATING executable may have consumed its input
                    # buffers before failing — re-running fn on deleted
                    # args would bury the real fault under a
                    # buffer-deleted error; the original propagates to
                    # the supervisor's classifier instead
                    raise
        mm = _metrics()
        if mm is not None:
            mm.counter("compile.misses").inc()
        if report is not None:
            report.count("aot_misses")
        with self._lock:
            claimed = key not in self._pending
            if claimed:
                self._pending.add(key)
                if key not in self._entries:
                    self._entries[key] = self._new_entry(
                        sig, fn_fp=fp, donate=donate,
                        portable=portable, bucketed=bucketed)
                    self._seal_entry_locked(key)
                    self._write_manifest_locked()
                    observed = True
                else:
                    observed = False
        if not claimed:
            # another thread owns this key's compile: the plain jitted
            # path is the honest concurrent fallback
            return fn(*args)
        if observed:
            mm = _metrics()
            if mm is not None:
                mm.counter("compile.observed").inc()
        try:
            compiled = self._build(fn, key, _avals_of(args))
        except BaseException:
            with self._lock:
                self._pending.discard(key)
            mm = _metrics()
            if mm is not None:
                mm.counter("compile.store_corrupt").inc()
            return fn(*args)  # an exotic fn .lower refuses: jit path
        # persistence (serialize + write + manifest) rides the pool —
        # pending is released by the task; the dispatch returns as soon
        # as the program ran
        self._submit(self._persist_task, key, compiled, portable)
        return compiled(*args)

    def _new_entry(self, sig: dict, *, fn_fp: str, donate: bool,
                   portable: bool, bucketed: bool) -> dict:
        mesh_tok = None
        for leaf in sig["leaves"]:
            if leaf[2] not in ("host", "device"):
                mesh_tok = leaf[2]
                break
        return {"fn": fn_fp, "tree": sig["tree"],
                "leaves": sig["leaves"], "donate": bool(donate),
                "portable": bool(portable), "bucketed": bool(bucketed),
                "mesh": mesh_tok,
                "mesh_axes": _mesh_axes_of_token(mesh_tok),
                "backend": self._backend_or_none(),
                "created_ts": time.time(), "compile_s": None,
                "exe": None, "exe_crc32": None, "exe_nbytes": None}

    def _seal_entry_locked(self, key: str) -> None:
        entry = self._entries[key]
        entry["crc"] = _entry_crc(entry)

    def _submit(self, task, *a) -> None:
        with self._lock:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=2, thread_name_prefix="tpudl-aot")
            fut = self._pool.submit(task, *a)
            self._futures.append(fut)
            del self._futures[:-64]  # bounded: drained futures only

    def _build(self, fn, key, avals):
        """Lower+compile one signature from the live fn and insert it
        into the program table. The deterministic ``compile.precompile``
        fault point fires per program — a kill here must leave a valid
        manifest behind (the entry was already written atomically; its
        ``exe`` stays null until the persist completes)."""
        _faults.fire("compile.precompile", key=key[:12])
        t0 = time.perf_counter()
        compiled = fn.lower(*avals).compile()
        dt = time.perf_counter() - t0
        with self._lock:
            self._table[key] = compiled
            entry = self._entries.get(key)
            if entry is not None:
                entry["compile_s"] = round(dt, 4)
                self._seal_entry_locked(key)
        mm = _metrics()
        if mm is not None:
            mm.counter("compile.programs_compiled").inc()
            mm.counter("compile.aot_s").inc(dt)
            # attribution pairing with compile.aot_s (same guard: a
            # minimal subprocess without obs charges neither side): a
            # dispatch-path miss charges the dispatching scope (carried
            # onto the window thread); a background warm/restore build
            # runs scope-free and lands in unattributed — both
            # reconcile
            from tpudl.obs import attribution as _attr

            _attr.charge("compile_s", dt)
        return compiled

    def _persist_task(self, key, compiled, portable) -> None:
        try:
            self._persist_exe(key, compiled, portable)
        except Exception:
            # the background pool's backstop: the program already runs
            # from the table; only its durability was lost
            mm = _metrics()
            if mm is not None:
                mm.counter("compile.store_corrupt").inc()
        finally:
            with self._lock:
                self._pending.discard(key)

    def _persist_exe(self, key, compiled, portable) -> None:
        """Serialize one compiled program beside the manifest and seal
        its entry. Bin first, manifest second, both atomic: a crash
        between the two leaves a bin whose entry still reads
        ``exe: null`` — the validator recognizes that in-flight shape
        and the next store open sweeps it (never an integrity error,
        never a partial file)."""
        exe_name = exe_crc = exe_nbytes = None
        if portable:
            try:
                from jax.experimental import serialize_executable as se

                blob = pickle.dumps(se.serialize(compiled))
                import threading

                exe_name = f"{EXE_PREFIX}{key}.bin"
                tmp = os.path.join(
                    self.root, f"{exe_name}.tmp.{os.getpid()}."
                               f"{threading.get_ident()}")
                with open(tmp, "wb") as f:
                    f.write(blob)
                os.replace(tmp, os.path.join(self.root, exe_name))
                exe_crc = zlib.crc32(blob) & 0xFFFFFFFF
                exe_nbytes = len(blob)
            except Exception:  # unserializable backend: table-only
                exe_name = exe_crc = exe_nbytes = None
                mm = _metrics()
                if mm is not None:
                    mm.counter("compile.serialize_failed").inc()
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                entry["exe"] = exe_name
                entry["exe_crc32"] = exe_crc
                entry["exe_nbytes"] = exe_nbytes
                entry["backend"] = self._backend_or_none()
                self._seal_entry_locked(key)
                self._write_manifest_locked()

    # -- explicit (warmup-path) compilation --------------------------------
    def compile_signature(self, fn, args_or_avals, *,
                          donate: bool = False, bucketed: bool = False,
                          block: bool = True) -> bool:
        """AOT-compile ``fn`` at one declared signature — the warmup
        entry point (``ImageBatchWarmup``, ``TinyCausalLM``): no
        synthetic batch, no real-data trace, no device execution.
        Returns True when the program is (or already was) in the
        table."""
        fp, portable = fn_fingerprint(fn)
        if fp is None or not hasattr(fn, "lower"):
            return False
        sig = signature_of(args_or_avals)
        key = self._key_for(fp, sig, donate)
        for _attempt in range(2):
            with self._lock:
                if key in self._table:
                    return True
                claimed = key not in self._pending
                if claimed:
                    self._pending.add(key)
                    if key not in self._entries:
                        self._entries[key] = self._new_entry(
                            sig, fn_fp=fp, donate=donate,
                            portable=portable, bucketed=bucketed)
                        self._seal_entry_locked(key)
                        self._write_manifest_locked()
            if claimed:
                avals = _avals_of(args_or_avals)
                if block:
                    try:
                        compiled = self._build(fn, key, avals)
                        self._persist_exe(key, compiled, portable)
                    finally:
                        with self._lock:
                            self._pending.discard(key)
                    return True
                self._submit(self._warm_task, fn, key, avals, portable)
                return True
            # another thread (a dispatch miss's persist) owns this key:
            # never race it onto the same tmp file or strip its pending
            # marker — wait it out, then re-check (one more claim
            # attempt covers a failed background task)
            if not block:
                return True
            self.drain(180)
        with self._lock:
            return key in self._table

    def _warm_task(self, fn, key, avals, portable) -> None:
        try:
            compiled = self._build(fn, key, avals)
            self._persist_exe(key, compiled, portable)
        except Exception:
            mm = _metrics()
            if mm is not None:
                mm.counter("compile.store_corrupt").inc()
        finally:
            with self._lock:
                self._pending.discard(key)

    # -- restore -----------------------------------------------------------
    def ensure_restored(self, block: bool = False) -> int:
        """Deserialize every persisted executable valid for THIS
        backend into the program table — the fresh-process warm start.
        Idempotent once COMPLETE; an attempt that could not reach the
        backend resets so a later call retries instead of latching the
        process cold forever. ``block=False`` runs on the background
        pool (the executor's setup path must not stall on a big
        store); a later ``block=True`` call waits for an in-flight
        background restore rather than skipping it. Returns the number
        restored by THIS call (0 when deferred/waited)."""
        with self._lock:
            if self._restore_state == "done":
                return 0
            waiting = self._restore_state == "pending"
            if not waiting:
                self._restore_state = "pending"
                todo = [(k, dict(e)) for k, e in self._entries.items()
                        if e.get("exe")]
        if waiting:
            if block:
                self.drain(180)  # the background restore finishes first
            return 0
        if not todo:
            with self._lock:
                self._restore_state = "done"
            return 0
        if block:
            n, completed = self._restore_entries(todo)
            with self._lock:
                self._restore_state = "done" if completed else None
            return n
        self._submit(self._restore_task, todo)
        return 0

    def _restore_task(self, todo) -> None:
        n, completed = self._restore_entries(todo)
        with self._lock:
            self._restore_state = "done" if completed else None

    def _restore_entries(self, todo) -> tuple[int, bool]:
        """(restored count, completed): ``completed=False`` means the
        backend was unreachable and the whole pass should retry later;
        per-entry failures (corrupt/foreign binaries) are final."""
        try:
            backend = backend_token()
        except Exception:
            return 0, False  # backend not up yet: retryable
        try:
            from jax.experimental import serialize_executable as se
        except Exception:
            return 0, True  # this jax cannot deserialize, ever
        n = 0
        t0 = time.perf_counter()
        for key, entry in todo:
            if entry.get("backend") != backend:
                continue  # another topology's binary: not stale, not ours
            path = os.path.join(self.root, str(entry["exe"]))
            try:
                if _crc32_file(path) != entry.get("exe_crc32"):
                    mm = _metrics()
                    if mm is not None:
                        mm.counter("compile.store_corrupt").inc()
                    continue
                with open(path, "rb") as f:
                    payload, in_tree, out_tree = pickle.loads(f.read())
                exe = se.deserialize_and_load(payload, in_tree,
                                              out_tree)
            except Exception:
                # a stale/foreign binary: skipped, the jit path covers
                # it (the counter is the staleness evidence)
                mm = _metrics()
                if mm is not None:
                    mm.counter("compile.deserialize_failed").inc()
                continue
            with self._lock:
                self._table.setdefault(key, exe)
            n += 1
        if n:
            mm = _metrics()
            if mm is not None:
                mm.counter("compile.programs_restored").inc(n)
                mm.counter("compile.aot_s").inc(
                    time.perf_counter() - t0)
        return n, True

    def drain(self, timeout: float | None = None) -> None:
        """Wait for every queued background compile/restore (tests,
        and a process that must persist before exiting)."""
        with self._lock:
            futs = list(self._futures)
        for f in futs:
            try:
                f.result(timeout)
            # tpudl: ignore[swallowed-except] — drain reports nothing:
            # each task already counted its own failure
            except Exception:
                pass


# -- the process-wide store --------------------------------------------------

_STORE: ProgramStore | None = None
_STORE_LOCK = _tsan.named_lock("compile.store.singleton")


def get_program_store() -> ProgramStore:
    """The process-wide store at the CURRENT ``store_dir()`` (a changed
    env — tests, child processes — transparently re-roots)."""
    global _STORE
    root = store_dir()
    with _STORE_LOCK:
        if _STORE is None or _STORE.root != root:
            _STORE = ProgramStore(root)
        return _STORE


def reset_program_store() -> None:
    global _STORE
    with _STORE_LOCK:
        _STORE = None


def warm_start(block: bool = True) -> int:
    """Restore the persisted program store (no-op unarmed) — call it
    first thing in a serving process so the first batch dispatches
    through restored executables. ``jobs`` calls it on resume; the
    executor calls the non-blocking form at run setup."""
    if not aot_enabled():
        return 0
    return get_program_store().ensure_restored(block=block)
