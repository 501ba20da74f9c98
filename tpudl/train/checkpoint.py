"""Checkpoint / resume — first-class, unlike the reference.

SURVEY.md §5.4: the reference only *consumes* checkpoints
(TFInputGraph.fromCheckpoint) and returns final HDF5 blobs; there is no
periodic checkpoint/resume loop anywhere in its tree. Here it is a core
subsystem: atomic checksummed snapshots of the whole training state
(params + opt_state + step + data cursor), periodic saves, latest-wins
restore — the substrate for the Runner's fault recovery (§5.3: SPMD
programs die together; recovery is restart-from-last-checkpoint) and
the job runtime's resume state (JOBS.md).

Durability contract (the shard-manifest contract, applied to model
state — a checkpoint a preempted run will bet its resume on must be
trustworthy the way the prepared-batch cache is):

- **atomic writes** — each step is ONE ``ckpt-<step>.npz`` written to
  a temp name and ``os.replace``d into place, then indexed in
  ``ckpt-manifest.json`` (itself tmp+rename). A kill at ANY byte
  leaves either the previous state or the new one, never a torn file
  that parses;
- **checksums** — the manifest records crc32 + byte size per
  checkpoint; ``restore`` verifies before trusting;
- **corruption → fall back, not crash** — a truncated/bit-flipped/
  unparseable newest checkpoint is dropped (``train.checkpoint.corrupt``
  counter + a flight-recorder error sample) and ``restore()`` falls
  back to the newest VALID step; only when no step survives does it
  return None (fresh start — the honest answer).

Leaves are stored as raw bytes + (shape, dtype) metadata rather than
native ``.npy`` entries: ``np.save`` silently degrades non-builtin
dtypes (bfloat16 → V2 void), and a checkpoint that changes dtype on
round-trip is corruption with extra steps. ``restore(like=...)`` puts
each leaf back onto the `like` leaf's sharding, so TP-sharded state
comes back device-sharded (not gathered). Scope: ``save`` gathers
single-host sharded leaves to host bytes; state spanning
NON-addressable devices (multi-host) is refused with a clear error —
gather it (``multihost_utils.process_allgather``) before saving.
"""

from __future__ import annotations

import io
import json
import os
import threading

import jax
import numpy as np

# the ONE chunked-crc32 helper (tools/validate_job.py keeps its own
# copy on purpose: validators stay stdlib-pure, importing no tpudl)
from tpudl.data.shards import _crc32_file
from tpudl.testing import tsan as _tsan

__all__ = ["CheckpointManager", "CheckpointCorruption", "as_numpy_state"]

MANIFEST_NAME = "ckpt-manifest.json"
MANIFEST_SCHEMA = "tpudl-checkpoint-manifest"
MANIFEST_VERSION = 1
PAYLOAD_VERSION = 1


class CheckpointCorruption(Exception):
    """A checkpoint failed its integrity check (restore() converts it
    into a fallback to the next-newest valid step)."""


def _resolve_dtype(name: str) -> np.dtype:
    """dtype by saved name, including the ml_dtypes extended set
    (bfloat16, float8_*) numpy alone cannot construct by string."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _path_components(path) -> list:
    """One tree_flatten_with_path key path → JSON-able components
    (enough to rebuild nested dict/list states for like-less restore;
    exotic containers round-trip through ``like=`` instead)."""
    comps = []
    for k in path:
        if hasattr(k, "key"):
            comps.append({"t": "key", "k": str(k.key)})
        elif hasattr(k, "idx"):
            comps.append({"t": "idx", "i": int(k.idx)})
        elif hasattr(k, "name"):
            comps.append({"t": "attr", "k": str(k.name)})
        else:  # pragma: no cover - future key kinds
            comps.append({"t": "key", "k": str(k)})
    return comps


class CheckpointManager:
    """Atomic checksummed store of the {params, opt_state, step, ...}
    training-state pytree under one directory."""

    def __init__(self, directory: str, *, save_every: int = 100,
                 max_to_keep: int = 3):
        self._dir = os.path.abspath(directory)
        os.makedirs(self._dir, exist_ok=True)
        self.save_every = int(save_every)
        self.max_to_keep = int(max_to_keep)
        self._lock = _tsan.named_lock("train.checkpoint.manifest")
        self._manifest: dict[str, dict] = {}
        self._load_manifest()

    # -- manifest ----------------------------------------------------------
    def _manifest_path(self) -> str:
        return os.path.join(self._dir, MANIFEST_NAME)

    def _file_for(self, step: int) -> str:
        return os.path.join(self._dir, f"ckpt-{int(step):08d}.npz")

    def _load_manifest(self) -> None:
        try:
            with open(self._manifest_path()) as f:
                m = json.load(f)
            if (isinstance(m, dict) and m.get("schema") == MANIFEST_SCHEMA
                    and isinstance(m.get("checkpoints"), dict)):
                self._manifest = m["checkpoints"]
            else:
                self._manifest = {}
        except (OSError, json.JSONDecodeError):
            self._manifest = {}

    def _write_manifest_locked(self) -> None:
        """Raises OSError on failure: ``save()`` must not report a
        checkpoint durable-and-indexed when the index write was lost —
        an unindexed file is only reachable through the orphan scan,
        which cannot size/crc-verify it. Maintenance callers (prune,
        corrupt-drop) tolerate the failure themselves."""
        m = {"schema": MANIFEST_SCHEMA, "version": MANIFEST_VERSION,
             "checkpoints": self._manifest}
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
            raise

    # -- write -------------------------------------------------------------
    def due(self, step: int) -> bool:
        """Whether ``step`` hits the save cadence."""
        return self.save_every > 0 and step % self.save_every == 0

    def save(self, step: int, state: dict, *, force: bool = False) -> bool:
        """Save if ``step`` hits the cadence (or ``force``). Blocking
        and durable-before-return is deliberate: resume-equivalence
        (and the job runtime's bounded-rework contract) require the
        write to be on disk before the step counter advances."""
        if not force and not self.due(step):
            return False
        leaves = jax.tree_util.tree_flatten_with_path(state)[0]
        meta = {"version": PAYLOAD_VERSION, "step": int(step),
                "leaves": []}
        entries: dict[str, np.ndarray] = {}
        for i, (path, leaf) in enumerate(leaves):
            if getattr(leaf, "is_fully_addressable", True) is False:
                # multi-host sharded state: np.asarray would raise an
                # opaque RuntimeError mid-save. Name the gap instead —
                # this store checkpoints host-visible state; gather
                # (multihost_utils.process_allgather) before saving
                raise NotImplementedError(
                    f"CheckpointManager.save: leaf "
                    f"{jax.tree_util.keystr(path)} spans non-"
                    "addressable devices (multi-host sharding); gather "
                    "it host-side before checkpointing")
            # NOT ascontiguousarray: it silently promotes 0-d scalars
            # to shape (1,); tobytes() already yields C-order bytes for
            # any layout
            arr = np.asarray(leaf)
            entries[f"leaf_{i:05d}"] = np.frombuffer(
                arr.tobytes(), dtype=np.uint8)
            meta["leaves"].append({
                "key": jax.tree_util.keystr(path),
                "path": _path_components(path),
                "shape": list(arr.shape), "dtype": str(arr.dtype)})
        entries["__meta__"] = np.frombuffer(
            json.dumps(meta).encode(), dtype=np.uint8)
        out = self._file_for(step)
        tmp = out + f".tmp.{os.getpid()}.{threading.get_ident()}"
        try:
            with open(tmp, "wb") as f:
                np.savez(f, **entries)
                f.flush()
                os.fsync(f.fileno())
            crc = _crc32_file(tmp)
            nbytes = os.stat(tmp).st_size
            os.replace(tmp, out)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        with self._lock:
            self._manifest[str(int(step))] = {
                "file": os.path.basename(out), "crc32": crc,
                "nbytes": nbytes, "n_leaves": len(leaves)}
            self._write_manifest_locked()
            self._prune_locked()
        return True

    def maybe_save(self, step: int, state: dict) -> bool:
        return self.save(step, state)

    def _prune_locked(self) -> None:
        steps = sorted(int(s) for s in self._manifest)
        for s in steps[: max(0, len(steps) - self.max_to_keep)]:
            entry = self._manifest.pop(str(s), None)
            if entry:
                try:
                    os.unlink(os.path.join(self._dir, entry["file"]))
                except OSError:
                    pass
        if len(steps) > self.max_to_keep:
            try:
                self._write_manifest_locked()
            except OSError:
                # stale manifest entries point at unlinked files; the
                # restore path already treats those as corrupt + drops
                pass

    # -- read --------------------------------------------------------------
    def _candidate_steps(self) -> list[int]:
        """Known steps, newest first: manifest entries plus any orphan
        ``ckpt-*.npz`` a crash left un-indexed (file replaced, manifest
        write lost — the file is durable, so it is a candidate)."""
        with self._lock:
            steps = {int(s) for s in self._manifest}
        try:
            for name in os.listdir(self._dir):
                if name.startswith("ckpt-") and name.endswith(".npz"):
                    try:
                        steps.add(int(name[5:-4]))
                    except ValueError:
                        pass
        except OSError:
            pass
        return sorted(steps, reverse=True)

    def latest_step(self) -> int | None:
        steps = self._candidate_steps()
        return steps[0] if steps else None

    def _load_verified(self, step: int) -> dict:
        """Parse + verify one checkpoint file → {meta, arrays} or raise
        CheckpointCorruption."""
        path = self._file_for(step)
        with self._lock:
            entry = self._manifest.get(str(int(step)))
        try:
            size = os.stat(path).st_size
        except OSError as e:
            raise CheckpointCorruption(f"missing {path}") from e
        if entry is not None:
            if size != entry["nbytes"]:
                raise CheckpointCorruption(
                    f"{path}: size {size} != manifest {entry['nbytes']} "
                    "(truncated or partial write)")
            if _crc32_file(path) != entry["crc32"]:
                raise CheckpointCorruption(
                    f"{path}: crc32 mismatch (bit rot or torn write)")
        try:
            with open(path, "rb") as f:
                blob = f.read()
            z = np.load(io.BytesIO(blob), allow_pickle=False)
            meta = json.loads(bytes(z["__meta__"]).decode())
            arrays = []
            for i, lf in enumerate(meta["leaves"]):
                dt = _resolve_dtype(lf["dtype"])
                buf = z[f"leaf_{i:05d}"]
                want = int(np.prod(lf["shape"], dtype=np.int64)) * dt.itemsize
                if buf.nbytes != want:
                    raise CheckpointCorruption(
                        f"{path}: leaf {i} has {buf.nbytes} bytes, "
                        f"expected {want}")
                arrays.append(np.frombuffer(
                    buf.tobytes(), dtype=dt).reshape(lf["shape"]))
        except CheckpointCorruption:
            raise
        except Exception as e:  # zip/json/npy damage of any shape
            raise CheckpointCorruption(f"{path}: unreadable ({e!r})") from e
        return {"meta": meta, "arrays": arrays}

    def _drop(self, step: int, reason: str) -> None:
        from tpudl.obs import flight as _flight
        from tpudl.obs import metrics as _metrics

        _metrics.counter("train.checkpoint.corrupt").inc()
        _flight.record_error("train.checkpoint.corrupt", reason,
                             step=int(step), dir=self._dir)
        with self._lock:
            if self._manifest.pop(str(int(step)), None) is not None:
                try:
                    self._write_manifest_locked()
                except OSError:
                    pass  # in-memory drop still prevents re-reads
        try:
            os.unlink(self._file_for(step))
        except OSError:
            pass

    def restore(self, step: int | None = None, *, like: dict | None = None):
        """Restore the state pytree at ``step`` (default: the newest
        VALID step — a corrupt newest checkpoint falls back to its
        predecessor instead of crashing the resume). ``like`` provides
        the target structure/shardings: each restored leaf is placed
        onto the corresponding ``like`` leaf's sharding, so TP-sharded
        state comes back device-sharded. Returns None when nothing
        restorable exists."""
        if step is not None:
            payload = self._load_verified(step)  # explicit step: raise
            return self._rebuild(payload, like)
        for cand in self._candidate_steps():
            try:
                payload = self._load_verified(cand)
            except CheckpointCorruption as e:
                self._drop(cand, repr(e))
                continue
            return self._rebuild(payload, like)
        return None

    def _rebuild(self, payload: dict, like: dict | None):
        meta, arrays = payload["meta"], payload["arrays"]
        if like is not None:
            flat, treedef = jax.tree_util.tree_flatten(like)
            keys = [jax.tree_util.keystr(p) for p, _ in
                    jax.tree_util.tree_flatten_with_path(like)[0]]
            saved = [lf["key"] for lf in meta["leaves"]]
            if keys != saved:
                raise ValueError(
                    f"checkpoint structure does not match `like`: saved "
                    f"leaves {saved[:4]}... vs target {keys[:4]}...")
            placed = []
            for ref, arr in zip(flat, arrays):
                sharding = getattr(ref, "sharding", None)
                if sharding is not None:
                    placed.append(jax.device_put(arr, sharding))
                elif hasattr(ref, "devices"):  # jax array, default place
                    placed.append(jax.device_put(arr))
                else:
                    placed.append(np.array(arr))  # writable host copy
            return jax.tree_util.tree_unflatten(treedef, placed)
        # like-less restore: rebuild nested dict/list containers from
        # the recorded path components (attr paths degrade to dict keys
        # — pass `like=` for exotic containers, as the Trainer does)
        root: dict | list | None = None

        def _place(container, comps, value):
            head, rest = comps[0], comps[1:]
            key = head["k"] if head["t"] in ("key", "attr") else head["i"]
            if not rest:
                if isinstance(container, list):
                    while len(container) <= key:
                        container.append(None)
                container[key] = value
                return
            nxt_is_idx = rest[0]["t"] == "idx"
            if isinstance(container, list):
                while len(container) <= key:
                    container.append(None)
                if container[key] is None:
                    container[key] = [] if nxt_is_idx else {}
                _place(container[key], rest, value)
            else:
                child = container.setdefault(
                    key, [] if nxt_is_idx else {})
                _place(child, rest, value)

        for lf, arr in zip(meta["leaves"], arrays):
            comps = lf["path"]
            if not comps:
                return np.array(arr)  # bare-leaf state
            if root is None:
                root = [] if comps[0]["t"] == "idx" else {}
            _place(root, comps, np.array(arr))
        return root

    # -- maintenance -------------------------------------------------------
    def validate(self) -> list[str]:
        """Integrity errors across every known step (the audit path
        ``tools/validate_job.py`` drives); empty = clean."""
        errs = []
        for s in self._candidate_steps():
            try:
                self._load_verified(s)
            except CheckpointCorruption as e:
                errs.append(str(e))
        return errs

    def close(self):
        pass  # every save is already durable; kept for API compat

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()




def as_numpy_state(state: dict) -> dict:
    """Device pytree → host numpy (for handing across process restarts)."""
    return jax.tree.map(lambda x: np.asarray(x), state)
