"""Checkpoints: directory handles + sharded-array save/restore.

Analog of the reference ray.train.Checkpoint
(python/ray/train/_checkpoint.py — a directory handle on storage) with
the TPU-native twist promised in SURVEY.md §5.4: sharded jax arrays are
written per-shard via orbax (async-capable), so a multi-host gang
checkpoints without gathering to one host. Plain python state falls back
to pickle in the same directory.

Crash-atomicity (r12): every write lands in a ``<path>.tmp`` staging
directory and is ``os.rename``d into place only when complete — a rank
killed mid-save (the elastic trainer's common case) leaves a ``.tmp``
residue, never a half-written checkpoint a resume could load.
``latest_complete`` / ``prune_partial`` are the restore-side guards:
partial directories are skipped AND deleted so they can't shadow a good
checkpoint or accumulate across recoveries.
"""

from __future__ import annotations

import contextlib
import os
import pickle
import shutil
import tempfile
import threading
from typing import Any, Optional

from ray_tpu import obs

_ORBAX_SUBDIR = "sharded_state"
_PICKLE_FILE = "state.pkl"
_PARTIAL_SUFFIX = ".tmp"
_OLD_SUFFIX = ".old"


def _swap_into_place(tmp: str, dest: str) -> None:
    """Install a fully-written staging dir at ``dest`` without a window
    where a crash loses BOTH checkpoints: the previous ``dest`` is
    renamed aside (not rmtree'd) before the staging dir renames in, so
    every crash point leaves at least one complete checkpoint on disk —
    ``prune_partial`` renames an orphaned ``.old`` back on restore."""
    old = dest + _OLD_SUFFIX
    if os.path.exists(dest):
        # a stale .old alongside a live dest means the last swap
        # completed — safe to drop. An ORPHANED .old (dest missing,
        # e.g. a retry after a crash mid-swap) is the only complete
        # copy and must survive until the new dest is installed.
        if os.path.exists(old):
            shutil.rmtree(old)
        os.rename(dest, old)
    os.rename(tmp, dest)
    if os.path.exists(old):
        shutil.rmtree(old, ignore_errors=True)


class Checkpoint:
    """A directory handle. Create with `from_directory`, read with
    `to_directory` / `as_directory` (reference Checkpoint API surface)."""

    def __init__(self, path: str):
        self.path = os.path.abspath(path)

    @classmethod
    def from_directory(cls, path: str) -> "Checkpoint":
        return cls(path)

    def to_directory(self, dest: Optional[str] = None) -> str:
        if dest is None or os.path.abspath(dest) == self.path:
            return self.path
        shutil.copytree(self.path, dest, dirs_exist_ok=True)
        return dest

    def as_directory(self):
        import contextlib

        @contextlib.contextmanager
        def cm():
            yield self.path

        return cm()

    # -- typed helpers -------------------------------------------------------

    @classmethod
    def from_state(cls, state: Any, path: str, sharded: bool = False) -> "Checkpoint":
        """Crash-atomic: the whole checkpoint is staged in ``path.tmp``
        and renamed into place — readers either see a complete
        checkpoint at ``path`` or nothing."""
        with obs.layer_span("train.checkpoint.save", attrs={"sharded": sharded}):
            path = os.path.abspath(path)
            tmp = path + _PARTIAL_SUFFIX
            if os.path.exists(tmp):
                shutil.rmtree(tmp)
            os.makedirs(tmp)
            if sharded:
                _save_sharded(state, os.path.join(tmp, _ORBAX_SUBDIR), wait=True)
            else:
                with open(os.path.join(tmp, _PICKLE_FILE), "wb") as f:
                    pickle.dump(state, f)
            _swap_into_place(tmp, path)
            return cls(path)

    def load_state(self, template: Any = None) -> Any:
        orbax_dir = os.path.join(self.path, _ORBAX_SUBDIR)
        if os.path.isdir(orbax_dir):
            return restore_sharded(orbax_dir, template)
        with open(os.path.join(self.path, _PICKLE_FILE), "rb") as f:
            return pickle.load(f)

    def __repr__(self):
        return f"Checkpoint({self.path})"


class _PendingSave:
    """Handle for an in-flight async sharded save: the staged ``.tmp``
    directory is renamed into place only in ``wait_until_finished`` —
    before that the destination either holds the previous checkpoint or
    nothing, never a torn write."""

    def __init__(self, ckptr, tmp: str, dest: str):
        self._ckptr = ckptr
        self._tmp = tmp
        self._dest = dest
        self._finalized = False

    def wait_until_finished(self) -> None:
        # layer span train.checkpoint.wait: the stall a loop pays for an
        # async save it has to wait out
        with obs.layer_span("train.checkpoint.wait"):
            self._ckptr.wait_until_finished()
            if not self._finalized:
                self._finalized = True
                _swap_into_place(self._tmp, self._dest)

    def close(self) -> None:
        self.wait_until_finished()
        self._ckptr.close()


def save_sharded(state: Any, path: str, wait: bool = True):
    """Write a pytree of (possibly sharded) jax arrays with orbax. Each host
    writes only its shards; async unless wait=True. Crash-atomic: orbax
    writes into ``path.tmp`` and the rename to ``path`` happens only
    after the write completed (a killed rank leaves ``.tmp`` residue,
    pruned on restore, never a partial checkpoint). Layer span
    train.checkpoint.save: the part the caller's thread pays (all of
    it with wait=True, the dispatch otherwise)."""
    with obs.layer_span("train.checkpoint.save",
                        attrs={"sharded": True, "wait": wait}):
        return _save_sharded(state, path, wait)


def _save_sharded(state: Any, path: str, wait: bool):
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    tmp = path + _PARTIAL_SUFFIX
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    ckptr = ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
    ckptr.save(tmp, args=ocp.args.StandardSave(state))
    pending = _PendingSave(ckptr, tmp, path)
    if wait:
        pending.close()
        return None
    return pending  # caller must wait_until_finished()/close()


def is_complete(path: str) -> bool:
    """A checkpoint directory is complete iff it was renamed into place
    (not a ``.tmp`` staging dir or a ``.old`` swap residue) and carries
    a payload."""
    if (
        path.endswith(_PARTIAL_SUFFIX)
        or path.endswith(_OLD_SUFFIX)
        or not os.path.isdir(path)
    ):
        return False
    return (
        os.path.isdir(os.path.join(path, _ORBAX_SUBDIR))
        or os.path.isfile(os.path.join(path, _PICKLE_FILE))
    )


def prune_partial(root: str) -> list:
    """Delete ``.tmp`` staging residue (and payload-less checkpoint
    directories) a killed rank left under ``root``; returns the pruned
    paths. Safe to call while a save is in flight elsewhere ONLY on a
    fresh restore path — which is exactly when it runs."""
    pruned = []
    if not os.path.isdir(root):
        return pruned
    for name in sorted(os.listdir(root)):
        p = os.path.join(root, name)
        if not os.path.isdir(p):
            continue
        if name.endswith(_OLD_SUFFIX):
            # swap residue: a crash between _swap_into_place's renames
            # leaves the previous good checkpoint aside as .old with
            # nothing at the base path — rename it back (sorted order
            # guarantees the base, if present, was already visited).
            # With the base present the swap completed; drop the residue.
            base = p[: -len(_OLD_SUFFIX)]
            if os.path.exists(base):
                shutil.rmtree(p, ignore_errors=True)
                pruned.append(p)
            else:
                os.rename(p, base)
            continue
        if name.endswith(_PARTIAL_SUFFIX) or (
            name.startswith("checkpoint_") and not is_complete(p)
        ):
            shutil.rmtree(p, ignore_errors=True)
            pruned.append(p)
    return pruned


def latest_complete(root: str) -> Optional["Checkpoint"]:
    """Newest COMPLETE ``checkpoint_*`` directory under ``root`` (the
    cold-resume entry point: partial dirs are pruned, never loaded)."""
    prune_partial(root)
    if not os.path.isdir(root):
        return None
    names = sorted(
        n for n in os.listdir(root)
        if n.startswith("checkpoint_") and is_complete(os.path.join(root, n))
    )
    return Checkpoint(os.path.join(root, names[-1])) if names else None


def restore_sharded(path: str, template: Any = None) -> Any:
    """Restore; with a template of jax.ShapeDtypeStructs carrying shardings,
    arrays come back sharded onto the mesh without a host gather."""
    import orbax.checkpoint as ocp

    ckptr = ocp.Checkpointer(ocp.StandardCheckpointHandler())
    try:
        if template is not None:
            return ckptr.restore(path, args=ocp.args.StandardRestore(template))
        return ckptr.restore(path)
    finally:
        ckptr.close()


class CheckpointManager:
    """Retention policy over reported checkpoints (reference
    CheckpointConfig.num_to_keep semantics)."""

    def __init__(self, root: str, num_to_keep: Optional[int] = None,
                 score_attribute: Optional[str] = None, score_order: str = "max"):
        self.root = root
        self.num_to_keep = num_to_keep
        self.score_attribute = score_attribute
        self.score_order = score_order
        self._ckpts: list[tuple[float, int, Checkpoint]] = []
        self._lock = threading.Lock()
        # path currently being restored from: num_to_keep eviction must
        # never delete it out from under the restore (the elastic
        # trainer registers new checkpoints while older recoveries may
        # still be reading the one they resumed from)
        self._restoring: Optional[str] = None
        os.makedirs(root, exist_ok=True)
        # resume the dir sequence past what is already on disk: a fresh
        # manager over an old root (cold resume after a driver crash)
        # must never hand out a checkpoint_NNNNNN name that from_state
        # would then rmtree out from under latest_complete
        self._seq = max(
            (
                int(n[len("checkpoint_"):])
                for n in os.listdir(root)
                if n.startswith("checkpoint_")
                and n[len("checkpoint_"):].isdigit()
            ),
            default=0,
        )

    def register(self, ckpt: Checkpoint, metrics: Optional[dict] = None) -> None:
        with self._lock:
            score = 0.0
            if self.score_attribute and metrics:
                score = float(metrics.get(self.score_attribute, 0.0))
                if self.score_order == "min":
                    score = -score
            self._seq += 1
            self._ckpts.append((score, self._seq, ckpt))
            if self.num_to_keep is not None and len(self._ckpts) > self.num_to_keep:
                # evict lowest score (or oldest) WITHOUT reordering the
                # registration-ordered list — latest() must stay the most
                # recent checkpoint, it drives failure-resume. The
                # checkpoint being restored is pinned: evict the next
                # candidate instead (briefly keeping num_to_keep + 1).
                candidates = [
                    t for t in self._ckpts if t[2].path != self._restoring
                ]
                if not candidates:
                    return
                if self.score_attribute:
                    evicted = min(candidates, key=lambda t: (t[0], t[1]))
                else:
                    evicted = candidates[0]
                self._ckpts.remove(evicted)
                shutil.rmtree(evicted[2].path, ignore_errors=True)

    def mark_restoring(self, ckpt: Optional[Checkpoint]) -> None:
        """Pin ``ckpt`` against num_to_keep eviction for the duration of
        a restore (pass None to unpin)."""
        with self._lock:
            self._restoring = ckpt.path if ckpt is not None else None

    @contextlib.contextmanager
    def restoring(self, ckpt: Checkpoint):
        """Context manager form of the restore pin."""
        self.mark_restoring(ckpt)
        try:
            yield ckpt
        finally:
            self.mark_restoring(None)

    def latest(self) -> Optional[Checkpoint]:
        with self._lock:
            return max(self._ckpts, key=lambda t: t[1])[2] if self._ckpts else None

    def best(self) -> Optional[Checkpoint]:
        with self._lock:
            if not self._ckpts:
                return None
            return max(self._ckpts, key=lambda t: (t[0], t[1]))[2]

    def new_checkpoint_dir(self) -> str:
        with self._lock:
            self._seq += 1
            return os.path.join(self.root, f"checkpoint_{self._seq:06d}")
