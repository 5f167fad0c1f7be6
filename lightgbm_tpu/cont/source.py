"""Batch sources for the continual training daemon.

The out-of-core framing ("Out-of-Core GPU Gradient Boosting",
PAPERS.md): training data arrives as a stream of finite batch shards
on disk, not a resident matrix.  :class:`DirectoryBatchSource` tails a
directory in NAME order — producers write shards under temporary names
and rename into place, so a sorted listing is a stable consumption
order — and owns the failure classes of getting bytes off disk:

- **transient** read failures (``OSError``: flaky NFS, a mid-copy
  file) retry under bounded exponential backoff
  (``continual_read_retries`` x ``continual_backoff_base_s``), each
  retry emitting a ``continual``/``backoff`` telemetry record;
- **non-transient** failures (truncated zip, missing arrays, a pickle
  where an array should be) quarantine the file immediately — retrying
  a deterministic parse error just burns the backoff budget.

Quarantined batches are MOVED (``os.replace``) into the quarantine
directory so the ingest dir never wedges on one bad file, and every
move emits a ``continual``/``quarantine`` record carrying the reason —
the accounting the chaos e2e reconciles.

Shard formats:

- ``<name>.npz`` with arrays ``X`` and ``y`` (or ``label``), optional
  ``weight`` and ``group``;
- mmap pairs ``<name>.X.npy`` + ``<name>.y.npy`` (optional
  ``<name>.weight.npy`` / ``<name>.group.npy``), loaded with
  ``mmap_mode='r'`` — the zero-copy form for shards written by a
  separate producer process.

Fault-injection point: ``ingest.read`` (modes ``error`` = transient,
``corrupt`` = non-transient; ``utils/faults.py``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import time
import zipfile
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..utils import faults as _faults
from ..utils.log import Log

__all__ = ["Batch", "BatchSource", "DirectoryBatchSource"]


@dataclasses.dataclass
class Batch:
    """One ingested training batch."""

    name: str
    paths: Tuple[str, ...]
    X: np.ndarray
    y: np.ndarray
    weight: Optional[np.ndarray] = None
    group: Optional[np.ndarray] = None

    @property
    def rows(self) -> int:
        return int(np.asarray(self.X).shape[0]) if \
            np.asarray(self.X).ndim >= 1 else 0


class BatchSource:
    """Abstract batch source: ``next_batch`` yields the next pending
    batch (or None), ``quarantine``/``mark_done`` retire it.
    ``quarantined`` counts every quarantine THIS source performed —
    reads before validation and trainer-initiated rejects alike — so
    the daemon's accounting has one source of truth."""

    quarantined: int = 0

    def pending(self) -> List[str]:
        raise NotImplementedError

    def next_batch(self) -> Optional[Batch]:
        raise NotImplementedError

    def quarantine(self, batch, reason: str, detail: str = "") -> None:
        raise NotImplementedError

    def mark_done(self, batch: Batch) -> None:
        raise NotImplementedError


class DirectoryBatchSource(BatchSource):
    """Tail a directory of npz / mmap-npy batch shards in name order."""

    def __init__(self, root: str, quarantine_dir: str = "",
                 processed_dir: str = "", read_retries: int = 3,
                 backoff_base_s: float = 0.1, backoff_max_s: float = 5.0,
                 recorder=None):
        self.root = str(root)
        self.quarantine_dir = quarantine_dir or \
            os.path.join(self.root, "_quarantine")
        self.processed_dir = processed_dir or \
            os.path.join(self.root, "_processed")
        self.read_retries = max(int(read_retries), 0)
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_max_s = float(backoff_max_s)
        self.recorder = recorder
        os.makedirs(self.root, exist_ok=True)

    # -- telemetry -----------------------------------------------------
    def _emit(self, event: str, **fields) -> None:
        from ..utils import telemetry as _telemetry
        _telemetry.counters.incr(f"continual_{event}s")
        rec = self.recorder or _telemetry.get_recorder()
        if rec is not None:
            rec.emit("continual", event=event, **fields)

    # -- discovery -----------------------------------------------------
    def pending(self) -> List[str]:
        """Batch names awaiting consumption, sorted (= consumption
        order).  Hidden/underscore names and in-flight temp files are
        producers' business, not batches."""
        names = set()
        for path in glob.glob(os.path.join(self.root, "*.npz")):
            base = os.path.basename(path)
            if not base.startswith((".", "_")):
                names.add(base)
        for path in glob.glob(os.path.join(self.root, "*.X.npy")):
            base = os.path.basename(path)
            if base.startswith((".", "_")):
                continue
            stem = base[:-len(".X.npy")]
            # a pair is pending only once BOTH halves landed — a
            # producer renaming X before y must not get the batch
            # quarantined (and its late y orphaned) by the gap
            if os.path.exists(os.path.join(self.root,
                                           f"{stem}.y.npy")):
                names.add(stem)
        return sorted(names)

    def _paths_for(self, name: str) -> Tuple[str, ...]:
        if name.endswith(".npz"):
            return (os.path.join(self.root, name),)
        out = [os.path.join(self.root, f"{name}.X.npy"),
               os.path.join(self.root, f"{name}.y.npy")]
        for part in ("weight", "group"):
            p = os.path.join(self.root, f"{name}.{part}.npy")
            if os.path.exists(p):
                out.append(p)
        return tuple(out)

    # -- reading -------------------------------------------------------
    @staticmethod
    def _arrays_from_npz(path: str) -> Dict[str, Any]:
        with np.load(path, allow_pickle=False) as z:
            files = set(z.files)
            if "X" not in files and "x" not in files:
                raise ValueError("npz batch has no 'X' array")
            X = z["X"] if "X" in files else z["x"]
            y = None
            for key in ("y", "label", "labels"):
                if key in files:
                    y = z[key]
                    break
            if y is None:
                raise ValueError("npz batch has no 'y'/'label' array")
            out = {"X": X, "y": y}
            if "weight" in files:
                out["weight"] = z["weight"]
            if "group" in files:
                out["group"] = z["group"]
        return out

    def _load(self, name: str) -> Batch:
        mode = _faults.fire("ingest.read")
        if mode == "error":
            raise OSError(f"injected fault (ingest.read:error) "
                          f"reading {name}")
        if mode == "corrupt":
            raise ValueError(f"injected fault (ingest.read:corrupt) "
                             f"parsing {name}")
        paths = self._paths_for(name)
        if name.endswith(".npz"):
            arrays = self._arrays_from_npz(paths[0])
        else:
            # mmap pair: X/y stay memory-mapped (read-only views);
            # Dataset construction copies what it bins
            arrays = {"X": np.load(paths[0], mmap_mode="r",
                                   allow_pickle=False),
                      "y": np.load(paths[1], mmap_mode="r",
                                   allow_pickle=False)}
            for part in ("weight", "group"):
                p = os.path.join(self.root, f"{name}.{part}.npy")
                if os.path.exists(p):
                    arrays[part] = np.load(p, mmap_mode="r",
                                           allow_pickle=False)
        return Batch(name=name, paths=paths, X=arrays["X"],
                     y=arrays["y"], weight=arrays.get("weight"),
                     group=arrays.get("group"))

    def next_batch(self) -> Optional[Batch]:
        """Load the next pending batch.  Transient read failures back
        off and retry; exhausted retries and parse failures quarantine
        the file and move on to the NEXT poll (returning None so the
        caller re-enters its loop checks)."""
        pending = self.pending()
        if not pending:
            return None
        name = pending[0]
        attempt = 0
        while True:
            try:
                return self._load(name)
            except OSError as exc:
                attempt += 1
                if attempt > self.read_retries:
                    self.quarantine(name, "read",
                                    f"transient read failure persisted "
                                    f"through {attempt} attempts: {exc}")
                    return None
                sleep_s = min(self.backoff_base_s * (2 ** (attempt - 1)),
                              self.backoff_max_s)
                Log.warning("continual: transient read failure on %s "
                            "(attempt %d/%d, backing off %.2fs): %s",
                            name, attempt, self.read_retries, sleep_s,
                            exc)
                self._emit("backoff", batch=name, attempt=attempt,
                           sleep_s=round(sleep_s, 3),
                           error=str(exc)[:200])
                time.sleep(sleep_s)
            except (ValueError, KeyError, zipfile.BadZipFile,
                    EOFError) as exc:
                # deterministic parse failure: retrying cannot help
                self.quarantine(name, "read", f"unreadable batch: {exc}")
                return None

    # -- retirement ----------------------------------------------------
    def _move_all(self, name: str, dest_dir: str) -> None:
        os.makedirs(dest_dir, exist_ok=True)
        for path in self._paths_for(name):
            if os.path.exists(path):
                os.replace(path,
                           os.path.join(dest_dir,
                                        os.path.basename(path)))

    def quarantine(self, batch, reason: str, detail: str = "") -> None:
        """Move a rejected batch (or raw name) out of the ingest dir
        and account for it in telemetry — the ingest stream must never
        wedge on one bad file."""
        name = batch if isinstance(batch, str) else batch.name
        self.quarantined += 1
        try:
            self._move_all(name, self.quarantine_dir)
        except OSError as exc:  # pragma: no cover - quarantine FS issue
            Log.warning("continual: could not quarantine %s: %s",
                        name, exc)
        Log.warning("continual: QUARANTINED batch %s (%s)%s", name,
                    reason, f": {detail}" if detail else "")
        self._emit("quarantine", batch=name, reason=str(reason),
                   error=str(detail)[:300])

    def mark_done(self, batch: Batch) -> None:
        self._move_all(batch.name, self.processed_dir)
