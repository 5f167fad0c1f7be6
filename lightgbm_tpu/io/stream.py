"""Fault-tolerant out-of-core streaming ingest + host->device streaming.

The data plane's last ceiling was "rows must fit in host RAM and land
on the device in one staged copy".  This module converts it into
"rows must fit on disk":

1. **Streamed binning** — the raw matrix is read chunk-by-chunk from a
   :class:`RawSource` (never fully resident); bin mappers are fit ONCE
   from a single streamed sample pass (the exact ``sample_rows``
   sample when the source can count its rows — bit-identical mappers
   to the in-memory path — or a :class:`ReservoirSampler` when it
   cannot), and each chunk is binned with the SAME ``bin_rows`` code
   the in-memory path uses, so the cached matrix is byte-identical to
   ``TpuDataset.from_raw``'s.

2. **Crash-safe cache** (``io/cache.py``) — binned chunks are written
   to a content-keyed mmap cache under the PR 5 atomic-writer
   discipline (per-chunk attestation after durable bytes, dataset
   manifest LAST).  A SIGKILL mid-ingest resumes reusing the fit
   mappers and every published chunk; a corrupt or truncated chunk is
   re-binned ALONE; every chunk is sha256-verified on load.

3. **Double-buffered host->device streaming** (:class:`BlockFetcher`)
   — training consumes the cache through bounded upload windows
   (``stream_host_budget_mb``): a prefetch thread prepares window
   ``i+1`` (mmap page-in + transpose + pad + EFB transform) while
   window ``i``'s async device copy and donated in-place
   ``dynamic_update_slice`` run, so the host-side prep cost hides
   under device transfer.  The device program that trains afterwards
   is IDENTICAL to the in-memory path's — parity is structural, not
   numerical luck.  The elastic abort fence extends here:
   :func:`abort_active_fetchers` cancels in-flight window prep/copies
   before a re-mesh, so recovery never consumes a stale block.

Failure policy (shared with ``cont/source.py``): transient chunk
reads (``OSError``) retry under bounded exponential backoff emitting
``ingest``/``backoff`` records; after ``stream_read_retries`` the
chunk is QUARANTINED (``ingest``/``quarantine``, a HIGH anomaly) and
— since a training matrix cannot silently lose rows — ingest fails
loudly AFTER binning every other chunk, so the retry run only owes
the quarantined ones.  Deterministic parse failures quarantine
immediately.

Fault points (``utils/faults.py``): ``stream.chunk_read``
(``error`` = transient, ``corrupt``/``truncate`` = non-transient,
``hang``, ``sleep_<ms>``), ``stream.cache_write`` (``io/cache.py``)
and ``stream.prefetch`` (``error``, ``hang``, ``sleep_<ms>``).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import queue
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..utils import faults as _faults
from ..utils import telemetry as _telemetry
from ..utils.log import Log
from . import cache as cache_mod
from .binning import BinMapper, find_bin_mappers, sample_rows
from .dataset import Metadata, TpuDataset, bin_rows

__all__ = ["IngestError", "StreamAborted", "RawSource", "ArraySource",
           "NpyPairSource", "NpzShardSource", "ReservoirSampler",
           "StreamInfo", "StreamedTpuDataset", "BlockFetcher",
           "abort_active_fetchers", "ingest", "ingest_dataset",
           "resolve_source", "prune_cache_root"]


class IngestError(Exception):
    """Streamed ingest could not produce a complete dataset."""


class StreamAborted(IngestError):
    """An in-flight host->device stream was fenced off (elastic
    re-mesh, shutdown) before completing."""


# ----------------------------------------------------------------------
# telemetry plumbing
# ----------------------------------------------------------------------
def _emit(recorder, event: str, **fields) -> None:
    _telemetry.counters.incr(f"ingest_{event}s")
    rec = recorder or _telemetry.get_recorder()
    if rec is not None:
        rec.emit("ingest", event=event, **fields)


# ----------------------------------------------------------------------
# raw sources
# ----------------------------------------------------------------------
class RawSource:
    """A raw training matrix readable in row ranges.

    ``rows`` may be None for unbounded producers (the reservoir-sample
    path); every bundled source can count, which is what makes the
    sample — and therefore the mappers, the binned matrix and the
    model — bit-identical to the in-memory path."""

    rows: Optional[int] = None
    cols: int = 0

    def identity(self) -> str:
        raise NotImplementedError

    def read_rows(self, start: int, stop: int) -> np.ndarray:
        raise NotImplementedError

    def read_meta(self) -> Dict[str, Optional[np.ndarray]]:
        """label (+ optional weight/group/init_score) arrays."""
        raise NotImplementedError


class ArraySource(RawSource):
    """In-memory (or mmap-backed) arrays.  ``np.load(..., mmap_mode=
    'r')`` inputs stay on disk; ``read_rows`` pages in one chunk."""

    def __init__(self, X, y=None, weight=None, group=None,
                 init_score=None, name: str = ""):
        self.X = X
        self.y = y
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.name = str(name)
        self.rows = int(X.shape[0])
        self.cols = int(X.shape[1])

    def identity(self) -> str:
        # cheap content fingerprint: full label bytes (N x 4, the
        # small axis) + a strided row sample of X + shape/dtype.  The
        # per-chunk sha256 attestations are the integrity layer; the
        # key only has to distinguish datasets.
        h = hashlib.sha256()
        h.update(str((self.X.shape, str(self.X.dtype),
                      self.name)).encode())
        # shape-derived, not self.rows: an uncounted subclass sets
        # rows=None until the sample pass counts it
        step = max(1, int(self.X.shape[0]) // 512)
        h.update(np.ascontiguousarray(
            np.asarray(self.X[::step][:512])).data)
        if self.y is not None:
            h.update(np.ascontiguousarray(
                np.asarray(self.y, np.float64)).data)
        return "array:" + h.hexdigest()

    def read_rows(self, start: int, stop: int) -> np.ndarray:
        return np.ascontiguousarray(self.X[start:stop])

    def read_meta(self) -> Dict[str, Optional[np.ndarray]]:
        return {"label": None if self.y is None
                else np.asarray(self.y),
                "weight": None if self.weight is None
                else np.asarray(self.weight),
                "group": None if self.group is None
                else np.asarray(self.group),
                "init_score": None if self.init_score is None
                else np.asarray(self.init_score)}


class NpyPairSource(ArraySource):
    """``<stem>.X.npy`` + ``<stem>.y.npy`` (+ optional
    ``<stem>.weight.npy`` / ``<stem>.group.npy``), the continual
    daemon's mmap shard format (``cont/source.py``) — X stays
    memory-mapped, so the raw matrix never enters host RAM whole."""

    def __init__(self, stem: str):
        self.stem = str(stem)
        paths = {part: f"{self.stem}.{part}.npy"
                 for part in ("X", "y", "weight", "group")}
        if not os.path.exists(paths["X"]):
            raise IngestError(f"{paths['X']}: no such file")
        X = np.load(paths["X"], mmap_mode="r", allow_pickle=False)
        y = np.load(paths["y"], mmap_mode="r", allow_pickle=False) \
            if os.path.exists(paths["y"]) else None
        opt = {}
        for part in ("weight", "group"):
            if os.path.exists(paths[part]):
                opt[part] = np.load(paths[part], allow_pickle=False)
        super().__init__(X, y, weight=opt.get("weight"),
                         group=opt.get("group"))
        self._paths = paths

    def identity(self) -> str:
        # path + size is NOT enough: a regenerated same-shape file
        # would silently reuse the stale cache (its chunk hashes
        # verify against their own stale bytes).  Include the
        # ArraySource content fingerprint (strided row sample + full
        # labels — the mmaps page in only that much) AND mtimes, so
        # both a content change and a re-export re-key
        parts = []
        for part in ("X", "y", "weight", "group"):
            p = self._paths[part]
            if os.path.exists(p):
                st = os.stat(p)
                parts.append((os.path.abspath(p), st.st_size,
                              st.st_mtime_ns))
        return "npy:" + json.dumps(
            {"paths": parts, "content": super().identity()},
            sort_keys=True)


class NpzShardSource(RawSource):
    """A directory of ``*.npz`` shards consumed in name order (the
    producer contract of ``cont/source.py``).  Row counts come from
    the (small) label arrays, so the chunk grid is known before any
    X bytes are read; ``read_rows`` spans shard boundaries."""

    def __init__(self, directory: str):
        self.directory = str(directory)
        import glob as _glob
        self.paths = sorted(
            p for p in _glob.glob(os.path.join(self.directory, "*.npz"))
            if not os.path.basename(p).startswith((".", "_")))
        if not self.paths:
            raise IngestError(f"{directory}: no *.npz shards")
        self._lens: List[int] = []
        self._labels: List[np.ndarray] = []
        for p in self.paths:
            with np.load(p, allow_pickle=False) as z:
                key = "y" if "y" in z.files else "label"
                y = z[key]
            self._labels.append(np.asarray(y).reshape(-1))
            self._lens.append(len(self._labels[-1]))
        self._bounds = np.concatenate([[0], np.cumsum(self._lens)])
        self.rows = int(self._bounds[-1])
        with np.load(self.paths[0], allow_pickle=False) as z:
            self.cols = int(z["X"].shape[1])

    def identity(self) -> str:
        return "npz:" + json.dumps(
            [(os.path.abspath(p), os.path.getsize(p))
             for p in self.paths], sort_keys=True)

    def read_rows(self, start: int, stop: int) -> np.ndarray:
        out: List[np.ndarray] = []
        s0 = int(np.searchsorted(self._bounds, start, side="right") - 1)
        pos = start
        while pos < stop:
            lo, hi = int(self._bounds[s0]), int(self._bounds[s0 + 1])
            with np.load(self.paths[s0], allow_pickle=False) as z:
                out.append(np.asarray(z["X"][pos - lo:
                                             min(stop, hi) - lo]))
            pos = min(stop, hi)
            s0 += 1
        return np.ascontiguousarray(np.concatenate(out, axis=0)
                                    if len(out) > 1 else out[0])

    def read_meta(self) -> Dict[str, Optional[np.ndarray]]:
        return {"label": np.concatenate(self._labels),
                "weight": None, "group": None, "init_score": None}


def resolve_source(data, label=None, weight=None, group=None,
                   init_score=None) -> RawSource:
    """ndarray -> :class:`ArraySource`; directory -> npz shards;
    ``<stem>`` / ``<stem>.X.npy`` -> mmap pair.  Explicitly passed
    label/weight/group/init_score OVERRIDE a file source's sidecars —
    they must never be silently dropped."""
    if isinstance(data, RawSource):
        src = data
    elif isinstance(data, (str, os.PathLike)):
        path = str(data)
        if os.path.isdir(path):
            src = NpzShardSource(path)
        else:
            stem = path[:-len(".X.npy")] if path.endswith(".X.npy") \
                else path
            src = NpyPairSource(stem)
    else:
        return ArraySource(np.asarray(data), label, weight=weight,
                           group=group, init_score=init_score)
    overrides = {"y": label, "weight": weight, "group": group,
                 "init_score": init_score}
    applied = {k: v for k, v in overrides.items() if v is not None}
    if applied:
        if not isinstance(src, ArraySource):
            raise IngestError(
                f"explicit {sorted(applied)} cannot be attached to a "
                f"{type(src).__name__}; write them as sidecar files")
        for k, v in applied.items():
            setattr(src, k, np.asarray(v))
    return src


# ----------------------------------------------------------------------
# sampling
# ----------------------------------------------------------------------
class ReservoirSampler:
    """Classic reservoir sampling for sources that cannot count their
    rows up front.  Mappers fit from a reservoir are NOT bit-identical
    to the in-memory path's ``sample_rows`` draw (different sample =>
    possibly different boundaries), so counted sources use the exact
    sample instead — this is the documented unbounded-producer
    fallback."""

    def __init__(self, sample_cnt: int, seed: int):
        self.k = max(int(sample_cnt), 1)
        self._rng = np.random.RandomState(seed & 0x7FFFFFFF)
        self._seen = 0
        self._rows: List[np.ndarray] = []

    def offer(self, rows: np.ndarray) -> None:
        for row in np.asarray(rows):
            self._seen += 1
            if len(self._rows) < self.k:
                self._rows.append(np.array(row, copy=True))
            else:
                j = self._rng.randint(self._seen)
                if j < self.k:
                    self._rows[j] = np.array(row, copy=True)

    @property
    def seen(self) -> int:
        return self._seen

    def sample(self) -> np.ndarray:
        return np.stack(self._rows) if self._rows else \
            np.zeros((0, 0))


# ----------------------------------------------------------------------
# chunk reading with the shared transient/quarantine policy
# ----------------------------------------------------------------------
def _read_chunk(source: RawSource, index: int, start: int, stop: int,
                retries: int, backoff_base_s: float,
                backoff_max_s: float, recorder) -> np.ndarray:
    """One chunk read under the cont/source.py failure classes:
    transient ``OSError`` -> bounded exponential backoff + retry;
    exhausted retries or a deterministic parse error -> the chunk is
    quarantined (telemetry) and :class:`IngestError` raised — the
    caller keeps binning OTHER chunks and fails loudly at the end."""
    attempt = 0
    while True:
        try:
            mode = _faults.fire("stream.chunk_read")
            if mode == "error":
                raise OSError(f"injected fault (stream.chunk_read:"
                              f"error) reading chunk {index}")
            if mode in ("corrupt", "truncate"):
                raise ValueError(f"injected fault (stream.chunk_read:"
                                 f"{mode}) parsing chunk {index}")
            if mode == "hang":
                time.sleep(3600.0)
            elif mode.startswith("sleep_"):
                time.sleep(float(mode[len("sleep_"):]) / 1e3)
            t0 = time.perf_counter()
            arr = source.read_rows(start, stop)
            if arr.shape[0] != stop - start:
                raise ValueError(f"short read: {arr.shape[0]} rows "
                                 f"for chunk {index} [{start}:{stop})")
            _emit(recorder, "chunk_read", chunk=index, rows=stop - start,
                  attempt=attempt + 1,
                  duration_ms=round((time.perf_counter() - t0) * 1e3, 3))
            return arr
        except OSError as exc:
            attempt += 1
            if attempt > retries:
                _emit(recorder, "quarantine", chunk=index,
                      reason="read",
                      error=f"transient read failure persisted "
                            f"through {attempt} attempts: {exc}"[:300])
                raise IngestError(
                    f"chunk {index} quarantined after {attempt} "
                    f"attempts: {exc}") from exc
            sleep_s = min(backoff_base_s * (2 ** (attempt - 1)),
                          backoff_max_s)
            Log.warning("stream: transient read failure on chunk %d "
                        "(attempt %d/%d, backing off %.2fs): %s",
                        index, attempt, retries, sleep_s, exc)
            _emit(recorder, "backoff", chunk=index, attempt=attempt,
                  sleep_s=round(sleep_s, 3), error=str(exc)[:200])
            time.sleep(sleep_s)
        except (ValueError, KeyError, EOFError) as exc:
            _emit(recorder, "quarantine", chunk=index, reason="parse",
                  error=str(exc)[:300])
            raise IngestError(f"chunk {index} quarantined: "
                              f"{exc}") from exc


# ----------------------------------------------------------------------
# streamed dataset
# ----------------------------------------------------------------------
@dataclasses.dataclass
class StreamInfo:
    """How this dataset reached the device (rides on the dataset so
    the booster can stream construction and the checkpoint manifest
    can record the cache identity)."""

    cache_key: str
    cache_dir: str
    chunk_rows: int
    window_rows: int
    prefetch: bool
    from_cache: bool          # sealed-manifest open (no binning ran)
    mappers_reused: bool      # prelude hit: the sample pass was skipped
    rebinned: int             # chunks re-binned on this construct
    cache_hits: int           # chunks reused as-is
    ingested_at: float = 0.0  # wall time of this construct (the
    #                           checkpoint-resume freshness check)


class StreamedTpuDataset(TpuDataset):
    """A :class:`TpuDataset` whose ``binned`` matrix is a read-only
    mmap over the crash-safe cache (``io/cache.py``) — host residency
    is the OS page cache's business, and the booster uploads it in
    budgeted double-buffered windows (:class:`BlockFetcher`)."""

    def __init__(self, *args, stream: StreamInfo, **kwargs):
        super().__init__(*args, **kwargs)
        self.stream = stream


# ----------------------------------------------------------------------
# chunk sizing under the host budget
# ----------------------------------------------------------------------
def _budget_rows(budget_mb: int, row_bytes: int, floor: int = 256
                 ) -> int:
    budget = max(int(budget_mb), 1) * (1 << 20)
    # staging keeps ~4 copies of a chunk alive (raw read, binned
    # block, transpose, in-flight device buffer)
    return max(budget // max(row_bytes * 4, 1), floor)


def resolve_chunk_rows(cfg, cols: int, recorder=None,
                       raw_itemsize: int = 8) -> int:
    """The ingest chunk size: explicit ``stream_chunk_rows`` clamped
    to what ``stream_host_budget_mb`` can stage (graceful degradation
    to smaller windows instead of an OOM kill), else budget-derived."""
    cap = _budget_rows(int(getattr(cfg, "stream_host_budget_mb", 256)),
                       cols * raw_itemsize)
    req = int(getattr(cfg, "stream_chunk_rows", 0) or 0)
    if req <= 0:
        return cap
    if req > cap:
        Log.warning("stream: stream_chunk_rows=%d exceeds the "
                    "stream_host_budget_mb=%s staging budget; "
                    "degrading to %d-row chunks", req,
                    getattr(cfg, "stream_host_budget_mb", 256), cap)
        _emit(recorder, "clamp", requested_rows=req, clamped_rows=cap)
        return cap
    return req


def _window_rows(cfg, cols: int, itemsize: int) -> int:
    """Host->device upload window under the same budget (binned-dtype
    row bytes, so windows are larger than raw-ingest chunks).
    Explicit ``stream_window_rows`` wins, clamped to the budget."""
    cap = _budget_rows(int(getattr(cfg, "stream_host_budget_mb", 256)),
                       max(cols * itemsize, 1))
    req = int(getattr(cfg, "stream_window_rows", 0) or 0)
    if req <= 0:
        return cap
    return min(req, cap)


# ----------------------------------------------------------------------
# ingest
# ----------------------------------------------------------------------
def _bin_signature(cfg, categorical: Sequence[int]) -> Dict[str, Any]:
    return {"max_bin": int(cfg.max_bin),
            "min_data_in_bin": int(cfg.min_data_in_bin),
            "sample_cnt": int(cfg.bin_construct_sample_cnt),
            "seed": int(cfg.data_random_seed),
            "use_missing": bool(cfg.use_missing),
            "zero_as_missing": bool(cfg.zero_as_missing),
            "categorical": sorted(int(c) for c in categorical)}


def _gather_sample_and_fit(source: RawSource, cfg,
                           categorical: Sequence[int], chunk_rows: int,
                           retries: int, backoff_base_s: float,
                           recorder) -> List[BinMapper]:
    """ONE streamed pass: gather the exact ``sample_rows`` sample
    (bit-identical to ``find_bin_mappers``'s own draw) and fit the
    mappers from it.  Unknown-length sources reservoir-sample
    instead (documented parity caveat)."""
    t0 = time.perf_counter()
    sample_cnt = int(cfg.bin_construct_sample_cnt)
    seed = int(cfg.data_random_seed)
    if source.rows is None:
        # uncounted producer: reservoir-sample while COUNTING, so the
        # cache can still preallocate (the count becomes the source's
        # row count for the bin pass).  Not bit-identical to the
        # in-memory sample — the documented parity caveat
        res = ReservoirSampler(sample_cnt, seed)
        start = 0
        while True:
            try:
                blk = source.read_rows(start, start + chunk_rows)
            except (IndexError, ValueError):
                break
            if blk.shape[0] == 0:
                break
            res.offer(blk)
            start += blk.shape[0]
        if start == 0:
            raise IngestError("streamed ingest found no rows in the "
                              "uncounted source")
        source.rows = start
        Xs = res.sample()
    else:
        n = source.rows
        idx = sample_rows(n, min(sample_cnt, n), seed)
        picked: List[np.ndarray] = []
        for ci, (s, e) in enumerate(cache_mod.chunk_grid(n, chunk_rows)):
            lo = int(np.searchsorted(idx, s, side="left"))
            hi = int(np.searchsorted(idx, e, side="left"))
            if hi <= lo:
                continue
            blk = _read_chunk(source, ci, s, e, retries,
                              backoff_base_s, 5.0, recorder)
            picked.append(np.array(blk[idx[lo:hi] - s], copy=True))
        Xs = np.concatenate(picked, axis=0) if picked else \
            np.zeros((0, source.cols))
    mappers = find_bin_mappers(
        Xs, max_bin=cfg.max_bin, min_data_in_bin=cfg.min_data_in_bin,
        sample_cnt=max(Xs.shape[0], 1), seed=seed,
        categorical_features=categorical,
        use_missing=cfg.use_missing,
        zero_as_missing=cfg.zero_as_missing)
    _emit(recorder, "fit_mappers", rows_sampled=int(Xs.shape[0]),
          features=int(source.cols),
          duration_ms=round((time.perf_counter() - t0) * 1e3, 3))
    return mappers


def ingest(source: RawSource, cfg, cache_dir: str, recorder=None,
           categorical_features: Sequence[int] = (),
           feature_names: Optional[Sequence[str]] = None
           ) -> StreamedTpuDataset:
    """Streamed ingest into the crash-safe cache; idempotent: a sealed
    cache short-circuits to verify + (single-chunk) repair, a partial
    cache resumes binning only what is missing, a fresh directory runs
    the full sample + bin passes.  Returns a dataset whose ``binned``
    is the cache mmap."""
    t_start = time.perf_counter()
    retries = int(getattr(cfg, "stream_read_retries", 3))
    backoff = float(getattr(cfg, "stream_backoff_base_s", 0.1))
    key = cache_mod.dataset_key(
        source.identity(), _bin_signature(cfg, categorical_features))
    chunk_rows = resolve_chunk_rows(cfg, max(source.cols, 1), recorder)

    # ---- sealed cache: verify every chunk, repair the failures ------
    cache = cache_mod.BinnedCache.open(cache_dir, key=key)
    mappers: Optional[List[BinMapper]] = None
    from_cache = cache is not None
    mappers_reused = False
    rebinned = 0
    cache_hits = 0
    if cache is None:
        cache = cache_mod.BinnedCache.resume(cache_dir, key)
        if cache is None:
            # a cache for DIFFERENT data/config occupies the dir:
            # wipe and start fresh (the key is content-derived)
            stale = cache_mod.BinnedCache(cache_dir).read_prelude_meta()
            if stale is not None and stale.get("key") != key:
                cache_mod.BinnedCache.wipe(cache_dir)
        else:
            mappers_reused = True
    else:
        mappers_reused = True
    if mappers_reused:
        arrays = cache.read_prelude_arrays()
        mappers = _mappers_from_prelude(arrays)
        chunk_rows = cache.chunk_rows
        _emit(recorder, "prelude_hit", key=key[:16],
              chunks=len(cache.grid()))

    # ---- sample pass (fresh caches only) ----------------------------
    if mappers is None:
        # uncounted sources are counted by the reservoir pass inside
        # _gather_sample_and_fit (source.rows is set before return);
        # they must still support range re-reads for the bin pass
        mappers = _gather_sample_and_fit(
            source, cfg, categorical_features, chunk_rows, retries,
            backoff, recorder)
        meta_arrays = source.read_meta()
        used = [i for i, m in enumerate(mappers) if not m.is_trivial]
        dtype = np.uint8 if all(mappers[i].num_bin <= 256
                                for i in used) else np.uint16
        # object arrays need pickle; serialize mapper blobs as a
        # single concatenated buffer + offsets instead
        blobs = [m.to_bytes() for m in mappers]
        offsets = np.cumsum([0] + [len(b) for b in blobs])
        prelude = {"mapper_blob": np.frombuffer(b"".join(blobs),
                                                dtype=np.uint8),
                   "mapper_offsets": offsets.astype(np.int64)}
        for name in ("label", "weight", "group", "init_score"):
            if meta_arrays.get(name) is not None:
                prelude[name] = np.asarray(meta_arrays[name])
        cache = cache_mod.BinnedCache(cache_dir)
        cache.write_prelude(
            key, source.rows, len(used), dtype, chunk_rows, prelude,
            extra={"num_total_features": len(mappers),
                   "feature_names": list(feature_names or [])})

    # ---- bin pass: publish only what is missing/corrupt -------------
    used = [i for i, m in enumerate(mappers) if not m.is_trivial]
    grid = cache.grid()
    quarantined: List[int] = []
    if from_cache:
        validity = cache.valid_chunks()
    else:
        cache.open_binned(writable=True)
        validity = cache.valid_chunks()
    need = [i for i, ok in validity.items() if not ok]
    cache_hits = len(grid) - len(need)
    if need and from_cache:
        for i in need:
            _emit(recorder, "verify_fail", chunk=i)
        Log.warning("stream: %d/%d cached chunk(s) failed sha256 "
                    "verification; re-binning only those", len(need),
                    len(grid))
    if need:
        cache.open_binned(writable=True)
        for i in need:
            s, e = grid[i]
            try:
                blk = _read_chunk(source, i, s, e, retries, backoff,
                                  5.0, recorder)
            except IngestError:
                quarantined.append(i)
                continue
            t0 = time.perf_counter()
            binned = bin_rows(np.ascontiguousarray(blk), mappers,
                              used, cache.dtype)
            t_bin = time.perf_counter()
            cache.write_chunk(i, s, binned)
            _emit(recorder, "cache_write", chunk=i, rows=e - s,
                  bytes=int(binned.nbytes), rebin=bool(from_cache),
                  bin_ms=round((t_bin - t0) * 1e3, 3),
                  write_ms=round((time.perf_counter() - t_bin) * 1e3,
                                 3))
            if from_cache:
                rebinned += 1
    if quarantined:
        raise IngestError(
            f"{len(quarantined)} chunk(s) quarantined "
            f"({quarantined}); every other chunk is published — "
            f"re-run ingest once the source recovers")
    if need or cache.read_manifest() is None:
        # seal (or re-seal after repair).  The manifest-missing case
        # with need=[] is the crash-after-last-attestation resume:
        # every chunk was already published, only the commit record
        # is owed
        cache.finalize()
    if not from_cache:
        rebinned = 0

    # ---- assemble the dataset over the cache mmap -------------------
    arrays = cache.read_prelude_arrays()
    if mappers is None or not mappers:  # pragma: no cover - guarded
        raise IngestError("no mappers")
    meta = Metadata(cache.rows)
    meta.set_label(arrays["label"] if "label" in arrays
                   else np.zeros(cache.rows))
    if "weight" in arrays:
        meta.set_weight(arrays["weight"])
    if "group" in arrays:
        meta.set_query(arrays["group"])
    if "init_score" in arrays:
        meta.set_init_score(arrays["init_score"])
    prelude_meta = cache.read_prelude_meta() or {}
    names = prelude_meta.get("feature_names") or feature_names
    binned = cache.open_binned(writable=False)
    info = StreamInfo(
        cache_key=key, cache_dir=os.path.abspath(cache_dir),
        chunk_rows=cache.chunk_rows,
        window_rows=_window_rows(cfg, cache.cols,
                                 cache.dtype.itemsize),
        prefetch=bool(getattr(cfg, "stream_prefetch", True)),
        from_cache=from_cache, mappers_reused=mappers_reused,
        rebinned=rebinned, cache_hits=cache_hits,
        ingested_at=round(time.time(), 3))
    ds = StreamedTpuDataset(mappers, binned, meta,
                            feature_names=list(names) if names else None,
                            stream=info)
    # continue-training (init_model / the continual daemon's extend
    # path) replays seed trees over RAW values; keep the source so
    # the replay can stream chunk-by-chunk instead of requiring a
    # resident raw matrix
    ds.raw_source = source
    _emit(recorder, "ingest_done", key=key[:16], rows=cache.rows,
          chunks=len(grid), cache_hits=cache_hits, rebinned=rebinned,
          from_cache=from_cache, mappers_reused=mappers_reused,
          cached_bytes=int(cache.rows * cache.cols *
                           cache.dtype.itemsize),
          duration_ms=round((time.perf_counter() - t_start) * 1e3, 3))
    return ds


def _mappers_from_prelude(arrays: Dict[str, np.ndarray]
                          ) -> List[BinMapper]:
    blob = arrays["mapper_blob"].tobytes()
    offsets = arrays["mapper_offsets"]
    return [BinMapper.from_bytes(blob[int(offsets[i]):
                                      int(offsets[i + 1])])
            for i in range(len(offsets) - 1)]


def ingest_dataset(data, label=None, weight=None, group=None,
                   init_score=None, config=None,
                   feature_name="auto", categorical_feature="auto",
                   recorder=None) -> StreamedTpuDataset:
    """The ``basic.Dataset.construct`` entry: resolve a source, a
    cache directory and categorical indices from the config and run
    :func:`ingest`."""
    cfg = config
    cache_root = str(getattr(cfg, "stream_cache_dir", "") or "")
    if not cache_root:
        Log.fatal("stream_ingest=true requires stream_cache_dir")
    source = resolve_source(data, label=label, weight=weight,
                            group=group, init_score=init_score)
    cat: List[int] = []
    spec = categorical_feature
    if spec in ("auto", None):
        spec = getattr(cfg, "categorical_feature", "") or []
        if isinstance(spec, str):
            spec = [s.strip() for s in spec.split(",") if s.strip()]
    if spec:
        for c in spec:
            if isinstance(c, (int, np.integer)) or \
                    str(c).lstrip("+-").isdigit():
                cat.append(int(c))
            else:
                Log.warning("stream_ingest: categorical feature %r "
                            "ignored (streamed ingest resolves "
                            "categorical features by INDEX)", c)
    names = None if feature_name in ("auto", None) else list(feature_name)
    key = cache_mod.dataset_key(
        source.identity(), _bin_signature(cfg, cat))
    cache_dir = os.path.join(cache_root, key[:16])
    return ingest(source, cfg, cache_dir, recorder=recorder,
                  categorical_features=cat, feature_names=names)


def prune_cache_root(cache_root: str, keep_keys: Sequence[str] = (),
                     keep_last: int = 4) -> List[str]:
    """Retention for per-batch caches (the continual daemon's seam):
    keep ``keep_keys`` plus the ``keep_last`` most recently used
    cache dirs, delete the rest.  Returns pruned paths."""
    if not os.path.isdir(cache_root):
        return []
    keep16 = {str(k)[:16] for k in keep_keys}
    cands = []
    for name in os.listdir(cache_root):
        path = os.path.join(cache_root, name)
        if not os.path.isdir(path) or name in keep16:
            continue
        if cache_mod.BinnedCache(path).read_prelude_meta() is None and \
                not os.path.isfile(os.path.join(path, "manifest.json")):
            continue            # not ours — leave it alone
        cands.append((os.path.getmtime(path), path))
    cands.sort(reverse=True)
    pruned = []
    for _, path in cands[max(int(keep_last), 0):]:
        import shutil
        shutil.rmtree(path, ignore_errors=True)
        pruned.append(path)
    return pruned


# ----------------------------------------------------------------------
# double-buffered host->device block fetcher
# ----------------------------------------------------------------------
_ACTIVE_FETCHERS: "weakref.WeakSet[BlockFetcher]" = weakref.WeakSet()
_FETCHER_LOCK = threading.Lock()

# test hook (tests/test_stream.py): when set, upload() records the
# accumulator's buffer pointer after every window write — pinning that
# donation keeps the slot count CONSTANT (no per-window allocation
# growth).  Reading the pointer synchronizes, so it's never on by
# default.
_TRACK_SLOT_PTRS = False


def abort_active_fetchers() -> int:
    """The elastic abort fence, extended to in-flight host->device
    copies: cancel every active fetcher (its upload raises
    :class:`StreamAborted`) so a re-mesh never consumes a stale
    block.  Returns how many were fenced."""
    with _FETCHER_LOCK:
        fetchers = list(_ACTIVE_FETCHERS)
    n = 0
    for f in fetchers:
        if f.abort():
            n += 1
    return n


class BlockFetcher:
    """Budgeted double-buffered upload of the cached binned matrix to
    the device training layout ``(out_cols, n_pad)``.

    A prefetch thread prepares window ``i+1`` — mmap page-in,
    optional EFB bundle transform (row-independent, so per-window
    application is exact), transpose, zero padding — while the main
    thread issues window ``i``'s async ``device_put`` and the donated
    in-place ``dynamic_update_slice``.  ``overlap_s`` (telemetry)
    counts host prep time hidden under in-flight device work.
    Transient prep failures retry bounded; :meth:`abort` fences the
    stream (elastic re-mesh discipline)."""

    def __init__(self, binned, n_rows: int, n_pad: int, out_cols: int,
                 window_rows: int, transform=None, prefetch: bool = True,
                 read_retries: int = 3, backoff_base_s: float = 0.05,
                 recorder=None):
        self.binned = binned
        self.n_rows = int(n_rows)
        self.n_pad = int(n_pad)
        self.out_cols = int(out_cols)
        self.window_rows = max(min(int(window_rows), self.n_pad), 1)
        self.transform = transform
        self.prefetch = bool(prefetch)
        self.read_retries = max(int(read_retries), 0)
        self.backoff_base_s = float(backoff_base_s)
        self.recorder = recorder
        self._abort = threading.Event()
        self._stats: Dict[str, Any] = {}
        with _FETCHER_LOCK:
            _ACTIVE_FETCHERS.add(self)

    # -- fencing -------------------------------------------------------
    def abort(self) -> bool:
        """Fence this stream: in-flight window prep is dropped and
        :meth:`upload` raises :class:`StreamAborted` at its next
        window boundary.  Idempotent; True if it was still live."""
        was_live = not self._abort.is_set() and not self._stats
        self._abort.set()
        return was_live

    # -- window prep (prefetch thread or inline) ----------------------
    def _prep(self, start: int) -> np.ndarray:
        mode = _faults.fire("stream.prefetch")
        if mode == "error":
            raise OSError(f"injected fault (stream.prefetch:error) at "
                          f"window {start}")
        if mode == "hang":
            time.sleep(3600.0)
        elif mode.startswith("sleep_"):
            time.sleep(float(mode[len("sleep_"):]) / 1e3)
        width = min(self.window_rows, self.n_pad - start)
        data_rows = max(0, min(start + width, self.n_rows) - start)
        out = np.zeros((self.out_cols, width), dtype=self.binned.dtype)
        if data_rows > 0:
            blk = np.asarray(self.binned[start:start + data_rows])
            if self.transform is not None:
                blk = self.transform(blk)
            out[: blk.shape[1], :data_rows] = blk.T
        return out

    def _prep_retry(self, start: int) -> np.ndarray:
        attempt = 0
        while True:
            try:
                return self._prep(start)
            except OSError as exc:
                attempt += 1
                if attempt > self.read_retries:
                    raise IngestError(
                        f"prefetch window at row {start} failed "
                        f"through {attempt} attempts: {exc}") from exc
                sleep_s = min(self.backoff_base_s * 2 ** (attempt - 1),
                              2.0)
                _emit(self.recorder, "backoff", window=start,
                      attempt=attempt, sleep_s=round(sleep_s, 3),
                      error=str(exc)[:200])
                time.sleep(sleep_s)

    # -- the upload ----------------------------------------------------
    def upload(self, dtype=None, sharding=None, donate=None):
        """Stream the matrix to device in budgeted windows.

        ``sharding`` (a NamedSharding) places the accumulating buffer
        — and every window write — directly in the tree learner's
        layout (1-D ``P(None, "shard")`` rows, or the data2d
        ``P("feature", "data")`` tiles).  Without it the full
        ``(out_cols, n_pad)`` matrix materializes on ONE device and
        gets re-sharded afterwards, which is exactly the residency
        spike the windowed upload exists to avoid."""
        import jax
        import jax.numpy as jnp

        dtype = dtype or self.binned.dtype
        starts = list(range(0, self.n_pad, self.window_rows))
        t_all0 = time.perf_counter()
        # donation lets XLA write every window into the SAME
        # accumulator allocation (two live slots total: the buffer +
        # the in-flight window) instead of growing one allocation per
        # window; default off on CPU where the copy is cheap, and
        # overridable so the slot-reuse contract is testable there
        if donate is None:
            donate = jax.default_backend() not in ("cpu",)

        def _write(buf, win, s):
            return jax.lax.dynamic_update_slice(buf, win, (0, s))

        write = jax.jit(
            _write, donate_argnums=(0,) if donate else (),
            **({"out_shardings": sharding}
               if sharding is not None else {}))
        if sharding is not None:
            buf = jnp.zeros((self.out_cols, self.n_pad), dtype=dtype,
                            device=sharding)
        else:
            buf = jnp.zeros((self.out_cols, self.n_pad), dtype=dtype)

        prep_s = [0.0]
        wait_s = 0.0
        bytes_moved = 0
        slot_ptrs: list = []

        def _pin(b):
            # blocks until the write lands — test-hook only
            try:
                slot_ptrs.append(b.unsafe_buffer_pointer())
            except Exception:  # noqa: BLE001 — sharded array
                slot_ptrs.append(
                    b.addressable_shards[0].data.unsafe_buffer_pointer())

        if self.prefetch and len(starts) > 1:
            q: "queue.Queue" = queue.Queue(maxsize=1)

            def producer():
                for s in starts:
                    if self._abort.is_set():
                        q.put(("aborted", None, None))
                        return
                    t0 = time.perf_counter()
                    try:
                        win = self._prep_retry(s)
                    except BaseException as exc:  # noqa: BLE001
                        q.put(("error", s, exc))
                        return
                    prep_s[0] += time.perf_counter() - t0
                    q.put(("ok", s, win))
                q.put(("done", None, None))

            th = threading.Thread(target=producer, daemon=True,
                                  name="ltpu-stream-prefetch")
            th.start()
            try:
                while True:
                    t0 = time.perf_counter()
                    kind, s, win = q.get()
                    wait_s += time.perf_counter() - t0
                    if kind == "done":
                        break
                    if kind == "aborted" or self._abort.is_set():
                        raise StreamAborted("host->device stream "
                                            "fenced off mid-upload")
                    if kind == "error":
                        raise win
                    dev = jax.device_put(win)
                    buf = write(buf, dev, jnp.int32(s))
                    bytes_moved += win.nbytes
                    if _TRACK_SLOT_PTRS:
                        _pin(buf)
                th.join(timeout=5.0)
            finally:
                # an early consumer exit (abort fence, prep error)
                # must not leave the producer blocked in q.put
                # forever, pinning a budget-sized window buffer and
                # this fetcher for the process lifetime — drain until
                # the thread observes the abort flag and dies
                if th.is_alive():
                    self._abort.set()
                    for _ in range(100):
                        try:
                            q.get_nowait()
                        except queue.Empty:
                            pass
                        th.join(timeout=0.05)
                        if not th.is_alive():
                            break
        else:
            for s in starts:
                if self._abort.is_set():
                    raise StreamAborted("host->device stream fenced "
                                        "off mid-upload")
                t0 = time.perf_counter()
                win = self._prep_retry(s)
                prep_s[0] += time.perf_counter() - t0
                dev = jax.device_put(win)
                buf = write(buf, dev, jnp.int32(s))
                bytes_moved += win.nbytes
                if _TRACK_SLOT_PTRS:
                    _pin(buf)
        if self._abort.is_set():
            raise StreamAborted("host->device stream fenced off")
        overlap = max(prep_s[0] - wait_s, 0.0) if self.prefetch else 0.0
        self._stats = {
            "windows": len(starts), "bytes": int(bytes_moved),
            "window_rows": self.window_rows,
            "prefetch": self.prefetch,
            "overlap_s": round(overlap, 6),
            "wait_s": round(wait_s, 6),
            "prep_s": round(prep_s[0], 6),
            "duration_ms": round(
                (time.perf_counter() - t_all0) * 1e3, 3)}
        if slot_ptrs:
            self._stats["slot_unique_ptrs"] = len(set(slot_ptrs))
        _telemetry.counters.incr("ingest_prefetch_windows",
                                 len(starts))
        return buf

    def stats(self) -> Dict[str, Any]:
        return dict(self._stats)
