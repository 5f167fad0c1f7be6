"""Out-of-core streaming ingest (io/stream.py + io/cache.py).

The parity contract: a dataset fed through the streamed path — chunked
raw reads, one streamed sample pass, the crash-safe binned cache, the
double-buffered host->device window upload — trains to a model
BYTE-identical to the same data through the in-memory path, at every
sampling strategy, fused block size and (same-width) sharded mesh.
The robustness contract: a SIGKILL-shaped crash mid-ingest never
re-fits a mapper or re-bins a published chunk; a corrupt or truncated
chunk re-bins ALONE; transient reads retry bounded and quarantine
loudly; checkpoint manifests carry the cache identity and resume
verifies it was reused.
"""
import glob
import json
import os
import shutil
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.io import cache as cache_mod
from lightgbm_tpu.io import stream as stream_mod
from lightgbm_tpu.io.stream import (ArraySource, BlockFetcher,
                                    IngestError, NpyPairSource,
                                    NpzShardSource, ReservoirSampler,
                                    StreamAborted,
                                    abort_active_fetchers)
from lightgbm_tpu.utils import faults
from lightgbm_tpu.utils import telemetry as tele
from lightgbm_tpu.utils.faults import InjectedFault

N_ROWS, N_FEAT = 601, 12          # 601 % 97 != 0: the chunk grid does
CHUNK = 97                        # NOT divide the row count
BASE = {"objective": "binary", "num_leaves": 15, "verbose": -1,
        "metric": "None", "num_iterations": 8, "fused_iters": 4}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.configure("")
    faults.reset()
    tele.set_recorder(None)
    yield
    faults.configure("")
    faults.reset()
    tele.set_recorder(None)


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(7)
    X = rng.randn(N_ROWS, N_FEAT)
    w = rng.randn(N_FEAT)
    y = (1.0 / (1.0 + np.exp(-(X @ w) * 0.5)) >
         rng.random_sample(N_ROWS)).astype(np.float32)
    return X, y


def train_model(X, y, params):
    d = lgb.Dataset(X, label=y, params=dict(params))
    bst = lgb.train(dict(params), d, verbose_eval=False)
    return bst.model_to_string(), d


def stream_params(tmp, extra=None, **kw):
    p = dict(BASE, stream_ingest=True,
             stream_cache_dir=os.path.join(str(tmp), "cache"),
             stream_chunk_rows=CHUNK, stream_window_rows=128,
             stream_backoff_base_s=0.01)
    p.update(extra or {})
    p.update(kw)
    return p


@pytest.fixture(scope="module")
def oracle(data):
    X, y = data
    m, d = train_model(X, y, BASE)
    return m, d._constructed.binned


# ----------------------------------------------------------------------
# bit-parity
# ----------------------------------------------------------------------
def test_streamed_bit_identical_to_inmemory(data, oracle, tmp_path):
    X, y = data
    m_oracle, binned_oracle = oracle
    p = stream_params(tmp_path)
    m, d = train_model(X, y, p)
    assert m == m_oracle
    ds = d._constructed
    np.testing.assert_array_equal(np.asarray(ds.binned), binned_oracle)
    info = ds.stream
    assert not info.from_cache and not info.mappers_reused
    assert info.rebinned == 0
    # 601 rows / 97-row chunks -> 7 chunks, last one short
    assert len(cache_mod.chunk_grid(N_ROWS, CHUNK)) == 7


@pytest.mark.slow
def test_sealed_cache_reuse_trains_identically(data, oracle, tmp_path):
    X, y = data
    m_oracle, _ = oracle
    p = stream_params(tmp_path)
    train_model(X, y, p)
    m2, d2 = train_model(X, y, p)
    assert m2 == m_oracle
    info = d2._constructed.stream
    assert info.from_cache and info.mappers_reused
    assert info.cache_hits == 7 and info.rebinned == 0


@pytest.mark.parametrize("extra", [
    {"bagging_fraction": 0.7, "bagging_freq": 2, "fused_iters": 1},
    {"boosting": "goss", "fused_iters": 4},
])
def test_sampling_parity_fast(data, tmp_path, extra):
    X, y = data
    m_oracle, _ = train_model(X, y, dict(BASE, **extra))
    m, _ = train_model(X, y, stream_params(tmp_path, extra))
    assert m == m_oracle


@pytest.mark.slow
@pytest.mark.parametrize("fused", [1, 4])
@pytest.mark.parametrize("extra", [
    {},
    {"bagging_fraction": 0.7, "bagging_freq": 2},
    {"boosting": "goss"},
    {"boosting": "mvs"},
])
def test_sampling_parity_matrix(data, tmp_path, extra, fused):
    X, y = data
    cfg = dict(extra, fused_iters=fused)
    m_oracle, _ = train_model(X, y, dict(BASE, **cfg))
    m, _ = train_model(X, y, stream_params(tmp_path, cfg))
    assert m == m_oracle


@pytest.mark.slow
def test_sharded_data_parallel_parity(data, tmp_path):
    """Streamed vs in-memory at the SAME mesh width (the streamed
    path's device program is identical; only the host source of the
    bytes differs)."""
    X, y = data
    cfg = {"tree_learner": "data", "num_machines": 4}
    m_oracle, _ = train_model(X, y, dict(BASE, **cfg))
    m, _ = train_model(X, y, stream_params(tmp_path, cfg))
    assert m == m_oracle


@pytest.mark.slow
def test_sharded_data2d_streamed_parity(data, tmp_path):
    """Streamed ingest x the 2-D data x feature mesh: upload windows
    must land in the data2d ``P("feature", "data")`` tiles (NOT the
    1-D row layout), and the model stays byte-identical to the
    resident 2-D run."""
    X, y = data
    cfg = {"tree_learner": "data2d", "mesh_shape": "4x2"}
    m_oracle, _ = train_model(X, y, dict(BASE, **cfg))
    p = stream_params(tmp_path, cfg)
    d = lgb.Dataset(X, label=y, params=dict(p))
    bst = lgb.train(dict(p), d, verbose_eval=False)
    assert bst.model_to_string() == m_oracle
    g = bst._gbdt
    # the binned matrix sits in the learner's own 2-D tiles, placed
    # window-by-window during upload (no post-hoc re-shard)
    assert g._stream_upload is not None
    want = g._dist.shardings()["xt"]
    assert g._xt.sharding == want
    spec = tuple(g._xt.sharding.spec)
    assert None not in spec and len(spec) == 2


# ----------------------------------------------------------------------
# crash safety
# ----------------------------------------------------------------------
def test_crash_mid_binning_resumes_without_refit(data, oracle, tmp_path):
    X, y = data
    m_oracle, _ = oracle
    p = stream_params(tmp_path)
    # cache_write hits: prelude(1), chunk0(2), chunk1(3), CRASH on
    # chunk2's write — torn bytes on disk, no cleanup (BaseException)
    faults.configure("stream.cache_write:crash@4")
    with pytest.raises(InjectedFault):
        lgb.Dataset(X, label=y, params=p).construct()
    faults.configure("")
    rec = tele.RunRecorder(None)
    tele.set_recorder(rec)
    m, d = train_model(X, y, p)
    tele.set_recorder(None)
    assert m == m_oracle
    info = d._constructed.stream
    assert info.mappers_reused          # resume fit NO mapper twice
    assert info.cache_hits == 2         # chunks 0,1 reused as-is
    fits = [r for r in rec.records if r.get("type") == "ingest"
            and r.get("event") == "fit_mappers"]
    assert fits == []


def test_corrupt_chunk_rebins_only_that_chunk(data, oracle, tmp_path):
    X, y = data
    m_oracle, _ = oracle
    p = stream_params(tmp_path)
    _, d1 = train_model(X, y, p)
    dat = os.path.join(d1._constructed.stream.cache_dir, "binned.dat")
    with open(dat, "r+b") as f:
        f.seek(CHUNK * N_FEAT + 3)      # inside chunk 1
        f.write(b"\xff\xfe\xfd")
    rec = tele.RunRecorder(None)
    tele.set_recorder(rec)
    m2, d2 = train_model(X, y, p)
    tele.set_recorder(None)
    assert m2 == m_oracle
    info = d2._constructed.stream
    assert info.from_cache and info.rebinned == 1
    assert info.cache_hits == 6
    fails = [r for r in rec.records if r.get("type") == "ingest"
             and r.get("event") == "verify_fail"]
    assert [r["chunk"] for r in fails] == [1]


def test_truncated_cache_rebins_tail_only(data, oracle, tmp_path):
    X, y = data
    m_oracle, _ = oracle
    p = stream_params(tmp_path)
    _, d1 = train_model(X, y, p)
    dat = os.path.join(d1._constructed.stream.cache_dir, "binned.dat")
    size = os.path.getsize(dat)
    with open(dat, "r+b") as f:
        f.truncate(size - N_FEAT * 30)  # lose the tail chunk's bytes
    m2, d2 = train_model(X, y, p)
    assert m2 == m_oracle
    info = d2._constructed.stream
    assert info.mappers_reused
    assert info.cache_hits >= 5         # prefix chunks reused


def test_transient_read_fault_retried(data, oracle, tmp_path):
    X, y = data
    m_oracle, _ = oracle
    faults.configure("stream.chunk_read:error@2")
    rec = tele.RunRecorder(None)
    tele.set_recorder(rec)
    m, _ = train_model(X, y, stream_params(tmp_path))
    tele.set_recorder(None)
    assert m == m_oracle
    backoffs = [r for r in rec.records if r.get("type") == "ingest"
                and r.get("event") == "backoff"]
    assert len(backoffs) == 1


def test_quarantine_after_retries_fails_loudly(data, tmp_path):
    X, y = data
    # the sample pass reads all 7 chunks (hits 1-7); bin-pass chunks
    # 0,1 land (hits 8,9); every later read fails with retries=0 ->
    # chunks 2..6 quarantine and ingest raises AFTER binning the rest
    faults.configure("stream.chunk_read:error@10+")
    p = stream_params(tmp_path, stream_read_retries=0)
    rec = tele.RunRecorder(None)
    tele.set_recorder(rec)
    with pytest.raises(IngestError):
        lgb.Dataset(X, label=y, params=p).construct()
    tele.set_recorder(None)
    quar = [r for r in rec.records if r.get("type") == "ingest"
            and r.get("event") == "quarantine"]
    assert len(quar) == 5
    faults.configure("")
    faults.reset()
    # the retry run owes only the quarantined chunks
    d = lgb.Dataset(X, label=y, params=p)
    d.construct()
    assert d._constructed.stream.cache_hits == 2


def test_host_budget_clamps_chunk_rows(data, oracle, tmp_path):
    X, y = data
    m_oracle, _ = oracle
    rec = tele.RunRecorder(None)
    tele.set_recorder(rec)
    p = stream_params(tmp_path, stream_chunk_rows=10 ** 7,
                      stream_host_budget_mb=1)
    m, d = train_model(X, y, p)
    tele.set_recorder(None)
    assert m == m_oracle
    clamps = [r for r in rec.records if r.get("type") == "ingest"
              and r.get("event") == "clamp"]
    assert clamps and clamps[0]["requested_rows"] == 10 ** 7
    assert d._constructed.stream.chunk_rows < 10 ** 7


# ----------------------------------------------------------------------
# host->device streaming
# ----------------------------------------------------------------------
def test_prefetch_overlap_recorded(data, tmp_path):
    X, y = data
    rec = tele.RunRecorder(None)
    tele.set_recorder(rec)
    train_model(X, y, stream_params(tmp_path, stream_window_rows=64))
    tele.set_recorder(None)
    pf = [r for r in rec.records if r.get("type") == "ingest"
          and r.get("event") == "prefetch"]
    assert pf, "streamed construction must emit a prefetch record"
    assert pf[0]["windows"] >= 7 and pf[0]["prefetch"] is True
    assert pf[0]["overlap_s"] >= 0.0
    end = rec.summary()
    assert end["ingest_prefetch_windows"] >= 7


def test_prefetch_fault_retries_then_fails(data, tmp_path):
    X, y = data
    binned = (np.arange(N_ROWS * N_FEAT, dtype=np.uint8)
              .reshape(N_ROWS, N_FEAT) % 7)
    faults.configure("stream.prefetch:error@*")
    f = BlockFetcher(binned, n_rows=N_ROWS, n_pad=608, out_cols=N_FEAT,
                     window_rows=64, read_retries=1,
                     backoff_base_s=0.01)
    with pytest.raises(IngestError):
        f.upload()


def test_abort_fence_cancels_inflight_upload():
    binned = (np.arange(N_ROWS * N_FEAT, dtype=np.uint8)
              .reshape(N_ROWS, N_FEAT) % 7)
    faults.configure("stream.prefetch:sleep_150@*")
    f = BlockFetcher(binned, n_rows=N_ROWS, n_pad=608, out_cols=N_FEAT,
                     window_rows=64)
    t = threading.Timer(0.2, abort_active_fetchers)
    t.start()
    try:
        with pytest.raises(StreamAborted):
            f.upload()
    finally:
        t.cancel()


@pytest.mark.slow
def test_abort_fence_cancels_upload_during_2d_remesh(data, tmp_path):
    """The fence reaches a streamed re-upload riding INSIDE a 2-D
    re-mesh: remesh re-runs construction, construction re-streams the
    cache, the fence lands mid-window and StreamAborted surfaces out
    of remesh; a fault-free retry with the pre-captured snapshot then
    lands the new (R, F) shape and training state survives."""
    X, y = data
    p = stream_params(tmp_path, {"tree_learner": "data2d",
                                 "mesh_shape": "4x2",
                                 "num_iterations": 4})
    d = lgb.Dataset(X, label=y, params=dict(p))
    bst = lgb.train(dict(p), d, verbose_eval=False)
    g = bst._gbdt
    snap = g.training_snapshot()
    faults.configure("stream.prefetch:sleep_150@*")
    t = threading.Timer(0.2, abort_active_fetchers)
    t.start()
    try:
        with pytest.raises(StreamAborted):
            g.remesh(mesh_shape=(2, 4), snapshot=snap)
    finally:
        t.cancel()
    faults.configure("")
    faults.reset()
    assert g.remesh(mesh_shape=(2, 4), snapshot=snap) == 8
    assert (g._dist.row_shards, g._dist.feat_shards) == (2, 4)


def test_upload_donation_reuses_slots(monkeypatch):
    """Donated window writes reuse a CONSTANT number of device
    allocations (the accumulator slots) — per-window allocation growth
    would defeat the budget the windowed upload enforces."""
    rng = np.random.RandomState(5)
    binned = rng.randint(0, 9, size=(N_ROWS, N_FEAT)).astype(np.uint8)
    f = BlockFetcher(binned, n_rows=N_ROWS, n_pad=640, out_cols=16,
                     window_rows=64)
    monkeypatch.setattr(stream_mod, "_TRACK_SLOT_PTRS", True)
    got = np.asarray(f.upload(donate=True))
    want = np.pad(binned.T, ((0, 16 - N_FEAT), (0, 640 - N_ROWS)))
    np.testing.assert_array_equal(got, want)
    s = f.stats()
    assert s["windows"] == 10
    # ping-pong bound: at most the two paging slots, never one
    # allocation per window
    assert 1 <= s["slot_unique_ptrs"] <= 2


def test_upload_matches_monolithic_pad():
    """The windowed double-buffered upload assembles EXACTLY the
    transpose+pad the in-memory path builds."""
    rng = np.random.RandomState(3)
    binned = rng.randint(0, 9, size=(N_ROWS, N_FEAT)).astype(np.uint8)
    f = BlockFetcher(binned, n_rows=N_ROWS, n_pad=640, out_cols=16,
                     window_rows=100)
    got = np.asarray(f.upload())
    want = np.pad(binned.T, ((0, 16 - N_FEAT), (0, 640 - N_ROWS)))
    np.testing.assert_array_equal(got, want)
    assert f.stats()["windows"] == 7


# ----------------------------------------------------------------------
# checkpoint resume contract
# ----------------------------------------------------------------------
@pytest.mark.slow
def test_checkpoint_records_cache_identity_and_resume_hits(
        data, tmp_path):
    X, y = data
    ck = os.path.join(str(tmp_path), "ck")
    p = stream_params(tmp_path, checkpoint_dir=ck, snapshot_freq=4,
                      num_iterations=10)
    m_oracle, _ = train_model(X, y, p)
    shutil.rmtree(ck)
    shutil.rmtree(os.path.join(str(tmp_path), "cache"))
    p6 = dict(p, num_iterations=6)
    train_model(X, y, p6)
    man = sorted(glob.glob(os.path.join(ck, "ckpt_*",
                                        "manifest.json")))[-1]
    with open(man) as f:
        manifest = json.load(f)
    assert manifest["stream"]["cache_key"]
    rec = tele.RunRecorder(None)
    tele.set_recorder(rec)
    d = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(dict(p), d, verbose_eval=False,
                    resume_from="auto")
    tele.set_recorder(None)
    assert bst.model_to_string() == m_oracle
    resume = [r for r in rec.records if r.get("type") == "ingest"
              and r.get("event") == "resume"]
    assert [r["cache_hit"] for r in resume] == [True]


def test_resume_cache_miss_is_med_anomaly(data, tmp_path):
    from lightgbm_tpu.obs import rules
    X, y = data
    ck = os.path.join(str(tmp_path), "ck")
    p = stream_params(tmp_path, checkpoint_dir=ck, snapshot_freq=4,
                      num_iterations=6)
    train_model(X, y, p)
    shutil.rmtree(os.path.join(str(tmp_path), "cache"))  # the miss
    rec = tele.RunRecorder(None)
    tele.set_recorder(rec)
    d = lgb.Dataset(X, label=y, params=dict(p, num_iterations=10))
    lgb.train(dict(p, num_iterations=10), d, verbose_eval=False,
              resume_from="auto")
    tele.set_recorder(None)
    resume = [r for r in rec.records if r.get("type") == "ingest"
              and r.get("event") == "resume"]
    assert [r["cache_hit"] for r in resume] == [False]
    scanner = rules.OnlineScanner()
    fired = [a for r in rec.records for a in scanner.feed(r)]
    assert ("MED", "ingest_cache_miss") in [(s, c)
                                            for s, c, _ in fired]


# ----------------------------------------------------------------------
# sources + sampler
# ----------------------------------------------------------------------
def test_npy_pair_source_parity(data, oracle, tmp_path):
    X, y = data
    m_oracle, _ = oracle
    stem = os.path.join(str(tmp_path), "shard")
    np.save(stem + ".X.npy", X)
    np.save(stem + ".y.npy", y)
    p = stream_params(tmp_path)
    d = lgb.Dataset(stem + ".X.npy", params=p)
    bst = lgb.train(dict(p), d, verbose_eval=False)
    assert bst.model_to_string() == m_oracle


def test_npz_shard_source_spans_boundaries(data, tmp_path):
    X, y = data
    shard_dir = os.path.join(str(tmp_path), "shards")
    os.makedirs(shard_dir)
    for i, (lo, hi) in enumerate([(0, 200), (200, 450), (450, N_ROWS)]):
        np.savez(os.path.join(shard_dir, f"b{i:02d}.npz"),
                 X=X[lo:hi], y=y[lo:hi])
    src = NpzShardSource(shard_dir)
    assert src.rows == N_ROWS and src.cols == N_FEAT
    np.testing.assert_array_equal(src.read_rows(150, 470),
                                  X[150:470])
    np.testing.assert_array_equal(src.read_meta()["label"], y)
    m_oracle, _ = train_model(X, y, BASE)
    p = stream_params(tmp_path)
    d = lgb.Dataset(shard_dir, params=p)
    bst = lgb.train(dict(p), d, verbose_eval=False)
    assert bst.model_to_string() == m_oracle


def test_reservoir_sampler_bounds_and_determinism():
    rng = np.random.RandomState(0)
    rows = rng.randn(500, 4)
    a = ReservoirSampler(64, seed=5)
    b = ReservoirSampler(64, seed=5)
    for blk in np.array_split(rows, 7):
        a.offer(blk)
        b.offer(blk)
    assert a.seen == 500 and a.sample().shape == (64, 4)
    np.testing.assert_array_equal(a.sample(), b.sample())


def test_crash_before_manifest_seals_on_resume(data, tmp_path):
    """SIGKILL after the LAST chunk attestation but before
    manifest.json: the resume owes only the commit record — it must
    seal the cache so later opens are sealed-cache hits."""
    X, y = data
    p = stream_params(tmp_path)
    # cache_write hits: prelude(1), chunks(2-8), manifest(9) -> crash
    faults.configure("stream.cache_write:crash@9")
    with pytest.raises(InjectedFault):
        lgb.Dataset(X, label=y, params=p).construct()
    faults.configure("")
    d1 = lgb.Dataset(X, label=y, params=p)
    d1.construct()
    info = d1._constructed.stream
    assert info.mappers_reused and info.cache_hits == 7
    assert os.path.isfile(os.path.join(info.cache_dir,
                                       "manifest.json"))
    d2 = lgb.Dataset(X, label=y, params=p)
    d2.construct()
    assert d2._constructed.stream.from_cache


def test_npy_rewrite_rekeys_cache(data, tmp_path):
    """A regenerated same-shape/same-size raw file must NOT reuse the
    stale binned cache (content is part of the source identity)."""
    X, y = data
    stem = os.path.join(str(tmp_path), "raw")
    np.save(stem + ".X.npy", X)
    np.save(stem + ".y.npy", y)
    p = stream_params(tmp_path)
    d1 = lgb.Dataset(stem + ".X.npy", params=p)
    d1.construct()
    k1 = d1._constructed.stream.cache_key
    X2 = X.copy()
    X2[3, 4] += 1.0                      # same shape, same byte size
    np.save(stem + ".X.npy", X2)
    d2 = lgb.Dataset(stem + ".X.npy", params=p)
    d2.construct()
    assert d2._constructed.stream.cache_key != k1
    assert not d2._constructed.stream.from_cache


def test_explicit_label_overrides_npy_sidecar(data, tmp_path):
    X, y = data
    stem = os.path.join(str(tmp_path), "raw")
    np.save(stem + ".X.npy", X)
    np.save(stem + ".y.npy", np.zeros_like(y))   # stale sidecar
    p = stream_params(tmp_path)
    d = lgb.Dataset(stem + ".X.npy", label=y, params=p)
    d.construct()
    np.testing.assert_array_equal(
        np.asarray(d._constructed.metadata.label), y)


def test_unstreamable_path_falls_through_to_inmemory(data, tmp_path):
    """stream_ingest=true with a CSV path uses the normal loader
    (with a warning) instead of failing inside the stream path."""
    X, y = data
    path = os.path.join(str(tmp_path), "train.tsv")
    np.savetxt(path, np.column_stack([y, X]), delimiter="\t")
    p = stream_params(tmp_path)
    d = lgb.Dataset(path, params=p)
    d.construct()
    assert d._constructed is not None
    assert getattr(d._constructed, "stream", None) is None
    assert d._constructed.num_data == N_ROWS


def test_uncounted_source_reservoir_ingest(data, tmp_path):
    """An uncounted producer is reservoir-sampled and COUNTED in one
    pass; ingest still seals a trainable cache (parity caveat
    documented — mappers come from the reservoir, not sample_rows)."""
    X, y = data

    class Uncounted(ArraySource):
        def __init__(self):
            super().__init__(X, y)
            self.rows = None

    from lightgbm_tpu.config import Config
    p = stream_params(tmp_path)
    cfg = Config(dict(p))
    ds = stream_mod.ingest(Uncounted(), cfg,
                           os.path.join(str(tmp_path), "cache", "u"))
    assert ds.num_data == N_ROWS
    assert ds.stream.cache_key
    d = lgb.Dataset(X, label=y, params=dict(BASE))   # shape sanity
    bst = lgb.train(dict(BASE), d, verbose_eval=False)
    assert bst.model_to_string().startswith("tree")


def test_continual_trainer_resolves_stream_alias(tmp_path):
    from lightgbm_tpu.cont import ContinualTrainer
    params = {"objective": "regression", "num_leaves": 7,
              "verbose": -1, "metric": "None",
              "checkpoint_dir": os.path.join(str(tmp_path), "ck"),
              "continual_ingest_dir": os.path.join(str(tmp_path),
                                                   "in"),
              "stream": "true"}          # the registered alias
    tr = ContinualTrainer(params)
    assert tr._stream_batches
    assert tr._stream_cache_dir.endswith("_stream_cache")


def test_array_source_identity_tracks_content(data):
    X, y = data
    s1 = ArraySource(X, y).identity()
    assert s1 == ArraySource(X.copy(), y.copy()).identity()
    X2 = X.copy()
    X2[5, 3] += 1.0
    assert s1 != ArraySource(X2, y).identity()


# ----------------------------------------------------------------------
# telemetry / triage surfaces
# ----------------------------------------------------------------------
def test_ingest_records_lint_clean(data, tmp_path):
    X, y = data
    path = os.path.join(str(tmp_path), "tele.jsonl")
    rec = tele.RunRecorder(path)
    tele.set_recorder(rec)
    train_model(X, y, stream_params(tmp_path))
    tele.set_recorder(None)
    rec.close(log=False)
    n, errs = tele.lint_file(path)
    assert n > 0 and errs == []
    records = tele.read_records(path)
    kinds = {r.get("event") for r in records
             if r.get("type") == "ingest"}
    assert {"fit_mappers", "chunk_read", "cache_write", "ingest_done",
            "prefetch"} <= kinds
    end = [r for r in records if r.get("type") == "run_end"][-1]
    s = end["summary"]
    assert s["ingest_cache_writes"] == 7
    assert s["ingest_mapper_fits"] == 1


@pytest.mark.slow
def test_streamed_dart_resume_and_continue_training(data, tmp_path):
    """DART rides the chunked raw-source replay (leaf-assignment
    rebuild on resume, seed-tree score replay on init_model) —
    byte-identical to the in-memory counterparts."""
    X, y = data
    ck = os.path.join(str(tmp_path), "ck")
    p = stream_params(tmp_path, {"boosting": "dart"},
                      checkpoint_dir=ck, snapshot_freq=4,
                      num_iterations=10)
    p.pop("fused_iters", None)
    m_oracle, _ = train_model(X, y, p)
    shutil.rmtree(ck)
    train_model(X, y, dict(p, num_iterations=6))
    d = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(dict(p), d, verbose_eval=False,
                    resume_from="auto")
    assert bst.model_to_string() == m_oracle


@pytest.mark.slow
def test_continual_streamed_batches_parity(tmp_path):
    """The continual daemon's BatchSource seam: streamed per-batch
    ingest (mmap pairs end to end) trains byte-identical to the
    in-memory daemon over the same batches, and finished batches'
    caches are pruned."""
    from lightgbm_tpu.cont import ContinualTrainer
    rng = np.random.RandomState(0)

    def fill(ingest):
        os.makedirs(ingest, exist_ok=True)
        r = np.random.RandomState(0)
        for i in range(3):
            X = r.randn(400, 6)
            yb = X[:, 0] + 0.1 * r.randn(400)
            np.save(os.path.join(ingest, f"b{i:03d}.X.npy"), X)
            np.save(os.path.join(ingest, f"b{i:03d}.y.npy"), yb)

    def run(root, extra):
        ingest = os.path.join(root, "ingest")
        fill(ingest)
        params = {"objective": "regression", "num_leaves": 7,
                  "verbose": -1, "metric": "None",
                  "checkpoint_dir": os.path.join(root, "ck"),
                  "continual_ingest_dir": ingest,
                  "continual_rounds_per_batch": 4, "fused_iters": 2,
                  "continual_idle_exit_s": 0.5,
                  "continual_poll_s": 0.1}
        params.update(extra)
        tr = ContinualTrainer(params)
        stats = tr.run()
        assert stats["batches"] == 3 and stats["quarantined"] == 0
        return tr._model_text

    m_stream = run(os.path.join(str(tmp_path), "a"),
                   {"stream_ingest": True, "stream_chunk_rows": 150})
    m_mem = run(os.path.join(str(tmp_path), "b"), {})
    assert m_stream == m_mem
    cache_root = os.path.join(str(tmp_path), "a", "ck",
                              "_stream_cache")
    assert len(os.listdir(cache_root)) <= 2     # keep-last retention


def test_triage_summary_has_ingest_line(data, tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "triage_run", os.path.join(os.path.dirname(__file__), "..",
                                   "tools", "triage_run.py"))
    triage_run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(triage_run)
    X, y = data
    path = os.path.join(str(tmp_path), "tele.jsonl")
    rec = tele.RunRecorder(path)
    tele.set_recorder(rec)
    train_model(X, y, stream_params(tmp_path))
    tele.set_recorder(None)
    rec.close(log=False)
    report = triage_run.triage(tele.read_records(path))
    assert "ingest      :" in report
    assert "7 cache writes" in report
