"""Serve-time explanation engine + single-row fast path (PR-20).

Pins the acceptance contract:

- the device TreeSHAP engine (``ops/shap.py``) matches the per-tree
  host reference within 1e-10 across missing x categorical x
  multiclass (it actually lands ~1e-15; the engine runs f64 under a
  scoped ``enable_x64``);
- additivity: per row, contributions + bias reproduce ``predict_raw``
  exactly (trained models — consistent covers);
- 504 concurrent distinct-size ``/explain`` requests after warmup
  record ZERO ``xla_compiles`` and ZERO ``jax_traces`` (publish-time
  warmup pre-compiles the explain bucket ladder);
- the single-row fast path is BIT-identical to the bucketed engine
  (same kernels, tiny power-of-two buckets) and its buckets are
  pre-warmed at publish;
- the serve surface end to end: ``Server.explain`` layout vs
  ``Booster.predict(pred_contrib=True)``, HTTP ``POST /explain`` and
  ``/v1/<model>/explain``, router forwarding + ``route_explain_cost``
  admission weighting, the ``serve.explain`` fault point, ``explain``
  telemetry records + rollups, and re-publish (rejoined replica)
  warm-start.
"""
import contextlib
import json
import os
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.serve import ServeConfig, ServeError, Server
from lightgbm_tpu.utils import faults
from lightgbm_tpu.utils.telemetry import (counters_snapshot, lint_file,
                                          validate_record)


@contextlib.contextmanager
def oracle_env():
    """Force the per-tree host loop, restoring the prior env value."""
    prev = os.environ.get("LTPU_PREDICT_ENGINE")
    os.environ["LTPU_PREDICT_ENGINE"] = "0"
    try:
        yield
    finally:
        if prev is None:
            del os.environ["LTPU_PREDICT_ENGINE"]
        else:
            os.environ["LTPU_PREDICT_ENGINE"] = prev


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    faults.reset()
    yield
    faults.clear()
    faults.reset()


def _messy(rng, rows, cols, nan_frac=0.15):
    X = rng.randn(rows, cols)
    X[rng.rand(rows, cols) < nan_frac] = np.nan
    return X


def _train_binary(n_rounds=5, seed=0, rows=1500, leaves=15,
                  missing=False):
    rng = np.random.RandomState(seed)
    X = _messy(rng, rows, 8) if missing else rng.randn(rows, 8)
    y = (np.nan_to_num(X[:, 0]) + 0.4 * rng.randn(rows) > 0)
    d = lgb.Dataset(X, label=y.astype(float),
                    params={"objective": "binary", "verbose": -1})
    bst = lgb.train({"objective": "binary", "num_leaves": leaves,
                     "verbose": -1, "metric": "None"},
                    d, num_boost_round=n_rounds)
    return bst, X


def _train_multiclass(n_rounds=4, seed=3, rows=1200, leaves=11):
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, 6)
    y = (np.digitize(X[:, 0] + 0.3 * rng.randn(rows),
                     [-0.5, 0.5])).astype(float)
    d = lgb.Dataset(X, label=y, params={"objective": "multiclass",
                                        "num_class": 3, "verbose": -1})
    bst = lgb.train({"objective": "multiclass", "num_class": 3,
                     "num_leaves": leaves, "verbose": -1,
                     "metric": "None"},
                    d, num_boost_round=n_rounds)
    return bst, X


def _train_categorical(n_rounds=4, seed=7, rows=1200, leaves=9):
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, 5)
    X[:, 0] = rng.randint(0, 12, size=rows)     # categorical column
    y = ((X[:, 0] % 3 == 0).astype(float) + 0.2 * rng.randn(rows) > 0.5)
    d = lgb.Dataset(X, label=y.astype(float),
                    params={"objective": "binary", "verbose": -1,
                            "categorical_feature": [0]})
    bst = lgb.train({"objective": "binary", "num_leaves": leaves,
                     "verbose": -1, "metric": "None",
                     "categorical_feature": [0]},
                    d, num_boost_round=n_rounds)
    return bst, X


@pytest.fixture(scope="module")
def binary_pair():
    return _train_binary(missing=True)


@pytest.fixture(scope="module")
def warm_explain_server(binary_pair):
    bst, _ = binary_pair
    srv = Server(bst, config=ServeConfig(max_batch_rows=1024,
                                         batch_wait_ms=0.5,
                                         timeout_ms=60000)).start()
    yield srv
    srv.stop()


# ----------------------------------------------------------------------
# ACCEPTANCE: device TreeSHAP == host reference within 1e-10
# ----------------------------------------------------------------------
@pytest.mark.parametrize("maker", [_train_binary, _train_multiclass,
                                   _train_categorical],
                         ids=["binary-missing", "multiclass",
                              "categorical"])
def test_device_matches_host_reference(maker):
    bst, X = maker() if maker is not _train_binary \
        else _train_binary(missing=True)
    Q = X[:257]                               # off-bucket row count
    dev = bst.predict(Q, pred_contrib=True)
    with oracle_env():
        host = bst.predict(Q, pred_contrib=True)
    assert dev.shape == host.shape
    # 1e-10 is the BINDING acceptance bound; the engine actually sits
    # at f64 rounding noise — pin an order of magnitude below the
    # bound so a regression trips long before the contract does
    np.testing.assert_allclose(dev, host, rtol=0, atol=1e-10)
    assert np.abs(dev - host).max() < 1e-11


def test_device_matches_host_with_nan_probe_rows(binary_pair):
    """Rows that are ENTIRELY NaN and rows with no NaN both agree."""
    bst, X = binary_pair
    probe = np.vstack([X[:64], np.full((3, X.shape[1]), np.nan)])
    dev = bst.predict(probe, pred_contrib=True)
    with oracle_env():
        host = bst.predict(probe, pred_contrib=True)
    np.testing.assert_allclose(dev, host, rtol=0, atol=1e-10)


# ----------------------------------------------------------------------
# additivity: contributions + bias reproduce the raw score per row
# ----------------------------------------------------------------------
def test_additivity_binary(binary_pair):
    bst, X = binary_pair
    contrib = bst.predict(X[:300], pred_contrib=True)
    raw = bst.predict(X[:300], raw_score=True)
    assert contrib.shape == (300, X.shape[1] + 1)
    np.testing.assert_allclose(contrib.sum(axis=1), raw,
                               rtol=0, atol=1e-9)


def test_additivity_multiclass_blocks():
    bst, X = _train_multiclass()
    nf = X.shape[1]
    contrib = bst.predict(X[:200], pred_contrib=True)
    raw = bst.predict(X[:200], raw_score=True)
    assert contrib.shape == (200, 3 * (nf + 1))
    assert raw.shape == (200, 3)
    for k in range(3):
        block = contrib[:, k * (nf + 1):(k + 1) * (nf + 1)]
        np.testing.assert_allclose(block.sum(axis=1), raw[:, k],
                                   rtol=0, atol=1e-9)


# ----------------------------------------------------------------------
# engine-level: bucket ladder + LRU bound the compiled-program count
# ----------------------------------------------------------------------
def test_engine_bucket_ladder_bounds_traces(binary_pair):
    from lightgbm_tpu.ops.shap import get_shap_engine
    bst, X = binary_pair
    eng = get_shap_engine()
    flat = bst._gbdt._shap_forest()
    buckets = eng.bucket_set(flat)
    assert buckets == sorted(buckets)
    assert all(b & (b - 1) == 0 for b in buckets)   # powers of two
    # warm EVERY rung: a max-rows call only compiles the top bucket
    # (one full chunk), and suite-order LRU eviction can have dropped
    # the smaller rungs other tests happened to compile
    for b in buckets:
        eng.predict_contrib(flat, X[:b])
    base = counters_snapshot()
    for n in (1, 2, 3, 50, 129, 200, 511):
        out = eng.predict_contrib(flat, X[:n])
        assert out.shape[-1] == n
    now = counters_snapshot()
    assert now.get("jax_traces", 0) == base.get("jax_traces", 0)
    info = eng.cache_info()
    assert {"hits", "misses", "evictions", "entries", "capacity",
            "traces"} <= set(info)
    assert info["hits"] > 0


# ----------------------------------------------------------------------
# serve surface: layout parity + publish-time warmup
# ----------------------------------------------------------------------
def test_server_explain_matches_booster(warm_explain_server,
                                        binary_pair):
    bst, X = binary_pair
    for n in (1, 9, 200):
        out = warm_explain_server.explain(X[:n])
        np.testing.assert_allclose(
            out, bst.predict(X[:n], pred_contrib=True),
            rtol=0, atol=1e-12)


def test_warmup_covers_explain_and_fastpath_buckets(
        warm_explain_server):
    from lightgbm_tpu.ops.predict import PredictEngine, get_engine
    from lightgbm_tpu.ops.shap import get_shap_engine
    ver = warm_explain_server.registry.current()
    info = ver.warmup_info
    assert info is not None
    assert info["explain_buckets"] == \
        get_shap_engine().bucket_set(ver.shap, ver.chunk_rows)
    assert info["fastpath_buckets"] == \
        PredictEngine.fast_bucket_set(ver.fastpath_rows) == [1, 2, 4, 8]
    assert info["buckets"] == get_engine().bucket_set(ver.flat, 1024)


# ----------------------------------------------------------------------
# ACCEPTANCE: 504 concurrent distinct-size explains, zero compiles
# ----------------------------------------------------------------------
@pytest.mark.slow
def test_steady_state_explain_504_distinct_sizes_zero_compiles(
        warm_explain_server, binary_pair):
    bst, X = binary_pair
    nf = X.shape[1]
    warm_explain_server.explain(X[:17])   # settle any lazy first-touch
    base = counters_snapshot()
    n_threads, per_thread = 8, 63         # 504 requests, all DISTINCT
    failures = []

    def client(tid):
        # disjoint per-thread ranges: every one of the 504 row counts
        # is first-seen, so a per-size compile anywhere on the explain
        # path cannot hide behind the process-global jit cache; the
        # mix spans the whole warmed bucket ladder AND the sub-128
        # sizes that pad up to the smallest bucket
        for j in range(per_thread):
            n = 1 + tid * per_thread * 2 + j * 2 + (tid + j) % 2
            n = min(n, len(X))
            try:
                out = warm_explain_server.explain(X[:n])
                if out.shape != (n, nf + 1):
                    failures.append(("shape", n, out.shape))
            except Exception as exc:      # noqa: BLE001 - recorded
                failures.append(("error", n, str(exc)))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    now = counters_snapshot()
    assert not failures, failures[:5]
    assert now.get("xla_compiles", 0) == base.get("xla_compiles", 0), \
        "steady-state explanation must not compile"
    assert now.get("jax_traces", 0) == base.get("jax_traces", 0), \
        "steady-state explanation must not retrace"
    assert now.get("serve_explain_requests", 0) - \
        base.get("serve_explain_requests", 0) >= n_threads * per_thread


def test_republished_version_explains_without_compiling(binary_pair):
    """A re-publish of a same-layout model (the rejoined-replica path:
    fleet reconciliation -> /swap -> publish -> warmup) must answer its
    FIRST explain request from warmed programs."""
    bst, X = binary_pair
    srv = Server(bst, config=ServeConfig(max_batch_rows=1024,
                                         batch_wait_ms=0.0,
                                         timeout_ms=60000)).start()
    try:
        base = counters_snapshot()
        out = srv.explain(X[:33])
        now = counters_snapshot()
        np.testing.assert_allclose(
            out, bst.predict(X[:33], pred_contrib=True),
            rtol=0, atol=1e-12)
        assert now.get("xla_compiles", 0) == base.get("xla_compiles", 0)
        assert now.get("jax_traces", 0) == base.get("jax_traces", 0)
    finally:
        srv.stop()


# ----------------------------------------------------------------------
# single-row fast path: bit-identical, occupancy-gated
# ----------------------------------------------------------------------
def test_fastpath_bit_identical_to_bucketed(warm_explain_server,
                                            binary_pair):
    bst, X = binary_pair
    fp_rows = warm_explain_server.config.fastpath_max_rows
    assert fp_rows >= 1
    # an idle queue + tiny request routes through the fast path (the
    # stats counter proves it below); outputs must be BIT-identical to
    # the bucketed engine — same kernels, smaller padding
    base = counters_snapshot()
    for n in range(1, fp_rows + 1):
        out = warm_explain_server.predict(X[:n])
        assert np.array_equal(out, bst.predict(X[:n])), n
        raw = warm_explain_server.predict(X[:n], raw=True)
        assert np.array_equal(raw, bst.predict(X[:n], raw_score=True))
    now = counters_snapshot()
    assert now.get("serve_fastpath_batches", 0) > \
        base.get("serve_fastpath_batches", 0)
    assert now.get("xla_compiles", 0) == base.get("xla_compiles", 0), \
        "fast-path buckets are pre-warmed at publish"


def test_fastpath_engine_raw_parity(binary_pair):
    from lightgbm_tpu.ops.predict import get_engine
    bst, X = binary_pair
    eng = get_engine()
    flat = bst._gbdt._flat_forest()
    for n in (1, 2, 5, 8):
        fast = eng.predict_raw_fast(flat, X[:n])
        full = eng.predict_raw(flat, X[:n])
        assert np.array_equal(np.asarray(fast), np.asarray(full)), n


def test_fastpath_respects_row_gate(binary_pair):
    """Requests past ``fastpath_max_rows`` use the bucketed path."""
    bst, X = binary_pair
    srv = Server(bst, config=ServeConfig(max_batch_rows=512,
                                         batch_wait_ms=0.0,
                                         timeout_ms=60000,
                                         fastpath_max_rows=0)).start()
    try:
        base = counters_snapshot()
        out = srv.predict(X[:2])
        np.testing.assert_allclose(out, bst.predict(X[:2]),
                                   rtol=1e-12, atol=1e-12)
        now = counters_snapshot()
        assert now.get("serve_fastpath_batches", 0) == \
            base.get("serve_fastpath_batches", 0)
    finally:
        srv.stop()


# ----------------------------------------------------------------------
# lane isolation: predict and explain never share a device batch
# ----------------------------------------------------------------------
def test_admission_never_mixes_kinds():
    from lightgbm_tpu.serve.admission import AdmissionQueue, Request
    q = AdmissionQueue(max_rows=10000, max_requests=100)
    stop = threading.Event()
    X = np.zeros((4, 3))

    class _V:                              # identity stand-in
        pass

    v = _V()
    reqs = [Request(i, X, False, 0, None, v,
                    kind="explain" if i % 2 else "predict")
            for i in range(6)]
    for r in reqs:
        q.admit(r)
    drained = []
    while q.depth()[0]:
        batch, _ = q.drain_batch(1024, 0.0, stop)
        if batch:
            assert len({r.kind for r in batch}) == 1
            drained.extend(batch)
    assert len(drained) == 6


def test_mixed_predict_explain_traffic_stays_correct(
        warm_explain_server, binary_pair):
    bst, X = binary_pair
    exp_pred = bst.predict(X)
    exp_contrib = bst.predict(X[:64], pred_contrib=True)
    failures = []

    def client(tid):
        r = np.random.RandomState(tid)
        for _ in range(30):
            n = int(r.randint(1, 64))
            if tid % 2:
                out = warm_explain_server.explain(X[:n])
                if not np.allclose(out, exp_contrib[:n], atol=1e-12):
                    failures.append(("explain", tid, n))
            else:
                out = warm_explain_server.predict(X[:n])
                if not np.allclose(out, exp_pred[:n], atol=1e-12):
                    failures.append(("predict", tid, n))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not failures, failures[:5]


# ----------------------------------------------------------------------
# fault injection: serve.explain scopes to the explanation lane
# ----------------------------------------------------------------------
def test_serve_explain_fault_point_scoped(binary_pair):
    bst, X = binary_pair
    srv = Server(bst, config=ServeConfig(max_batch_rows=512,
                                         batch_wait_ms=0.0,
                                         timeout_ms=60000)).start()
    try:
        faults.configure("serve.explain:error@1")
        with pytest.raises(ServeError, match="injected"):
            srv.explain(X[:4])
        # the predict lane never saw the fault, and the explain lane
        # recovers on the next request
        np.testing.assert_allclose(srv.predict(X[:4]),
                                   bst.predict(X[:4]),
                                   rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(
            srv.explain(X[:4]), bst.predict(X[:4], pred_contrib=True),
            rtol=0, atol=1e-12)
    finally:
        srv.stop()


# ----------------------------------------------------------------------
# HTTP front + router forwarding
# ----------------------------------------------------------------------
def _post(port, path, obj, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    try:
        r = urllib.request.urlopen(req, timeout=timeout)
        return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_http_explain_roundtrip(binary_pair):
    from lightgbm_tpu.serve.http import serve_http
    bst, X = binary_pair
    srv = Server(bst, config=ServeConfig(max_batch_rows=512,
                                         batch_wait_ms=0.5,
                                         timeout_ms=60000, port=0))
    httpd, _ = serve_http(srv, port=0, background=True)
    try:
        port = httpd.server_address[1]
        st, out = _post(port, "/explain", {"rows": X[:5].tolist()})
        assert st == 200 and out["version"] == 1
        np.testing.assert_allclose(
            out["contributions"],
            bst.predict(X[:5], pred_contrib=True),
            rtol=0, atol=1e-10)
        st, out = _post(port, "/v1/default/explain",
                        {"rows": X[:3].tolist()})
        assert st == 200
        np.testing.assert_allclose(
            out["contributions"],
            bst.predict(X[:3], pred_contrib=True),
            rtol=0, atol=1e-10)
        st, out = _post(port, "/explain", {"rows": "garbage"})
        assert st == 400
        st, out = _post(port, "/v1/nosuch/explain",
                        {"rows": X[:2].tolist()})
        assert st == 404
        metrics = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read()
        assert b"ltpu_serve_explain_requests_total" in metrics
        assert b"ltpu_serve_explain_rows_total" in metrics
        assert b"ltpu_serve_fastpath_batches_total" in metrics
    finally:
        httpd.shutdown()
        srv.stop()


def test_router_forwards_explain_and_weights_admission(binary_pair):
    from lightgbm_tpu.serve import Router, RouterConfig
    from lightgbm_tpu.serve.http import serve_http
    bst, X = binary_pair
    srv = Server(bst, config=ServeConfig(max_batch_rows=512,
                                         batch_wait_ms=0.5,
                                         timeout_ms=60000, port=0))
    httpd, _ = serve_http(srv, port=0, background=True)
    router = Router(RouterConfig(port=0, probe_interval_s=0.05,
                                 timeout_ms=30000.0, hedge_ms=0.0,
                                 explain_cost=4.0))
    try:
        port = httpd.server_address[1]
        # a near-zero refill isolates the burst accounting: tokens
        # only ever go DOWN inside this test
        router.add_model("default",
                         urls=[f"http://127.0.0.1:{port}"],
                         rows_per_s=0.001, burst_rows=10.0)
        router.start()
        body = json.dumps({"rows": X[:2].tolist()}).encode()
        res = router.route_request("default", body, rows=2,
                                   verb="/explain")
        assert res.code == 200, res.body
        out = json.loads(res.body)
        np.testing.assert_allclose(
            out["contributions"],
            bst.predict(X[:2], pred_contrib=True),
            rtol=0, atol=1e-10)
        # explain rows charge explain_cost x: the first explain took
        # 8 of the 10 burst tokens, so a SECOND 2-row explain (8 more)
        # sheds while the same 2 rows as predict (2 tokens) admit
        res = router.route_request("default", body, rows=2,
                                   verb="/explain")
        assert res.code == 429, res.body
        res = router.route_request("default", body, rows=2,
                                   verb="/predict")
        assert res.code == 200, res.body
    finally:
        router.stop()
        httpd.shutdown()
        srv.stop()


# ----------------------------------------------------------------------
# telemetry: explain records lint clean and roll up separately
# ----------------------------------------------------------------------
def test_explain_telemetry_records_and_rollups(binary_pair, tmp_path):
    bst, X = binary_pair
    path = str(tmp_path / "explain.jsonl")
    cfg = ServeConfig(max_batch_rows=512, batch_wait_ms=0.5,
                      timeout_ms=60000, telemetry_file=path)
    srv = Server(bst, config=cfg).start()
    for n in (1, 32, 200):
        srv.explain(X[:n])
    srv.predict(X[:8])
    srv.stop()

    n_rec, errs = lint_file(path)          # triage_run.py --check gate
    assert not errs, errs[:5]
    recs = [json.loads(line) for line in open(path)]
    assert all(not validate_record(r) for r in recs)
    exps = [r for r in recs if r["type"] == "explain"]
    assert len([r for r in exps if r["status"] == "ok"]) == 3
    for r in exps:
        assert {"rows", "total_ms", "xla_compiles", "version"} <= set(r)
        assert r["xla_compiles"] == 0      # warmed lane never compiles
    serves = [r for r in recs if r["type"] == "serve"]
    assert len([r for r in serves if r["status"] == "ok"]) == 1
    end = [r for r in recs if r["type"] == "run_end"][-1]
    s = end["summary"]
    assert s["explain_requests"] == 3
    assert s["explain_rows"] == 233
    assert s["explain_total_ms_p50"] > 0
    assert s["explain_total_ms_p99"] >= s["explain_total_ms_p50"]
    assert "explain_compiles" not in s


def test_stats_exposes_explain_cache(warm_explain_server):
    stats = warm_explain_server.stats()
    assert {"hits", "misses", "entries", "capacity"} <= \
        set(stats["explain_cache"])
