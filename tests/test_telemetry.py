"""Structured run telemetry (utils/telemetry.py).

Pins the observability contract the round-5 VERDICT asked for:

- JSONL schema round-trip: a train + predict run with
  ``telemetry_file=`` set produces schema-valid records carrying phase
  timings, >= 1 compile event, predict-cache counters and the
  tier/gate decision.
- No-recompile pin: the XLA compile counter stays FLAT across repeated
  same-shape predicts (a climbing counter is a retrace storm).
- Tier-decision records match the gates the config exercises
  (wave/quantized/two_col vs exact, with the rejecting gate named).
- The recorder is thread-safe under concurrent predicts (no torn JSONL
  lines, no lost records).
- The bench-artifact recovery parser handles the driver wrapper's
  truncated ``tail`` and skips outage rounds.
"""
import json
import os
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils import telemetry
from lightgbm_tpu.utils.telemetry import (
    RunRecorder, SCHEMA_VERSION, counters_snapshot, lint_file,
    parse_bench_artifact, read_records, validate_record)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _small_data(n=400, f=6, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.3 * rng.randn(n) > 0).astype(np.float64)
    return X, y


@pytest.fixture(scope="module")
def telemetry_run(tmp_path_factory):
    """One small train + predict run with a telemetry file; shared so
    the module pays the XLA compiles once."""
    path = str(tmp_path_factory.mktemp("tele") / "run.jsonl")
    X, y = _small_data()
    d = lgb.Dataset(X, label=y,
                    params={"objective": "binary", "verbose": -1})
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "min_data_in_leaf": 5, "verbose": -1,
                     "metric": "auc", "telemetry_file": path},
                    d, num_boost_round=3,
                    valid_sets=[d.create_valid(X[:100], y[:100])])
    bst.predict(X[:64])
    bst.predict(X[:64])            # same shape: cache hit, no compile
    return path, bst


def test_jsonl_schema_roundtrip(telemetry_run):
    path, _ = telemetry_run
    n, errs = lint_file(path)
    assert errs == []
    assert n >= 3 + 2 + 1          # iterations + predicts + run_start
    recs = read_records(path)
    types = [r["type"] for r in recs]
    assert types[0] == "run_start"
    assert types.count("iteration") == 3
    assert types.count("predict") >= 2
    assert types.count("eval") == 3
    # every record validates standalone and round-trips through JSON
    for r in recs:
        assert validate_record(json.loads(json.dumps(r))) == []
        assert r["schema"] == SCHEMA_VERSION
    # acceptance-criteria payloads: phase timings, >=1 compile event,
    # cache hit/miss counts, tier decision
    start = recs[0]
    assert start["backend"] == "cpu"
    assert start["tier"]["tier"] in ("exact", "speculative")
    it = next(r for r in recs if r["type"] == "iteration")
    assert it["phases_ms"] and any(k.startswith("tree/")
                                   for k in it["phases_ms"])
    compiles = sum((r.get("counters") or {}).get("xla_compiles", 0)
                   for r in recs if r["type"] == "iteration")
    assert compiles >= 1
    pred = [r for r in recs if r["type"] == "predict"]
    cache = pred[-1]["cache"]
    assert cache["misses"] >= 1 and cache["hits"] >= 1
    assert pred[-1]["engine"] is True
    # seq is strictly increasing (single writer)
    seqs = [r["seq"] for r in recs]
    assert seqs == sorted(seqs) == list(range(len(recs)))


def test_compile_counter_flat_on_repeated_predicts(telemetry_run):
    """No-recompile pin: same-shape predicts re-run cached programs."""
    _, bst = telemetry_run
    X, _ = _small_data()
    bst.predict(X[:64])            # warm (already warmed by fixture)
    c0 = counters_snapshot()
    for _ in range(3):
        bst.predict(X[:64])
    c1 = counters_snapshot()
    assert c1.get("xla_compiles", 0) == c0.get("xla_compiles", 0)
    # and the engine served those calls from its compile cache
    assert c1.get("predict_cache_hits", 0) >= \
        c0.get("predict_cache_hits", 0) + 3


def test_run_end_summary(telemetry_run):
    path, bst = telemetry_run
    summ = bst._gbdt.telemetry_summary()
    assert summ["iterations"] == 3
    assert summ["xla_compiles"] >= 1
    assert summ["phase_totals_ms"]
    rec = bst._gbdt._telemetry
    rec.close()
    rec.close()                    # idempotent
    recs = read_records(path)
    assert recs[-1]["type"] == "run_end"
    assert recs[-1]["summary"]["iterations"] == 3
    n, errs = lint_file(path)
    assert errs == []


def _booster(params, X, y):
    d = lgb.Dataset(X, label=y, params=dict(params, verbose=-1))
    return lgb.Booster(params=dict(params, verbose=-1), train_set=d)


class TestTierDecisions:
    """run_start tier records match the gates the config exercises
    (the same configs tests/test_c2f.py-style suites train with)."""

    def test_default_is_exact_with_named_gates(self):
        X, y = _small_data()
        g = _booster({"objective": "binary"}, X, y)._gbdt
        td = g.tier_decision
        assert td["tier"] == "exact"
        assert td["gates"]["wave"] == "wave_splits=false"
        assert td["gates"]["two_col"] == "use_quantized_grad=false"
        assert "cpu backend" in td["gates"]["routed"]
        assert not g.grow_params.wave and not g.grow_params.two_col

    def test_wave_tier(self):
        X, y = _small_data()
        g = _booster({"objective": "binary", "wave_splits": True,
                      "enable_bundle": False, "num_leaves": 8}, X, y)._gbdt
        td = g.tier_decision
        assert td["tier"] == "wave"
        assert "wave" not in td["gates"]
        assert g.grow_params.wave
        assert td["gates"]["two_col"] == "use_quantized_grad=false"

    def test_two_col_tier_and_missing_gate(self):
        X, y = _small_data()
        base = {"objective": "binary", "wave_splits": True,
                "use_quantized_grad": True, "enable_bundle": False,
                "num_leaves": 8, "min_sum_hessian_in_leaf": 1e-3}
        g = _booster(dict(base, min_data_in_leaf=0), X, y)._gbdt
        td = g.tier_decision
        assert td["tier"] == "two_col" and g.grow_params.two_col
        assert td["quantize"] > 0 and td["wave"]
        # the count channel gate: min_data_in_leaf > 1 rejects two_col
        g2 = _booster(dict(base, min_data_in_leaf=20), X, y)._gbdt
        td2 = g2.tier_decision
        assert td2["tier"] == "wave_quant"
        assert not g2.grow_params.two_col
        assert td2["gates"]["two_col"] == \
            "min_data_in_leaf > 1 needs counts"

    def test_categorical_gates_two_col_off(self):
        X, y = _small_data()
        Xc = X.copy()
        Xc[:, 0] = np.floor(np.abs(Xc[:, 0]) * 3) % 5
        g = _booster({"objective": "binary", "wave_splits": True,
                      "use_quantized_grad": True, "enable_bundle": False,
                      "min_data_in_leaf": 0, "num_leaves": 8,
                      "categorical_feature": "0"}, Xc, y)._gbdt
        td = g.tier_decision
        assert not g.grow_params.two_col
        assert "counts" in td["gates"]["two_col"]

    def test_iteration_records_carry_tier(self, tmp_path):
        path = str(tmp_path / "tier.jsonl")
        X, y = _small_data(n=300)
        d = lgb.Dataset(X, label=y, params={"objective": "binary",
                                            "verbose": -1})
        bst = lgb.train({"objective": "binary", "num_leaves": 6,
                         "min_data_in_leaf": 5, "verbose": -1,
                         "telemetry_file": path}, d, num_boost_round=2)
        recs = read_records(path)
        g = bst._gbdt
        for r in recs:
            if r["type"] == "iteration":
                assert r["tier"] == g.tier_decision["tier"]
        start = recs[0]
        assert start["tier"]["gates"] == g.tier_decision["gates"]


def test_recorder_thread_safety(tmp_path):
    """Concurrent predicts: no torn JSONL lines, no lost records."""
    path = str(tmp_path / "mt.jsonl")
    X, y = _small_data()
    d = lgb.Dataset(X, label=y, params={"objective": "binary",
                                        "verbose": -1})
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "min_data_in_leaf": 5, "verbose": -1,
                     "telemetry_file": path}, d, num_boost_round=2)
    ref = bst.predict(X[:64])
    from lightgbm_tpu.ops.predict import get_engine
    cache0 = dict(get_engine().cache_info())
    n_threads, n_calls = 6, 4
    errors = []

    def worker(i):
        try:
            for j in range(n_calls):
                out = bst.predict(X[:64])
                np.testing.assert_allclose(out, ref, rtol=1e-12)
        except Exception as exc:  # surfaced below
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    n, errs = lint_file(path)
    assert errs == []              # no interleaved partial lines
    recs = read_records(path)
    preds = [r for r in recs if r["type"] == "predict"]
    # 1 warm-up + n_threads * n_calls concurrent, none lost
    assert len(preds) == 1 + n_threads * n_calls
    # every concurrent same-shape call hit the compile cache: no lost
    # or double-counted cache events under the lock
    cache1 = get_engine().cache_info()
    assert cache1["hits"] - cache0["hits"] == n_threads * n_calls
    assert cache1["misses"] == cache0["misses"]


def test_in_memory_recorder_and_callback():
    """record_telemetry callback form + in-memory recorder."""
    rec = RunRecorder(path=None, run_info={"backend": "cpu"})
    X, y = _small_data(n=300)
    d = lgb.Dataset(X, label=y, params={"objective": "binary",
                                        "verbose": -1})
    lgb.train({"objective": "binary", "num_leaves": 6, "verbose": -1,
               "min_data_in_leaf": 5}, d, num_boost_round=2,
              callbacks=[lgb.record_telemetry(rec)])
    types = [r["type"] for r in rec.records]
    assert types.count("iteration") == 2
    assert types.count("run_start") == 2   # recorder's own + booster's
    for r in rec.records:
        assert validate_record(r) == []


def test_bare_recorder_file_is_schema_valid(tmp_path):
    """A RunRecorder constructed WITHOUT run_info (the documented
    record_telemetry(RunRecorder(path)) flow) must still produce JSONL
    that passes its own schema lint — its placeholder run_start is
    followed by the booster's fully-populated one."""
    path = str(tmp_path / "bare.jsonl")
    rec = RunRecorder(path)
    X, y = _small_data(n=300)
    d = lgb.Dataset(X, label=y, params={"objective": "binary",
                                        "verbose": -1})
    lgb.train({"objective": "binary", "num_leaves": 6, "verbose": -1,
               "min_data_in_leaf": 5}, d, num_boost_round=2,
              callbacks=[lgb.record_telemetry(rec)])
    rec.close(log=False)
    n, errs = lint_file(path)
    assert errs == []
    recs = read_records(path)
    starts = [r for r in recs if r["type"] == "run_start"]
    assert starts[0]["backend"] == "unknown"
    assert starts[1]["backend"] == "cpu" and starts[1]["tier"]


def test_validate_record_rejects_malformed():
    assert validate_record([]) != []
    assert validate_record({}) != []
    good = {"schema": SCHEMA_VERSION, "type": "iteration", "seq": 0,
            "wall_time": 1.0, "iter": 0, "duration_ms": 1.5}
    assert validate_record(good) == []
    assert validate_record(dict(good, schema=99)) != []
    assert validate_record(dict(good, type="bogus")) != []
    assert validate_record(dict(good, seq=True)) != []
    bad = dict(good)
    del bad["iter"]
    assert validate_record(bad) != []


def test_validate_superstep_record():
    """The fused super-step record type: k is REQUIRED (a consumer
    must be able to amortize duration_ms to per-iteration figures)."""
    good = {"schema": SCHEMA_VERSION, "type": "superstep", "seq": 0,
            "wall_time": 1.0, "iter": 1, "k": 8, "duration_ms": 80.0}
    assert validate_record(good) == []
    bad = dict(good)
    del bad["k"]
    assert validate_record(bad) != []
    assert validate_record(dict(good, k=True)) != []


def test_superstep_aggregates_as_k_iterations():
    """A superstep record counts as k iterations in the run summary —
    the aggregate the shutdown Log line and render tools read."""
    from lightgbm_tpu.utils.telemetry import RunRecorder
    rec = RunRecorder(None)
    rec.emit("iteration", iter=0, duration_ms=10.0)
    rec.emit("superstep", iter=1, k=8, duration_ms=80.0,
             phases_ms={"superstep/dispatch": 75.0})
    s = rec.summary()
    assert s["iterations"] == 9
    assert s["train_ms"] == 90.0
    rec.close(log=False)


def test_lint_file_flags_corruption(tmp_path):
    p = tmp_path / "corrupt.jsonl"
    p.write_text('{"schema": 1, "type": "run_start", "seq": 0, '
                 '"wall_time": 1.0, "backend": "cpu"}\n'
                 '{"half a rec\n')
    n, errs = lint_file(str(p))
    assert n == 2 and any("not JSON" in e for e in errs)


class TestBenchArtifacts:
    def test_truncated_tail_recovery(self, tmp_path):
        # driver wrapper whose tail's last line lost its head bytes
        inner = {"metric": "m", "value": 7.5, "vs_baseline": 1.1}
        line = json.dumps(inner)
        p = tmp_path / "BENCH_r07.json"
        p.write_text(json.dumps(
            {"n": 7, "cmd": "python bench.py", "rc": 0,
             "tail": "noise\n" + line[9:], "parsed": None}))
        rec = parse_bench_artifact(str(p))
        assert rec is not None and rec["value"] == 7.5

    def test_rc_nonzero_skipped(self, tmp_path):
        p = tmp_path / "BENCH_r08.json"
        p.write_text(json.dumps(
            {"n": 8, "cmd": "python bench.py", "rc": 1,
             "tail": '{"metric": "m", "value": 1.0}', "parsed": None}))
        assert parse_bench_artifact(str(p)) is None


def test_render_benchmarks_byte_identical():
    """docs/Benchmarks.md is a pure function of the checked-in
    artifacts (never hand-edited again)."""
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "render_benchmarks.py"), "--check"],
        capture_output=True, text=True, timeout=60, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr


def test_triage_check_cli(telemetry_run):
    import subprocess
    import sys
    path, _ = telemetry_run
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "triage_run.py"),
         path, "--check"],
        capture_output=True, text=True, timeout=60, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "triage_run.py"),
         path], capture_output=True, text=True, timeout=60, cwd=REPO)
    assert out.returncode == 0
    assert "tier" in out.stdout and "phase" in out.stdout
