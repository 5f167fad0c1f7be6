"""Observability plane (lightgbm_tpu/obs/, docs/Observability.md):

- trace spans: nesting, record tagging, carriers (thread/env/HTTP),
  announce-at-entry dedupe, checkpoint propagation
- metrics registry: render/parse round trip, bounded histograms,
  fleet aggregation, telemetry-counter mirror bit-for-bit
- RunRecorder + registry under CONCURRENT multi-subsystem writers
  (the ISSUE 13 satellite): no lost increments, no interleaved JSONL
  lines, scrape-during-write safety
- online anomaly rules: parity with the offline triage report, the
  shared evaluator firing instantly (--follow, flight recorder)
- flight recorder: capture directory contents, debounce, budget
- trace_view: publish-continuity lint
"""
import io
import json
import os
import sys
import threading

import numpy as np
import pytest

from lightgbm_tpu.obs import flight as obs_flight
from lightgbm_tpu.obs import metrics as obs_metrics
from lightgbm_tpu.obs import rules as obs_rules
from lightgbm_tpu.obs import spans
from lightgbm_tpu.utils import telemetry as tele

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)


@pytest.fixture(autouse=True)
def _clean_process_state():
    yield
    obs_flight.uninstall()
    obs_metrics.uninstall_telemetry_mirror()


# ----------------------------------------------------------------------
# spans
# ----------------------------------------------------------------------
def test_span_nesting_tags_records_and_lints():
    rec = tele.RunRecorder()
    with spans.span("root", recorder=rec, root=True, task="t") as sp:
        rec.emit("checkpoint", event="save", duration_ms=1.0)
        with spans.span("child", recorder=rec):
            rec.emit("fleet", event="publish", model_id="m")
    rec.close(log=False)
    types = [r["type"] for r in rec.records]
    assert types == ["run_start", "checkpoint", "fleet", "span",
                     "span", "run_end"]
    ck, fleet = rec.records[1], rec.records[2]
    root = next(r for r in rec.records
                if r["type"] == "span" and r["name"] == "root")
    child = next(r for r in rec.records
                 if r["type"] == "span" and r["name"] == "child")
    assert ck["trace_id"] == root["trace_id"] == sp.trace_id
    assert ck["span_id"] == root["span_id"]          # enclosing span
    assert fleet["span_id"] == child["span_id"]
    assert child["parent_id"] == root["span_id"]
    assert "parent_id" not in root
    for r in rec.records:
        assert not tele.validate_record(r), (r, tele.validate_record(r))
    # context is cleared outside
    assert spans.current() is None


def test_span_error_status_and_announce():
    rec = tele.RunRecorder()
    with pytest.raises(ValueError):
        with spans.span("boom", recorder=rec, announce=True):
            raise ValueError("x")
    rec.close(log=False)
    sp = [r for r in rec.records if r["type"] == "span"]
    assert [s["status"] for s in sp] == ["open", "error"]
    assert sp[0]["span_id"] == sp[1]["span_id"]
    assert "error" in sp[1]


def test_carriers_roundtrip_and_reject_garbage():
    with spans.span("root", root=True):
        c = spans.current()
        assert spans.parse(spans.format_carrier()) == c
        assert spans.env_carrier() == {spans.ENV_VAR:
                                       f"{c[0]}:{c[1]}"}
        assert spans.http_headers() == {spans.HTTP_HEADER:
                                        f"{c[0]}:{c[1]}"}
    assert spans.env_carrier() == {}
    for bad in ("", "zz", "a:b:c", "xyz:!!", None, "a;b"):
        assert spans.parse(bad) is None
    # thread propagation is explicit: use() re-enters a carrier
    seen = {}

    def worker(carrier):
        with spans.use(carrier):
            seen["ctx"] = spans.current()
    with spans.span("root", root=True):
        carrier = spans.current()
        th = threading.Thread(target=worker, args=(carrier,))
        th.start()
        th.join()
    assert seen["ctx"] == carrier


# ----------------------------------------------------------------------
# metrics
# ----------------------------------------------------------------------
def test_metrics_render_parse_roundtrip():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("ltpu_t_total", "help text", ("status",))
    c.inc(status="ok")
    c.inc(2.0, status='we"ird\nlabel')
    g = reg.gauge("ltpu_g", "gauge")
    g.set(3.5)
    reg.gauge_callback("ltpu_cb", lambda: 7)
    h = reg.histogram("ltpu_h_ms", "hist", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    text = reg.render()
    assert "# HELP ltpu_t_total help text" in text
    assert "# TYPE ltpu_h_ms histogram" in text
    parsed = obs_metrics.parse_text(text)
    assert parsed[("ltpu_t_total", (("status", "ok"),))] == 1
    assert parsed[("ltpu_t_total",
                   (("status", 'we"ird\nlabel'),))] == 2
    assert parsed[("ltpu_g", ())] == 3.5
    assert parsed[("ltpu_cb", ())] == 7
    assert parsed[("ltpu_h_ms_count", ())] == 3
    assert parsed[("ltpu_h_ms_bucket", (("le", "1"),))] == 1
    assert parsed[("ltpu_h_ms_bucket", (("le", "+Inf"),))] == 3
    with pytest.raises(ValueError):
        obs_metrics.parse_text("not a metric line at all { \n")


def test_histogram_bounded_memory_and_percentiles():
    h = obs_metrics.Histogram("x", buckets=(1, 2, 4, 8, 16))
    child = h.labels()
    for v in range(1, 1001):
        h.observe(v % 17)
    assert len(child._counts) == 6          # fixed, whatever the count
    assert child.count == 1000
    p50 = h.percentile(0.5)
    assert 4 <= p50 <= 16
    assert h.percentile(1.0) == 16
    assert obs_metrics.Histogram("y", buckets=(1,)).percentile(0.5) \
        == 0.0


def test_rolling_histogram_is_recency_windowed(monkeypatch):
    import lightgbm_tpu.obs.metrics as m
    clock = [0.0]
    monkeypatch.setattr(m.time, "monotonic", lambda: clock[0])
    h = m.RollingHistogram(buckets=(1, 10, 100, 1000), window_s=10.0)
    for _ in range(1000):
        h.observe(5.0)                      # long healthy history
    assert h.percentile(0.99) <= 10.0
    # two full windows later the old epoch has aged out entirely;
    # a fresh latency regression must OWN the percentile (the
    # rollback watchdog's p99 trigger depends on this recency)
    clock[0] = 25.0
    for _ in range(50):
        h.observe(500.0)
    assert h.percentile(0.99) > 100.0
    assert h.count == 50                    # old epochs dropped
    # memory stays O(buckets): rotation never retains samples
    assert len(h._cur._counts) == 5


def test_rolling_histogram_epoch_flip_boundaries(monkeypatch):
    """The percentile during an epoch swap never returns a diluted
    lifetime view: exactly at the flip the previous window is still
    merged, one flip later it is gone entirely, and a long silence
    resets both epochs (the SLO latency objective samples this path
    every scrape)."""
    import lightgbm_tpu.obs.metrics as m
    clock = [0.0]
    monkeypatch.setattr(m.time, "monotonic", lambda: clock[0])
    h = m.RollingHistogram(buckets=(1, 10, 100, 1000), window_s=10.0)
    for _ in range(1000):
        h.observe(5.0)                      # window 1: healthy lifetime
    # exactly AT the boundary the read path itself rotates: the healthy
    # epoch moves to prev but stays visible (no data cliff mid-swap)
    clock[0] = 10.0
    assert h.percentile(0.99) <= 10.0
    assert h.count == 1000
    for _ in range(50):
        h.observe(500.0)                    # window 2: a regression
    assert h.count == 1050                  # merged view: prev + cur
    # next flip: window-1 samples vanish ENTIRELY — a diluted lifetime
    # merge would keep 1000 healthy samples drowning the p99
    clock[0] = 20.0
    assert h.percentile(0.99) > 100.0
    assert h.count == 50
    # a gap of >= two windows with no traffic resets BOTH epochs: the
    # percentile reports silence, not stale history
    clock[0] = 40.0
    assert h.percentile(0.99) == 0.0
    assert h.count == 0


def test_online_scanner_state_is_bounded():
    scanner = obs_rules.OnlineScanner()
    for i in range(obs_rules.OnlineScanner.MAX_SEGMENTS + 50):
        scanner.feed({"type": "run_start", "backend": "cpu",
                      "tier": {}})
        for j in range(5):
            scanner.feed({"type": "superstep", "iter": j * 4, "k": 4,
                          "duration_ms": 1.0, "split_kernel": "xla",
                          "split_fallback": "categorical"})
    assert len(scanner._segs) == obs_rules.OnlineScanner.MAX_SEGMENTS
    # per-segment split state is a single tuple, not a history
    assert scanner._cur_seg["ss_last"] == ("xla", "categorical")


def test_aggregate_adds_replica_labels():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("ltpu_x_total", "x", ("status",)).inc(status="ok")
    text = reg.render()
    agg = obs_metrics.aggregate([("0", text), ("1", text)])
    parsed = obs_metrics.parse_text(agg)
    assert parsed[("ltpu_x_total",
                   (("replica", "0"), ("status", "ok")))] == 1
    assert parsed[("ltpu_x_total",
                   (("replica", "1"), ("status", "ok")))] == 1
    assert agg.count("# HELP ltpu_x_total") == 1


def test_telemetry_mirror_bit_for_bit():
    tele.counters.incr("obs_test_counter", 5)
    obs_metrics.install_telemetry_mirror()
    tele.counters.incr("obs_test_counter", 2)
    reg = obs_metrics.get_registry()
    want = tele.counters_snapshot()["obs_test_counter"]
    assert reg.counter("ltpu_telemetry_obs_test_counter").value() \
        == want
    # uninstall stops mirroring; reinstall tops up to the snapshot
    obs_metrics.uninstall_telemetry_mirror()
    tele.counters.incr("obs_test_counter", 3)
    assert reg.counter("ltpu_telemetry_obs_test_counter").value() \
        == want
    obs_metrics.install_telemetry_mirror()
    assert reg.counter("ltpu_telemetry_obs_test_counter").value() \
        == tele.counters_snapshot()["obs_test_counter"]


# ----------------------------------------------------------------------
# concurrency (the satellite): daemon + serve + supervisor writers on
# ONE recorder and the process-wide registry, scraped mid-write
# ----------------------------------------------------------------------
def test_concurrent_multi_subsystem_writers(tmp_path):
    path = str(tmp_path / "conc.jsonl")
    rec = tele.RunRecorder(path)
    obs_metrics.install_telemetry_mirror()
    reg = obs_metrics.get_registry()
    hist = reg.histogram("ltpu_conc_lat_ms", "x")
    n_per, n_threads = 200, 6
    base = tele.counters_snapshot().get("obs_conc", 0.0)
    scrapes = []
    stop = threading.Event()

    def serve_writer(i):
        for k in range(n_per):
            rec.emit("serve", status="ok", rows=2, total_ms=1.0 + k)
            hist.observe(1.0 + k)
            tele.counters.incr("obs_conc")

    def train_writer(i):
        for k in range(n_per):
            rec.emit("iteration", iter=k, duration_ms=2.0)
            tele.counters.incr("obs_conc")

    def cont_writer(i):
        for k in range(n_per):
            rec.emit("continual", event="batch", rows=1,
                     duration_ms=1.0)
            tele.counters.incr("obs_conc")

    def scraper():
        # must never throw/tear; the last scrape STARTS after the
        # writers joined (one render of a large process-global registry
        # can outlast them all, and then holds no observation yet)
        while True:
            last = stop.is_set()
            scrapes.append(reg.render())
            if last:
                return

    threads = [threading.Thread(target=f, args=(i,))
               for i, f in enumerate([serve_writer, serve_writer,
                                      train_writer, train_writer,
                                      cont_writer, cont_writer])]
    sc = threading.Thread(target=scraper)
    sc.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    sc.join()
    rec.close(log=False)
    # JSONL: every line parses and lints; none interleaved/torn
    n, errs = tele.lint_file(path)
    assert not errs, errs[:5]
    records = tele.read_records(path)
    assert n == n_threads * n_per + 2       # + run_start/run_end
    # seq strictly increasing and gapless: no lost emissions
    seqs = [r["seq"] for r in records]
    assert seqs == list(range(len(records)))
    # counters: no lost increments, mirror agrees bit-for-bit
    total = tele.counters_snapshot()["obs_conc"]
    assert total - base == n_threads * n_per
    assert reg.counter("ltpu_telemetry_obs_conc").value() == total
    # histogram observed every serve write
    assert hist.count() == 2 * n_per
    # the recorder's own rollup saw every serve record
    summary = records[-1]["summary"]
    assert summary["serve_requests"] == 2 * n_per
    assert summary["iterations"] == 2 * n_per
    assert summary["continual_batches"] == 2 * n_per
    assert scrapes and all("ltpu_conc_lat_ms_count" in s
                           for s in scrapes[-1:])


# ----------------------------------------------------------------------
# shared anomaly rules
# ----------------------------------------------------------------------
def _storm_stream(depth=0, overlap=0.0):
    recs = [{"type": "run_start", "backend": "tpu",
             "tier": {"tier": "wave", "split_kernel": "pallas"}}]
    for i in range(6):
        r = {"type": "superstep", "iter": i * 4, "k": 4,
             "duration_ms": 5.0,
             "counters": {"xla_compiles": 1, "xla_compile_secs": 0.5}}
        if depth:
            r["pipeline_depth"] = depth
            r["fetch_overlap_s"] = overlap
        recs.append(r)
    return recs


def test_online_scanner_matches_offline_triage():
    from triage_run import scan_anomalies
    stream = _storm_stream()
    offline = scan_anomalies(stream)
    assert any("superstep retrace storm" in m for _, m in offline)
    scanner = obs_rules.OnlineScanner()
    fired = [a for r in stream for a in scanner.feed(r)]
    assert [c for _, c, _ in fired] == ["retrace_storm"] * 5
    # summary text identical to the triage report's aggregate
    summary = scanner.summary_anomalies()
    assert summary[0] == offline[0]


def test_scanner_instant_rules():
    scanner = obs_rules.OnlineScanner()
    fired = []
    for r in [
        {"type": "run_start", "backend": "tpu", "tier": {}},
        {"type": "continual", "event": "stall_restart",
         "batch": "b", "stalled_s": 9.0, "attempt": 1},
        {"type": "continual", "event": "nonfinite", "iter": 3,
         "phase": "gradients"},
        {"type": "fleet", "event": "rollback", "from_id": "a",
         "to_id": "b", "reason": "error_rate"},
        {"type": "superstep", "iter": 0, "k": 4, "duration_ms": 1.0,
         "split_kernel": "xla", "split_fallback": "categorical"},
        {"type": "superstep", "iter": 4, "k": 4, "duration_ms": 1.0,
         "split_kernel": "xla", "split_fallback": "categorical"},
    ]:
        fired.extend(scanner.feed(r))
    codes = [c for _, c, _ in fired]
    assert codes == ["stall", "nonfinite", "rollback", "xla_fallback"]
    # explicit operator choice is not an anomaly
    scanner2 = obs_rules.OnlineScanner()
    fired2 = []
    for r in [{"type": "run_start", "backend": "tpu", "tier": {}},
              {"type": "superstep", "iter": 0, "k": 4,
               "duration_ms": 1.0, "split_kernel": "xla",
               "split_fallback": "split_kernel=xla requested"}]:
        fired2.extend(scanner2.feed(r))
    assert not fired2


def test_pipelining_rule_parity():
    from triage_run import scan_anomalies
    stalled = _storm_stream(depth=2, overlap=0.0)
    healthy = _storm_stream(depth=2, overlap=0.004)
    assert any("pipelining silently disabled" in m
               for _, m in scan_anomalies(stalled))
    assert not any("pipelining" in m
                   for _, m in scan_anomalies(healthy))
    scanner = obs_rules.OnlineScanner()
    fired = [a for r in stalled for a in scanner.feed(r)]
    assert "pipelining_disabled" in [c for _, c, _ in fired]


# ----------------------------------------------------------------------
# flight recorder
# ----------------------------------------------------------------------
def test_flight_recorder_capture_and_budget(tmp_path):
    fr = obs_flight.FlightRecorder(str(tmp_path / "caps"),
                                   ring_records=32, cooldown_s=0.0,
                                   max_captures=2)
    tele.add_emit_observer(fr.observe)
    try:
        rec = tele.RunRecorder()
        rec.emit("continual", event="stall_restart", batch="b",
                 stalled_s=5.0, attempt=1)
        rec.emit("continual", event="stall_restart", batch="b",
                 stalled_s=5.0, attempt=2)
        rec.emit("continual", event="stall_restart", batch="b",
                 stalled_s=5.0, attempt=3)   # over budget: no capture
        rec.close(log=False)
        caps = [r for r in rec.records if r["type"] == "capture"]
        assert len(caps) == 2 and len(fr.captures) == 2
        cap = caps[0]
        assert cap["trigger"] == "stall"
        assert not tele.validate_record(cap)
        ring_path = os.path.join(cap["path"], "ring.jsonl")
        with open(os.path.join(cap["path"], "anomaly.json")) as f:
            anomaly = json.load(f)
        assert anomaly["code"] == "stall"
        ring = [json.loads(l) for l in open(ring_path)]
        assert len(ring) == cap["ring_records"] >= 2
        # ring holds the records that PRECEDED the trigger
        assert ring[-1]["type"] == "continual"
    finally:
        tele.remove_emit_observer(fr.observe)


def test_flight_recorder_cooldown(tmp_path):
    fr = obs_flight.FlightRecorder(str(tmp_path / "caps"),
                                   cooldown_s=3600.0, max_captures=8)
    tele.add_emit_observer(fr.observe)
    try:
        rec = tele.RunRecorder()
        for i in range(4):
            rec.emit("fleet", event="rollback", from_id="a",
                     to_id="b", reason="p99")
        rec.close(log=False)
        assert len(fr.captures) == 1        # debounced
    finally:
        tele.remove_emit_observer(fr.observe)


def test_ensure_installed_is_gated_and_idempotent(tmp_path):
    class Cfg:
        obs_flight_recorder = False
    assert obs_flight.ensure_installed(Cfg()) is None

    class On:
        obs_flight_recorder = True
        obs_capture_dir = str(tmp_path / "c")
        obs_ring_records = 64
        obs_capture_profile_ms = 0
        obs_capture_cooldown_s = 0.0
        obs_max_captures = 1
        telemetry_file = ""
    fr = obs_flight.ensure_installed(On())
    assert fr is not None
    assert obs_flight.ensure_installed(On()) is fr


# ----------------------------------------------------------------------
# --follow and trace_view
# ----------------------------------------------------------------------
def test_follow_prints_instant_anomalies(tmp_path):
    from triage_run import follow
    path = str(tmp_path / "run.jsonl")
    with open(path, "w") as f:
        for r in _storm_stream():
            f.write(json.dumps(r) + "\n")
        f.write('{"broken json\n')          # torn tail must not kill
        f.write(json.dumps({"type": "capture", "trigger": "stall",
                            "path": "/x"}) + "\n")
    out = io.StringIO()
    fired = follow(path, idle_timeout_s=0.5, poll_s=0.05, out=out)
    text = out.getvalue()
    assert fired == 5
    assert "retrace_storm" in text
    assert "[CAPTURE] stall" in text


def test_trace_view_lint_and_dedupe(tmp_path):
    from trace_view import lint_publish_continuity, load_records, \
        render_trace, traces
    path = str(tmp_path / "t.jsonl")
    tid = "ab" * 8
    recs = [
        {"type": "span", "name": "batch", "trace_id": tid,
         "span_id": "s1", "duration_ms": 0.0, "status": "open",
         "wall_time": 1.0, "pid": 10},
        {"type": "span", "name": "batch", "trace_id": tid,
         "span_id": "s1", "duration_ms": 100.0, "status": "ok",
         "wall_time": 1.1, "pid": 10},
        {"type": "span", "name": "publish", "trace_id": tid,
         "span_id": "s2", "parent_id": "s1", "duration_ms": 5.0,
         "wall_time": 1.2, "pid": 20},
        {"type": "fleet", "event": "publish", "trace_id": tid,
         "span_id": "s2", "wall_time": 1.2, "path": "ckpt_x",
         "pid": 20},
    ]
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    loaded = load_records([path])
    assert not lint_publish_continuity(loaded, require_processes=2)
    tv = traces(loaded)
    assert len(tv[tid]["spans"]) == 2       # open/closed deduped
    closed = next(s for s in tv[tid]["spans"] if s["span_id"] == "s1")
    assert closed["status"] == "ok"
    lines = render_trace(tid, tv[tid]["spans"], tv[tid]["events"])
    assert any("publish" in ln for ln in lines)
    # an orphan publish (no daemon-side root) fails the lint
    orphan = [dict(recs[3], trace_id="cd" * 8)]
    errs = lint_publish_continuity(loaded + orphan)
    assert errs and "does not join" in errs[0]
    # a publish with no trace at all fails too
    errs2 = lint_publish_continuity(
        [{"type": "fleet", "event": "publish", "path": "p"}])
    assert errs2 and "orphan" in errs2[0]


# ----------------------------------------------------------------------
# serve integration: /metrics endpoint + publish->first_request trace
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_booster():
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.randn(300, 5)
    y = (X[:, 0] > 0).astype(float)
    d = lgb.Dataset(X, label=y, params={"objective": "binary",
                                        "verbose": -1})
    return lgb.train({"objective": "binary", "num_leaves": 7,
                      "verbose": -1, "metric": "None"}, d,
                     num_boost_round=3), X


def test_serve_metrics_endpoint_and_stats_histogram(tiny_booster):
    import urllib.request

    from lightgbm_tpu.serve import ServeConfig, Server
    from lightgbm_tpu.serve.http import serve_http
    bst, X = tiny_booster
    srv = Server(bst, config=ServeConfig(port=0, batch_wait_ms=0.0,
                                         timeout_ms=30000))
    httpd, _ = serve_http(srv, port=0, background=True)
    url = "http://127.0.0.1:%d" % httpd.server_address[1]
    try:
        for _ in range(3):
            srv.predict(X[:4])
        text = urllib.request.urlopen(url + "/metrics",
                                      timeout=10).read().decode()
        parsed = obs_metrics.parse_text(text)
        assert parsed[("ltpu_serve_requests_total",
                       (("status", "ok"),))] >= 3
        assert ("ltpu_serve_latency_ms_count", ()) in parsed
        assert ("ltpu_serve_queue_rows", ()) in parsed
        stats = json.loads(urllib.request.urlopen(
            url + "/stats", timeout=10).read())
        assert stats["latency_ms"]["p50"] > 0
        assert stats["latency_ms"]["p99"] >= stats["latency_ms"]["p50"]
    finally:
        httpd.shutdown()
        httpd.server_close()
        srv.stop()


def test_serve_metrics_disabled_404(tiny_booster):
    import urllib.error
    import urllib.request

    from lightgbm_tpu.serve import ServeConfig, Server
    from lightgbm_tpu.serve.http import serve_http
    bst, _ = tiny_booster
    srv = Server(bst, config=ServeConfig(port=0, metrics=False,
                                         timeout_ms=30000))
    httpd, _ = serve_http(srv, port=0, background=True)
    url = "http://127.0.0.1:%d" % httpd.server_address[1]
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url + "/metrics", timeout=10)
        assert ei.value.code == 404
    finally:
        httpd.shutdown()
        httpd.server_close()
        srv.stop()


def test_swap_trace_joins_first_request(tiny_booster):
    from lightgbm_tpu.serve import ServeConfig, Server
    bst, X = tiny_booster
    rec = tele.RunRecorder()
    srv = Server(bst, config=ServeConfig(port=0, batch_wait_ms=0.0,
                                         timeout_ms=30000),
                 telemetry=rec)
    srv.start()
    try:
        with spans.span("publish", recorder=rec, root=True):
            srv.swap(booster=bst)
        srv.predict(X[:2])
        srv.predict(X[:2])
    finally:
        srv.stop()
    rec.close(log=False)
    sp = [r for r in rec.records if r["type"] == "span"]
    swap = next(r for r in sp if r["name"] == "swap")
    pub = next(r for r in sp if r["name"] == "publish")
    first = [r for r in sp if r["name"] == "first_request"]
    assert len(first) == 1                  # only the FIRST request
    assert first[0]["trace_id"] == swap["trace_id"] == pub["trace_id"]
    assert first[0]["parent_id"] == swap["span_id"]
    serve_recs = [r for r in rec.records if r["type"] == "serve"
                  and r.get("status") == "swap"]
    assert serve_recs and serve_recs[0]["trace_id"] == pub["trace_id"]


def test_engine_train_records_trace_in_checkpoint(tmp_path):
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serve.watcher import CheckpointWatcher
    rng = np.random.RandomState(0)
    X = rng.randn(300, 5)
    y = (X[:, 0] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "metric": "None",
              "checkpoint_dir": str(tmp_path / "ck"),
              "telemetry_file": str(tmp_path / "t.jsonl")}
    d = lgb.Dataset(X, label=y, params=params)
    bst = lgb.train(params, d, num_boost_round=3)
    bst._gbdt._telemetry.close(log=False)
    ck = sorted((tmp_path / "ck").glob("ckpt_*"))[-1]
    with open(ck / "extra.json") as f:
        carrier = spans.parse(json.load(f).get("trace"))
    assert carrier is not None
    # the watcher joins the same trace from the snapshot
    assert CheckpointWatcher._snapshot_trace(str(ck)) == carrier
    recs = tele.read_records(str(tmp_path / "t.jsonl"))
    train_spans = [r for r in recs if r["type"] == "span"
                   and r["name"] == "train"]
    assert any(r.get("span_id") == carrier[1] for r in train_spans)
    assert any(r["status"] == "open" for r in train_spans)
