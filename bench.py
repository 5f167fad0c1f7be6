"""Benchmark harness: Higgs-shaped boosting throughput on one chip.

Reproduces the reference's headline speed experiment shape
(``docs/Experiments.rst:42-117``): 10.5M x 28 dense numerical binary
classification, 500 iterations, num_leaves=255, max_bin=255,
learning_rate=0.1, min_sum_hessian_in_leaf=100.  The reference's
baseline on 2x E5-2670v3 is 238.5 s (``BASELINE.md``).

Variants (each trained for the SAME number of measured iterations, so
the reported holdout AUCs are iteration-matched):

- ``wave255``  — PRIMARY: wave growth + quantized histograms at the
  reference's 255-bin config (this framework's best settings at the
  reference's bin resolution, the way the reference's own numbers use
  its best settings).
- ``exact255`` — strict best-first serial growth, same split semantics
  as the reference CPU learner (the AUC anchor).
- ``wave63``   — the reference's GPU-comparison config
  (``docs/GPU-Performance.rst:109-139`` benches 63 bins at documented
  near-identical AUC).
- ``wave15``   — optional (BENCH_15=1), the GPU doc's speed-leaning
  15-bin point.

The dataset is synthetic (deterministic seed) since the real Higgs data
is not available in this image; shapes, cardinalities and the training
configuration match the published experiment, so the wall-clock is
comparable even though the absolute AUC is not.

Emits the result as a JSON line after the primary measurement and
RE-EMITS it enriched after each variant — the last line printed is
always the most complete parsable result:
  {"metric": "higgs_shape_train_time_500iter", "value": <s>, "unit": "s",
   "vs_baseline": <value / 238.5>, ..., "phases": {...}}

The backend is acquired in this process (``ensure_backend``): the CPU
only when ``JAX_PLATFORMS=cpu`` asks for it (the harness smoke; its
numbers are not speeds), otherwise a TPU or a non-zero exit.  A phase
that fails leaves a ``*_error`` key and the process exits non-zero.
The primary variant additionally writes
schema-versioned telemetry JSONL (BENCH_telemetry.jsonl; disable with
BENCH_TELEMETRY=0) and every variant reports
``measured_xla_compiles`` — a non-zero value flags a retrace storm
inside the measured window (``retrace_warning``).
"""
import json
import os
import subprocess
import sys
import time

BASELINE_S = 238.5   # Higgs 500 iters, reference CPU (Experiments.rst:104)
N_ROWS = 10_500_000
N_FEATURES = 28
N_ITERS = 500
WARMUP = 2           # first two updates carry the XLA compiles


def make_higgs_shaped(n_rows, n_features, seed=0):
    import numpy as np
    rng = np.random.RandomState(seed)
    # mixture of unit-scale kinematic-like features, chunked to bound
    # peak host memory
    X = np.empty((n_rows, n_features), dtype=np.float32)
    chunk = 1_000_000
    w = rng.randn(n_features).astype(np.float32)
    y = np.empty(n_rows, dtype=np.float32)
    for lo in range(0, n_rows, chunk):
        hi = min(lo + chunk, n_rows)
        Xc = rng.randn(hi - lo, n_features).astype(np.float32)
        Xc[:, ::3] = np.abs(Xc[:, ::3])          # momentum-like positives
        X[lo:hi] = Xc
        logits = Xc @ w * 0.5 + 0.3 * Xc[:, 0] * Xc[:, 1] - 0.1
        p = 1.0 / (1.0 + np.exp(-logits))
        y[lo:hi] = (rng.random_sample(hi - lo) < p).astype(np.float32)
    return X, y


def ensure_backend(force_host_devices=0):
    """The ONE backend-acquisition path every bench entry point uses,
    in this process (a probe in a child would take the chip first and
    a chip belongs to one process).  Returns the platform: ``"cpu"``
    only when ``JAX_PLATFORMS=cpu`` asked for it, otherwise ``"tpu"``.
    Anything else — no accelerator found, or a platform list that
    resolved to the CPU unasked — exits non-zero: a measurement path
    does not continue on the CPU.

    ``force_host_devices``: on a requested-CPU run, force that many
    virtual host devices BEFORE the backend initializes (the
    weak-scale grid needs the mesh on a host with one device)."""
    asked = {p.strip() for p in
             os.environ.get("JAX_PLATFORMS", "").split(",") if p.strip()}
    cpu_asked = asked == {"cpu"}
    if force_host_devices and cpu_asked:
        from lightgbm_tpu.utils.env import force_host_platform_devices
        force_host_platform_devices(int(force_host_devices))
    import jax
    platform = jax.devices()[0].platform
    if platform != ("cpu" if cpu_asked else "tpu"):
        sys.exit(f"bench.py: JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}"
                 f" resolved to platform {platform!r}; the bench runs on"
                 f" a TPU, or on the CPU only under JAX_PLATFORMS=cpu")
    return platform


def bench_predict(booster, X, reps=3):
    """Batch-inference throughput: flattened engine vs per-tree loop."""
    def med(fn):
        ts = []
        for _ in range(reps):
            t0 = time.time()
            fn()
            ts.append(time.time() - t0)
        return sorted(ts)[len(ts) // 2]

    n = X.shape[0]
    booster.predict(X, raw_score=True, predict_engine=True)  # warm
    t_eng = med(lambda: booster.predict(X, raw_score=True,
                                        predict_engine=True))
    t_loop = med(lambda: booster.predict(X, raw_score=True,
                                         predict_engine=False))
    res = {"predict_rows": n, "predict_trees": booster.num_trees(),
           "predict_engine_rows_per_s": round(n / t_eng),
           "predict_loop_rows_per_s": round(n / t_loop),
           "predict_engine_speedup": round(t_loop / t_eng, 2)}
    from lightgbm_tpu.ops.predict import engine_enabled
    if not engine_enabled():
        # LTPU_PREDICT_ENGINE=0 overrides the per-call request: both
        # legs measured the loop — mark the row so it's not mistaken
        # for a real engine number
        res["predict_engine_disabled_by_env"] = True
    return res


def bench_serve(booster, n_features, swap_booster=None,
                n_requests=400, threads=8, rows_max=900,
                max_batch_rows=1024, batch_wait_ms=1.0, seed=0,
                kind="predict", fastpath_max_rows=None):
    """Online-serving microbench: in-process Server, concurrent
    clients issuing mixed row-count requests through the
    micro-batching scheduler (one mid-run hot-swap when
    ``swap_booster`` is given).  ``kind="explain"`` drives the
    explanation lane (per-row SHAP contributions) instead;
    ``fastpath_max_rows`` overrides the single-row fast-path gate
    (0 disables — the knob the fastpath-vs-bucketed cells flip).
    Reports latency percentiles, throughput, batch occupancy and the
    steady-state compile count — the serving analog of
    ``bench_predict``."""
    import threading as _threading

    import numpy as np
    from lightgbm_tpu.serve import ServeConfig, Server
    from lightgbm_tpu.utils.telemetry import counters_snapshot

    cfg_kw = {}
    if fastpath_max_rows is not None:
        cfg_kw["fastpath_max_rows"] = fastpath_max_rows
    cfg = ServeConfig(max_batch_rows=max_batch_rows,
                      batch_wait_ms=batch_wait_ms, timeout_ms=60000,
                      queue_rows=max(rows_max * threads * 4, 16384),
                      **cfg_kw)
    srv = Server(booster, config=cfg).start()
    lat, lock = [], _threading.Lock()
    errors, rows_done = [], [0]
    issued = [0]
    swap_at = n_requests // 2 if swap_booster is not None else -1

    def client(tid):
        r = np.random.RandomState(seed + tid)
        while True:
            with lock:
                if issued[0] >= n_requests:
                    return
                issued[0] += 1
                i = issued[0]
            if i == swap_at:
                srv.swap(booster=swap_booster)
                continue
            n = int(r.randint(1, rows_max + 1))
            X = r.randn(n, n_features)
            t0 = time.time()
            try:
                if kind == "explain":
                    srv.explain(X)
                else:
                    srv.predict(X)
            except Exception as exc:   # noqa: BLE001 - recorded
                errors.append(str(exc)[:120])
                continue
            with lock:
                lat.append((time.time() - t0) * 1e3)
                rows_done[0] += n

    try:
        srv.predict(np.zeros((1, n_features)))   # settle first touch
        if kind == "explain":
            srv.explain(np.zeros((1, n_features)))
        base = counters_snapshot()
        t_start = time.time()
        clients = [_threading.Thread(target=client, args=(i,))
                   for i in range(threads)]
        for t in clients:
            t.start()
        for t in clients:
            t.join()
        wall = time.time() - t_start
        now = counters_snapshot()
    finally:
        srv.stop()
    lat.sort()
    from lightgbm_tpu.utils.telemetry import percentile

    def pct(q):
        return round(percentile(lat, q), 2) if lat else None

    batches = now.get("serve_batches", 0) - base.get("serve_batches", 0)
    breal = now.get("serve_batch_rows", 0) - \
        base.get("serve_batch_rows", 0)
    bpad = now.get("serve_padded_rows", 0) - \
        base.get("serve_padded_rows", 0)
    return {
        "kind": kind,
        "fastpath_batches": int(now.get("serve_fastpath_batches", 0) -
                                base.get("serve_fastpath_batches", 0)),
        "requests": len(lat),
        "threads": threads,
        "rows_total": rows_done[0],
        "wall_s": round(wall, 3),
        "rows_per_s": round(rows_done[0] / max(wall, 1e-9)),
        "req_per_s": round(len(lat) / max(wall, 1e-9), 1),
        "p50_ms": pct(0.50), "p95_ms": pct(0.95), "p99_ms": pct(0.99),
        "batches": int(batches),
        "mean_batch_rows": round(breal / max(batches, 1), 1),
        "mean_occupancy": round(breal / max(bpad, 1), 4),
        "hot_swaps": 1 if swap_booster is not None else 0,
        "failed_requests": len(errors),
        "steady_xla_compiles": int(now.get("xla_compiles", 0) -
                                   base.get("xla_compiles", 0)),
        "errors": errors[:5],
    }


def run_variant(lgb, params, train, n_meas, auc_fn, profiling=None,
                diagnose_fetch=False, keep=None):
    """Train WARMUP + n_meas iterations; return timing + AUC stats.
    ``keep``: dict that receives the trained booster under "booster"
    (for follow-on inference benchmarks)."""
    from lightgbm_tpu.utils import telemetry as _telemetry
    booster = lgb.Booster(params=params, train_set=train)
    if keep is not None:
        keep["booster"] = booster
    t0 = time.time()
    for _ in range(WARMUP):
        booster.update()
    warmup_s = time.time() - t0
    ph0 = profiling.snapshot() if profiling is not None else {}
    c0 = _telemetry.counters_snapshot()
    times = []
    arm = []
    g = booster._gbdt
    for _ in range(n_meas):
        t1 = time.time()
        booster.update()
        times.append(time.time() - t1)
        if hasattr(g, "last_arm_passes"):
            arm.append(g.last_arm_passes)
    c1 = _telemetry.counters_snapshot()
    ts = sorted(times)
    median = ts[len(ts) // 2]
    mean = sum(times) / max(len(times), 1)
    out = {
        "iters_per_s": round(1.0 / median, 4),
        # the fused super-step serves K-1 of every K updates from a
        # precomputed block (microseconds), so ITS per-iteration cost
        # is the mean over whole blocks — reported for every variant
        # so fused/unfused rows compare on the same statistic
        "mean_iter_s": round(mean, 5),
        "projected_500iter_s": round(warmup_s + median *
                                     (N_ITERS - WARMUP), 2),
        "best_iter_s": round(ts[0], 3),
        "best_projected_s": round(warmup_s + ts[0] * (N_ITERS - WARMUP),
                                  2),
        "measured_iters": n_meas + WARMUP,
        "warmup_compile_s": round(warmup_s, 2),
        # self-diagnosis: compiles DURING the measured window mean the
        # median carries recompile time, not steady-state throughput —
        # exactly the silent retrace storms rounds 4-5 couldn't see
        "measured_xla_compiles": int(c1.get("xla_compiles", 0.0) -
                                     c0.get("xla_compiles", 0.0)),
    }
    if out["measured_xla_compiles"]:
        out["retrace_warning"] = True
        out["measured_xla_compile_s"] = round(
            c1.get("xla_compile_secs", 0.0) -
            c0.get("xla_compile_secs", 0.0), 2)
    try:
        out["auc_holdout"] = auc_fn(booster)
    except Exception as exc:  # the timing result must survive
        out["auc_holdout"] = None
        out["auc_error"] = str(exc)[:200]
    if arm:
        out["hist_passes_per_tree"] = round(
            sorted(arm)[len(arm) // 2] + 1, 1)  # + root pass
    if profiling is not None:
        ph1 = profiling.snapshot()
        phases = {}
        for name in ("boosting/gradients", "tree/prep", "tree/dispatch",
                     "tree/fetch", "tree/to_tree", "tree/renew",
                     "tree/score_update", "tree/valid"):
            t0_, c0_ = ph0.get(name, (0.0, 0))
            t, c = ph1.get(name, (0.0, 0))
            if c - c0_:
                phases[name.split("/")[-1]] = round(
                    (t - t0_) / (c - c0_) * 1e3, 1)
        if phases:
            out["phase_ms_per_iter"] = phases
    if diagnose_fetch:
        # the "fetch" phase at steady state is the WAIT for the
        # in-flight device build, not transfer.  The honest probe is a
        # pipeline on/off A/B on the SAME booster (contiguous blocks;
        # a 1-element-sync split timer mis-attributes, because the
        # pack fetch queues behind the next build by construction).
        prev_pipe = g._pipeline_enabled
        try:
            g._pipeline_enabled = False
            booster.update()              # flush transition
            ts_off = []
            for _ in range(6):
                t1 = time.time()
                booster.update()
                ts_off.append(time.time() - t1)
            g._pipeline_enabled = prev_pipe
            booster.update()
            ts_on = []
            for _ in range(6):
                t1 = time.time()
                booster.update()
                ts_on.append(time.time() - t1)
            med = lambda ts: sorted(ts)[len(ts) // 2]
            out["pipeline_gain_ms_per_iter"] = round(
                (med(ts_off) - med(ts_on)) * 1e3, 1)
        except Exception as exc:
            out["pipeline_probe_error"] = str(exc)[:200]
        finally:
            g._pipeline_enabled = prev_pipe
    return out


def router_only():
    """Fast path (``python bench.py --router-only``): aggregate fleet
    throughput and latency THROUGH the routing front
    (``serve/router.py``) vs clients round-robining
    ``FleetSupervisor.endpoints()`` directly — steady state, a mid-run
    deploy, and an injected backend brownout with hedging on vs off.
    Records BENCH_router_cpu.json (rendered into docs/Benchmarks.md
    by tools/render_benchmarks.py) with the acceptance pins: hedging
    bounds the brownout p99 below the no-hedge cell, every
    budget-shed request is a STRUCTURED 429, and zero requests drop
    through the router across every cell."""
    import datetime
    import threading as _threading

    ensure_backend()
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serve import (FleetConfig, FleetSupervisor,
                                    InprocReplica, Router,
                                    RouterConfig, ServeConfig)
    from lightgbm_tpu.serve.router import route_http
    from lightgbm_tpu.utils import faults as _faults
    from lightgbm_tpu.utils import telemetry as _telemetry
    from lightgbm_tpu.utils.telemetry import percentile
    _telemetry.install_jax_hooks()

    n_features = 28
    rng = np.random.RandomState(0)
    X = rng.randn(20000, n_features).astype(np.float32)
    w = rng.randn(n_features).astype(np.float32)
    y = (1.0 / (1.0 + np.exp(-(X @ w) * 0.5)) >
         rng.random_sample(20000)).astype(np.float32)

    def train(rounds, seed):
        d = lgb.Dataset(X, label=y, params={"objective": "binary",
                                            "verbose": -1})
        return lgb.train({"objective": "binary", "num_leaves": 31,
                          "verbose": -1, "metric": "None",
                          "seed": seed}, d, num_boost_round=rounds)

    b1, b2 = train(20, 1), train(20, 2)
    forest = (f"{b1.num_trees()}-tree 31-leaf binary forest over "
              f"{n_features} features, 2 in-process replicas")
    n_req = int(os.environ.get("BENCH_ROUTER_REQUESTS", "300"))
    threads = 4
    rows_per_req = 32

    sup = FleetSupervisor(
        lambda i: InprocReplica(b1, config=ServeConfig(
            port=0, batch_wait_ms=1.0, timeout_ms=60000)),
        FleetConfig(replicas=2, probe_interval_s=0.1,
                    probe_timeout_s=5.0))
    sup.start(wait_healthy_s=60)

    def drive(post_one, label, mid_deploy=False):
        """n_req fixed-size requests from `threads` clients through
        ``post_one(client_rng) -> (ok, latency_ms)``."""
        lat, lock = [], _threading.Lock()
        dropped = [0]
        issued = [0]
        deploy_at = n_req // 2 if mid_deploy else -1

        def client(tid):
            r = np.random.RandomState(100 + tid)
            while True:
                with lock:
                    if issued[0] >= n_req:
                        return
                    issued[0] += 1
                    i = issued[0]
                if i == deploy_at:
                    sup.publish_model(b2.model_to_string())
                    continue
                t0 = time.time()
                ok = post_one(r)
                ms = (time.time() - t0) * 1e3
                with lock:
                    if ok:
                        lat.append(ms)
                    else:
                        dropped[0] += 1

        t_start = time.time()
        cls = [_threading.Thread(target=client, args=(i,))
               for i in range(threads)]
        for t in cls:
            t.start()
        for t in cls:
            t.join()
        wall = time.time() - t_start
        lat.sort()
        cell = {
            "label": label,
            "requests": len(lat),
            "dropped": dropped[0],
            "wall_s": round(wall, 3),
            "req_per_s": round(len(lat) / max(wall, 1e-9), 1),
            "rows_per_s": round(len(lat) * rows_per_req /
                                max(wall, 1e-9)),
            "p50_ms": round(percentile(lat, 0.50), 2),
            "p99_ms": round(percentile(lat, 0.99), 2),
        }
        return cell

    def http_post(url, path, body, timeout=60):
        import urllib.error
        import urllib.request
        req = urllib.request.Request(
            url + path, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            try:
                return e.code, json.loads(e.read())
            except ValueError:
                return e.code, {}
        except Exception:              # noqa: BLE001 - counted
            return 599, {}

    def direct_one(r):
        """The pre-router client: round-robin endpoints() yourself."""
        eps = sup.endpoints()
        if not eps:
            return False
        lo = int(r.randint(0, len(X) - rows_per_req))
        url = eps[int(r.randint(0, len(eps)))]
        st, out = http_post(url, "/predict",
                            {"rows": X[lo:lo + rows_per_req].tolist()})
        return st == 200

    def arm_brownout():
        """ONE slow replica: every attempt forwarded to backend 0 of
        the route's URL order is delayed 200 ms (vs the ~10 ms mean)
        — the hedge goes to the OTHER backend and wins the race."""
        _faults.configure("router.backend:sleepb0_200@*")

    cells = []
    shed_stats = {}
    try:
        cells.append(drive(direct_one, "direct round-robin"))
        print(json.dumps({"router_cell": cells[-1]}), flush=True)

        for label, hedge_ms, brownout, mid_deploy in (
                ("router", 60.0, False, False),
                ("router + mid-run deploy", 60.0, False, True),
                ("router + brownout, hedge off", 0.0, True, False),
                ("router + brownout, hedge on", 60.0, True, False)):
            router = Router(RouterConfig(
                port=0, probe_interval_s=0.1, probe_timeout_s=5.0,
                timeout_ms=60000.0, hedge_ms=hedge_ms, max_retries=3))
            router.add_model("default", supervisor=sup)
            httpd, _ = route_http(router, port=0, background=True)
            url = "http://127.0.0.1:%d" % httpd.server_address[1]

            def router_one(r, url=url):
                lo = int(r.randint(0, len(X) - rows_per_req))
                st, _o = http_post(
                    url, "/predict",
                    {"rows": X[lo:lo + rows_per_req].tolist()})
                return st == 200
            if brownout:
                arm_brownout()
            cell = drive(router_one, label, mid_deploy=mid_deploy)
            _faults.configure("")
            st = router.stats()
            cell["hedges"] = st["hedges"]
            cell["hedge_wins"] = st["hedge_wins"]
            cell["retries"] = st["retries"]
            cells.append(cell)
            print(json.dumps({"router_cell": cell}), flush=True)
            httpd.shutdown()
            httpd.server_close()
            router.stop()

        # shed cell: a tight admission budget must shed every excess
        # request with a STRUCTURED 429 (code + retry_after_ms +
        # Retry-After header), never an error or a backend touch
        router = Router(RouterConfig(
            port=0, probe_interval_s=0.1, probe_timeout_s=5.0,
            timeout_ms=60000.0, hedge_ms=0.0,
            rows_per_s=rows_per_req * 4.0,
            burst_rows=rows_per_req * 4))
        router.add_model("default", supervisor=sup)
        httpd, _ = route_http(router, port=0, background=True)
        url = "http://127.0.0.1:%d" % httpd.server_address[1]
        structured, unstructured, ok_n = 0, 0, 0
        for _ in range(80):
            lo = 0
            st, out = http_post(
                url, "/predict",
                {"rows": X[lo:lo + rows_per_req].tolist()})
            if st == 200:
                ok_n += 1
            elif st == 429 and out.get("code") == "backpressure" \
                    and out.get("retry_after_ms") is not None:
                structured += 1
            else:
                unstructured += 1
        shed_stats = {"ok": ok_n, "shed_structured": structured,
                      "shed_unstructured": unstructured}
        print(json.dumps({"router_shed": shed_stats}), flush=True)
        httpd.shutdown()
        httpd.server_close()
        router.stop()
    finally:
        _faults.configure("")
        sup.stop()

    by_label = {c["label"]: c for c in cells}
    pins = {
        "zero_dropped": all(c["dropped"] == 0 for c in cells
                            if c["label"].startswith("router")),
        "hedge_bounds_p99":
            by_label["router + brownout, hedge on"]["p99_ms"] <
            by_label["router + brownout, hedge off"]["p99_ms"],
        "sheds_all_structured":
            shed_stats.get("shed_structured", 0) > 0 and
            shed_stats.get("shed_unstructured", 0) == 0,
    }
    out = {
        "metric": "router_front_cpu",
        "unit": "ms",
        "backend": "cpu",
        "date": datetime.date.today().isoformat(),
        "source": "JAX_PLATFORMS=cpu python bench.py --router-only",
        "env": "2-core CPU container",
        "forest": forest,
        "config": {"replicas": 2, "threads": threads,
                   "rows_per_request": rows_per_req,
                   "requests": n_req, "hedge_ms": 60.0,
                   "brownout": "router.backend:sleepb0_200@* — every "
                               "attempt to replica 0 delayed 200 ms "
                               "(one slow replica)"},
        "cells": cells,
        "shed": shed_stats,
        "pins": pins,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_router_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(json.dumps({"wrote": os.path.basename(path),
                      "pins": pins}), flush=True)
    return 0 if all(pins.values()) else 1


def autoscale_only():
    """Control-plane microbench (``python bench.py --autoscale-only``):
    the SLO engine + closed-loop autoscaler driven by injected clocks
    against a scripted error stream — reaction latency from surge to
    grow, hysteresis from idle to drain, dry-run parity, and the
    per-evaluate overhead of the control loop itself.  Records
    BENCH_autoscale_cpu.json (rendered into docs/Benchmarks.md by
    tools/render_benchmarks.py) with the acceptance pins: the grow
    decision lands within the mid burn window of surge onset (the
    binding window for the page-grade signal), the drain respects the
    sustained-idle hysteresis exactly, dry-run replays an identical
    decision sequence with zero actuations, and the control step stays
    far below its own cadence."""
    import datetime

    ensure_backend()
    from lightgbm_tpu.obs.metrics import MetricsRegistry
    from lightgbm_tpu.obs.slo import SloEngine, SloObjective
    from lightgbm_tpu.serve.autoscaler import Autoscaler
    from lightgbm_tpu.serve.config import AutoscaleConfig, SloConfig

    class _Fleet:
        """Capacity lever that records every actuation."""

        def __init__(self):
            self.n = 1
            self.calls = []

        def slots(self):
            return [{"in_rotation": True}] * self.n

        def replica_count(self):
            return self.n

        def scale_to(self, n, reason=""):
            self.calls.append((self.n, n, reason))
            self.n = n
            return n

    scfg = SloConfig(interval_s=1.0, window_fast_s=60.0,
                     window_mid_s=300.0, window_slow_s=1800.0,
                     fast_burn=14.4, slow_burn=3.0,
                     budget_window_s=30 * 86400.0,
                     availability_target=0.99)
    acfg = AutoscaleConfig(interval_s=1.0, min_replicas=1,
                           max_replicas=4, grow_burn=2.0,
                           grow_queue=0.8, drain_idle_s=60.0,
                           drain_util=0.2, cooldown_s=30.0,
                           drain_cooldown_s=60.0,
                           shed_rows_per_s=256.0, budget_floor=0.25)

    def run(dry_run):
        """One scripted day: healthy -> 20%-error surge -> recovery ->
        sustained idle.  Clock-driven: each loop turn is one second of
        engine tick + controller evaluate."""
        clock = {"t": 0.0}
        stream = {"good": 0.0, "bad": 0.0, "err": 0.0}

        def source():
            stream["good"] += 100.0 * (1.0 - stream["err"])
            stream["bad"] += 100.0 * stream["err"]
            return stream["good"], stream["bad"]

        engine = SloEngine(
            [SloObjective("availability", scfg.availability_target,
                          source)],
            config=scfg, registry=MetricsRegistry(),
            clock=lambda: clock["t"])
        cfg = AutoscaleConfig(**{**acfg.__dict__, "dry_run": dry_run})
        fleet = _Fleet()
        scaler = Autoscaler(supervisor=fleet, slo=engine, config=cfg,
                            clock=lambda: clock["t"])
        timeline = []
        marks = {}
        inputs_log = []
        orig_inputs = scaler.inputs

        def logged_inputs():
            inp = orig_inputs()
            inputs_log.append((clock["t"], inp))
            return inp

        scaler.inputs = logged_inputs

        def step(phase, seconds, err):
            stream["err"] = err
            for _ in range(int(seconds)):
                clock["t"] += 1.0
                engine.tick()
                for d in scaler.evaluate():
                    timeline.append((clock["t"], d["action"],
                                     d["rule"]))
                    marks.setdefault((phase, d["action"]), clock["t"])

        step("healthy", 300, 0.0)
        surge_at = clock["t"]
        step("surge", 120, 0.20)           # burn 20x the 1% budget
        surge_end = clock["t"]
        step("recovery", scfg.window_mid_s + 5, 0.0)
        step("idle", 180, 0.0)
        return {"fleet": fleet, "timeline": timeline, "marks": marks,
                "surge_at": surge_at, "surge_end": surge_end,
                "inputs_log": inputs_log}

    active = run(dry_run=False)

    # dry-run parity is defined over IDENTICAL inputs (in a closed
    # loop the inputs themselves depend on actuation): replay the
    # active run's recorded evidence through a dry-run controller
    def replay_dry(inputs_log):
        fleet = _Fleet()
        scaler = Autoscaler(
            supervisor=fleet,
            config=AutoscaleConfig(**{**acfg.__dict__,
                                      "dry_run": True}))
        timeline = []
        for t, inp in inputs_log:
            scaler.inputs = lambda _i=inp: _i
            for d in scaler.evaluate(now=t):
                timeline.append((t, d["action"], d["rule"]))
        return {"fleet": fleet, "timeline": timeline}

    dry = replay_dry(active["inputs_log"])

    grow_t = active["marks"].get(("surge", "grow"))
    grow_reaction_s = (grow_t - active["surge_at"]) if grow_t else -1.0
    drains = sorted(t for t, a, _r in active["timeline"]
                    if a == "drain")
    first_drain_gap_s = (drains[0] - active["surge_end"]) \
        if drains else -1.0
    drain_spacing_s = min((b - a for a, b in zip(drains, drains[1:])),
                          default=float("inf"))

    # control-step overhead: a quiet evaluate() in steady state
    fleet = _Fleet()
    engine = SloEngine([SloObjective("availability", 0.99,
                                     lambda: (1e6, 0.0))],
                       config=scfg, registry=MetricsRegistry())
    engine.tick()
    scaler = Autoscaler(supervisor=fleet, slo=engine, config=acfg)
    lats = []
    for _ in range(2000):
        t0 = time.perf_counter()
        scaler.evaluate()
        lats.append((time.perf_counter() - t0) * 1e3)
    lats.sort()
    from lightgbm_tpu.utils.telemetry import percentile
    overhead = {"evaluations": len(lats),
                "p50_ms": round(percentile(lats, 0.50), 4),
                "p99_ms": round(percentile(lats, 0.99), 4)}

    pins = {
        # the page-grade signal needs the burn above threshold on BOTH
        # windows; the mid window is the binding one by construction
        "grow_within_mid_window":
            0.0 < grow_reaction_s <= scfg.window_mid_s,
        # draining needs quiet SUSTAINED for drain_idle_s after the
        # surge ends, and consecutive drains respect the cooldown
        "drain_respects_hysteresis":
            bool(drains) and
            first_drain_gap_s >= acfg.drain_idle_s and
            drain_spacing_s >= acfg.drain_cooldown_s,
        # the loop closes: the fleet is back at min size by the end
        "drained_back_to_min":
            active["fleet"].n == acfg.min_replicas,
        # scripted replay: dry-run decides identically, acts never
        "dry_run_parity":
            [(a, r) for _t, a, r in active["timeline"]] ==
            [(a, r) for _t, a, r in dry["timeline"]] and
            dry["fleet"].calls == [],
        "active_actions_reconciled":
            len(active["fleet"].calls) ==
            len(active["timeline"]),
        # the control step must stay far below its own 1 s cadence
        "decide_overhead_bounded": overhead["p99_ms"] < 50.0,
    }
    cells = [
        {"label": "surge -> grow reaction",
         "grow_reaction_s": grow_reaction_s,
         "window_mid_s": scfg.window_mid_s},
        {"label": "idle -> drain hysteresis",
         "first_drain_after_surge_end_s": round(first_drain_gap_s, 1),
         "drain_spacing_s": (round(drain_spacing_s, 1)
                             if drains[1:] else None),
         "drain_idle_s": acfg.drain_idle_s,
         "drain_cooldown_s": acfg.drain_cooldown_s},
        {"label": "decision timeline (active)",
         "decisions": len(active["timeline"]),
         "actions": len(active["fleet"].calls),
         "sequence": [(a, r) for _t, a, r in active["timeline"]]},
        {"label": "evaluate() overhead", **overhead},
    ]
    out = {
        "metric": "autoscale_control_cpu",
        "unit": "s",
        "backend": "cpu",
        "date": datetime.date.today().isoformat(),
        "source": "JAX_PLATFORMS=cpu python bench.py "
                  "--autoscale-only",
        "env": "2-core CPU container",
        "forest": "control-plane only: scripted 100-req/s stream, "
                  "20% error surge, injected clocks (no sleeping)",
        "config": {"slo": {"windows_s": [scfg.window_fast_s,
                                         scfg.window_mid_s,
                                         scfg.window_slow_s],
                           "fast_burn": scfg.fast_burn,
                           "slow_burn": scfg.slow_burn,
                           "availability_target":
                               scfg.availability_target},
                   "autoscale": {"grow_burn": acfg.grow_burn,
                                 "grow_queue": acfg.grow_queue,
                                 "drain_idle_s": acfg.drain_idle_s,
                                 "cooldown_s": acfg.cooldown_s,
                                 "drain_cooldown_s":
                                     acfg.drain_cooldown_s,
                                 "replicas": [acfg.min_replicas,
                                              acfg.max_replicas]}},
        "cells": cells,
        "pins": pins,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_autoscale_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(json.dumps({"wrote": os.path.basename(path),
                      "pins": pins}), flush=True)
    return 0 if all(pins.values()) else 1


def serve_only():
    """Fast path (``python bench.py --serve-only``): train a small
    booster pair on the CPU backend and record the online-serving
    latency/throughput matrix as BENCH_serve_cpu.json — the artifact
    ``tools/render_benchmarks.py`` renders into docs/Benchmarks.md.
    Runs anywhere (CI serve-bench smoke); the absolute numbers are
    only meaningful per-backend, like the other *_cpu artifacts."""
    import datetime

    ensure_backend()
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils import telemetry as _telemetry
    _telemetry.install_jax_hooks()

    n_features = 28
    rng = np.random.RandomState(0)
    X = rng.randn(20000, n_features).astype(np.float32)
    w = rng.randn(n_features).astype(np.float32)
    y = (1.0 / (1.0 + np.exp(-(X @ w) * 0.5)) >
         rng.random_sample(20000)).astype(np.float32)

    def train(rounds, seed):
        d = lgb.Dataset(X, label=y, params={"objective": "binary",
                                            "verbose": -1})
        return lgb.train({"objective": "binary", "num_leaves": 31,
                          "verbose": -1, "metric": "None",
                          "seed": seed}, d, num_boost_round=rounds)

    b1, b2 = train(20, 1), train(20, 2)
    forest = (f"{b1.num_trees()}-tree 31-leaf binary forest over "
              f"{n_features} features, float64 engine scoring")
    n_req = int(os.environ.get("BENCH_SERVE_REQUESTS", "400"))
    cells = []
    for label, threads, wait_ms, swap in (
            ("sequential", 1, 0.0, None),
            ("concurrent x8", 8, 1.0, None),
            ("concurrent x8 + hot-swap", 8, 1.0, b2)):
        res = bench_serve(b1, n_features, swap_booster=swap,
                          n_requests=n_req, threads=threads,
                          batch_wait_ms=wait_ms)
        res["label"] = label
        cells.append(res)
        print(json.dumps({"serve_cell": label, **res}), flush=True)
    out = {
        "metric": "serve_latency_throughput_cpu",
        "unit": "ms",
        "backend": "cpu",
        "date": datetime.date.today().isoformat(),
        "source": "JAX_PLATFORMS=cpu python bench.py --serve-only",
        "env": "2-core CPU container",
        "forest": forest,
        "config": {"max_batch_rows": 1024, "rows_max": 900,
                   "requests": n_req, "timeout_ms": 60000},
        "cells": cells,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_serve_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(json.dumps({"wrote": os.path.basename(path)}), flush=True)
    return 0


def explain_only():
    """Fast path (``python bench.py --explain-only``): train a small
    booster on the CPU backend and record the serve-time explanation
    matrix as BENCH_explain_cpu.json — explanation-lane latency/
    throughput (device TreeSHAP through the micro-batcher) plus the
    single-row fastpath-vs-bucketed predict cells, all with the
    steady-state compile count pinned at 0 (publish-time warmup
    pre-compiles every bucket).  Rendered into docs/Benchmarks.md by
    ``tools/render_benchmarks.py``."""
    import datetime

    ensure_backend()
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils import telemetry as _telemetry
    _telemetry.install_jax_hooks()

    n_features = 28
    rng = np.random.RandomState(0)
    X = rng.randn(20000, n_features).astype(np.float32)
    w = rng.randn(n_features).astype(np.float32)
    y = (1.0 / (1.0 + np.exp(-(X @ w) * 0.5)) >
         rng.random_sample(20000)).astype(np.float32)
    d = lgb.Dataset(X, label=y, params={"objective": "binary",
                                        "verbose": -1})
    bst = lgb.train({"objective": "binary", "num_leaves": 31,
                     "verbose": -1, "metric": "None", "seed": 1},
                    d, num_boost_round=20)
    forest = (f"{bst.num_trees()}-tree 31-leaf binary forest over "
              f"{n_features} features, float64 device TreeSHAP")
    n_req = int(os.environ.get("BENCH_EXPLAIN_REQUESTS", "200"))
    cells = []
    # -- explanation lane: mixed row counts through the explain lane
    for label, threads, wait_ms, rows_max in (
            ("explain sequential", 1, 0.0, 400),
            ("explain concurrent x8", 8, 1.0, 400)):
        res = bench_serve(bst, n_features, n_requests=n_req,
                          threads=threads, rows_max=rows_max,
                          batch_wait_ms=wait_ms, kind="explain")
        res["label"] = label
        cells.append(res)
        print(json.dumps({"explain_cell": label, **res}), flush=True)
    # -- single-row predict: occupancy-routed fast path vs the same
    # requests forced through the full bucketed path (fastpath gate
    # off) — the p50 delta IS the fast path's reason to exist
    for label, fp_rows in (("single-row fastpath", 8),
                           ("single-row bucketed", 0)):
        res = bench_serve(bst, n_features, n_requests=n_req,
                          threads=1, rows_max=1, batch_wait_ms=0.0,
                          kind="predict", fastpath_max_rows=fp_rows)
        res["label"] = label
        cells.append(res)
        print(json.dumps({"explain_cell": label, **res}), flush=True)
    by_label = {c["label"]: c for c in cells}
    fast = by_label["single-row fastpath"]
    slow = by_label["single-row bucketed"]
    speedup = round(slow["p50_ms"] / max(fast["p50_ms"], 1e-9), 2)
    out = {
        "metric": "explain_latency_throughput_cpu",
        "unit": "ms",
        "backend": "cpu",
        "date": datetime.date.today().isoformat(),
        "source": "JAX_PLATFORMS=cpu python bench.py --explain-only",
        "env": "2-core CPU container",
        "forest": forest,
        "config": {"max_batch_rows": 1024, "requests": n_req,
                   "timeout_ms": 60000},
        "fastpath_p50_speedup": speedup,
        "cells": cells,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_explain_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(json.dumps({"wrote": os.path.basename(path),
                      "fastpath_p50_speedup": speedup}), flush=True)
    return 0


def obs_only():
    """Fast path (``python bench.py --obs-only``): measure what the
    observability plane COSTS on the CPU smoke shapes and write
    BENCH_obs_cpu.json — train wall and serve throughput with the
    plane off vs fully on (telemetry JSONL + span tagging + metrics
    registry + armed flight recorder).  The plane must stay under 2%
    wall on these shapes (docs/Observability.md pins the bar).

    OFF = the telemetry JSONL with span tagging (inseparable from the
    telemetry layer once obs is loaded: a contextvar read per record);
    ON adds the REST of the plane — Prometheus metrics registry +
    counter mirror and the armed flight-recorder ring — so the cells
    price the plane's optional half on top of the always-on half.
    OFF cells
    run before any ON cell: the telemetry-counter mirror is a
    process-wide install, so arming it first would retro-tax the
    baseline.  ``spread_pct`` records the off-rep min..max spread —
    on a noisy 2-core container an overhead below the spread is a
    noise-floor reading, and render_benchmarks.py says so."""
    import datetime
    import tempfile

    ensure_backend()
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serve import ServeConfig, Server
    from lightgbm_tpu.utils import telemetry as _telemetry
    _telemetry.install_jax_hooks()

    n_rows = int(os.environ.get("BENCH_OBS_ROWS", "20000"))
    n_feat = 28
    rounds = int(os.environ.get("BENCH_OBS_ROUNDS", "30"))
    reps = int(os.environ.get("BENCH_OBS_REPS", "3"))
    n_req = int(os.environ.get("BENCH_OBS_REQUESTS", "300"))
    rng = np.random.RandomState(0)
    X = rng.randn(n_rows, n_feat).astype(np.float32)
    w = rng.randn(n_feat).astype(np.float32)
    y = (1.0 / (1.0 + np.exp(-(X @ w) * 0.5)) >
         rng.random_sample(n_rows)).astype(np.float32)
    Xq = rng.randn(64, n_feat)
    tmp = tempfile.mkdtemp(prefix="bench_obs_")

    def train_wall(extra):
        params = {"objective": "binary", "num_leaves": 31,
                  "verbose": -1, "metric": "None", "fused_iters": 4,
                  **extra}
        d = lgb.Dataset(X, label=y, params=dict(params))
        t0 = time.perf_counter()
        bst = lgb.train(dict(params), d, num_boost_round=rounds)
        wall = time.perf_counter() - t0
        rec = getattr(bst._gbdt, "_telemetry", None)
        if rec is not None:
            rec.close(log=False)
        return wall, bst

    def serve_rps(booster, cfg):
        srv = Server(booster, config=cfg)
        srv.start()
        srv.predict(Xq)                    # warm the bucket
        t0 = time.perf_counter()
        for _ in range(n_req):
            srv.predict(Xq)
        wall = time.perf_counter() - t0
        srv.stop()                         # flushes the recorder too
        return n_req / wall

    def tele(name, i):
        return {"telemetry_file": os.path.join(tmp,
                                               f"{name}_{i}.jsonl")}

    # discarded warmup: the first train/serve pass pays the XLA
    # compiles; without it the OFF cells eat warmup the ON cells
    # then ride, and the "overhead" comes out negative
    _, warm_bst = train_wall(tele("warm", 0))
    serve_rps(warm_bst, ServeConfig(port=0, batch_wait_ms=0.0,
                                    timeout_ms=60000, metrics=False,
                                    warmup=False))
    # interleaved ABBA reps: container-level drift (page cache, CPU
    # governor, co-tenants) dwarfs the plane's cost, so off/on
    # alternate within each rep pair and the order flips per pair;
    # the plane is UNINSTALLED after each on-cell so off-cells stay
    # a true baseline
    from lightgbm_tpu.obs import flight as _flight
    from lightgbm_tpu.obs import metrics as _om

    def one_train(on, i):
        if not on:
            return train_wall(tele("toff", i))[0]
        w = train_wall({**tele("ton", i),
                        "obs_flight_recorder": True,
                        "obs_capture_dir":
                            os.path.join(tmp, "caps")})[0]
        _flight.uninstall()
        return w

    def one_serve(on, i):
        r = serve_rps(warm_bst,
                      ServeConfig(port=0, batch_wait_ms=0.0,
                                  timeout_ms=60000, metrics=on,
                                  warmup=False,
                                  **tele("son" if on else "soff", i)))
        if on:
            _om.uninstall_telemetry_mirror()
        return r

    t_off, t_on, rps_off, rps_on = [], [], [], []
    for i in range(reps):
        for on in ((False, True) if i % 2 == 0 else (True, False)):
            (t_on if on else t_off).append(one_train(on, i))
    for i in range(reps):
        for on in ((False, True) if i % 2 == 0 else (True, False)):
            (rps_on if on else rps_off).append(one_serve(on, i))
    t_off.sort(), t_on.sort(), rps_off.sort(), rps_on.sort()

    def med(vals):
        return vals[len(vals) // 2]

    def spread(vals):
        return round(100.0 * (vals[-1] - vals[0]) / med(vals), 2)

    cells = [
        {"cell": "train", "rows": n_rows, "rounds": rounds,
         "off_s": round(med(t_off), 3), "on_s": round(med(t_on), 3),
         "spread_pct": spread(t_off),
         "overhead_pct": round(
             100.0 * (med(t_on) - med(t_off)) / med(t_off), 2)},
        {"cell": "serve", "requests": n_req, "rows_per_req": 64,
         "off_rps": round(med(rps_off), 1),
         "on_rps": round(med(rps_on), 1),
         "spread_pct": spread(rps_off),
         "overhead_pct": round(
             100.0 * (med(rps_off) - med(rps_on)) / med(rps_off), 2)},
    ]
    for c in cells:
        print(json.dumps({"obs_cell": c["cell"], **c}), flush=True)
    out = {
        "metric": "obs_overhead_cpu",
        "unit": "percent",
        "backend": "cpu",
        "date": datetime.date.today().isoformat(),
        "source": "JAX_PLATFORMS=cpu python bench.py --obs-only",
        "env": "2-core CPU container",
        "plane": "metrics registry + counter mirror + armed flight "
                 "recorder, on top of telemetry JSONL + span tagging "
                 "(always-on once obs loads, present in BOTH cells)",
        "reps": reps,
        "cells": cells,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_obs_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(json.dumps({"wrote": os.path.basename(path)}), flush=True)
    return 0


def ckpt_only():
    """Fast path (``python bench.py --ckpt-only``): measure the
    checkpoint subsystem's cost envelope on the CPU backend and write
    BENCH_ckpt_cpu.json — per-snapshot save wall/bytes, load/restore
    time, the resume path's warmup compiles, and the save overhead as
    a fraction of train wall time (triage_run.py flags runs past 5%).
    One cell per training path (sequential, fused super-steps), since
    a mid-fused-block save exercises the alignment replay."""
    import datetime
    import tempfile

    ensure_backend()
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ckpt import CheckpointManager
    from lightgbm_tpu.utils import telemetry as _telemetry
    _telemetry.install_jax_hooks()

    n_rows = int(os.environ.get("BENCH_CKPT_ROWS", "20000"))
    n_features = 28
    rounds = int(os.environ.get("BENCH_CKPT_ROUNDS", "40"))
    freq = 10
    rng = np.random.RandomState(0)
    X = rng.randn(n_rows, n_features).astype(np.float32)
    w = rng.randn(n_features).astype(np.float32)
    y = (1.0 / (1.0 + np.exp(-(X @ w) * 0.5)) >
         rng.random_sample(n_rows)).astype(np.float32)

    def run_cell(label, extra):
        cell = {"label": label}
        with tempfile.TemporaryDirectory() as td:
            ck = os.path.join(td, "ck")
            tele = os.path.join(td, "tele.jsonl")
            p = {"objective": "binary", "num_leaves": 31,
                 "verbose": -1, "metric": "None",
                 "num_iterations": rounds, "checkpoint_dir": ck,
                 "snapshot_freq": freq, "keep_last_n": 3,
                 "telemetry_file": tele}
            p.update(extra)
            d = lgb.Dataset(X, label=y, params=p)
            t0 = time.time()
            bst = lgb.train(p, d, verbose_eval=False)
            train_wall = time.time() - t0
            bst._gbdt._telemetry.close(log=False)
            recs = _telemetry.read_records(tele)
            saves = [r for r in recs if r.get("type") == "checkpoint"
                     and r.get("event") == "save"]
            save_ms = [float(r["duration_ms"]) for r in saves]
            train_ms = sum(float(r.get("duration_ms", 0.0))
                           for r in recs
                           if r.get("type") in ("iteration",
                                                "superstep"))
            cell.update({
                "saves": len(saves),
                "save_ms_mean": round(sum(save_ms) /
                                      max(len(save_ms), 1), 2),
                "save_ms_max": round(max(save_ms), 2) if save_ms
                else None,
                "ckpt_bytes": int(saves[-1]["bytes"]) if saves else 0,
                "train_wall_s": round(train_wall, 3),
                "save_overhead_pct": round(
                    100.0 * sum(save_ms) / max(train_ms, 1e-9), 2),
            })
            mgr = CheckpointManager(ck)
            t0 = time.time()
            loaded = mgr.load_latest()
            cell["load_ms"] = round((time.time() - t0) * 1e3, 2)
            assert loaded is not None
            # resume warmup: in-process continuation (new Booster +
            # restore + 5 iterations).  Same-shape programs hit the
            # process executable cache, so the compile count here is
            # the RESUME-SPECIFIC delta; a fresh replacement machine
            # additionally pays the normal first-run compile bill
            base = _telemetry.counters_snapshot()
            t0 = time.time()
            p2 = dict(p, num_iterations=rounds + 5)
            p2.pop("telemetry_file")
            d2 = lgb.Dataset(X, label=y, params=p2)
            lgb.train(p2, d2, verbose_eval=False, resume_from="auto")
            now = _telemetry.counters_snapshot()
            cell["resume_warmup_s"] = round(time.time() - t0, 3)
            cell["resume_xla_compiles"] = int(
                now.get("xla_compiles", 0) - base.get("xla_compiles", 0))
        print(json.dumps({"ckpt_cell": label, **cell}), flush=True)
        return cell

    cells = [run_cell("sequential", {}),
             run_cell("fused_iters=4", {"fused_iters": 4})]
    out = {
        "metric": "checkpoint_overhead_cpu",
        "unit": "ms",
        "backend": "cpu",
        "date": datetime.date.today().isoformat(),
        "source": "JAX_PLATFORMS=cpu python bench.py --ckpt-only",
        "env": "2-core CPU container",
        "forest": (f"31-leaf binary forest, {n_rows} x {n_features} "
                   f"train matrix, {rounds} iterations"),
        "config": {"rows": n_rows, "features": n_features,
                   "rounds": rounds, "snapshot_freq": freq,
                   "keep_last_n": 3},
        "cells": cells,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_ckpt_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(json.dumps({"wrote": os.path.basename(path)}), flush=True)
    return 0


def continual_only():
    """Fast path (``python bench.py --continual-only``): measure the
    continual training daemon's steady-state cost envelope on the CPU
    backend and write BENCH_continual_cpu.json — per-batch
    ingest->validate->train->checkpoint wall time for extend vs refit
    batches, the validation pipeline's overhead, and the watcher's
    manifest+canary publish latency — the batch-to-publish figure a
    live deployment plans around (``docs/Continual.md``)."""
    import datetime
    import tempfile

    ensure_backend()
    import numpy as np
    from lightgbm_tpu.cont import (Batch, BatchValidator,
                                   ContinualTrainer)
    from lightgbm_tpu.serve import (CheckpointWatcher, RegistryTarget,
                                    ServeConfig, Server)
    from lightgbm_tpu.serve.config import FleetConfig
    from lightgbm_tpu.serve.watcher import CanarySet
    from lightgbm_tpu.utils import telemetry as _telemetry
    _telemetry.install_jax_hooks()

    n_batches = int(os.environ.get("BENCH_CONTINUAL_BATCHES", "5"))
    rows = int(os.environ.get("BENCH_CONTINUAL_ROWS", "4000"))
    n_features = 28
    rounds = int(os.environ.get("BENCH_CONTINUAL_ROUNDS", "10"))

    def write_stream(ingest):
        for i in range(n_batches):
            rng = np.random.RandomState(50 + i)
            X = rng.randn(rows, n_features).astype(np.float32)
            w = np.random.RandomState(7).randn(n_features)
            y = (X @ w + 0.5 * rng.randn(rows)).astype(np.float32)
            np.savez(os.path.join(ingest, f"batch_{i:03d}.npz"),
                     X=X, y=y)

    def run_cell(label, extra):
        with tempfile.TemporaryDirectory() as td:
            ingest = os.path.join(td, "ingest")
            root = os.path.join(td, "ck")
            os.makedirs(ingest)
            write_stream(ingest)
            tele = os.path.join(td, "tele.jsonl")
            p = {"objective": "regression", "num_leaves": 31,
                 "verbose": -1, "metric": "None",
                 "checkpoint_dir": root,
                 "continual_ingest_dir": ingest,
                 "continual_rounds_per_batch": rounds,
                 "continual_max_batches": n_batches,
                 "continual_poll_s": 0.05}
            p.update(extra)
            rec = _telemetry.RunRecorder(tele)
            trainer = ContinualTrainer(p, recorder=rec)
            stats = trainer.run()
            rec.close(log=False)
            assert stats["batches"] == n_batches, stats
            recs = _telemetry.read_records(tele)
            by_mode = {}
            for r in recs:
                if r.get("type") == "continual" and \
                        r.get("event") == "batch":
                    by_mode.setdefault(r.get("mode", "?"), []).append(
                        float(r["duration_ms"]))
            # validation overhead: the same gates the daemon ran,
            # re-timed against the same bytes (check is pure)
            validator = BatchValidator()
            v_ms = []
            pdir = trainer.source.processed_dir
            for name in sorted(os.listdir(pdir)):
                with np.load(os.path.join(pdir, name)) as z:
                    b = Batch(name, (), z["X"], z["y"])
                    t0 = time.perf_counter()
                    validator.check(b)
                    v_ms.append((time.perf_counter() - t0) * 1e3)
                    validator.observe(b)
            # publish latency: manifest verify + canary + flatten +
            # swap of the newest snapshot into a cold server
            server = Server(config=ServeConfig(warmup=False)).start()
            try:
                canary = CanarySet(np.random.RandomState(1)
                                   .randn(64, n_features))
                watcher = CheckpointWatcher(
                    root, RegistryTarget(server),
                    config=FleetConfig(), canary=canary)
                t0 = time.perf_counter()
                watcher.poll_once()
                publish_ms = (time.perf_counter() - t0) * 1e3
                assert server.registry.current() is not None
            finally:
                server.stop()
            steady = {m: vals[1:] if len(vals) > 1 else vals
                      for m, vals in by_mode.items()}
            mean_ms = {m: sum(v) / max(len(v), 1)
                       for m, v in steady.items()}
            primary = "refit" if label == "refit" else "extend"
            batch_ms = mean_ms.get(primary, 0.0)
            cell = {
                "label": label,
                "batches": stats["batches"],
                "rows_per_batch": rows,
                "rounds_per_batch": 0 if label == "refit" else rounds,
                "batch_ms_mean": round(batch_ms, 2),
                "batch_ms_by_mode": {m: round(v, 2)
                                     for m, v in mean_ms.items()},
                "validate_ms_mean": round(sum(v_ms) /
                                          max(len(v_ms), 1), 3),
                "validate_overhead_pct": round(
                    100.0 * (sum(v_ms) / max(len(v_ms), 1)) /
                    max(batch_ms, 1e-9), 3),
                "publish_ms": round(publish_ms, 2),
                "batch_to_publish_ms": round(batch_ms + publish_ms, 2),
            }
        print(json.dumps({"continual_cell": label, **cell}),
              flush=True)
        return cell

    cells = [run_cell("extend", {}),
             run_cell("extend fused_iters=5", {"fused_iters": 5}),
             run_cell("refit", {"continual_refit_every": 1})]
    out = {
        "metric": "continual_batch_to_publish_cpu",
        "unit": "ms",
        "backend": "cpu",
        "date": datetime.date.today().isoformat(),
        "source": "JAX_PLATFORMS=cpu python bench.py --continual-only",
        "env": "2-core CPU container",
        "forest": (f"31-leaf regression forest, {rows} x "
                   f"{n_features} rows/batch, {rounds} "
                   f"rounds/extend-batch, {n_batches} batches"),
        "config": {"batches": n_batches, "rows": rows,
                   "features": n_features, "rounds": rounds},
        "cells": cells,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_continual_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(json.dumps({"wrote": os.path.basename(path)}), flush=True)
    return 0


def weakscale_curve(shards=(1, 2, 4, 8), rows_per_shard=2048,
                    n_features=8, num_leaves=15, max_bin=63,
                    fused_iters=8, iters=16, reps=2,
                    telemetry_file=None):
    """Measure the weak-scaling curve of the SHARDED FUSED super-step:
    per-iteration time at a FIXED per-shard row count as the data-
    parallel mesh widens, with collective accounting and the device-
    call budget per iteration.  Shared by ``bench.py --weakscale-only``
    and ``tests/test_weak_scaling.py`` (one generator, one schema — the
    committed WEAKSCALE.json can never drift from the test's).

    Three series per point, because the dryrun mesh timeshares
    physical cores:

    - ``iter_s``              wall per iteration (the headline on real
      chips; on a virtual mesh with shards > cores it necessarily
      grows with the oversubscription factor),
    - ``cpu_s_per_shard_iter`` process-CPU seconds per shard per
      iteration — flat iff per-shard cost is O(1) in the mesh size
      (the dryrun-meaningful weak-scaling pin: the per-shard dispatch
      overhead WEAKSCALE measured through r05 made it grow with D),
    - ``device_calls_per_iter`` measured host->device dispatches per
      iteration (2/K for the fused scan at ANY mesh size, vs ~5 PER
      SHARD per iteration on the pre-refactor per-call path).

    ``shards == 1`` runs the serial learner (the 1-shard anchor);
    wider points run ``tree_learner=data`` over a mesh of the first D
    devices.  D=1 and D=8 at the same rows/shard is the acceptance
    comparison."""
    import time as _time

    import numpy as np
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops.grow import collective_bytes_per_pass
    from lightgbm_tpu.utils import telemetry as _telemetry

    rec = None
    if telemetry_file:
        rec = _telemetry.RunRecorder(
            str(telemetry_file),
            run_info={"backend": jax.default_backend(),
                      "bench": "weakscale"})
    avail = len(jax.devices())
    skipped = [D for D in shards if D > avail]
    live = [D for D in shards if D <= avail]
    boosters = {}
    for D in live:
        rng = np.random.RandomState(0)
        N = rows_per_shard * D
        X = rng.random_sample((N, n_features)).astype(np.float32)
        y = (X[:, 0] + 0.5 * (X[:, 1] > 0.5) +
             0.1 * rng.randn(N) > 0.7).astype(np.float32)
        params = {"objective": "binary", "num_leaves": num_leaves,
                  "max_bin": max_bin, "verbose": -1, "metric": "None",
                  "fused_iters": fused_iters,
                  # no tail block inside the measured window
                  "num_iterations": 1_000_000,
                  "tree_learner": "serial" if D == 1 else "data"}
        mesh = None
        if D > 1:
            mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:D]),
                                     ("shard",))
        d = lgb.Dataset(X, label=y, params=params)
        d.construct()
        bst = lgb.Booster(params=params, train_set=d, mesh=mesh)
        if rec is not None:
            bst._gbdt.attach_telemetry(rec)
        # warmup: bias iteration + TWO whole blocks — the first block
        # consumes the single-device score the unfused bias iteration
        # left behind and the second runs on the mesh-replicated carry,
        # so both XLA executables (same trace, different input
        # sharding) are compiled before the measured window
        for _ in range(1 + 2 * fused_iters):
            bst.update()
        boosters[D] = bst
    if rec is not None:
        # re-baseline every cell's counter snapshot AFTER all warmups:
        # the compile counters are process-wide, so without this the
        # first measured block of each cell would absorb the OTHER
        # cells' warmup compiles into its superstep record and read as
        # a retrace storm in triage
        for bst in boosters.values():
            bst._gbdt._tele_counters_last = \
                _telemetry.counters_snapshot()
    # interleaved reps (the docs/Benchmarks.md protocol discipline:
    # this container's clock jitters 20-40% minute to minute, so
    # back-to-back cells measure the machine, not the mesh size);
    # min-per-cell estimates each point's noise floor
    wall_min = {D: float("inf") for D in live}
    cpu_min = {D: float("inf") for D in live}
    calls = {D: 0.0 for D in live}
    for _ in range(reps):
        for D in live:
            bst = boosters[D]
            c0 = _telemetry.counters_snapshot()
            t0, p0 = _time.time(), _time.process_time()
            for _ in range(iters):
                bst.update()
            wall_min[D] = min(wall_min[D],
                              (_time.time() - t0) / iters)
            cpu_min[D] = min(cpu_min[D],
                             (_time.process_time() - p0) / iters)
            c1 = _telemetry.counters_snapshot()
            calls[D] += (c1.get("superstep_dispatches", 0) -
                         c0.get("superstep_dispatches", 0) +
                         c1.get("superstep_fetches", 0) -
                         c0.get("superstep_fetches", 0))
    curve = []
    for D in live:
        g = boosters[D]._gbdt
        # per-SHARD per-iteration collective estimate, mirroring the
        # superstep telemetry accounting (grow.py estimate x one pass
        # per split + the leaf-assignment gather's per-shard send)
        cb = co = 0
        if g._dist is not None:
            est = collective_bytes_per_pass(g._dist.params, g._F_pad,
                                            g._n_pad)
            passes = max(num_leaves, 1)
            cb = est["total"] * passes + \
                (g._n_pad // g._dist.num_shards) * 4
            co = est["ops"] * passes + 1
        curve.append({
            "shards": int(D),
            "rows_per_shard": int(rows_per_shard),
            "collective_bytes": int(cb),
            "collective_ops": int(co),
            "iter_s": round(wall_min[D], 4),
            "cpu_s_per_shard_iter": round(cpu_min[D] / D, 4),
            "device_calls_per_iter": round(calls[D] / (reps * iters),
                                           3),
        })
    if rec is not None:
        rec.close(log=False)
    cores = os.cpu_count() or 1
    pts = {c["shards"]: c for c in curve}
    lo, hi = min(pts), max(pts)
    out = {
        "metric": "weak_scaling_fixed_rows_per_shard",
        "learner": "data+fused_scan" if len(pts) > 1 else "serial",
        "fused_iters": int(fused_iters),
        "cores": int(cores),
        "source": "python bench.py --weakscale-only",
        "curve": curve,
    }
    if len(pts) > 1:
        out["flat_ratio_wall"] = round(
            pts[hi]["iter_s"] / max(pts[lo]["iter_s"], 1e-9), 3)
        out["flat_ratio_cpu_per_shard"] = round(
            pts[hi]["cpu_s_per_shard_iter"] /
            max(pts[lo]["cpu_s_per_shard_iter"], 1e-9), 3)
        sharded = sorted(d for d in pts if d > 1)
        if len(sharded) > 1:
            # the scaling-law ratio among SHARDED points: the 1-shard
            # anchor is the serial program (no collectives at all), so
            # lo->hi mixes the one-time serial->sharded collective
            # cost into the curve; widest-vs-narrowest MESH is the
            # per-shard-cost-O(1)-in-D pin proper
            out["flat_ratio_cpu_per_shard_sharded"] = round(
                pts[sharded[-1]]["cpu_s_per_shard_iter"] /
                max(pts[sharded[0]]["cpu_s_per_shard_iter"], 1e-9), 3)
        out["oversubscription"] = round(max(hi / cores, 1.0), 2)
        out["note"] = (
            "wall iter_s on a virtual CPU mesh timeshares "
            f"{hi} shards over {cores} core(s); the dryrun weak-"
            "scaling pin is cpu_s_per_shard_iter (per-shard cost flat "
            "in mesh size) and the flat device_calls_per_iter — wall "
            "flatness is only meaningful with one real device per "
            "shard")
    if skipped:
        out["skipped_shards"] = skipped
    return out


def weakscale_grid_2d(shapes=((1, 8), (2, 4), (4, 2), (8, 1)),
                      rows_per_shard=2048, n_features=8,
                      num_leaves=15, max_bin=63, fused_iters=8,
                      iters=8, reps=2, telemetry_file=None):
    """The SECOND weak-scaling axis: the 2-D ``data2d`` mesh grid at a
    FIXED total device count, sweeping how the devices factor into
    (data x feature) = RxF.  Fixed rows per ROW shard (total rows grow
    with R), so every cell moves the same per-device row block; what
    varies is the collective schedule — the "data"-axis histogram
    reduction shrinks as O(1/F) (each device reduces only its feature
    tile) while the "feature"-axis merge stays O(F) and its routing
    term shrinks as 1/R.  Shared by ``bench.py --weakscale-only`` and
    the CI mesh-smoke microbench (one generator, one schema).

    Per-cell series mirror :func:`weakscale_curve` (wall, per-shard
    CPU, measured device calls — flat at 2/K on every shape) plus the
    per-AXIS collective estimate the superstep telemetry carries
    (``collective_bytes_axis``), which is the acceptance series: the
    "data" entry must fall as 1/F across the grid row."""
    import time as _time

    import numpy as np
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops.grow import collective_bytes_per_pass
    from lightgbm_tpu.utils import telemetry as _telemetry

    rec = None
    if telemetry_file:
        rec = _telemetry.RunRecorder(
            str(telemetry_file),
            run_info={"backend": jax.default_backend(),
                      "bench": "weakscale2d"})
    avail = len(jax.devices())
    skipped = [list(s) for s in shapes if s[0] * s[1] > avail]
    live = [tuple(s) for s in shapes if s[0] * s[1] <= avail]
    boosters = {}
    for shape in live:
        R, F = shape
        rng = np.random.RandomState(0)
        N = rows_per_shard * R
        X = rng.random_sample((N, n_features)).astype(np.float32)
        y = (X[:, 0] + 0.5 * (X[:, 1] > 0.5) +
             0.1 * rng.randn(N) > 0.7).astype(np.float32)
        params = {"objective": "binary", "num_leaves": num_leaves,
                  "max_bin": max_bin, "verbose": -1, "metric": "None",
                  "fused_iters": fused_iters,
                  "num_iterations": 1_000_000,
                  "tree_learner": "data2d",
                  "mesh_shape": f"{R}x{F}",
                  "num_machines": R * F}
        d = lgb.Dataset(X, label=y, params=params)
        d.construct()
        bst = lgb.Booster(params=params, train_set=d)
        if rec is not None:
            bst._gbdt.attach_telemetry(rec)
        for _ in range(1 + 2 * fused_iters):   # bias + 2 warm blocks
            bst.update()
        boosters[shape] = bst
    if rec is not None:
        for bst in boosters.values():
            bst._gbdt._tele_counters_last = \
                _telemetry.counters_snapshot()
    wall_min = {s: float("inf") for s in live}
    cpu_min = {s: float("inf") for s in live}
    calls = {s: 0.0 for s in live}
    for _ in range(reps):                      # interleaved reps
        for shape in live:
            bst = boosters[shape]
            c0 = _telemetry.counters_snapshot()
            t0, p0 = _time.time(), _time.process_time()
            for _ in range(iters):
                bst.update()
            wall_min[shape] = min(wall_min[shape],
                                  (_time.time() - t0) / iters)
            cpu_min[shape] = min(cpu_min[shape],
                                 (_time.process_time() - p0) / iters)
            c1 = _telemetry.counters_snapshot()
            calls[shape] += (c1.get("superstep_dispatches", 0) -
                             c0.get("superstep_dispatches", 0) +
                             c1.get("superstep_fetches", 0) -
                             c0.get("superstep_fetches", 0))
    grid = []
    passes = max(num_leaves, 1)
    for shape in live:
        R, F = shape
        g = boosters[shape]._gbdt
        est = collective_bytes_per_pass(g._dist.params, g._F_pad,
                                        g._n_pad)
        ax_b = {a: int(v["bytes"] * passes)
                for a, v in est.get("per_axis", {}).items()}
        ax_o = {a: int(v["ops"] * passes)
                for a, v in est.get("per_axis", {}).items()}
        # the leaf-assignment gather rides the data axis
        ax_b["data"] = ax_b.get("data", 0) + \
            (g._n_pad // g._dist.row_shards) * 4
        ax_o["data"] = ax_o.get("data", 0) + 1
        grid.append({
            "shape": [int(R), int(F)],
            "shards": int(R * F),
            "rows_per_shard": int(rows_per_shard),
            "collective_bytes_axis": ax_b,
            "collective_ops_axis": ax_o,
            "iter_s": round(wall_min[shape], 4),
            "cpu_s_per_shard_iter": round(cpu_min[shape] / (R * F), 4),
            "device_calls_per_iter": round(
                calls[shape] / (reps * iters), 3),
        })
    if rec is not None:
        rec.close(log=False)
    cores = os.cpu_count() or 1
    total = live[0][0] * live[0][1] if live else 0
    out = {
        "metric": "weak_scaling_2d_mesh_grid",
        "learner": "data2d+fused_scan",
        "devices": int(total),
        "fused_iters": int(fused_iters),
        "cores": int(cores),
        "source": "python bench.py --weakscale-only",
        "grid": grid,
        "note": (
            "fixed devices, sweeping the (data x feature) factoring; "
            "the acceptance series is collective_bytes_axis['data'] "
            "falling as 1/F down the grid (each device reduces only "
            "its feature tile).  Wall iter_s on a virtual CPU mesh "
            f"timeshares {total} shards over {cores} core(s) — only "
            "the per-axis bytes and the flat device_calls_per_iter "
            "are dryrun-meaningful"),
    }
    if len(grid) > 1:
        # the 1/F acceptance pin, precomputed for the render/CI side:
        # data-axis bytes at the widest feature axis over the F=1
        # (pure-data-parallel schedule through the 2-D path) cell
        by_f = {c["shape"][1]: c["collective_bytes_axis"].get(
            "data", 0) for c in grid}
        f_lo, f_hi = min(by_f), max(by_f)
        if by_f[f_lo] > 0:
            out["data_axis_bytes_ratio"] = round(
                by_f[f_hi] / by_f[f_lo], 4)
            out["data_axis_ideal_ratio"] = round(f_lo / f_hi, 4)
    if skipped:
        out["skipped_shapes"] = skipped
    return out


def weakscale_only():
    """Fast path (``python bench.py --weakscale-only``): regenerate
    WEAKSCALE.json from the sharded fused super-step on a
    host-platform-device-count mesh (or real devices when present),
    plus a telemetry JSONL carrying the per-block collective counters
    for ``tools/triage_run.py``.  The 1-D curve is followed by the 2-D
    ``data2d`` (data x feature) grid at the full device count
    (``grid2d`` key).  ``tools/render_benchmarks.py`` renders the
    curve + ideal line + the 2-D table into docs/Benchmarks.md."""
    max_shards = int(os.environ.get("BENCH_WEAKSCALE_SHARDS", "8"))
    ensure_backend(force_host_devices=max_shards)
    from lightgbm_tpu.utils import telemetry as _telemetry
    _telemetry.install_jax_hooks()
    shards = tuple(d for d in (1, 2, 4, 8, 16, 32, 64, 128, 256)
                   if d <= max_shards)
    root = os.path.dirname(os.path.abspath(__file__))
    tele = os.environ.get("BENCH_WEAKSCALE_TELEMETRY",
                          os.path.join(root, "WEAKSCALE_telemetry.jsonl"))
    try:
        if tele and os.path.exists(tele):
            os.remove(tele)
    except OSError:
        tele = ""
    out = weakscale_curve(
        shards=shards,
        rows_per_shard=int(os.environ.get("BENCH_WEAKSCALE_ROWS",
                                          "2048")),
        iters=int(os.environ.get("BENCH_WEAKSCALE_ITERS", "16")),
        reps=int(os.environ.get("BENCH_WEAKSCALE_REPS", "3")),
        telemetry_file=tele or None)
    grid_n = min(max_shards, 8)
    shapes = tuple((r, grid_n // r)
                   for r in (1, 2, 4, 8) if grid_n % r == 0)
    out["grid2d"] = weakscale_grid_2d(
        shapes=shapes,
        rows_per_shard=int(os.environ.get("BENCH_WEAKSCALE_ROWS",
                                          "2048")),
        iters=int(os.environ.get("BENCH_WEAKSCALE_ITERS_2D", "8")),
        reps=int(os.environ.get("BENCH_WEAKSCALE_REPS", "3")),
        telemetry_file=tele or None)
    print(json.dumps(out), flush=True)
    path = os.environ.get("BENCH_WEAKSCALE_OUT",
                          os.path.join(root, "WEAKSCALE.json"))
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(json.dumps({"wrote": os.path.basename(path),
                      "telemetry": os.path.basename(tele) if tele
                      else None}), flush=True)
    return 0


def main():
    t_start = time.time()
    budget = float(os.environ.get("BENCH_TIME_BUDGET_S", "240"))
    n_rows = int(os.environ.get("BENCH_ROWS", str(N_ROWS)))
    n_meas = int(os.environ.get("BENCH_MEAS_ITERS", "20"))

    backend = ensure_backend()
    from lightgbm_tpu.utils import telemetry as _telemetry
    _telemetry.install_jax_hooks()   # compile/retrace counters
    cpu_smoke = backend == "cpu"
    if cpu_smoke:
        # CPU smoke mode: tiny shapes so the harness stays runnable
        # anywhere; the recorded number is only meaningful on TPU.
        # num_leaves/max_bin are clamped too — the 255-leaf wave
        # kernels take several hundred seconds of XLA CPU compile on
        # small hosts, which is pure harness overhead here
        n_rows = min(n_rows, 200_000)
        n_meas = min(n_meas, 5)

    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.metrics import AUCMetric
    from lightgbm_tpu.utils import profiling

    t0 = time.time()
    n_hold = 200_000
    X, y = make_higgs_shaped(n_rows + n_hold, N_FEATURES)
    X, Xh = X[:n_rows], X[n_rows:]
    y, yh = y[:n_rows], y[n_rows:]
    gen_s = time.time() - t0

    base_params = {
        "objective": "binary",
        "num_leaves": 63 if cpu_smoke else 255,
        "max_bin": 63 if cpu_smoke else 255,
        "learning_rate": 0.1,
        "min_sum_hessian_in_leaf": 100.0,
        "min_data_in_leaf": 0,
        "verbose": -1,
        "metric": "None",
    }
    # CPU smoke: the wave/quantized tier costs several minutes of XLA
    # CPU compile PER UPDATE on small hosts; the smoke's job is the
    # harness contract, so it runs the serial exact tier instead
    fast = {} if cpu_smoke else {"wave_splits": True,
                                 "use_quantized_grad": True}

    def auc_fn(bst):
        return round(AUCMetric(Config()).eval(
            np.asarray(yh, np.float64), bst.predict(Xh)), 4)

    trains = {}

    def train_for(max_bin):
        if max_bin not in trains:
            t1 = time.time()
            p = dict(base_params, max_bin=max_bin)
            d = lgb.Dataset(X, label=y, params=p)
            d.construct()
            trains[max_bin] = (d, time.time() - t1)
        return trains[max_bin][0]

    out = {
        "metric": "higgs_shape_train_time_500iter",
        "unit": "s",
        "backend": backend,
        "rows": n_rows,
        "projected": True,
        "datagen_s": round(gen_s, 2),
    }

    # structured run telemetry for the PRIMARY variant: the JSONL is
    # the round's attributable-time artifact (tools/triage_run.py);
    # BENCH_TELEMETRY=0 disables, a path overrides the default
    tele_file = os.environ.get("BENCH_TELEMETRY", "")
    if tele_file != "0":
        tele_file = tele_file or os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_telemetry.jsonl")
        try:                         # fresh file per bench run
            if os.path.exists(tele_file):
                os.remove(tele_file)
        except OSError:
            tele_file = ""
    else:
        tele_file = ""
    if tele_file:
        out["telemetry_file"] = os.path.basename(tele_file)

    # ---- PRIMARY: wave + quantized at the reference's 255 bins ------
    # (CPU smoke runs serial exact at 63 bins — label it honestly so
    # recorded JSON never passes a smoke row off as a wave255 number)
    primary = "smoke63" if cpu_smoke else "wave255"
    out["primary_variant"] = primary
    mb_primary = base_params["max_bin"]
    train255 = train_for(mb_primary)
    out["binning_s"] = round(trains[mb_primary][1], 2)
    kept = {}
    p_primary = dict(base_params, **fast)
    if tele_file:
        p_primary["telemetry_file"] = tele_file
    res = run_variant(lgb, p_primary, train255, n_meas,
                      auc_fn, profiling,
                      diagnose_fetch=backend != "cpu", keep=kept)
    out.update({f"{primary}_{k}": v for k, v in res.items()
                if k not in ("phase_ms_per_iter",)})
    out["phase_ms_per_iter"] = res.get("phase_ms_per_iter", {})
    out["value"] = res["projected_500iter_s"]
    out["vs_baseline"] = round(res["projected_500iter_s"] / BASELINE_S, 4)
    out["iters_per_s"] = res["iters_per_s"]
    out["measured_iters"] = res["measured_iters"]
    out["auc_holdout"] = res["auc_holdout"]
    summ = kept["booster"]._gbdt.telemetry_summary()
    if summ:
        out["telemetry_summary"] = {
            k: summ[k] for k in
            ("iterations", "xla_compiles", "xla_compile_secs",
             "jax_traces", "hist_passes", "tier")
            if k in summ}
    print(json.dumps(out), flush=True)

    # ---- batch inference: flattened engine vs per-tree host loop ----
    try:
        out.update(bench_predict(kept["booster"], Xh))
    except Exception as exc:      # the training result must survive
        out["predict_bench_error"] = str(exc)[:200]
    print(json.dumps(out), flush=True)

    # ---- online serving: micro-batching scheduler over the engine ---
    # (p50/p99 request latency, rows/s, batch occupancy, plus one
    # mid-run hot-swap republishing the primary booster; the compile
    # counter pins the zero-steady-state-compile serving contract.
    # The standalone matrix is `bench.py --serve-only` ->
    # BENCH_serve_cpu.json)
    if os.environ.get("BENCH_SERVE", "1") != "0":
        try:
            res = bench_serve(
                kept["booster"], N_FEATURES,
                swap_booster=kept["booster"],
                n_requests=100 if cpu_smoke else 400,
                rows_max=300 if cpu_smoke else 900)
            out.update({f"serve_{k}": v for k, v in res.items()
                        if k != "errors"})
        except Exception as exc:  # the training result must survive
            out["serve_bench_error"] = str(exc)[:200]
        print(json.dumps(out), flush=True)

    # ---- fused super-steps: K iterations per device dispatch --------
    # (runs on the CPU smoke too — the fused-vs-unfused pair is the
    # in-repo microbench for the scan path; the unfused pair member is
    # the primary row above.  measured_xla_compiles pins that the scan
    # compiled once: repeated same-K blocks in the measured window
    # must re-run the cached program)
    if os.environ.get("BENCH_FUSED", "1") != "0":
        try:
            fk = int(os.environ.get("BENCH_FUSED_ITERS",
                                    "4" if cpu_smoke else "8"))
            # accelerator: cover >= 2 whole blocks; CPU smoke: one
            # block (the contract run — budget counters + flat
            # compiles — not a speed number at smoke shapes)
            n_f = fk if cpu_smoke else max(n_meas, 2 * fk)
            res = run_variant(lgb, dict(base_params, **fast,
                                        fused_iters=fk,
                                        num_iterations=N_ITERS),
                              train255, n_f, auc_fn)
            # the MEDIAN update of a fused run is a microsecond queue
            # serve, not an iteration: suppress the median-derived
            # keys (an absurd iters_per_s next to the honest
            # amortized one would poison any cross-variant consumer)
            out.update({f"fused{fk}_{k}": v for k, v in res.items()
                        if k not in ("iters_per_s", "best_iter_s",
                                     "best_projected_s",
                                     "projected_500iter_s")})
            # block-amortized projection instead
            out[f"fused{fk}_projected_500iter_s"] = round(
                res["warmup_compile_s"] +
                res["mean_iter_s"] * (N_ITERS - WARMUP), 2)
            out[f"fused{fk}_iters_per_s_amortized"] = round(
                1.0 / max(res["mean_iter_s"], 1e-9), 4)
            base_mean = out.get(f"{primary}_mean_iter_s")
            if base_mean:
                out["fused_vs_unfused_iter_ratio"] = round(
                    base_mean / max(res["mean_iter_s"], 1e-9), 3)
        except Exception as exc:  # the primary result must survive
            out["fused_error"] = str(exc)[:200]
        print(json.dumps(out), flush=True)

    # ---- exact best-first at 255 bins: the AUC anchor ---------------
    # (CPU smoke mode runs the primary only — each variant costs an
    # XLA compile that dwarfs the tiny-shape training)
    if backend != "cpu" and \
            os.environ.get("BENCH_SKIP_EXACT", "") != "1" and \
            time.time() - t_start < 3 * budget:
        try:
            res = run_variant(lgb, base_params, train255, n_meas, auc_fn)
            out.update({f"exact255_{k}": v for k, v in res.items()})
            # iteration-matched quality delta of the wave redesign
            if out.get("wave255_auc_holdout") is not None and \
                    res.get("auc_holdout") is not None:
                out["wave_vs_exact_auc_delta"] = round(
                    out["wave255_auc_holdout"] - res["auc_holdout"], 4)
        except Exception as exc:  # the primary result must survive
            out["exact255_error"] = str(exc)[:200]
        print(json.dumps(out), flush=True)

    # ---- the reference's GPU-comparison config: 63 bins -------------
    if backend != "cpu" and \
            os.environ.get("BENCH_SKIP_63", "") != "1" and \
            time.time() - t_start < 4 * budget:
        try:
            train63 = train_for(63)
            res = run_variant(lgb, dict(base_params, max_bin=63, **fast),
                              train63, n_meas, auc_fn)
            out.update({f"wave63_{k}": v for k, v in res.items()})
            out["bins63_projected_500iter_s"] = \
                res["projected_500iter_s"]
            out["bins63_vs_baseline"] = round(
                res["projected_500iter_s"] / BASELINE_S, 4)
        except Exception as exc:
            out["wave63_error"] = str(exc)[:200]
        print(json.dumps(out), flush=True)

    # ---- optional: 15 bins (GPU doc's speed-leaning point) ----------
    if backend != "cpu" and os.environ.get("BENCH_15", "") == "1":
        try:
            train15 = train_for(15)
            res = run_variant(lgb, dict(base_params, max_bin=15, **fast),
                              train15, n_meas, auc_fn)
            out.update({f"wave15_{k}": v for k, v in res.items()})
        except Exception as exc:
            out["wave15_error"] = str(exc)[:200]

    # ---- optional: GOSS sampling overhead (device-side masks) -------
    if backend != "cpu" and os.environ.get("BENCH_GOSS", "") == "1":
        try:
            res = run_variant(
                lgb, dict(base_params, boosting="goss", **fast),
                train255, n_meas, auc_fn)
            out.update({f"goss255_{k}": v for k, v in res.items()})
            out["goss_vs_gbdt_iter_ratio"] = round(
                out["wave255_iters_per_s"] / max(res["iters_per_s"],
                                                 1e-9), 3)
        except Exception as exc:
            out["goss_error"] = str(exc)[:200]

    # ---- Epsilon-shaped wide data (400K x 2000, sparse CSR ingest) --
    # exercises the histogram kernel's feature-chunked grid at 70x
    # Higgs width plus the chunked sparse ingest path
    # (docs/GPU-Performance.rst:141); runs by default when the budget
    # allows, BENCH_WIDE=0 disables / =1 forces
    wide_flag = os.environ.get("BENCH_WIDE", "")
    if backend != "cpu" and wide_flag != "0" and \
            (wide_flag == "1" or time.time() - t_start < 5 * budget):
        try:
            import scipy.sparse as sp_mod
            rng = np.random.RandomState(7)
            n_w, f_w = 400_000, 2000
            # chunked generation + sparsification: bounds the transient
            # mask/randoms to chunk size (a full (n,f) f64 mask is
            # ~6.4 GB)
            Xw = np.empty((n_w, f_w), dtype=np.float32)
            chunk_w = 50_000
            for lo in range(0, n_w, chunk_w):
                hi = min(lo + chunk_w, n_w)
                blk = rng.randn(hi - lo, f_w).astype(np.float32)
                blk[rng.random_sample((hi - lo, f_w)) >= 0.25] = 0.0
                Xw[lo:hi] = blk
            yw = (Xw[:, :8].sum(axis=1) + 0.5 * rng.randn(n_w) > 0
                  ).astype(np.float32)
            pw = dict(base_params, max_bin=63, **fast)
            dw = lgb.Dataset(sp_mod.csr_matrix(Xw), label=yw, params=pw)
            dw.construct()
            bw = lgb.Booster(params=pw, train_set=dw)
            bw.update()
            bw.update()
            t0 = time.time()
            times_w = []
            # at least 5 samples even past the time cap: a single
            # outlier iteration (one recompile / device hiccup) must
            # not become "the median of one"
            while len(times_w) < 20 and (time.time() - t0 < 60 or
                                         len(times_w) < 5):
                t1 = time.time()
                bw.update()
                times_w.append(time.time() - t1)
            if times_w:
                perw = sorted(times_w)[len(times_w) // 2]
                out["epsilon_shape_iters_per_s"] = round(1.0 / perw, 4)
                out["epsilon_shape_samples"] = len(times_w)
        except Exception as exc:
            out["epsilon_shape_error"] = str(exc)[:200]
        print(json.dumps(out), flush=True)

    # ---- missing + categorical Higgs-shape --------------------------
    # real-world data shapes carry NaNs and categorical columns; the
    # fast tiers must stay engaged there (VERDICT r4 #2).  10% NaN
    # over the same Higgs-shaped numericals; the categorical variant
    # additionally remaps 4 columns to 12-level categories (wave +
    # quantized, W=42 tier — categorical scans need real counts)
    if backend != "cpu" and os.environ.get("BENCH_MISSING", "1") != "0" \
            and time.time() - t_start < 5.5 * budget:
        try:
            rngm = np.random.RandomState(29)
            Xm_ = X.copy()
            # chunked NaN injection bounds the transient mask memory
            for lo_ in range(0, Xm_.shape[0], 1_000_000):
                hi_ = min(lo_ + 1_000_000, Xm_.shape[0])
                blk_ = rngm.random_sample((hi_ - lo_, Xm_.shape[1]))
                Xm_[lo_:hi_][blk_ < 0.10] = np.nan
            pm_ = dict(base_params, **fast)
            dm_ = lgb.Dataset(Xm_, label=y, params=pm_)
            dm_.construct()
            bm_ = lgb.Booster(params=pm_, train_set=dm_)
            bm_.update(); bm_.update()
            gpm = bm_._gbdt.grow_params
            out["missing_shape_tiers"] = {
                "wave": bool(gpm.wave), "quantize": int(gpm.quantize),
                "two_col": bool(gpm.two_col),
                "refine_shift": int(gpm.refine_shift)}
            times_n = []
            t0 = time.time()
            while len(times_n) < 15 and (time.time() - t0 < 60 or
                                         len(times_n) < 5):
                t1 = time.time(); bm_.update()
                times_n.append(time.time() - t1)
            pern = sorted(times_n)[len(times_n) // 2]
            out["missing_shape_iters_per_s"] = round(1.0 / pern, 4)
            if out.get("iters_per_s"):
                out["missing_vs_headline_ratio"] = round(
                    out["iters_per_s"] / (1.0 / pern), 3)
            del bm_, dm_
            # categorical variant: 4 columns -> 12-level categories
            Xc_ = Xm_
            for c in range(4):
                Xc_[:, c] = np.floor(
                    np.abs(np.nan_to_num(Xc_[:, c])) * 4) % 12
            pc_ = dict(base_params, **fast,
                       categorical_feature="0,1,2,3")
            dc_ = lgb.Dataset(Xc_, label=y, params=pc_,
                              categorical_feature=[0, 1, 2, 3])
            dc_.construct()
            bc_ = lgb.Booster(params=pc_, train_set=dc_)
            bc_.update(); bc_.update()
            gpc = bc_._gbdt.grow_params
            assert gpc.split.any_cat and gpc.wave and gpc.quantize > 0
            times_c = []
            t0 = time.time()
            while len(times_c) < 12 and (time.time() - t0 < 60 or
                                         len(times_c) < 4):
                t1 = time.time(); bc_.update()
                times_c.append(time.time() - t1)
            perc = sorted(times_c)[len(times_c) // 2]
            out["missing_cat_shape_iters_per_s"] = round(1.0 / perc, 4)
            del bc_, dc_, Xm_, Xc_
        except Exception as exc:
            out["missing_shape_error"] = str(exc)[:200]
        print(json.dumps(out), flush=True)

    # ---- reference-DEFAULT learning-control config ------------------
    # the headline rides min_data_in_leaf=0 (two_col W=64 tier); a user
    # keeping the reference default (min_data_in_leaf=20, config.h) gets
    # the W=42 quantized tier — report it so the headline is
    # reproducible by a default user (docs/Design.md fast-path tiering)
    if backend != "cpu" and os.environ.get("BENCH_DEFAULTCFG", "1") != "0" \
            and time.time() - t_start < 6 * budget:
        try:
            res = run_variant(
                lgb, dict(base_params, min_data_in_leaf=20, **fast),
                train255, max(n_meas // 2, 8), auc_fn)
            out.update({f"default255_{k}": v for k, v in res.items()})
        except Exception as exc:
            out["default255_error"] = str(exc)[:200]
        print(json.dumps(out), flush=True)

    # ---- ranking: MS-LTR-shaped lambdarank --------------------------
    # reference speed table row: MS-LTR 2.27M x 136, 10K queries,
    # 215.3 s / 500 iters (Experiments.rst:104-143)
    if backend != "cpu" and os.environ.get("BENCH_RANK", "1") != "0" \
            and time.time() - t_start < 7 * budget:
        try:
            from lightgbm_tpu.metrics import NDCGMetric
            rng = np.random.RandomState(11)
            n_r, f_r, docs_per_q = 2_270_000, 136, 227
            n_r = (n_r // docs_per_q) * docs_per_q
            Xr = rng.randn(n_r, f_r).astype(np.float32)
            rel = Xr[:, 0] + 0.5 * Xr[:, 1] + 0.8 * rng.randn(n_r)
            yr = np.clip(np.digitize(
                rel, np.percentile(rel, [60, 80, 92, 98])), 0, 4
            ).astype(np.float32)
            groups = np.full(n_r // docs_per_q, docs_per_q, np.int64)
            pr = dict(base_params, objective="lambdarank",
                      metric="ndcg", eval_at=[1, 3, 5, 10],
                      num_leaves=255, **fast)
            dr = lgb.Dataset(Xr, label=yr, group=groups, params=pr)
            dr.construct()
            br = lgb.Booster(params=pr, train_set=dr)
            br.update(); br.update()
            times_r = []
            t0 = time.time()
            while len(times_r) < 12 and (time.time() - t0 < 90 or
                                         len(times_r) < 4):
                t1 = time.time(); br.update()
                times_r.append(time.time() - t1)
            perr = sorted(times_r)[len(times_r) // 2]
            out["msltr_shape_iters_per_s"] = round(1.0 / perr, 4)
            out["msltr_shape_projected_500iter_s"] = round(500 * perr, 1)
            out["msltr_shape_rows"] = n_r
            # NDCG@{1,3,5,10} sanity on a 200-query train subset (the
            # synthetic relevances make absolute values incomparable to
            # MS-LTR; this pins that ranking learning happened at all)
            n_sub = 200 * docs_per_q
            cfg_r = Config()
            cfg_r.eval_at = [1, 3, 5, 10]
            nd = NDCGMetric(cfg_r)
            qb = np.arange(0, n_sub + 1, docs_per_q)
            pred_sub = br.predict(Xr[:n_sub], raw_score=True)
            for (name, val) in nd.eval_all(
                    yr[:n_sub].astype(np.float64), pred_sub,
                    query_boundaries=qb):
                out[f"msltr_shape_{name.replace('@', '_at_')}"] = \
                    round(float(val), 4)
        except Exception as exc:
            out["msltr_shape_error"] = str(exc)[:200]
        print(json.dumps(out), flush=True)

    # ---- sparse one-hot + EFB (Allstate/Expo-like) ------------------
    # reference rows: Allstate 13M x 4228 one-hot, Expo 11M x 700
    # (Experiments.rst:42-61); scaled shape, EFB actually engaged
    if backend != "cpu" and os.environ.get("BENCH_EFB", "1") != "0" \
            and time.time() - t_start < 8 * budget:
        try:
            import scipy.sparse as sp_mod
            rng = np.random.RandomState(13)
            n_e, n_cats = 1_000_000, 40
            # 40 categorical columns one-hot encoded at ~16 levels each
            # -> 640 mutually-exclusive-in-blocks indicator columns
            levels = rng.randint(8, 25, size=n_cats)
            cols, rows_idx = [], []
            col0 = 0
            data_cols = []
            for c, L in enumerate(levels):
                v = rng.randint(0, L, size=n_e)
                rows_idx.append(np.arange(n_e))
                cols.append(col0 + v)
                col0 += L
            f_e = int(col0)
            ridx = np.concatenate(rows_idx)
            cidx = np.concatenate(cols)
            Xe = sp_mod.csr_matrix(
                (np.ones(ridx.size, np.float32), (ridx, cidx)),
                shape=(n_e, f_e))
            ye = (rng.random_sample(n_e) <
                  1 / (1 + np.exp(-(Xe[:, :40].toarray().sum(1).ravel()
                                    - 1)))).astype(np.float32)
            pe = dict(base_params, max_bin=63, enable_bundle=True)
            de = lgb.Dataset(Xe, label=ye, params=pe)
            t0 = time.time(); de.construct()
            out["allstate_shape_binning_s"] = round(time.time() - t0, 2)
            be = lgb.Booster(params=pe, train_set=de)
            be.update(); be.update()
            times_e = []
            t0 = time.time()
            while len(times_e) < 12 and (time.time() - t0 < 90 or
                                         len(times_e) < 4):
                t1 = time.time(); be.update()
                times_e.append(time.time() - t1)
            pere = sorted(times_e)[len(times_e) // 2]
            out["allstate_shape_iters_per_s"] = round(1.0 / pere, 4)
            out["allstate_shape_cols"] = f_e
            bun = be._gbdt._bundles
            out["allstate_shape_efb_groups"] = (
                int(bun.num_groups) if bun is not None else f_e)
        except Exception as exc:
            out["allstate_shape_error"] = str(exc)[:200]
        print(json.dumps(out), flush=True)

    # ---- multiclass ------------------------------------------------
    if backend != "cpu" and os.environ.get("BENCH_MULTI", "1") != "0" \
            and time.time() - t_start < 9 * budget:
        try:
            rng = np.random.RandomState(17)
            n_m, f_m, k_m = 1_000_000, 28, 5
            Xm = rng.randn(n_m, f_m).astype(np.float32)
            logits = Xm[:, :k_m] + 0.5 * rng.randn(n_m, k_m)
            ym = logits.argmax(axis=1).astype(np.float32)
            pm = dict(base_params, objective="multiclass",
                      num_class=k_m, num_leaves=63, **fast)
            dm = lgb.Dataset(Xm, label=ym, params=pm)
            dm.construct()
            bm = lgb.Booster(params=pm, train_set=dm)
            bm.update(); bm.update()
            times_m = []
            t0 = time.time()
            while len(times_m) < 10 and (time.time() - t0 < 90 or
                                         len(times_m) < 4):
                t1 = time.time(); bm.update()
                times_m.append(time.time() - t1)
            perm = sorted(times_m)[len(times_m) // 2]
            out["multiclass_shape_iters_per_s"] = round(1.0 / perm, 4)
        except Exception as exc:
            out["multiclass_shape_error"] = str(exc)[:200]

    # ---- device memory ---------------------------------------------
    # reference GPU row: <= ~1 GB device memory for its largest run
    # (GPU-Performance.rst:186-189).  The CPU backend reports none.
    import jax as _jax
    stats = _jax.local_devices()[0].memory_stats() or {}
    for k_src, k_dst in (("peak_bytes_in_use", "peak"),
                         ("bytes_in_use", "in_use"),
                         ("bytes_limit", "limit")):
        if k_src in stats:
            out[f"device_memory_{k_dst}_gb"] = round(
                stats[k_src] / 1e9, 3)

    # flush run_end into the telemetry JSONL
    rec = kept["booster"]._gbdt._telemetry
    if rec is not None:
        rec.close(log=False)
    print(json.dumps(out))
    failed = sorted(k for k in out if k.endswith("_error"))
    if failed:
        print("bench.py: failed phases: " + ", ".join(failed),
              file=sys.stderr)
    return 1 if failed else 0


def ingest_only():
    """Fast path (``python bench.py --ingest-only``): measure the
    out-of-core streamed ingest's cost envelope on the CPU backend
    and write BENCH_ingest_cpu.json — streamed bin-pass throughput,
    cache write/load (verify) bandwidth, prefetch overlap fraction of
    the double-buffered host->device upload, and streamed-vs-resident
    train wall on the CPU smoke shape (docs/Streaming.md)."""
    import datetime
    import tempfile

    ensure_backend()
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.io.cache import chunk_grid
    from lightgbm_tpu.io.stream import BlockFetcher
    from lightgbm_tpu.utils import telemetry as _telemetry
    _telemetry.install_jax_hooks()

    n_rows = int(os.environ.get("BENCH_INGEST_ROWS", "120000"))
    n_features = 28
    rounds = int(os.environ.get("BENCH_INGEST_ROUNDS", "10"))
    chunk = int(os.environ.get("BENCH_INGEST_CHUNK", "16000"))
    rng = np.random.RandomState(0)
    X = rng.randn(n_rows, n_features)
    w = rng.randn(n_features)
    y = (1.0 / (1.0 + np.exp(-(X @ w) * 0.5)) >
         rng.random_sample(n_rows)).astype(np.float32)
    raw_mb = X.nbytes / 1e6

    base = {"objective": "binary", "num_leaves": 31, "verbose": -1,
            "metric": "None", "num_iterations": rounds,
            "fused_iters": 4}
    cells = {}
    with tempfile.TemporaryDirectory() as td:
        stem = os.path.join(td, "raw")
        np.save(stem + ".X.npy", X)
        np.save(stem + ".y.npy", y)
        cache = os.path.join(td, "cache")
        p = dict(base, stream_ingest=True, stream_cache_dir=cache,
                 stream_chunk_rows=chunk)

        # -- bin pass (fresh ingest, mmap source -> sealed cache) ----
        t0 = time.time()
        d1 = lgb.Dataset(stem + ".X.npy", params=p)
        d1.construct()
        bin_wall = time.time() - t0
        info = d1._constructed.stream
        binned_mb = np.asarray(d1._constructed.binned).nbytes / 1e6
        cells["bin_pass"] = {
            "wall_s": round(bin_wall, 3),
            "raw_mb": round(raw_mb, 2),
            "raw_mb_per_s": round(raw_mb / max(bin_wall, 1e-9), 2),
            "cache_write_mb": round(binned_mb, 2),
            "cache_write_mb_per_s": round(
                binned_mb / max(bin_wall, 1e-9), 2),
            "chunks": len(chunk_grid(n_rows, info.chunk_rows)),
        }

        # -- cache load (sealed reopen + full sha256 verify) ---------
        t0 = time.time()
        d2 = lgb.Dataset(stem + ".X.npy", params=p)
        d2.construct()
        load_wall = time.time() - t0
        assert d2._constructed.stream.from_cache
        cells["cache_load"] = {
            "wall_s": round(load_wall, 3),
            "verify_mb_per_s": round(
                binned_mb / max(load_wall, 1e-9), 2)}

        # -- double-buffered upload: prefetch on vs off --------------
        window = int(os.environ.get("BENCH_INGEST_WINDOW", "8000"))
        binned = d2._constructed.binned
        up = {}
        for label, pf in (("prefetch_on", True), ("prefetch_off",
                                                  False)):
            f = BlockFetcher(binned, n_rows=n_rows,
                             n_pad=n_rows + (-n_rows) % 8,
                             out_cols=n_features, window_rows=window,
                             prefetch=pf)
            buf = f.upload()
            buf.block_until_ready()
            up[label] = f.stats()
        cells["upload"] = {
            "windows": up["prefetch_on"]["windows"],
            "window_rows": window,
            "bytes_mb": round(up["prefetch_on"]["bytes"] / 1e6, 2),
            "on_ms": up["prefetch_on"]["duration_ms"],
            "off_ms": up["prefetch_off"]["duration_ms"],
            "overlap_s": up["prefetch_on"]["overlap_s"],
            "overlap_fraction": round(
                up["prefetch_on"]["overlap_s"] /
                max(up["prefetch_on"]["prep_s"], 1e-9), 3)}

        # -- streamed vs resident train wall -------------------------
        t0 = time.time()
        lgb.train(dict(p), d2, verbose_eval=False)
        streamed_wall = time.time() - t0
        d0 = lgb.Dataset(X, label=y, params=dict(base))
        t0 = time.time()
        lgb.train(dict(base), d0, verbose_eval=False)
        resident_wall = time.time() - t0
        cells["train"] = {
            "rounds": rounds,
            "streamed_wall_s": round(streamed_wall, 3),
            "resident_wall_s": round(resident_wall, 3),
            "streamed_over_resident": round(
                streamed_wall / max(resident_wall, 1e-9), 3)}
        print(json.dumps({"ingest_cells": cells}), flush=True)

    out = {
        "metric": "streamed_ingest_cpu",
        "unit": "mixed",
        "backend": "cpu",
        "date": datetime.date.today().isoformat(),
        "source": "JAX_PLATFORMS=cpu python bench.py --ingest-only",
        "env": "2-core CPU container",
        "forest": (f"31-leaf binary forest, {n_rows} x {n_features} "
                   f"train matrix, {rounds} iterations, "
                   f"{chunk}-row ingest chunks"),
        "config": {"rows": n_rows, "features": n_features,
                   "rounds": rounds, "chunk_rows": chunk},
        "cells": cells,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_ingest_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(json.dumps({"wrote": os.path.basename(path)}), flush=True)
    return 0


def paged_only():
    """Fast path (``python bench.py --paged-only``): measure the
    device-block pager's cost envelope on the CPU backend and write
    BENCH_paged_cpu.json — resident-vs-paged train wall at two page
    geometries (explicit ``paged_page_rows`` and ``hbm_budget_mb``
    auto), the prefetch overlap fraction, and the device-call budget
    re-pin from ``tools/prof_superstep.measure_paged`` (page serves
    are pure_callbacks inside the compiled scan, so the fused
    super-step stays at 2 host->device calls per K-block at any page
    count).  Acceptance pins: the paged model is BYTE-IDENTICAL to
    the resident one, pages actually flowed, and the budget held.

    Honest caveat (recorded in the artifact): on this 2-core CPU
    container host RAM backs both the "device" buffers and the page
    store, so page prep is a near-free memcpy — the paged slowdown
    prices the pure_callback serve machinery, not real HBM<->host
    bandwidth, and the overlap numbers are milliseconds of trivially
    cheap prep, not the transfer walls the prefetch thread exists to
    hide.  The TPU-side point of the pager (training sets larger
    than HBM) is the ROADMAP real-hardware item."""
    import datetime

    ensure_backend()
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils import telemetry as _telemetry
    _telemetry.install_jax_hooks()

    n_rows = int(os.environ.get("BENCH_PAGED_ROWS", "60000"))
    n_features = 28
    rounds = int(os.environ.get("BENCH_PAGED_ROUNDS", "10"))
    rng = np.random.RandomState(0)
    X = rng.randn(n_rows, n_features).astype(np.float32)
    w = rng.randn(n_features).astype(np.float32)
    y = (1.0 / (1.0 + np.exp(-(X @ w) * 0.5)) >
         rng.random_sample(n_rows)).astype(np.float32)
    base = {"objective": "binary", "num_leaves": 31, "verbose": -1,
            "metric": "None", "num_iterations": rounds,
            "fused_iters": 4}

    def run_cell(label, extra):
        p = dict(base, **extra)
        d = lgb.Dataset(X, label=y, params=p)
        d.construct()
        binned_mb = np.asarray(d._constructed.binned).nbytes / 1e6
        t0 = time.time()
        bst = lgb.train(p, d, verbose_eval=False)
        wall = time.time() - t0
        g = bst._gbdt
        cell = {"label": label, "rounds": rounds,
                "wall_s": round(wall, 3),
                "binned_mb": round(binned_mb, 2)}
        pager = getattr(g, "_pager", None)
        if pager is not None:
            s = pager.stats()
            busy = s["overlap_s"] + s["wait_s"]
            cell.update({
                "page_rows": int(s["page_rows"]),
                "n_pages": int(s["n_pages"]),
                "pages_served": int(s["pages"]),
                "paged_mb": round(s["bytes"] / 1e6, 2),
                "prefetch_hits": int(s["prefetch_hits"]),
                "stalls": int(s["stalls"]),
                "overlap_s": round(s["overlap_s"], 4),
                "wait_s": round(s["wait_s"], 4),
                # fraction of page-prep wall absorbed by the prefetch
                # thread instead of stalling the serve callback
                "overlap_fraction": round(
                    s["overlap_s"] / max(busy, 1e-9), 3),
            })
        rec = getattr(g, "_telemetry", None)
        if rec is not None:
            rec.close(log=False)
        model = bst.model_to_string()
        print(json.dumps({"paged_cell": label,
                          **{k: v for k, v in cell.items()
                             if k != "label"}}), flush=True)
        return cell, model

    cells = []
    resident_cell, resident_model = run_cell("resident", {})
    cells.append(resident_cell)
    page_rows = int(os.environ.get("BENCH_PAGED_PAGE_ROWS",
                                   str(max(n_rows // 8, 1))))
    paged_cell, paged_model = run_cell(
        f"paged page_rows={page_rows}",
        {"paged_training": "on", "paged_page_rows": page_rows})
    cells.append(paged_cell)
    # auto lane: a budget sized to ~1/4 of the binned matrix must
    # trigger paging on its own and land the same model bytes
    budget_mb = max(resident_cell["binned_mb"] / 4.0, 0.001)
    auto_cell, auto_model = run_cell(
        f"paged auto hbm_budget_mb={budget_mb:.2f}",
        {"paged_training": "auto", "hbm_budget_mb": budget_mb})
    cells.append(auto_cell)
    for c in cells[1:]:
        c["wall_over_resident"] = round(
            c["wall_s"] / max(resident_cell["wall_s"], 1e-9), 3)

    # device-call budget re-pin (hard-asserts inside): 2 calls per
    # K-block at every page count — recorded in THIS artifact per the
    # ISSUE acceptance, same numbers prof_superstep.py pins
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    from prof_superstep import measure_paged
    budget = measure_paged(reps=3)
    print(json.dumps({"paged_budget": {
        "budget_ok_at_all_page_counts":
            budget["budget_ok_at_all_page_counts"],
        "page_counts": [c["n_pages"] for c in budget["cells"]],
    }}), flush=True)

    pins = {
        "byte_identical_paged_vs_resident":
            paged_model == resident_model,
        "byte_identical_auto_vs_resident":
            auto_model == resident_model,
        "auto_lane_paged": auto_cell.get("n_pages", 0) >= 3,
        "pages_served_nonzero":
            paged_cell.get("pages_served", 0) > 0,
        "device_call_budget_2_per_block":
            budget["budget_ok_at_all_page_counts"],
    }
    out = {
        "metric": "paged_training_cpu",
        "unit": "s",
        "backend": "cpu",
        "date": datetime.date.today().isoformat(),
        "source": "JAX_PLATFORMS=cpu python bench.py --paged-only",
        "env": "2-core CPU container",
        "forest": (f"31-leaf binary forest, {n_rows} x {n_features} "
                   f"train matrix, {rounds} iterations, fused_iters=4"),
        "note": "CPU numbers price the pure_callback serve machinery "
                "only — host RAM backs both sides on this 2-core "
                "container, so page prep is a near-free memcpy and "
                "the overlap columns are milliseconds of trivially "
                "cheap prep, not the HBM<->host transfer walls the "
                "prefetch thread exists to hide; the HBM-ceiling win "
                "is the ROADMAP real-hardware item",
        "config": {"rows": n_rows, "features": n_features,
                   "rounds": rounds, "page_rows": page_rows,
                   "auto_hbm_budget_mb": round(budget_mb, 3)},
        "cells": cells,
        "device_call_budget": budget,
        "pins": pins,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_paged_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(json.dumps({"wrote": os.path.basename(path),
                      "pins": pins}), flush=True)
    return 0 if all(pins.values()) else 1


_SWEEP_SOLO_DRIVER = """\
import json, sys
import numpy as np
import lightgbm_tpu as lgb
z = np.load(sys.argv[1])
params = json.loads(sys.argv[2])
d = lgb.Dataset(z["X"], label=z["y"], free_raw_data=False)
lgb.train(params, d, verbose_eval=False)
"""


def sweep_only():
    """Fast path (``python bench.py --sweep-only``): measure the
    vmapped booster battery (models/battery.py) against B sequential
    solo trainings and write BENCH_sweep_cpu.json — one cell per
    battery width B, with a models/s column for both lanes.  Every
    member varies only traced per-model params (learning rate +
    bagging seed), so each battery is ONE compiled program however
    wide it is.

    Two baselines, both reported:

    - ``solo_proc``: one training per process — how sequential sweep
      drivers actually run trainings, each paying JAX init + its own
      compiles.  The battery amortizes exactly those costs, so this is
      the headline ``speedup`` (the acceptance bar: B=16 battery wall
      < 0.5x of 16 sequential solo trainings).
    - ``solo_warm``: an in-process loop sharing one warm compile
      cache — the floor a perfectly-cached sequential driver could
      hit.  On a 1-core CPU the device compute is the same work
      either way, so ``speedup_warm`` hovers near 1 there and the
      battery's device-side win only appears with real accelerators
      (dispatch amortization + the model axis on spare devices)."""
    import datetime
    import tempfile

    backend = ensure_backend()
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.models import battery as battery_mod
    from lightgbm_tpu.utils import telemetry as _telemetry
    _telemetry.install_jax_hooks()

    n_rows = int(os.environ.get("BENCH_SWEEP_ROWS", "2000"))
    n_features = 28
    rounds = int(os.environ.get("BENCH_SWEEP_ROUNDS", "30"))
    widths = [int(b) for b in
              os.environ.get("BENCH_SWEEP_B", "1,4,16").split(",")]
    # the solo_proc lane starts one training process per member; a
    # chip belongs to one process, so it runs on the CPU lane only
    run_proc = os.environ.get("BENCH_SWEEP_PROC", "1") != "0" and \
        backend == "cpu"
    X, y = make_higgs_shaped(n_rows, n_features, seed=3)

    base = {"objective": "binary", "num_leaves": 15, "verbose": -1,
            "metric": "None", "num_iterations": rounds,
            "bagging_fraction": 0.8, "bagging_freq": 1,
            "deterministic": True, "seed": 11}

    def member_params(i):
        # traced-only variation: one static group, one compile
        return dict(base, learning_rate=0.05 + 0.005 * i,
                    bagging_seed=100 + i)

    with tempfile.TemporaryDirectory() as td:
        npz = os.path.join(td, "data.npz")
        np.savez(npz, X=X, y=y)
        cells = []
        for B in widths:
            ds = lgb.Dataset(X, label=y, free_raw_data=False)
            specs = [battery_mod.MemberSpec(params=member_params(i),
                                            tag=f"m{i}")
                     for i in range(B)]
            t0 = time.time()
            report = battery_mod.train_battery(ds, specs)
            battery_wall = time.time() - t0
            assert all(not r.failed for r in report.results)

            t0 = time.time()
            for i in range(B):
                d = lgb.Dataset(X, label=y, free_raw_data=False)
                lgb.train(member_params(i), d, verbose_eval=False)
            warm_wall = time.time() - t0

            cell = {
                "B": B,
                "battery_wall_s": round(battery_wall, 3),
                "battery_models_per_s": round(B / battery_wall, 3),
                "solo_warm_wall_s": round(warm_wall, 3),
                "solo_warm_models_per_s": round(B / warm_wall, 3),
                "speedup_warm": round(warm_wall / battery_wall, 2),
                "groups": report.groups,
                "xla_compiles": report.xla_compiles,
                "retraces_per_model": round(
                    report.retraces_per_model, 3),
            }
            if run_proc:
                t0 = time.time()
                for i in range(B):
                    subprocess.run(
                        [sys.executable, "-c", _SWEEP_SOLO_DRIVER,
                         npz, json.dumps(member_params(i))],
                        check=True)
                proc_wall = time.time() - t0
                cell.update({
                    "solo_proc_wall_s": round(proc_wall, 3),
                    "solo_proc_models_per_s": round(B / proc_wall, 3),
                    "speedup": round(proc_wall / battery_wall, 2),
                })
            cells.append(cell)
            print(json.dumps({"sweep_cell": B, **cell}), flush=True)

    out = {
        "metric": "sweep_battery_cpu",
        "unit": "models/s",
        "backend": backend,
        "date": datetime.date.today().isoformat(),
        "source": "JAX_PLATFORMS=cpu python bench.py --sweep-only",
        "env": "1-core CPU container",
        "forest": (f"15-leaf binary forest, {n_rows} x {n_features} "
                   f"Higgs-shaped train matrix, {rounds} iterations, "
                   f"bagging 0.8/1"),
        "config": {"rows": n_rows, "features": n_features,
                   "rounds": rounds, "widths": widths},
        "cells": cells,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_sweep_cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(json.dumps({"wrote": os.path.basename(path)}), flush=True)
    return 0


if __name__ == "__main__":
    if "--serve-only" in sys.argv:
        sys.exit(serve_only())
    if "--explain-only" in sys.argv:
        sys.exit(explain_only())
    if "--router-only" in sys.argv:
        sys.exit(router_only())
    if "--autoscale-only" in sys.argv:
        sys.exit(autoscale_only())
    if "--ckpt-only" in sys.argv:
        sys.exit(ckpt_only())
    if "--obs-only" in sys.argv:
        sys.exit(obs_only())
    if "--continual-only" in sys.argv:
        sys.exit(continual_only())
    if "--ingest-only" in sys.argv:
        sys.exit(ingest_only())
    if "--paged-only" in sys.argv:
        sys.exit(paged_only())
    if "--weakscale-only" in sys.argv:
        sys.exit(weakscale_only())
    if "--sweep-only" in sys.argv:
        sys.exit(sweep_only())
    sys.exit(main())
