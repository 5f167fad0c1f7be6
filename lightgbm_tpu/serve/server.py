"""The serving front: dispatcher loop + in-process Python API.

``Server(booster)`` owns the admission queue, the micro-batcher, the
model registry and the dispatcher thread(s); ``predict()`` is the
blocking client surface (``submit()`` returns the request future).
Every request — completed, shed, timed out or rejected — feeds one
``serve`` telemetry record (``utils/telemetry.py``) carrying the
queue-wait / batch-assembly / dispatch / total latency split, the
batch occupancy, and the version that scored it; the recorder's
``run_end`` summary rolls up p50/p95/p99 latency and shed/timeout
counts.  Steady-state serving re-runs only cached XLA programs: the
batcher packs to warmed buckets and swaps pre-warm off the request
path, so the ``xla_compiles`` counter stays flat after warmup (pinned
in ``tests/test_serve.py``).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ..obs import metrics as _obs_metrics
from ..obs import spans as _spans
from ..utils import faults as _faults
from ..utils.log import Log
from ..utils.telemetry import counters as _tele_counters
from .admission import (AdmissionQueue, QueueSaturated, Request,
                        ServerClosed, UnknownModel)
from .batcher import Batch, MicroBatcher
from .config import ServeConfig
from .registry import ModelRegistry

#: the registry name un-prefixed routes (``/predict``, ``/swap``)
#: resolve to; named tenants ride ``/v1/<model>/...``
DEFAULT_MODEL = "default"


class Server:
    """In-process online predict server over the jitted engine."""

    def __init__(self, booster=None,
                 params: Optional[Dict[str, Any]] = None,
                 config: Optional[ServeConfig] = None,
                 telemetry=None):
        from ..utils.env import configure_compile_cache
        configure_compile_cache()
        self.config = config or ServeConfig.from_params(params)
        self.config.validate()
        self.queue = AdmissionQueue(
            self.config.queue_rows, self.config.queue_requests,
            batch_rows_hint=self.config.max_batch_rows)
        self.batcher = MicroBatcher(self.queue, self.config)
        # multi-model tenancy: one ModelRegistry per named model, all
        # sharing this server's queue/batcher/dispatchers (requests pin
        # their ModelVersion at admission and the batcher groups by
        # version identity, so tenants never mix in a device batch).
        # ``registry`` stays the default tenant for the single-model
        # API surface.
        self._registries: Dict[str, ModelRegistry] = {
            DEFAULT_MODEL: ModelRegistry(
                chunk_rows=self.config.max_batch_rows,
                warm=self.config.warmup,
                fastpath_rows=self.config.fastpath_max_rows)}
        self._registries_lock = threading.Lock()
        self._stop = threading.Event()
        self.draining = False
        self._threads: List[threading.Thread] = []
        self._rid = 0
        self._rid_lock = threading.Lock()
        # bounded ROLLING histogram (obs/metrics.py): /stats
        # percentiles come from fixed buckets over the last one-to-
        # two minutes, so a long-lived replica's stats memory is O(1)
        # AND its p99 reflects current behavior — the rollback
        # watchdog compares p99 across a deploy, which a lifetime
        # histogram would dilute on a replica with request history.
        # Kept SEPARATE from the registry's ltpu_serve_latency_ms on
        # purpose: /stats is per-server and recency-windowed, the
        # registry series is process-wide and cumulative (Prometheus
        # scrapers window buckets themselves)
        lat_buckets = self.config.metrics_latency_buckets or \
            _obs_metrics.DEFAULT_LATENCY_BUCKETS_MS
        self._lat_hist = _obs_metrics.RollingHistogram(
            buckets=lat_buckets)
        self._counts: Dict[str, int] = {}
        self._counts_lock = threading.Lock()
        self._metrics = self._make_metrics(lat_buckets) \
            if self.config.metrics else None
        self._recorder = self._make_recorder(telemetry)
        self._owns_recorder = telemetry is None and \
            self._recorder is not None
        # the serve path scores through the engine directly (pinned
        # flat tables, not GBDT.predict_raw), so the LRU-capacity knob
        # must be applied here — GBDT._engine() never runs
        if self.config.predict_cache_slots > 0:
            from ..ops.predict import get_engine
            from ..ops.shap import get_shap_engine
            get_engine().set_cache_size(self.config.predict_cache_slots)
            # the explanation engine shares the LRU-capacity contract:
            # its serve-visible layouts x buckets must stay resident
            # or steady-state explains would recompile
            get_shap_engine().set_cache_size(
                self.config.predict_cache_slots)
        if booster is not None:
            self.registry.publish(booster)

    def _make_metrics(self, lat_buckets) -> Dict[str, Any]:
        """Register this server's live-metrics series (GET /metrics).
        Counters/histograms are process-wide and fed at the SAME call
        sites as the telemetry records, so the scrape matches the
        run_end rollups bit-for-bit; gauges are scrape-time callbacks
        re-pointed at the newest server."""
        _obs_metrics.install_telemetry_mirror()
        reg = _obs_metrics.get_registry()
        m = {
            "requests": reg.counter(
                "ltpu_serve_requests_total",
                "serve requests by terminal status", ("status",)),
            "rows": reg.counter(
                "ltpu_serve_rows_total",
                "rows admitted into terminal requests", ("status",)),
            "latency": reg.histogram(
                "ltpu_serve_latency_ms",
                "total request latency (ok requests)",
                buckets=lat_buckets),
            "occupancy": reg.histogram(
                "ltpu_serve_batch_occupancy",
                "dispatch-batch fill fraction",
                buckets=_obs_metrics.OCCUPANCY_BUCKETS),
            "swaps": reg.counter(
                "ltpu_serve_swaps_total", "model hot-swaps"),
            # the explanation lane gets its own request/row/latency
            # series: explain latency is a different distribution
            # (O(depth^2) per leaf) and blending it into the predict
            # histogram would poison the rollback watchdog's p99
            "ex_requests": reg.counter(
                "ltpu_serve_explain_requests_total",
                "explain requests by terminal status", ("status",)),
            "ex_rows": reg.counter(
                "ltpu_serve_explain_rows_total",
                "rows admitted into terminal explain requests",
                ("status",)),
            "ex_latency": reg.histogram(
                "ltpu_serve_explain_latency_ms",
                "total explain request latency (ok requests)",
                buckets=lat_buckets),
            "fp_batches": reg.counter(
                "ltpu_serve_fastpath_batches_total",
                "predict batches dispatched on the single-row "
                "fast path"),
            "fp_rows": reg.counter(
                "ltpu_serve_fastpath_rows_total",
                "rows dispatched on the single-row fast path"),
        }
        # request-path fast lane: labeled children resolved once, not
        # per request (the registry lookup costs real microseconds at
        # serve rates)
        m["lat_child"] = m["latency"].labels()
        m["ex_lat_child"] = m["ex_latency"].labels()
        m["occ_child"] = m["occupancy"].labels()
        m["req_children"] = {}
        m["ex_req_children"] = {}
        # gauges capture self: remember the closures so stop() can
        # release them (a dead server must not stay pinned in the
        # process-global registry through its scrape callbacks)
        m["gauges"] = {
            "ltpu_serve_queue_requests":
                ("admitted requests pending dispatch",
                 lambda: self.queue.depth()[0]),
            "ltpu_serve_queue_rows":
                ("admitted rows pending dispatch",
                 lambda: self.queue.depth()[1]),
            "ltpu_serve_draining":
                ("1 once a graceful drain began",
                 lambda: 1.0 if self.draining else 0.0),
            "ltpu_serve_model_version":
                ("active published model version",
                 lambda: float(self.version() or 0)),
        }
        for name, (help_, fn) in m["gauges"].items():
            reg.gauge_callback(name, fn, help_)
        return m

    def _metric_children(self, status: str, kind: str = "predict"):
        key = "ex_req_children" if kind == "explain" \
            else "req_children"
        ch = self._metrics[key].get(status)
        if ch is None:                     # benign race: idempotent
            base = ("ex_requests", "ex_rows") if kind == "explain" \
                else ("requests", "rows")
            ch = (self._metrics[base[0]].labels(status=status),
                  self._metrics[base[1]].labels(status=status))
            self._metrics[key][status] = ch
        return ch

    def _make_recorder(self, telemetry):
        from ..utils import telemetry as _t
        if telemetry is not None:
            return telemetry                     # caller-owned recorder
        if not self.config.telemetry_file:
            return None
        import jax
        from ..ops.predict import engine_device_info
        info: Dict[str, Any] = {"task": "serve",
                                "backend": jax.default_backend(),
                                "engine_device": engine_device_info()}
        return _t.RunRecorder(self.config.telemetry_file, run_info=info)

    # -- lifecycle -------------------------------------------------------
    def start(self) -> "Server":
        if self._threads:
            return self
        self._stop.clear()
        for i in range(self.config.workers):
            t = threading.Thread(target=self._worker,
                                 name=f"ltpu-serve-{i}", daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def stop(self, timeout: float = 30.0) -> None:
        """Stop admissions, drain pending work, join the dispatchers,
        flush telemetry.  Idempotent."""
        self.queue.close()
        for t in self._threads:
            t.join(timeout)
        self._threads = []
        self._stop.set()
        # anything a dead worker left behind fails loudly, not silently
        while True:
            leftovers, _ = self.queue.drain_batch(1 << 30, 0.0,
                                                  self._stop)
            if not leftovers:
                break
            for r in leftovers:
                if r.finish("error", error="server stopped"):
                    self._emit(r)
        if self._metrics is not None:
            reg = _obs_metrics.get_registry()
            for name, (_help, fn) in self._metrics["gauges"].items():
                reg.release_gauge_callback(name, fn)
        if self._owns_recorder and self._recorder is not None:
            self._recorder.close()
            self._recorder = None

    def drain(self, grace_s: Optional[float] = None) -> None:
        """Graceful drain: stop admitting (the HTTP front answers 503
        + Retry-After while ``draining`` is set), finish every
        already-admitted request, then stop.  This is what a SIGTERM
        triggers, so supervisor-driven restarts never drop admitted
        work.  Idempotent."""
        self.draining = True
        grace = self.config.drain_grace_s if grace_s is None \
            else float(grace_s)
        self.queue.close()                 # new submits raise ServerClosed
        deadline = time.monotonic() + max(grace, 0.0)
        while self.queue.depth()[0] > 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        self.stop(timeout=max(deadline - time.monotonic(), 0.1))

    def __enter__(self) -> "Server":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- model management ------------------------------------------------
    @property
    def registry(self) -> ModelRegistry:
        """The default tenant's registry (the single-model API)."""
        return self._registries[DEFAULT_MODEL]

    def registry_for(self, model: Optional[str],
                     create: bool = False) -> ModelRegistry:
        """The named tenant's registry.  ``create=True`` (the swap
        path) opens the tenancy seam: publishing to a new name creates
        its registry; the request path NEVER creates one — an unknown
        name raises :class:`UnknownModel` (HTTP 404)."""
        name = model or DEFAULT_MODEL
        with self._registries_lock:
            reg = self._registries.get(name)
            if reg is None:
                if not create:
                    raise UnknownModel(
                        f"no model {name!r} published (known: "
                        f"{sorted(self._registries)})")
                reg = ModelRegistry(
                    chunk_rows=self.config.max_batch_rows,
                    warm=self.config.warmup,
                    fastpath_rows=self.config.fastpath_max_rows)
                self._registries[name] = reg
        return reg

    def models(self) -> Dict[str, Optional[str]]:
        """{model name: active fingerprint} across tenants (the
        ``/healthz`` body's ``models`` map — what the fleet
        supervisor's reconciler and the router's scrape read)."""
        with self._registries_lock:
            regs = dict(self._registries)
        out: Dict[str, Optional[str]] = {}
        for name, reg in regs.items():
            ver = reg.current()
            out[name] = ver.model_id if ver is not None else None
        return out

    def swap(self, booster=None, model_file: Optional[str] = None,
             model_str: Optional[str] = None,
             model: Optional[str] = None) -> int:
        """Publish a new model version (flatten + pre-warm + atomic
        swap) to the named tenant (default when ``model`` is None).
        In-flight requests complete against their admitted version;
        only new admissions see the new one."""
        t0 = time.monotonic()
        name = model or DEFAULT_MODEL
        with self._registries_lock:
            created = name not in self._registries
        reg = self.registry_for(model, create=True)
        try:
            with _spans.span("swap", recorder=self._recorder) as sp:
                ver = reg.publish(booster=booster,
                                  model_file=model_file,
                                  model_str=model_str)
                sp.set(version=ver.version, model_id=ver.model_id,
                       model=name)
                # the publish trace rides the version: the FIRST
                # request this version serves emits a joined marker
                # span, closing the daemon->checkpoint->publish->
                # served-request loop
                ver.publish_trace = _spans.current()
        except BaseException:
            # a failed FIRST publish to a new name must not leave an
            # empty tenant behind: it would answer 500 (no model
            # published) instead of the documented 404 unknown_model
            # and pollute the /healthz models map
            if created:
                with self._registries_lock:
                    cur = self._registries.get(name)
                    if cur is reg and reg.current() is None:
                        del self._registries[name]
            raise
        if self._metrics is not None:
            self._metrics["swaps"].inc()
        if self._recorder is not None:
            self._recorder.emit(
                "serve", status="swap", rows=0,
                total_ms=round((time.monotonic() - t0) * 1e3, 3),
                version=ver.version, model_id=ver.model_id,
                model=model or DEFAULT_MODEL,
                warmup=ver.warmup_info)
        return ver.version

    def version(self) -> Optional[int]:
        ver = self.registry.current()
        return ver.version if ver is not None else None

    # -- client surface --------------------------------------------------
    def submit(self, data, priority: int = 0,
               timeout_ms: Optional[float] = None,
               raw: bool = False,
               model: Optional[str] = None,
               kind: str = "predict") -> Request:
        """Admit one request against the named tenant (default when
        ``model`` is None); returns the request future (``.value()``
        blocks for the result or raises).  ``kind="explain"`` admits
        into the explanation lane (per-row SHAP contributions; the
        batcher never mixes lanes in one device batch).  Raises
        :class:`QueueSaturated` immediately on backpressure and
        :class:`UnknownModel` for an unpublished tenant name."""
        if kind not in ("predict", "explain"):
            raise ValueError(f"unknown request kind {kind!r}")
        if not self._threads:
            raise ServerClosed("server not started (call start())")
        ver = self.registry_for(model).require()
        X = np.ascontiguousarray(np.asarray(data, np.float64))
        if X.ndim == 1:
            X = X[None, :]
        if X.ndim != 2 or X.shape[0] == 0:
            raise ValueError(f"expected a non-empty 2-D matrix, got "
                             f"shape {X.shape}")
        if X.shape[1] < ver.requires_features:
            raise ValueError(
                f"input has {X.shape[1]} features but model v"
                f"{ver.version} references feature "
                f"{ver.requires_features - 1}")
        if X.shape[1] != ver.num_features:
            # width-normalize so requests concatenate into one batch;
            # extra columns are ignored exactly as the engine would
            fixed = np.zeros((X.shape[0], ver.num_features))
            w = min(X.shape[1], ver.num_features)
            fixed[:, :w] = X[:, :w]
            X = fixed
        tmo = self.config.timeout_ms if timeout_ms is None \
            else float(timeout_ms)
        deadline = time.monotonic() + tmo / 1e3 if tmo > 0 else None
        with self._rid_lock:
            self._rid += 1
            rid = self._rid
        req = Request(rid, X, raw, priority, deadline, ver, kind=kind)
        # the serve record is emitted on a dispatcher thread; carry
        # the submitter's trace context (HTTP header / caller span)
        # on the request so the record still joins its trace
        req.trace = _spans.current()
        try:
            shed = self.queue.admit(req)
        except QueueSaturated as exc:
            req.finish("rejected", error=str(exc),
                       retry_after_ms=exc.retry_after_ms)
            self._emit(req)
            raise
        for v in shed:
            self._emit(v)
        return req

    def predict(self, data, priority: int = 0,
                timeout_ms: Optional[float] = None,
                raw: bool = False,
                model: Optional[str] = None) -> np.ndarray:
        """Blocking predict through the micro-batching scheduler.
        Output matches ``Booster.predict`` (``raw=True`` matches
        ``raw_score=True``)."""
        req = self.submit(data, priority=priority,
                          timeout_ms=timeout_ms, raw=raw, model=model)
        return self._await(req)

    def explain(self, data, priority: int = 0,
                timeout_ms: Optional[float] = None,
                model: Optional[str] = None) -> np.ndarray:
        """Blocking per-row SHAP contributions through the explanation
        lane.  Output matches ``Booster.predict(pred_contrib=True)``:
        (rows, nf+1) with the bias in the last column, multiclass
        flattened to (rows, k*(nf+1)).  Contributions are raw-score
        space by definition (per row, sum + bias == predict_raw)."""
        req = self.submit(data, priority=priority,
                          timeout_ms=timeout_ms, raw=True, model=model,
                          kind="explain")
        return self._await(req)

    def _await(self, req: Request) -> np.ndarray:
        # grace beyond the deadline: the dispatcher times the request
        # out itself; this guard only catches a wedged worker
        grace = None
        if req.deadline is not None:
            grace = max(req.deadline - time.monotonic(), 0.0) + 60.0
        if not req.wait(grace):
            # finish() is first-writer-wins: if the dispatcher beat us
            # between wait() and here, this is a no-op and no second
            # telemetry record is emitted
            if req.finish("error", error="dispatcher stalled"):
                self._emit(req)
        return req.value()

    # -- dispatcher ------------------------------------------------------
    def _worker(self) -> None:
        while True:
            batch, timed = self.batcher.next_batch(self._stop)
            for t in timed:
                self._emit(t)
            if batch is None:
                if (self._stop.is_set() or self.queue.closed()) \
                        and self.queue.depth()[0] == 0:
                    return
                continue
            self._dispatch(batch)

    def _dispatch(self, batch: Batch) -> None:
        from ..utils.telemetry import counters_snapshot
        t0 = time.monotonic()
        explain = batch.kind == "explain"
        compiles = 0.0
        try:
            # fault-injection points (utils/faults.py):
            # ``serve.dispatch`` covers every batch, ``serve.explain``
            # only the explanation lane — "error" exercises the real
            # failure path below; "sleep_<ms>" degrades latency so the
            # rollback controller's p99 trigger is testable without a
            # genuinely slow model
            mode = _faults.fire("serve.explain") if explain \
                else _faults.fire("serve.dispatch")
            if explain and not mode:
                mode = _faults.fire("serve.dispatch")
            if mode.startswith("sleep_"):
                time.sleep(max(float(mode.split("_", 1)[1]), 0.0) / 1e3)
            elif mode == "error":
                raise RuntimeError(
                    f"injected fault "
                    f"(serve.{'explain' if explain else 'dispatch'}"
                    f":error)")
            if explain:
                # steady-state explains must re-run cached programs;
                # the compile delta rides the explain record so
                # obs/rules.py can flag a warmed lane that compiles
                base = counters_snapshot().get("xla_compiles", 0.0)
                raw = batch.version.explain_batch(batch.X)
                compiles = counters_snapshot().get(
                    "xla_compiles", 0.0) - base
            elif batch.fastpath:
                raw = batch.version.predict_raw_fast_batch(batch.X)
            else:
                raw = batch.version.predict_raw_batch(batch.X)
        except Exception as exc:  # batch fails as a unit, loudly
            Log.warning("serve: batch dispatch failed: %s", exc)
            for r in batch.requests:
                r.timings["dispatch_ms"] = \
                    round((time.monotonic() - t0) * 1e3, 3)
                if r.finish("error", error=f"dispatch failed: {exc}"):
                    self._emit(r, batch)
            return
        dispatch_ms = round((time.monotonic() - t0) * 1e3, 3)
        # EWMA service-time hint drives the retry-after backpressure
        self.queue.service_ms_hint = round(
            0.8 * self.queue.service_ms_hint + 0.2 * dispatch_ms, 3)
        pos = 0
        for r in batch.requests:
            sl = raw[pos:pos + r.rows]
            pos += r.rows
            # contributions are raw-score space by definition (their
            # row sum reproduces predict_raw) — never converted
            out = sl if (r.raw or explain) \
                else batch.version.convert(sl)
            r.timings["dispatch_ms"] = dispatch_ms
            if r.finish("ok", result=out):
                self._emit(r, batch, compiles=compiles)
        _tele_counters.incr("serve_batches")
        _tele_counters.incr("serve_batch_rows", batch.rows)
        _tele_counters.incr("serve_padded_rows", batch.bucket_rows)
        if explain:
            _tele_counters.incr("serve_explain_batches")
            _tele_counters.incr("serve_explain_rows", batch.rows)
        elif batch.fastpath:
            _tele_counters.incr("serve_fastpath_batches")
            _tele_counters.incr("serve_fastpath_rows", batch.rows)
            if self._metrics is not None:
                self._metrics["fp_batches"].inc()
                self._metrics["fp_rows"].inc(batch.rows)

    # -- telemetry / stats -----------------------------------------------
    def _emit(self, req: Request, batch: Optional[Batch] = None,
              compiles: float = 0.0) -> None:
        status = req.status
        explain = req.kind == "explain"
        _tele_counters.incr("serve_requests")
        if explain:
            _tele_counters.incr("serve_explain_requests")
        if status != "ok":
            _tele_counters.incr(f"serve_{status}")
        with self._counts_lock:
            self._counts[status] = self._counts.get(status, 0) + 1
        if status == "ok":
            self._lat_hist.observe(req.timings.get("total_ms", 0.0))
        if self._metrics is not None:
            c_req, c_rows = self._metric_children(status, req.kind)
            c_req.inc()
            c_rows.inc(req.rows)
            if status == "ok":
                self._metrics["ex_lat_child" if explain
                              else "lat_child"].observe(
                    req.timings.get("total_ms", 0.0))
                if batch is not None:
                    self._metrics["occ_child"].observe(
                        batch.occupancy)
        ver = req.version
        pub_trace = getattr(ver, "publish_trace", None) if ver else None
        if status == "ok" and pub_trace is not None:
            # first served request of a freshly published version:
            # emit one marker span joined to the publish trace
            with self._counts_lock:
                pub_trace, ver.publish_trace = ver.publish_trace, None
            if pub_trace is not None:
                _spans.point("first_request", pub_trace,
                             recorder=self._recorder,
                             version=ver.version, model_id=ver.model_id,
                             rows=req.rows,
                             total_ms=round(
                                 req.timings.get("total_ms", 0.0), 3))
        if self._recorder is None:
            return
        fields: Dict[str, Any] = {
            "status": status, "rows": req.rows,
            "total_ms": round(req.timings.get("total_ms", 0.0), 3),
            "priority": req.priority,
        }
        for key in ("queue_ms", "assemble_ms", "dispatch_ms"):
            if key in req.timings:
                fields[key] = req.timings[key]
        if req.version is not None:
            fields["version"] = req.version.version
            fields["model_id"] = req.version.model_id
        if req.trace is not None:
            fields["trace_id"], fields["span_id"] = req.trace
        if batch is not None:
            fields["batch_rows"] = batch.rows
            fields["bucket_rows"] = batch.bucket_rows
            fields["occupancy"] = round(batch.occupancy, 4)
            if batch.fastpath:
                fields["fastpath"] = True
        if explain:
            # rides the record so obs/rules.py can flag a warmed
            # explain lane that still compiles (explain_compile MED);
            # 0 past warmup IS the contract, so it is always present
            fields["xla_compiles"] = compiles
        if req.error and status not in ("ok",):
            fields["error"] = str(req.error)[:200]
        self._recorder.emit("explain" if explain else "serve",
                            **fields)

    def stats(self) -> Dict[str, Any]:
        from ..ops.predict import engine_device_info, get_engine
        from ..ops.shap import get_shap_engine
        with self._counts_lock:
            counts = dict(self._counts)
        depth_reqs, depth_rows = self.queue.depth()
        ver = self.registry.current()
        return {
            "version": ver.version if ver else None,
            "model_id": ver.model_id if ver else None,
            "models": self.models(),
            "draining": self.draining,
            "queue_requests": depth_reqs,
            "queue_rows": depth_rows,
            "requests": counts,
            # interpolated from the bounded histogram (O(1) memory
            # and no per-scrape sort, whatever the request count)
            "latency_ms": {
                "p50": round(self._lat_hist.percentile(0.50), 3),
                "p95": round(self._lat_hist.percentile(0.95), 3),
                "p99": round(self._lat_hist.percentile(0.99), 3),
            },
            "retry_after_ms": self.queue.retry_after_ms(),
            "engine_device": engine_device_info(),
            "engine_cache": get_engine().cache_info(),
            "explain_cache": get_shap_engine().cache_info(),
            "versions": self.registry.history(),
        }

    def metrics_text(self) -> str:
        """The Prometheus exposition ``GET /metrics`` serves (the
        process-wide registry: this server's series plus every
        mirrored telemetry counter)."""
        return _obs_metrics.render()
