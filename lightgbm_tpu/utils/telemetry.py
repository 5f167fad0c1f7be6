"""Structured run telemetry: schema-versioned JSONL run records.

``docs/Benchmarks.md`` once drifted because it was written from
memory instead of from artifacts.  This module is the run-record
discipline GPU boosting systems lean on to attribute time to kernels,
transfers and comms
(XGBoost: Scalable GPU Accelerated Learning, arXiv:1806.11248;
Out-of-Core GPU Gradient Boosting, arXiv:2005.09148): every training
and inference entry point feeds a :class:`RunRecorder`, which appends
one JSON object per line to ``telemetry_file`` and logs an aggregate
summary through :class:`~lightgbm_tpu.utils.log.Log` at shutdown.

Record stream (all records carry ``schema``/``type``/``seq``/``wall_time``):

- ``run_start``  — backend identity (platform, device kind, the
  device the predict/SHAP engines compute on), the tier/gate decision
  for the booster (two_col vs wave vs routed vs exact, with the gate
  that rejected each higher tier), config subset, device memory stats
  when the backend exposes them.
- ``iteration``  — per boosting iteration: phase deltas
  (``phases_ms``; the phases of ``profiling.py`` are profiler
  annotations and ``phase_secs/*`` counters), XLA compile/retrace
  counter deltas, total and by program (hooked via
  ``jax.monitoring``, so a silent retrace storm becomes a visible
  number and names its program), histogram passes + pool hit rate,
  per-learner collective payload bytes, trees added.
- ``superstep``  — one record per fused K-iteration block
  (``fused_iters`` > 1, ``models/gbdt.py``): the block's first
  iteration, K, and the AMORTIZED phase/counter deltas — per-iteration
  wall time is ``duration_ms / k``, which is how ``triage_run.py``
  normalizes it (a K-fold drop in per-iteration time is the fused
  path working, not an anomaly).
- ``eval``       — metric results as the training loop computed them.
- ``predict``    — one per predict call: rows, trees, engine on/off,
  predict-engine compile-cache hit/miss/eviction deltas.
- ``run_end``    — the aggregate summary (also Log.info'd).

Consumers: ``tools/triage_run.py`` (anomaly triage + ``--check``
schema lint) and ``tools/render_benchmarks.py`` (regenerates
``docs/Benchmarks.md`` from artifacts).  The bench-artifact parser
lives here too, for the tools that read driver-wrapped artifacts.
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from .log import Log

__all__ = [
    "SCHEMA_VERSION", "RECORD_TYPES", "RunRecorder", "counters",
    "counters_snapshot", "install_jax_hooks", "validate_record",
    "lint_file", "read_records", "parse_bench_artifact",
    "get_recorder", "set_recorder", "percentile",
    "set_trace_provider", "add_emit_observer", "remove_emit_observer",
]


def percentile(sorted_vals, q: float) -> float:
    """Index-based percentile over an ascending-sorted sequence — the
    ONE implementation every latency rollup shares (run_end summary,
    serve stats, bench, loadgen), so their p50/p95/p99 agree."""
    if not sorted_vals:
        return 0.0
    return float(sorted_vals[min(int(q * len(sorted_vals)),
                                 len(sorted_vals) - 1)])

SCHEMA_VERSION = 1

RECORD_TYPES = ("run_start", "iteration", "superstep", "eval", "predict",
                "serve", "explain", "checkpoint", "fleet", "continual",
                "recovery", "router", "ingest", "span", "capture", "sweep",
                "slo", "autoscale", "pager", "run_end")

# per-type required fields on top of the common envelope; values are
# (field, type-or-types) pairs the lint enforces
_COMMON_FIELDS = (("schema", int), ("type", str), ("seq", int),
                  ("wall_time", float))
_TYPE_FIELDS: Dict[str, Tuple[Tuple[str, Any], ...]] = {
    "run_start": (("backend", str),),
    "iteration": (("iter", int), ("duration_ms", (int, float))),
    # one record per fused K-iteration super-step (fused_iters > 1):
    # ``iter`` is the block's first iteration, ``k`` the block size,
    # ``duration_ms``/``phases_ms``/``counters`` cover the WHOLE block
    # (per-iteration cost = value / k).  SHARDED super-steps (a
    # distributed tree learner running inside the fused scan,
    # docs/Distributed.md) additionally carry ``learner``,
    # ``num_shards``, ``mesh_shape`` and the per-block per-shard
    # ``collective_bytes``/``collective_ops`` estimates — the series
    # triage_run.py's weak-scaling anomaly reads.  Async-pipelined
    # runs (superstep_pipeline_depth > 0) add ``pipeline_depth`` (the
    # configured in-flight depth) and ``fetch_overlap_s`` (wall
    # between the block's dispatch and its fetch — the window its
    # device compute overlapped host work); triage_run.py flags
    # depth > 0 with ~zero overlap as pipelining silently disabled.
    # ``split_kernel`` records the best-split engine that ran inside
    # the block (pallas = the on-chip split-scan kernel, xla
    # = the vectorized scans) and ``split_fallback`` the tier gate
    # that rejected the kernel tier when it did; triage_run.py flags
    # an XLA fallback on a TPU backend as MED.
    "superstep": (("iter", int), ("k", int),
                  ("duration_ms", (int, float))),
    "eval": (("iter", int), ("results", list)),
    "predict": (("rows", int), ("n_trees", int), ("engine", bool)),
    # one record per ONLINE serving request (serve/server.py):
    # ``status`` is ok|shed|timeout|rejected|error|swap; ok records
    # carry the queue_ms/assemble_ms/dispatch_ms latency split plus
    # batch_rows/bucket_rows/occupancy for their dispatch unit, and
    # the model ``version`` that scored them.  The run_end summary
    # rolls up p50/p95/p99 total latency and shed/timeout counts.
    "serve": (("status", str), ("rows", int),
              ("total_ms", (int, float))),
    # one record per ONLINE explanation request (serve/server.py, the
    # /explain lane): same envelope and status vocabulary as ``serve``
    # plus ``xla_compiles`` — the compile-counter DELTA measured across
    # the request's device SHAP dispatch.  Steady state must be 0 (the
    # publish-time warmup pre-compiles every explain bucket); a
    # non-zero value past warmup is the explanation engine silently
    # recompiling per request (MED anomaly ``explain_compile``,
    # obs/rules.py).  The run_end summary rolls up request/row counts
    # and p50/p95/p99 explain latency separately from the predict lane.
    "explain": (("status", str), ("rows", int),
                ("total_ms", (int, float))),
    # one record per checkpoint event (ckpt/manager.py): ``event`` is
    # save|load|fallback; saves carry iter/reason(periodic|preempt|
    # final)/bytes, loads carry iter/bytes, fallbacks carry the
    # rejected path + validation error.  The run_end summary rolls up
    # counts, total bytes and total save/load time; triage_run.py
    # flags fallbacks and save overhead > 5% of train wall time.
    "checkpoint": (("event", str), ("duration_ms", (int, float))),
    # one record per resilience-layer event (serve/fleet.py,
    # serve/watcher.py): ``event`` is replica_start|replica_exit|
    # replica_restart|circuit_open|circuit_half_open (supervisor) or
    # publish|publish_verified|publish_unverified|publish_skip|
    # rollback|watch_error (watcher / rollback controller).  publish
    # records carry model_id/path/iter; publish_skip carries
    # reason=manifest|canary|holddown|error + the validation error;
    # rollback carries reason=error_rate|p99|stats_reset|forced +
    # from_id/to_id.  triage_run.py
    # summarizes them and flags skips, rollbacks and open circuits.
    "fleet": (("event", str),),
    # one record per continual-training-loop event (lightgbm_tpu/cont/
    # and the numerical-health guard, utils/health.py): ``event`` is
    # batch (one consumed batch: batch/rows/iter/mode=extend|refit/
    # duration_ms) | quarantine (reason=validate|nonfinite|read|stall|
    # error + batch + error detail) | backoff (a transient ingest read
    # retried: batch/attempt/sleep_s) | stall_restart (the watchdog
    # abandoned a wedged train step: batch/attempt/stalled_s) |
    # nonfinite (the numerical-health guard tripped: iter/phase —
    # also emitted by one-shot engine.train) | batch_error (a train
    # attempt raised: batch/attempt/error) | preempt | resume |
    # idle_exit | fault_unknown_point (utils/faults.py typo warning).
    # triage_run.py rolls up quarantine rate, stall restarts and
    # non-finite rewinds as anomalies.
    "continual": (("event", str),),
    # one record per elastic-recovery event (parallel/elastic.py and
    # the cross-width resume path, ckpt/manager.py): ``event`` is
    # detect (a shard failure was classified: cause=hang|error +
    # detail/iter/num_shards) | remesh (recovery rebuilt the mesh:
    # from_shards/to_shards/iter/cause/duration_ms) | remesh_failed
    # (one re-mesh attempt raised; recovery degrades further) |
    # reshard (a checkpoint taken on one mesh topology restored onto
    # another: from_shards/to_shards + learners) | escalate (recovery
    # budget exhausted: reason=max_remesh|min_shards — the run fails
    # loudly into the checkpoint restart story).  triage_run.py rolls
    # these up and flags repeated re-meshes of one run as HIGH.
    "recovery": (("event", str),),
    # one record per routing-front event (serve/router.py): ``event``
    # is request (one CLIENT-facing routed request: model/status/rows/
    # total_ms/attempts/retries + hedged/hedge_won when the tail-
    # latency hedge fired — status ok|shed|backpressure|timeout|
    # upstream|no_backend|unknown_model|bad_request (shed = the
    # router's own admission budget; backpressure = every backend
    # answered 429/503 and the hint passed through); a request that
    # needed a
    # retry or a hedge and still answered 200 is status ok, failures
    # made invisible being the router's whole job) | breaker_open /
    # breaker_close (the per-backend circuit breaker feeding the
    # balancer: backend + failures) | scrape_error (a /healthz scrape
    # failed).  The run_end summary rolls up request/hedge/shed/retry
    # counts and p50/p95/p99 routed latency; obs/rules.py flags hedge
    # rate > 20% (MED), budget-shed rate > 5% (HIGH) and breaker
    # opens (HIGH).
    "router": (("event", str),),
    # one record per streamed-ingest event (io/stream.py + io/cache.py,
    # docs/Streaming.md): ``event`` is chunk_read (one raw chunk off
    # the source: chunk/rows/attempt) | cache_write (one binned chunk
    # published: chunk/bytes/bin_ms/write_ms, rebin=true when it
    # REPLACED a corrupt cached chunk) | verify_fail (a cached chunk
    # failed its sha256 verify-on-load and will be re-binned alone) |
    # prelude_hit (the fit-once mappers + metadata were reused —
    # resume never fits a mapper twice) | fit_mappers (the streamed
    # sample pass ran: rows_sampled/duration_ms) | backoff (a
    # transient chunk read or prefetch window retried:
    # chunk|window/attempt/sleep_s) | quarantine (retries exhausted or
    # deterministic parse failure: chunk/reason — a HIGH anomaly,
    # obs/rules.py) | clamp (stream_chunk_rows degraded to fit
    # stream_host_budget_mb) | prefetch (one host->device upload:
    # windows/bytes/overlap_s — the host prep hidden under async
    # device copies; ~zero overlap with streaming enabled is a MED
    # anomaly) | ingest_done (rollup: chunks/cache_hits/rebinned/
    # from_cache) | resume (checkpoint restore compared the manifest's
    # recorded cache identity with the live dataset's: cache_hit=false
    # means a re-bin the manifest should have prevented — MED).
    "ingest": (("event", str),),
    # one record per device-block pager flush (io/pager.py via
    # models/gbdt.py): ``event`` is flush (per-iteration/per-block
    # DELTA stats: pages served, bytes paged, overlap_s of prep
    # hidden on the prefetch thread, wait_s the device program
    # blocked in callbacks, stalls = serve-path inline preps, spills/
    # evictions/spill_hits of the host spill cache, page_rows/
    # n_pages geometry) | done (cumulative rollup at train end).
    # obs/rules.py flags paging active with ~zero prefetch overlap
    # as MED (pager_no_overlap).
    "pager": (("event", str),),
    # one record per closed trace span (obs/spans.py): ``trace_id``
    # joins spans (and trace-tagged records of every other type)
    # emitted by ANY process into one timeline — the continual
    # daemon's per-batch root, the checkpoint save, the watcher's
    # validate/canary/publish and the first request the published
    # version serves all share one trace_id across OS processes
    # (env / HTTP-header / checkpoint-extra propagation).
    # ``parent_id`` is absent on trace roots; ``status`` is ok|error.
    # ``tools/trace_view.py`` renders the joined timeline.
    "span": (("name", str), ("trace_id", str), ("span_id", str),
             ("duration_ms", (int, float))),
    # one record per flight-recorder capture (obs/flight.py):
    # ``trigger`` is the firing rule code (retrace_storm |
    # pipelining_disabled | xla_fallback | stall | rollback |
    # nonfinite), ``path`` the capture directory holding
    # anomaly.json + ring.jsonl (+ profile/ on device backends).
    "capture": (("trigger", str), ("path", str)),
    # one record per battery sweep (models/battery.py + engine.sweep,
    # docs/Sweep.md): ``models`` is the battery width B, ``groups``
    # the number of distinct compiled programs (static-signature
    # groups — every member whose program-shaping params agree shares
    # ONE vmapped compile), ``xla_compiles`` the compile-counter delta
    # across the batched dispatches and ``retraces_per_model`` the
    # per-model compile count BEYOND the one expected warmup compile
    # per group — steady-state must be 0 (one compiled program serves
    # the whole battery); a positive value is the battery silently
    # degrading toward per-model compilation (MED anomaly,
    # obs/rules.py, surfaced by triage_run.py).  Also carries the
    # models/s rollup plus per-model best iterations and CV scores.
    "sweep": (("models", int), ("groups", int), ("xla_compiles", int),
              ("retraces_per_model", (int, float)),
              ("models_per_s", (int, float))),
    # one record per SLO objective per evaluation tick (obs/slo.py):
    # ``objective`` names the declared objective (availability |
    # latency_p99 | queue_saturation | shed:<model> | custom),
    # ``status`` is ok | slow_burn | fast_burn | budget_exhausted |
    # scrape_error (the source raised; the tick degraded to last-known
    # state).  Carries the multi-window burn rates
    # (burn_fast/burn_mid/burn_slow), budget_remaining (fraction of
    # the error budget left this period — persisted across restarts),
    # exhaustion_eta_s (-1 = not burning) and the window/period
    # good/bad totals.  obs/rules.py turns the statuses into anomalies
    # (budget-exhaustion HIGH, fast-burn HIGH, slow-burn MED) so
    # --follow, triage and the flight recorder all see SLO state.
    "slo": (("objective", str), ("status", str)),
    # one record per autoscaler decision (serve/autoscaler.py):
    # ``action`` is grow | drain | retune_shed | retune_restore | none
    # (a degraded decide), ``mode`` is active | dry_run | degraded,
    # ``rule`` the policy clause that fired (fast_burn |
    # queue_saturation | budget_floor | burn_cleared | idle |
    # decide_error), and ``evidence`` the full inputs snapshot the
    # decision was made from (burn rates, queue fraction, replica and
    # breaker counts) — the reconciliation surface the chaos e2e
    # diffs against actual fleet/router state changes.  grow/drain
    # carry from_replicas/to_replicas; retunes carry rows_per_s.
    "autoscale": (("action", str), ("mode", str)),
    "run_end": (("summary", dict),),
}


# ----------------------------------------------------------------------
# process-wide counters (compile/retrace events, predict-cache traffic)
# ----------------------------------------------------------------------
class _Counters:
    """Thread-safe monotonic counters; recorders snapshot-and-diff.
    Hooks (``add_hook``) observe every increment — the obs metrics
    registry mirrors the counters into Prometheus series through one
    (``obs/metrics.py``), so live scrapes and run_end rollups agree
    bit-for-bit."""

    def __init__(self):
        self._lock = threading.Lock()
        self._c: Dict[str, float] = {}
        self._hooks: List[Any] = []

    def incr(self, name: str, by: float = 1.0) -> None:
        # hooks fire INSIDE the lock: paired with add_hook's atomic
        # prime-then-register, no increment can land between a
        # mirror's seed snapshot and its hook activation (which would
        # skew the bit-for-bit scrape oracle forever).  Hooks must not
        # call back into incr.
        with self._lock:
            self._add(name, by)

    def set(self, name: str, value: float) -> None:
        """A gauge among the counters: ``name`` takes ``value`` (a
        size written once at construction); hooks see the difference."""
        with self._lock:
            self._add(name, value - self._c.get(name, 0.0))

    def _add(self, name: str, by: float) -> None:
        self._c[name] = self._c.get(name, 0.0) + by
        for fn in self._hooks:
            try:
                fn(name, by)
            except Exception:  # noqa: BLE001 - hooks never break
                pass

    def add_hook(self, fn, prime=None) -> None:
        """Register an increment hook.  ``prime`` (if given) runs
        UNDER the counter lock with a snapshot of current values
        immediately before the hook activates — the atomic
        seed-then-subscribe a mirror needs."""
        with self._lock:
            if fn in self._hooks:
                return
            if prime is not None:
                try:
                    prime(dict(self._c))
                except Exception:  # noqa: BLE001
                    pass
            self._hooks = self._hooks + [fn]

    def remove_hook(self, fn) -> None:
        with self._lock:
            self._hooks = [h for h in self._hooks if h is not fn]

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._c)


counters = _Counters()
# where the phases of utils/profiling.py keep their seconds and calls
PHASE_SECS, PHASE_CALLS = "phase_secs/", "phase_calls/"


def counters_snapshot() -> Dict[str, float]:
    return counters.snapshot()


_HOOKS_INSTALLED = False
_HOOKS_LOCK = threading.Lock()
# a compile request's outcome, by the thread that makes it
_COMPILE_OUTCOME = threading.local()
# what a compile request came to: served by the persistent cache, or not
_LOADED = ("xla_cache_loads", "xla_cache_load_secs")
_FRESH = ("xla_fresh_compiles", "xla_fresh_compile_secs")


def install_jax_hooks() -> None:
    """Register ``jax.monitoring`` listeners feeding the process-wide
    compile/retrace counters.  Idempotent.  Event mapping (checked on
    jax 0.9.0): ``.../backend_compile_duration`` fires once per XLA
    compile REQUEST — silent on in-process executable-cache hits, but
    it also fires when the persistent compilation cache serves the
    executable, which ``/jax/compilation_cache/cache_hits`` counts
    separately — and ``.../jaxpr_trace_duration`` fires per abstract
    trace: a flat compile counter with a climbing trace counter is the
    signature of a retrace storm served from the compile cache, both
    climbing is new-shape compilation.  Both events carry the
    program's ``fun_name`` (a jitted ``f`` arrives as ``jit(f)`` at the
    compile event and as ``f`` at the trace event), kept beside the
    totals as ``xla_compiles/<fun_name>``,
    ``xla_compile_secs/<fun_name>`` and ``jax_trace_secs/<fun_name>``,
    so a recompile inside a run names its program in the record's
    ``counters``.

    Each compile request also goes to exactly one outcome: where the
    persistent cache serves it, the thread that makes it sees
    ``cache_hits`` before its ``backend_compile_duration``, which
    consumes that mark.  Served, it counts in
    ``xla_cache_loads`` and ``xla_cache_load_secs``; else (a miss, or a
    request that never asked the cache) in ``xla_fresh_compiles`` and
    ``xla_fresh_compile_secs``, so the two outcomes add up to
    ``xla_compiles`` and ``xla_compile_secs``.  Each has its
    ``/<fun_name>`` counters too, and a request adds 0 to the other
    outcome's, so that a program loaded whole reads 0 fresh seconds.
    Lowering (``jaxpr_to_mlir_module_duration``, ``fun_name`` as
    ``jit(f)``) goes to ``jax_lower_secs`` and its
    ``jax_lower_secs/<fun_name>``.  The totals stand at 0 from here on:
    a process with the hooks reads 0 where nothing happened.
    ``jax_cache_hits`` counts the cache's hits; ``jax_cache_misses``
    counts its WRITES (jax fires ``cache_misses`` when it puts an entry
    in, and not for one under the cache's minimum size), not the
    requests it could not serve."""
    global _HOOKS_INSTALLED
    with _HOOKS_LOCK:
        if _HOOKS_INSTALLED:
            return
        import jax.monitoring as monitoring

        def _on_duration(name, secs, **kw):
            fun = kw.get("fun_name")
            if name.endswith("backend_compile_duration"):
                loaded = getattr(_COMPILE_OUTCOME, "loaded", False)
                _COMPILE_OUTCOME.loaded = False
                took, other = (_LOADED, _FRESH) if loaded else \
                    (_FRESH, _LOADED)
                counters.incr("xla_compiles")
                counters.incr("xla_compile_secs", secs)
                counters.incr(took[0])
                counters.incr(took[1], secs)
                if fun:
                    counters.incr(f"xla_compiles/{fun}")
                    counters.incr(f"xla_compile_secs/{fun}", secs)
                    counters.incr(f"{took[0]}/{fun}")
                    counters.incr(f"{took[1]}/{fun}", secs)
                    for key in other:
                        counters.incr(f"{key}/{fun}", 0.0)
            elif name.endswith("jaxpr_to_mlir_module_duration"):
                counters.incr("jax_lower_secs", secs)
                if fun:
                    counters.incr(f"jax_lower_secs/{fun}", secs)
            elif name.endswith("jaxpr_trace_duration"):
                counters.incr("jax_traces")
                counters.incr("jax_trace_secs", secs)
                if fun:
                    counters.incr(f"jax_trace_secs/{fun}", secs)

        def _on_event(name, **kw):
            if name.endswith("compilation_cache/cache_misses"):
                counters.incr("jax_cache_misses")
            elif name.endswith("compilation_cache/cache_hits"):
                _COMPILE_OUTCOME.loaded = True
                counters.incr("jax_cache_hits")

        for key in _LOADED + _FRESH + ("jax_lower_secs",):
            counters.incr(key, 0.0)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _HOOKS_INSTALLED = True


# ----------------------------------------------------------------------
# obs-plane hooks: trace tagging + emit observers
# ----------------------------------------------------------------------
# set by obs/spans.py at import: () -> Optional[(trace_id, span_id)].
# When a span is active, every emitted record is tagged with the
# trace context, so ANY record type joins its trace without the call
# site knowing about tracing.
_TRACE_PROVIDER: Optional[Any] = None

# observers see every record ANY recorder in this process emits (the
# flight recorder's ring + online anomaly rules, obs/flight.py);
# called OUTSIDE the recorder lock with (record, recorder)
_EMIT_OBSERVERS: List[Any] = []
_OBSERVER_LOCK = threading.Lock()


def set_trace_provider(fn) -> None:
    global _TRACE_PROVIDER
    _TRACE_PROVIDER = fn


def add_emit_observer(fn) -> None:
    with _OBSERVER_LOCK:
        if fn not in _EMIT_OBSERVERS:
            _EMIT_OBSERVERS.append(fn)


def remove_emit_observer(fn) -> None:
    with _OBSERVER_LOCK:
        if fn in _EMIT_OBSERVERS:
            _EMIT_OBSERVERS.remove(fn)


# ----------------------------------------------------------------------
# recorder
# ----------------------------------------------------------------------
_OPEN_RECORDERS: List["RunRecorder"] = []
_OPEN_LOCK = threading.Lock()
_GLOBAL: Optional["RunRecorder"] = None


def _atexit_close():  # pragma: no cover - exercised via CLI/bench runs
    with _OPEN_LOCK:
        recs = list(_OPEN_RECORDERS)
    for r in recs:
        try:
            r.close()
        except Exception:
            pass


atexit.register(_atexit_close)


def get_recorder() -> Optional["RunRecorder"]:
    """The process-default recorder (set by the CLI / bench), if any."""
    return _GLOBAL


def set_recorder(rec: Optional["RunRecorder"]) -> None:
    global _GLOBAL
    _GLOBAL = rec


class RunRecorder:
    """Collects run records and appends them as JSONL.

    Thread-safe: ``emit`` may be called from concurrent predict
    threads.  When ``path`` is falsy the records are kept in memory
    only (``self.records``) — the test/tooling mode."""

    def __init__(self, path: Optional[str] = None,
                 run_info: Optional[Dict[str, Any]] = None,
                 keep_records: Optional[bool] = None):
        self._lock = threading.RLock()
        self.path = path or None
        self._fh = open(self.path, "a", buffering=1) if self.path else None
        self.keep_records = (not self.path) if keep_records is None \
            else bool(keep_records)
        self.records: List[Dict[str, Any]] = []
        self._seq = 0
        self._closed = False
        self._t0 = time.time()
        # aggregates for the shutdown summary
        self._agg: Dict[str, float] = {}
        self._phase_totals: Dict[str, float] = {}
        self._tier: Optional[str] = None
        self._backend: Optional[str] = None
        # serve-latency ring for the close-time p50/p95/p99 rollup:
        # bounded (long-running servers must not grow the recorder)
        # and holding the most RECENT 64k samples, so the rollup
        # reflects current behavior, not the first hour's
        self._serve_lat: List[float] = []
        self._serve_lat_n = 0
        self._serve_occ_sum = 0.0
        self._serve_occ_n = 0
        self._explain_lat: List[float] = []
        self._explain_lat_n = 0
        # routed-request latency ring (serve/router.py), same bounded
        # most-recent-samples policy as the serve ring
        self._router_lat: List[float] = []
        self._router_lat_n = 0
        self._base = counters.snapshot()
        install_jax_hooks()
        with _OPEN_LOCK:
            _OPEN_RECORDERS.append(self)
        # the header record must satisfy its own schema even for a bare
        # recorder (no run_info yet): attach_telemetry emits a second,
        # fully-populated run_start once a booster adopts the recorder
        info = dict(run_info or {})
        info.setdefault("backend", "unknown")
        self.emit("run_start", **info)

    # ------------------------------------------------------------------
    def counters_delta(self, last: Dict[str, float]
                       ) -> Tuple[Dict[str, float], Dict[str, float]]:
        """(delta since ``last``, fresh snapshot).  The caller owns the
        snapshot so concurrent iteration/predict streams don't steal
        each other's deltas.  The phases stay out of it: a record
        carries them once, as ``phases_ms``."""
        now = counters.snapshot()
        delta = {k: round(v - last.get(k, 0.0), 6)
                 for k, v in now.items() if v != last.get(k, 0.0)
                 and not k.startswith((PHASE_SECS, PHASE_CALLS))}
        return delta, now

    def emit(self, rtype: str, **fields) -> Dict[str, Any]:
        rec = {"schema": SCHEMA_VERSION, "type": rtype,
               "wall_time": round(time.time(), 3)}
        rec.update(fields)
        # trace tagging: records emitted under an active span join its
        # trace (span records carry their OWN ids and are left alone)
        if _TRACE_PROVIDER is not None and rtype != "span" \
                and "trace_id" not in rec:
            try:
                ctx = _TRACE_PROVIDER()
            except Exception:  # noqa: BLE001 - tagging is best-effort
                ctx = None
            if ctx is not None:
                rec["trace_id"], rec["span_id"] = ctx
        with self._lock:
            if self._closed:
                return rec
            rec["seq"] = self._seq
            self._seq += 1
            self._aggregate(rec)
            if self.keep_records:
                self.records.append(rec)
            if self._fh is not None:
                # one atomic write per record: concurrent emitters must
                # never interleave partial lines
                self._fh.write(json.dumps(rec, sort_keys=True) + "\n")
        if _EMIT_OBSERVERS:
            with _OBSERVER_LOCK:
                observers = list(_EMIT_OBSERVERS)
            for fn in observers:
                try:
                    fn(rec, self)
                except Exception:  # noqa: BLE001 - observers never break
                    pass
        return rec

    def _aggregate(self, rec: Dict[str, Any]) -> None:
        t = rec.get("type")
        if t == "run_start":
            self._backend = rec.get("backend")
            tier = rec.get("tier")
            if isinstance(tier, dict):
                self._tier = tier.get("tier")
        elif t in ("iteration", "superstep"):
            # a superstep record stands for k iterations
            k = int(rec.get("k", 1)) if t == "superstep" else 1
            self._agg["iterations"] = self._agg.get("iterations", 0) + k
            self._agg["train_ms"] = self._agg.get("train_ms", 0.0) + \
                float(rec.get("duration_ms", 0.0))
            for name, ms in (rec.get("phases_ms") or {}).items():
                self._phase_totals[name] = \
                    self._phase_totals.get(name, 0.0) + float(ms)
            for key in ("xla_compiles", "xla_compile_secs", "jax_traces"):
                v = (rec.get("counters") or {}).get(key)
                if v:
                    self._agg[key] = self._agg.get(key, 0.0) + float(v)
            self._agg["hist_passes"] = self._agg.get("hist_passes", 0.0) \
                + float(rec.get("hist_passes", 0.0))
            self._agg["collective_bytes"] = \
                self._agg.get("collective_bytes", 0.0) + \
                float(rec.get("collective_bytes", 0.0))
            self._agg["collective_ops"] = \
                self._agg.get("collective_ops", 0.0) + \
                float(rec.get("collective_ops", 0.0))
        elif t == "serve":
            status = rec.get("status")
            if status == "swap":
                self._agg["serve_swaps"] = \
                    self._agg.get("serve_swaps", 0) + 1
                return
            self._agg["serve_requests"] = \
                self._agg.get("serve_requests", 0) + 1
            self._agg["serve_rows"] = \
                self._agg.get("serve_rows", 0) + int(rec.get("rows", 0))
            if status != "ok":
                self._agg[f"serve_{status}"] = \
                    self._agg.get(f"serve_{status}", 0) + 1
                return
            v = float(rec.get("total_ms", 0.0))
            if len(self._serve_lat) < 65536:
                self._serve_lat.append(v)
            else:
                self._serve_lat[self._serve_lat_n % 65536] = v
            self._serve_lat_n += 1
            occ = rec.get("occupancy")
            if occ is not None:
                self._serve_occ_sum += float(occ)
                self._serve_occ_n += 1
        elif t == "explain":
            status = rec.get("status")
            self._agg["explain_requests"] = \
                self._agg.get("explain_requests", 0) + 1
            self._agg["explain_rows"] = \
                self._agg.get("explain_rows", 0) + int(rec.get("rows", 0))
            compiles = float(rec.get("xla_compiles", 0.0) or 0.0)
            if compiles:
                self._agg["explain_compiles"] = \
                    self._agg.get("explain_compiles", 0.0) + compiles
            if status != "ok":
                self._agg[f"explain_{status}"] = \
                    self._agg.get(f"explain_{status}", 0) + 1
                return
            v = float(rec.get("total_ms", 0.0))
            if len(self._explain_lat) < 65536:
                self._explain_lat.append(v)
            else:
                self._explain_lat[self._explain_lat_n % 65536] = v
            self._explain_lat_n += 1
        elif t == "checkpoint":
            event = rec.get("event")
            if event in ("save", "load", "fallback"):
                self._agg[f"ckpt_{event}s"] = \
                    self._agg.get(f"ckpt_{event}s", 0) + 1
            if event in ("save", "load"):
                self._agg[f"ckpt_{event}_ms"] = round(
                    self._agg.get(f"ckpt_{event}_ms", 0.0) +
                    float(rec.get("duration_ms", 0.0)), 3)
            if event == "save":
                self._agg["ckpt_bytes"] = \
                    self._agg.get("ckpt_bytes", 0) + \
                    int(rec.get("bytes", 0))
        elif t == "fleet":
            key = {
                "replica_start": "fleet_replica_starts",
                "replica_exit": "fleet_replica_exits",
                "replica_restart": "fleet_restarts",
                "circuit_open": "fleet_circuit_opens",
                "publish": "fleet_publishes",
                "publish_verified": "fleet_publish_verified",
                "publish_unverified": "fleet_publish_unverified",
                "publish_skip": "fleet_skips",
                "rollback": "fleet_rollbacks",
                "watch_error": "fleet_watch_errors",
            }.get(rec.get("event"))
            if key:
                self._agg[key] = self._agg.get(key, 0) + 1
        elif t == "continual":
            event = rec.get("event")
            key = {
                "batch": "continual_batches",
                "quarantine": "continual_quarantines",
                "backoff": "continual_backoffs",
                "stall_restart": "continual_stall_restarts",
                "nonfinite": "continual_nonfinite",
                "batch_error": "continual_batch_errors",
                "resume": "continual_resumes",
            }.get(event)
            if key:
                self._agg[key] = self._agg.get(key, 0) + 1
            if event == "batch":
                self._agg["continual_rows"] = \
                    self._agg.get("continual_rows", 0) + \
                    int(rec.get("rows", 0))
                self._agg["continual_batch_ms"] = round(
                    self._agg.get("continual_batch_ms", 0.0) +
                    float(rec.get("duration_ms", 0.0)), 3)
        elif t == "router":
            event = rec.get("event")
            if event == "breaker_open":
                self._agg["router_breaker_opens"] = \
                    self._agg.get("router_breaker_opens", 0) + 1
                return
            if event != "request":
                return
            status = rec.get("status")
            self._agg["router_requests"] = \
                self._agg.get("router_requests", 0) + 1
            self._agg["router_rows"] = \
                self._agg.get("router_rows", 0) + int(rec.get("rows", 0))
            self._agg["router_retries"] = \
                self._agg.get("router_retries", 0) + \
                int(rec.get("retries", 0))
            if rec.get("hedged"):
                self._agg["router_hedges"] = \
                    self._agg.get("router_hedges", 0) + 1
                if rec.get("hedge_won"):
                    self._agg["router_hedge_wins"] = \
                        self._agg.get("router_hedge_wins", 0) + 1
            if status != "ok":
                self._agg[f"router_{status}"] = \
                    self._agg.get(f"router_{status}", 0) + 1
                return
            v = float(rec.get("total_ms", 0.0))
            if len(self._router_lat) < 65536:
                self._router_lat.append(v)
            else:
                self._router_lat[self._router_lat_n % 65536] = v
            self._router_lat_n += 1
        elif t == "ingest":
            event = rec.get("event")
            key = {
                "chunk_read": "ingest_chunk_reads",
                "cache_write": "ingest_cache_writes",
                "verify_fail": "ingest_verify_fails",
                "prelude_hit": "ingest_prelude_hits",
                "fit_mappers": "ingest_mapper_fits",
                "backoff": "ingest_backoffs",
                "quarantine": "ingest_quarantines",
                "clamp": "ingest_clamps",
                "resume": "ingest_resumes",
            }.get(event)
            if key:
                self._agg[key] = self._agg.get(key, 0) + 1
            if event == "cache_write":
                self._agg["ingest_cached_bytes"] = \
                    self._agg.get("ingest_cached_bytes", 0) + \
                    int(rec.get("bytes", 0))
                if rec.get("rebin"):
                    self._agg["ingest_rebins"] = \
                        self._agg.get("ingest_rebins", 0) + 1
            elif event == "chunk_read":
                self._agg["ingest_rows"] = \
                    self._agg.get("ingest_rows", 0) + \
                    int(rec.get("rows", 0))
            elif event == "prefetch":
                self._agg["ingest_prefetch_windows"] = \
                    self._agg.get("ingest_prefetch_windows", 0) + \
                    int(rec.get("windows", 0))
                self._agg["ingest_prefetch_overlap_s"] = round(
                    self._agg.get("ingest_prefetch_overlap_s", 0.0) +
                    float(rec.get("overlap_s", 0.0)), 6)
            elif event == "ingest_done":
                self._agg["ingest_runs"] = \
                    self._agg.get("ingest_runs", 0) + 1
                self._agg["ingest_cache_hits"] = \
                    self._agg.get("ingest_cache_hits", 0) + \
                    int(rec.get("cache_hits", 0))
            elif event == "resume" and not rec.get("cache_hit", True):
                self._agg["ingest_resume_misses"] = \
                    self._agg.get("ingest_resume_misses", 0) + 1
        elif t == "pager":
            if rec.get("event") == "flush":
                for field, key in (("pages", "pager_pages"),
                                   ("bytes", "pager_bytes"),
                                   ("stalls", "pager_stalls")):
                    self._agg[key] = self._agg.get(key, 0) + \
                        int(rec.get(field, 0))
                self._agg["pager_overlap_s"] = round(
                    self._agg.get("pager_overlap_s", 0.0) +
                    float(rec.get("overlap_s", 0.0)), 6)
                self._agg["pager_wait_s"] = round(
                    self._agg.get("pager_wait_s", 0.0) +
                    float(rec.get("wait_s", 0.0)), 6)
        elif t == "recovery":
            key = {
                "detect": "recovery_detects",
                "remesh": "recovery_remeshes",
                "remesh_failed": "recovery_remesh_failures",
                "reshard": "recovery_reshards",
                "escalate": "recovery_escalations",
            }.get(rec.get("event"))
            if key:
                self._agg[key] = self._agg.get(key, 0) + 1
        elif t == "slo":
            self._agg["slo_evals"] = self._agg.get("slo_evals", 0) + 1
            status = rec.get("status")
            if status and status != "ok":
                self._agg[f"slo_{status}"] = \
                    self._agg.get(f"slo_{status}", 0) + 1
        elif t == "autoscale":
            action = rec.get("action")
            if action and action != "none":
                self._agg["autoscale_actions"] = \
                    self._agg.get("autoscale_actions", 0) + 1
                self._agg[f"autoscale_{action}"] = \
                    self._agg.get(f"autoscale_{action}", 0) + 1
            if rec.get("mode") == "degraded":
                self._agg["autoscale_degraded"] = \
                    self._agg.get("autoscale_degraded", 0) + 1
        elif t == "span":
            self._agg["spans"] = self._agg.get("spans", 0) + 1
        elif t == "capture":
            self._agg["captures"] = self._agg.get("captures", 0) + 1
        elif t == "predict":
            self._agg["predicts"] = self._agg.get("predicts", 0) + 1
            self._agg["predict_rows"] = \
                self._agg.get("predict_rows", 0) + int(rec.get("rows", 0))
            # cache counters arrive CUMULATIVE (the engine is process-
            # wide and predicts may run concurrently — per-call deltas
            # would steal each other's events); keep the latest
            cache = rec.get("cache") or {}
            for key in ("hits", "misses", "evictions"):
                if key in cache:
                    self._agg[f"predict_cache_{key}"] = float(cache[key])

    # ------------------------------------------------------------------
    def summary(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = {
                "backend": self._backend,
                "tier": self._tier,
                "duration_s": round(time.time() - self._t0, 3),
            }
            out.update({k: (round(v, 6) if isinstance(v, float) else v)
                        for k, v in self._agg.items()})
            if self._serve_lat:
                lat = sorted(self._serve_lat)
                out["serve_total_ms_p50"] = round(percentile(lat, 0.50), 3)
                out["serve_total_ms_p95"] = round(percentile(lat, 0.95), 3)
                out["serve_total_ms_p99"] = round(percentile(lat, 0.99), 3)
            if self._serve_occ_n:
                out["serve_mean_occupancy"] = round(
                    self._serve_occ_sum / self._serve_occ_n, 4)
            if self._explain_lat:
                lat = sorted(self._explain_lat)
                out["explain_total_ms_p50"] = \
                    round(percentile(lat, 0.50), 3)
                out["explain_total_ms_p95"] = \
                    round(percentile(lat, 0.95), 3)
                out["explain_total_ms_p99"] = \
                    round(percentile(lat, 0.99), 3)
            if self._router_lat:
                lat = sorted(self._router_lat)
                out["router_total_ms_p50"] = \
                    round(percentile(lat, 0.50), 3)
                out["router_total_ms_p95"] = \
                    round(percentile(lat, 0.95), 3)
                out["router_total_ms_p99"] = \
                    round(percentile(lat, 0.99), 3)
            if self._phase_totals:
                out["phase_totals_ms"] = {
                    k: round(v, 3) for k, v in sorted(
                        self._phase_totals.items(),
                        key=lambda kv: -kv[1])}
            return out

    def close(self, log: bool = True) -> None:
        """Emit ``run_end`` with the aggregate summary, Log.info it, and
        release the file handle.  Idempotent."""
        with self._lock:
            if self._closed:
                return
            s = self.summary()
            self.emit("run_end", summary=s)
            self._closed = True
            if self._fh is not None:
                try:
                    self._fh.close()
                finally:
                    self._fh = None
        with _OPEN_LOCK:
            if self in _OPEN_RECORDERS:
                _OPEN_RECORDERS.remove(self)
        if log:
            parts = [f"telemetry: {s.get('iterations', 0):.0f} iterations"
                     if s.get("iterations") else "telemetry:"]
            if s.get("xla_compiles"):
                parts.append(f"{s['xla_compiles']:.0f} XLA compiles "
                             f"({s.get('xla_compile_secs', 0.0):.1f}s)")
            if s.get("predicts"):
                parts.append(
                    f"{s['predicts']:.0f} predicts "
                    f"({s.get('predict_cache_hits', 0):.0f} cache hits / "
                    f"{s.get('predict_cache_misses', 0):.0f} misses)")
            if s.get("ckpt_saves") or s.get("ckpt_loads"):
                parts.append(
                    f"{s.get('ckpt_saves', 0):.0f} checkpoints "
                    f"({s.get('ckpt_bytes', 0) / 1e6:.1f} MB, "
                    f"{s.get('ckpt_save_ms', 0.0):.0f} ms), "
                    f"{s.get('ckpt_loads', 0):.0f} loads, "
                    f"{s.get('ckpt_fallbacks', 0):.0f} fallbacks")
            if s.get("fleet_publishes") or s.get("fleet_restarts") or \
                    s.get("fleet_skips") or s.get("fleet_rollbacks"):
                parts.append(
                    f"fleet: {s.get('fleet_publishes', 0):.0f} "
                    f"publishes, {s.get('fleet_skips', 0):.0f} skips, "
                    f"{s.get('fleet_rollbacks', 0):.0f} rollbacks, "
                    f"{s.get('fleet_restarts', 0):.0f} restarts")
            if s.get("recovery_detects") or s.get("recovery_remeshes") \
                    or s.get("recovery_reshards"):
                parts.append(
                    f"elastic: {s.get('recovery_detects', 0):.0f} "
                    f"shard-failure detections, "
                    f"{s.get('recovery_remeshes', 0):.0f} re-meshes, "
                    f"{s.get('recovery_reshards', 0):.0f} resume "
                    f"re-shards, "
                    f"{s.get('recovery_escalations', 0):.0f} "
                    f"escalations")
            if s.get("continual_batches") or s.get("continual_quarantines"):
                parts.append(
                    f"continual: {s.get('continual_batches', 0):.0f} "
                    f"batches ({s.get('continual_rows', 0):.0f} rows), "
                    f"{s.get('continual_quarantines', 0):.0f} "
                    f"quarantined, "
                    f"{s.get('continual_stall_restarts', 0):.0f} stall "
                    f"restarts, {s.get('continual_nonfinite', 0):.0f} "
                    f"non-finite aborts")
            if s.get("serve_requests"):
                parts.append(
                    f"{s['serve_requests']:.0f} serve requests "
                    f"(p50 {s.get('serve_total_ms_p50', 0):.1f} / "
                    f"p99 {s.get('serve_total_ms_p99', 0):.1f} ms, "
                    f"{s.get('serve_shed', 0):.0f} shed, "
                    f"{s.get('serve_timeout', 0):.0f} timeout, "
                    f"{s.get('serve_rejected', 0):.0f} rejected)")
            if s.get("slo_evals"):
                parts.append(
                    f"slo: {s['slo_evals']:.0f} evals "
                    f"({s.get('slo_fast_burn', 0):.0f} fast-burn, "
                    f"{s.get('slo_slow_burn', 0):.0f} slow-burn, "
                    f"{s.get('slo_budget_exhausted', 0):.0f} "
                    f"budget-exhausted)")
            if s.get("autoscale_actions"):
                parts.append(
                    f"autoscale: {s['autoscale_actions']:.0f} actions "
                    f"({s.get('autoscale_grow', 0):.0f} grow, "
                    f"{s.get('autoscale_drain', 0):.0f} drain, "
                    f"{s.get('autoscale_retune_shed', 0):.0f} retune)")
            if s.get("captures"):
                parts.append(f"{s['captures']:.0f} flight-recorder "
                             f"capture(s)")
            if self.path:
                parts.append(f"records -> {self.path}")
            Log.info("%s", ", ".join(parts))
            for name, ms in list(
                    (s.get("phase_totals_ms") or {}).items())[:6]:
                Log.info("telemetry phase %-24s %10.1f ms", name, ms)


# ----------------------------------------------------------------------
# schema lint
# ----------------------------------------------------------------------
def validate_record(rec: Any) -> List[str]:
    """Schema-lint one record; returns a list of problems (empty =
    valid).  The contract ``tools/triage_run.py --check`` enforces."""
    errs: List[str] = []
    if not isinstance(rec, dict):
        return ["record is not a JSON object"]
    for field, ftype in _COMMON_FIELDS:
        if field not in rec:
            errs.append(f"missing field {field!r}")
            continue
        v = rec[field]
        # bool is an int subclass; numeric fields must be real numbers
        ok = isinstance(v, (int, float) if ftype is float else ftype) \
            and not isinstance(v, bool)
        if ftype is str:
            ok = isinstance(v, str)
        if not ok:
            errs.append(f"field {field!r} has type {type(v).__name__}")
    if errs:
        return errs
    if rec["schema"] != SCHEMA_VERSION:
        errs.append(f"schema version {rec['schema']} != {SCHEMA_VERSION}")
    rtype = rec["type"]
    if rtype not in RECORD_TYPES:
        errs.append(f"unknown record type {rtype!r}")
        return errs
    for field, ftype in _TYPE_FIELDS.get(rtype, ()):
        if field not in rec:
            errs.append(f"{rtype}: missing field {field!r}")
        elif field != "engine" and isinstance(rec[field], bool):
            errs.append(f"{rtype}: field {field!r} is bool")
        elif not isinstance(rec[field], ftype):
            errs.append(f"{rtype}: field {field!r} has type "
                        f"{type(rec[field]).__name__}")
    return errs


def read_records(path: str) -> List[Dict[str, Any]]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def lint_file(path: str) -> Tuple[int, List[str]]:
    """(record count, errors).  Errors carry 1-based line numbers."""
    n = 0
    errs: List[str] = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            n += 1
            try:
                rec = json.loads(line)
            except ValueError as exc:
                errs.append(f"line {lineno}: not JSON ({exc})")
                continue
            for e in validate_record(rec):
                errs.append(f"line {lineno}: {e}")
    if n == 0:
        errs.append("no records")
    return n, errs


# ----------------------------------------------------------------------
# bench-artifact recovery parser (driver-wrapped BENCH_r*.json files)
# ----------------------------------------------------------------------
def _recover_json_line(text: str) -> Optional[Dict[str, Any]]:
    """Last parseable JSON object in ``text``.  Driver wrappers keep
    only the final bytes of stdout, so the last line's HEAD may be cut
    mid-key — recover by dropping everything before the first complete
    ``, "key":`` boundary and re-opening the object."""
    lines = [ln.strip() for ln in text.strip().splitlines()
             if ln.strip().endswith("}")]
    for line in reversed(lines):
        if not line.startswith("{"):
            cut = line.find(', "')
            if cut < 0:
                continue
            line = "{" + line[cut + 2:]
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict):
            return obj
    return None


def parse_bench_artifact(path: str) -> Optional[Dict[str, Any]]:
    """Parse one BENCH artifact into the bench's result dict.

    Accepts the driver wrapper form ``{"n", "cmd", "rc", "tail",
    "parsed"}`` (preferring ``parsed``, recovering from a truncated
    ``tail`` otherwise; ``rc != 0`` yields None) and the raw
    JSON-lines form ``bench.py`` itself prints.  A recovered dict must
    look like a bench result (carry a known bench key) — driver noise
    never becomes a benchmark row."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError:
        return None
    obj = None
    try:
        obj = json.loads(text)
    except ValueError:
        pass
    if isinstance(obj, dict) and "tail" in obj and "rc" in obj:
        if obj.get("rc") != 0:
            return None
        parsed = obj.get("parsed")
        rec = parsed if isinstance(parsed, dict) \
            else _recover_json_line(str(obj.get("tail", "")))
    elif isinstance(obj, dict):
        rec = obj
    else:
        rec = _recover_json_line(text)
    if not isinstance(rec, dict):
        return None
    known = ("metric", "value", "vs_baseline", "iters_per_s")
    if not any(k in rec for k in known):
        return None
    return rec
