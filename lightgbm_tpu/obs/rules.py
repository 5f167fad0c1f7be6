"""Anomaly rules over the telemetry record stream — ONE implementation
for three consumers:

- **offline triage** (``tools/triage_run.py``): feed a whole run's
  records, read :meth:`OnlineScanner.summary_anomalies` — the
  aggregate messages the triage report has always printed.
- **live tailing** (``triage_run.py --follow``): feed records as a
  training/serving process appends them, print what
  :meth:`OnlineScanner.feed` returns the moment a rule trips.
- **the flight recorder** (``obs/flight.py``): feed every record as it
  is emitted in-process; a firing rule triggers a ring dump + (device
  backends) a time-boxed ``jax.profiler`` capture, so the FIRST
  misbehaving TPU run leaves artifacts instead of needing a repro.

The warmup-exemption discipline (which fused blocks are legitimately
compile-bearing) lives here as :func:`superstep_warmups` — triage
imports it rather than keeping a second copy.

Stdlib-only; importable without jax.
"""
from __future__ import annotations

from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Tuple

__all__ = ["WARMUP_ITERS", "FLIGHT_TRIGGERS", "superstep_warmups",
           "OnlineScanner", "Anomaly"]

# compiles after this many iterations are anomalous: steady-state
# boosting re-runs the same jitted programs, so a climbing compile
# counter past warmup is a retrace storm (shape drift, cache thrash)
WARMUP_ITERS = 3

# rule codes that trip the flight recorder by default (the anomaly set
# ISSUE 13 names: retrace storm, pipelining-disabled,
# XLA-fallback-on-TPU, stall, rollback, nonfinite; ISSUE 17 adds the
# page-grade SLO states — a burning error budget is exactly the moment
# a ring dump is worth having)
FLIGHT_TRIGGERS = ("retrace_storm", "pipelining_disabled",
                   "xla_fallback", "stall", "rollback", "nonfinite",
                   "sweep_retrace", "slo_fast_burn",
                   "slo_budget_exhausted")

# (severity, code, message)
Anomaly = Tuple[str, str, str]


def superstep_warmups(records) -> Iterator[Tuple[Dict[str, Any], bool]]:
    """Yield ``(record, is_warmup)`` for every superstep record — the
    ONE definition of which fused blocks are compile-bearing.  The
    scan program compiles once per distinct block size k (the
    auto-sized tail block is a shorter scan) AND per mesh identity (a
    sharded run's scan is a different program per learner x shard
    count — the weak-scale grid runs several in one file), so the
    FIRST superstep of each (k, learner, shards, mesh-shape) is
    per-shape warmup — a data2d 4x2 and 2x4 cell share a shard count
    but compile distinct scans.
    Sharded runs get TWO warmup blocks: block 1 consumes the
    single-device score the unfused bias iteration left behind,
    block 2 runs on the mesh-replicated carry — same trace, two XLA
    executables by input sharding, both structural.  A ``run_start``
    resets the tracking: it marks a new process segment (a continual
    daemon restart appending to the same JSONL) or a new booster
    adopting the recorder (one booster per continual batch) — either
    way a fresh jitted scan whose first block per shape is warmup,
    not a retrace storm.  The first checkpoint save and the first
    load per segment also compile once (the mid-block alignment
    replay and the restore path run eager jnp ops), and those
    compiles land in the NEXT superstep's counter delta — that
    superstep is exempt too.  An elastic re-mesh (``recovery`` record,
    event remesh/reshard — parallel/elastic.py) rebuilds the fused
    scan for the survivor mesh: the next TWO superstep records are
    exempt whatever their (k, learner, shards) key says — a recovery
    back onto a width this run already trained at (transient loss, a
    weak-scale grid that visited it) re-COMPILES even though the key
    counter is past its allowance."""
    state = _WarmupTracker()
    for r in records:
        out = state.feed(r)
        if out is not None:
            yield out


class _WarmupTracker:
    """The stateful core of :func:`superstep_warmups`, shared with the
    online scanner (which cannot replay the stream per rule)."""

    def __init__(self):
        self.seen: Dict[Tuple[int, str, int], int] = {}
        self.ckpt_firsts: set = set()
        self.ckpt_pending = False
        self.remesh_grace = 0

    def feed(self, r: Dict[str, Any]
             ) -> Optional[Tuple[Dict[str, Any], bool]]:
        rtype = r.get("type")
        if rtype == "run_start":
            self.seen = {}
            self.ckpt_firsts = set()
            self.ckpt_pending = False
            return None
        if rtype == "recovery":
            if r.get("event") in ("remesh", "reshard"):
                self.remesh_grace = 2
            return None
        if rtype == "checkpoint":
            event = r.get("event")
            if event in ("save", "load") and \
                    event not in self.ckpt_firsts:
                self.ckpt_firsts.add(event)
                self.ckpt_pending = True
            return None
        if rtype != "superstep":
            return None
        shards = int(r.get("num_shards", 1))
        # the mesh SHAPE is part of the program identity: a 4x2 and a
        # 2x4 data2d cell share (k, learner, 8) but compile distinct
        # scans, so each earns its own warmup allowance (the 2-D
        # weak-scale grid runs several shapes in one file)
        shape = tuple(int(s) for s in (r.get("mesh_shape") or ()))
        key = (int(r.get("k", 1)), r.get("learner", ""), shards, shape)
        n = self.seen.get(key, 0)
        self.seen[key] = n + 1
        warm = (n < (2 if shards > 1 else 1) or self.ckpt_pending or
                self.remesh_grace > 0)
        self.ckpt_pending = False
        if self.remesh_grace > 0:
            self.remesh_grace -= 1
        return r, warm


class OnlineScanner:
    """Stateful record-at-a-time anomaly scanner.

    :meth:`feed` returns anomalies the moment their rule trips (the
    --follow / flight-recorder readout); :meth:`summary_anomalies`
    renders the run-level aggregates afterwards, byte-compatible with
    the triage report's historical messages for the rules that moved
    here (retrace storms, pipelining-disabled, XLA fallback)."""

    # instant rules need a debounce: one stall cascade must not dump
    # the flight ring per record.  All state is BOUNDED: the armed
    # flight recorder feeds one scanner for the process lifetime (a
    # continual daemon emits a run_start per batch for weeks), so
    # per-segment state keeps only the newest superstep's split
    # decision and the segment deque is capped.
    MAX_SEGMENTS = 256

    def __init__(self):
        self._warm = _WarmupTracker()
        # aggregate state for summary_anomalies
        self._ss_late = 0.0
        self._ss_secs = 0.0
        self._iter_late = 0.0
        self._iter_secs = 0.0
        self._overlap_total = 0
        self._overlap_stalled = 0
        # router rollups (serve/router.py): hedge/shed rates judged
        # once enough requests have been seen
        self._rt_requests = 0
        self._rt_hedges = 0
        self._rt_shed = 0
        # explanation-lane rollups (serve/server.py): a warmed explain
        # lane re-runs cached programs; compiles past the allowance
        # mean the publish warm-up missed a bucket or the shap cache
        # is thrashing
        self._ex_requests = 0
        self._ex_compiles = 0.0
        # streamed-ingest rollups (io/stream.py): prefetch overlap is
        # judged once enough windows have streamed, mirroring the
        # pipelining-disabled rule
        self._ing_prefetches = 0
        self._ing_windows = 0
        self._ing_overlap_s = 0.0
        self._ing_quarantines = 0
        self._ing_resume_miss: Optional[Dict[str, Any]] = None
        # device-block pager rollups (io/pager.py): like the streamed
        # ingest rule, prefetch overlap is judged once enough pages
        # have been served — paging with no measured overlap means the
        # page loop is fully serialized behind host fetches
        self._pg_flushes = 0
        self._pg_pages = 0
        self._pg_overlap_s = 0.0
        # SLO rollups (obs/slo.py): worst observed state per objective
        # plus the autoscaler's response, so the triage summary can say
        # "the budget burned AND the controller did/didn't react"
        self._slo_worst: Dict[str, Dict[str, Any]] = {}
        self._as_actions = 0
        self._as_degraded = 0
        # 2-D weak-scaling per-axis watch: feature-axis collective
        # bytes keyed by (k, F) across data-axis sizes R — on the
        # data2d schedule the tile merge is O(F) and routing shrinks
        # as 1/R, so feature-axis bytes must NOT grow with R
        self._ws_feat: Dict[Tuple[int, int], Dict[int, float]] = {}
        self._ws_bad: Optional[Tuple[int, float, int, float, int]] = None
        self._segs: "deque[Dict[str, Any]]" = \
            deque(maxlen=self.MAX_SEGMENTS)
        self._cur_seg: Optional[Dict[str, Any]] = None
        # one-shot instant flags
        self._fired: set = set()

    # -- helpers -------------------------------------------------------
    def _seg_backend(self) -> str:
        return self._cur_seg["backend"] if self._cur_seg else ""

    # -- the scanner ---------------------------------------------------
    def feed(self, r: Dict[str, Any]) -> List[Anomaly]:
        out: List[Anomaly] = []
        rtype = r.get("type")
        if rtype == "run_start":
            self._cur_seg = {
                "backend": str(r.get("backend", "")).lower(),
                "tier": r.get("tier") or {}, "ss_last": None,
                "fallback_fired": False}
            self._segs.append(self._cur_seg)
        warm_out = self._warm.feed(r)
        if rtype == "iteration":
            if int(r.get("iter", 0)) >= WARMUP_ITERS:
                c = (r.get("counters") or {}).get("xla_compiles", 0)
                if c:
                    secs = (r.get("counters") or {}).get(
                        "xla_compile_secs", 0.0)
                    self._iter_late += c
                    self._iter_secs += secs
                    out.append((
                        "HIGH", "retrace_storm",
                        f"retrace storm: {c:.0f} XLA compile(s) "
                        f"({secs:.1f}s) at steady-state iteration "
                        f"{r.get('iter')}"))
        elif rtype == "superstep" and warm_out is not None:
            rec, warm = warm_out
            if not warm:
                c = (rec.get("counters") or {}).get("xla_compiles", 0)
                if c:
                    secs = (rec.get("counters") or {}).get(
                        "xla_compile_secs", 0.0)
                    self._ss_late += c
                    self._ss_secs += secs
                    out.append((
                        "HIGH", "retrace_storm",
                        f"superstep retrace storm: {c:.0f} XLA "
                        f"compile(s) ({secs:.1f}s) on a repeated "
                        f"same-k super-step (iter "
                        f"{rec.get('iter')}, k={rec.get('k')})"))
                if int(rec.get("pipeline_depth", 0)) > 0:
                    self._overlap_total += 1
                    if float(rec.get("fetch_overlap_s", 0.0)) < 1e-5:
                        self._overlap_stalled += 1
                    if ("pipelining_disabled" not in self._fired and
                            self._overlap_stalled >= 4 and
                            self._overlap_stalled >
                            self._overlap_total / 2):
                        self._fired.add("pipelining_disabled")
                        out.append((
                            "MED", "pipelining_disabled",
                            f"superstep pipelining silently disabled: "
                            f"{self._overlap_stalled}/"
                            f"{self._overlap_total} fused blocks show "
                            f"~zero fetch overlap at "
                            f"pipeline_depth > 0"))
            ax_b = rec.get("collective_bytes_axis") or {}
            shape2 = rec.get("mesh_shape") or []
            if len(shape2) == 2 and "feature" in ax_b:
                rr, ff = int(shape2[0]), int(shape2[1])
                per_it = float(ax_b["feature"]) / \
                    max(int(rec.get("k", 1)), 1)
                grid = self._ws_feat.setdefault(
                    (int(rec.get("k", 1)), ff), {})
                grid[rr] = per_it
                if "weakscale_axis" not in self._fired:
                    for r0 in sorted(grid):
                        b0, b1 = grid[r0], grid[max(grid)]
                        if r0 < max(grid) and b1 > 1.10 * b0 + 1024:
                            self._fired.add("weakscale_axis")
                            self._ws_bad = (r0, b0, max(grid), b1, ff)
                            out.append((
                                "MED", "weakscale_axis",
                                f"feature-axis collective bytes GROW "
                                f"with the data-axis size: "
                                f"{b1:.0f} B/iter at mesh "
                                f"{max(grid)}x{ff} vs {b0:.0f} B/iter "
                                f"at {r0}x{ff} — the 2-D schedule "
                                f"keeps the tile merge O(F) and "
                                f"shrinks routing as 1/R, so "
                                f"feature-axis traffic must not "
                                f"scale with R"))
                            break
            if self._cur_seg is not None and "split_kernel" in rec:
                self._cur_seg["ss_last"] = (rec.get("split_kernel"),
                                            rec.get("split_fallback"))
                backend = self._seg_backend()
                reason = rec.get("split_fallback")
                if (backend and backend not in ("cpu", "unknown", "?")
                        and rec.get("split_kernel") == "xla"
                        and reason
                        and "split_kernel=xla" not in str(reason)
                        and not self._cur_seg["fallback_fired"]):
                    self._cur_seg["fallback_fired"] = True
                    out.append((
                        "MED", "xla_fallback",
                        f"split kernel fell back to XLA on a "
                        f"{backend} backend: {reason}"))
        elif rtype == "sweep":
            # battery contract: members of one static group share ONE
            # compiled program — any compiles beyond groups mean the
            # vmap lane silently retraced per model (the exact cost
            # the battery exists to amortize)
            rpm = float(r.get("retraces_per_model", 0.0) or 0.0)
            if rpm > 0:
                out.append((
                    "MED", "sweep_retrace",
                    f"sweep battery retraced after warmup: "
                    f"{rpm:.2f} extra XLA compile(s) per model "
                    f"({r.get('xla_compiles', '?')} compiles for "
                    f"{r.get('groups', '?')} static group(s), "
                    f"{r.get('models', '?')} models)"))
        elif rtype == "continual":
            event = r.get("event")
            if event == "stall_restart":
                out.append((
                    "MED", "stall",
                    f"train step on {r.get('batch', '?')} stalled "
                    f"{float(r.get('stalled_s', 0.0)):.1f}s and was "
                    f"abandoned by the watchdog (attempt "
                    f"{r.get('attempt', '?')})"))
            elif event == "nonfinite":
                out.append((
                    "HIGH", "nonfinite",
                    f"numerical-health guard tripped: non-finite "
                    f"training state at iteration "
                    f"{r.get('iter', '?')} "
                    f"({r.get('phase', '?')})"))
        elif rtype == "fleet":
            event = r.get("event")
            if event == "rollback":
                out.append((
                    "HIGH", "rollback",
                    f"deploy ROLLED BACK: {r.get('from_id', '?')} -> "
                    f"{r.get('to_id', '?')} ({r.get('reason', '?')}: "
                    f"{str(r.get('detail', ''))[:120]})"))
            elif event == "circuit_open":
                out.append((
                    "HIGH", "circuit_open",
                    f"replica circuit breaker OPEN on slot "
                    f"{r.get('slot', '?')} (crash loop?)"))
        elif rtype == "router":
            event = r.get("event")
            if event == "breaker_open":
                out.append((
                    "HIGH", "router_breaker",
                    f"router circuit breaker OPEN on backend "
                    f"{r.get('backend', '?')} "
                    f"({str(r.get('detail', ''))[:120]})"))
            elif event == "request":
                self._rt_requests += 1
                if r.get("hedged"):
                    self._rt_hedges += 1
                if r.get("status") == "shed":
                    self._rt_shed += 1
                n = self._rt_requests
                if n >= 50:
                    if ("router_hedge_rate" not in self._fired and
                            self._rt_hedges > 0.20 * n):
                        self._fired.add("router_hedge_rate")
                        out.append((
                            "MED", "router_hedge_rate",
                            f"router hedge rate "
                            f"{self._rt_hedges}/{n} requests (> 20%) "
                            f"— hedging is rescuing the tail "
                            f"constantly; a backend is slow, not "
                            f"occasionally unlucky"))
                    if ("router_shed_rate" not in self._fired and
                            self._rt_shed > 0.05 * n):
                        self._fired.add("router_shed_rate")
                        out.append((
                            "HIGH", "router_shed_rate",
                            f"router budget-shed rate "
                            f"{self._rt_shed}/{n} requests (> 5%) — "
                            f"admission budgets are turning real "
                            f"traffic away; raise route_rows_per_s "
                            f"or add replicas"))
        elif rtype == "explain":
            # steady-state explain contract: publish pre-warms the
            # whole ShapEngine bucket ladder, so a served explain
            # request carrying a compile delta means a bucket was
            # missed or evicted.  Same warmup allowance as the
            # training retrace rule; one-shot, totals in the summary.
            self._ex_requests += 1
            c = float(r.get("xla_compiles", 0.0) or 0.0)
            if c and self._ex_requests > WARMUP_ITERS:
                self._ex_compiles += c
                if "explain_compile" not in self._fired:
                    self._fired.add("explain_compile")
                    out.append((
                        "MED", "explain_compile",
                        f"steady-state explain compiled: {c:.0f} XLA "
                        f"compile(s) on served explain request "
                        f"#{self._ex_requests} — the publish warm-up "
                        f"must cover every explain bucket "
                        f"(serve/registry.py warmup; shap cache "
                        f"eviction?)"))
        elif rtype == "slo":
            status = r.get("status", "")
            obj = str(r.get("objective", "?"))
            prev = self._slo_worst.get(obj)
            rank = {"ok": 0, "scrape_error": 1, "slow_burn": 2,
                    "fast_burn": 3, "budget_exhausted": 4}
            if prev is None or rank.get(status, 0) >= \
                    rank.get(prev.get("status", ""), 0):
                self._slo_worst[obj] = r
            # multi-window multi-burn-rate alerting: the SLO engine
            # already did the window math — the scanner just maps its
            # verdicts to anomalies, debounced per (code, objective) so
            # a sustained burn pages once, not once per scrape
            if status == "budget_exhausted" and \
                    ("slo_budget_exhausted", obj) not in self._fired:
                self._fired.add(("slo_budget_exhausted", obj))
                out.append((
                    "HIGH", "slo_budget_exhausted",
                    f"SLO error budget EXHAUSTED for objective "
                    f"{obj} (target {r.get('target', '?')}) — every "
                    f"further bad event is an SLO violation with no "
                    f"budget left to absorb it"))
            elif status == "fast_burn" and \
                    ("slo_fast_burn", obj) not in self._fired:
                self._fired.add(("slo_fast_burn", obj))
                eta = float(r.get("exhaustion_eta_s", -1.0) or -1.0)
                eta_txt = (f"; budget exhausts in ~{eta / 60:.0f} min "
                           f"at this rate" if eta > 0 else "")
                out.append((
                    "HIGH", "slo_fast_burn",
                    f"SLO fast burn on objective {obj}: burn rate "
                    f"{float(r.get('burn_fast', 0.0)):.1f}x on the "
                    f"fast window (confirmed on the mid window) — "
                    f"page-grade{eta_txt}"))
            elif status == "slow_burn" and \
                    ("slo_slow_burn", obj) not in self._fired:
                self._fired.add(("slo_slow_burn", obj))
                out.append((
                    "MED", "slo_slow_burn",
                    f"SLO slow burn on objective {obj}: burn rate "
                    f"{float(r.get('burn_slow', 0.0)):.1f}x on the "
                    f"slow window — ticket-grade budget leak"))
        elif rtype == "autoscale":
            if r.get("mode") == "degraded":
                self._as_degraded += 1
                if "autoscale_degraded" not in self._fired:
                    self._fired.add("autoscale_degraded")
                    out.append((
                        "MED", "autoscale_degraded",
                        f"autoscaler control step failed and degraded "
                        f"to no-op ({str(r.get('error', '?'))[:120]}) "
                        f"— the fleet keeps serving at its current "
                        f"size, but nobody is steering"))
            elif r.get("action") not in (None, "none"):
                self._as_actions += 1
        elif rtype == "ingest":
            event = r.get("event")
            if event == "quarantine":
                self._ing_quarantines += 1
                out.append((
                    "HIGH", "ingest_quarantine",
                    f"streamed-ingest chunk "
                    f"{r.get('chunk', r.get('batch', '?'))} "
                    f"QUARANTINED ({r.get('reason', '?')}: "
                    f"{str(r.get('error', ''))[:120]}) — the training "
                    f"matrix cannot silently lose rows; ingest fails "
                    f"loudly after binning every other chunk"))
            elif event == "resume" and not r.get("cache_hit", True):
                self._ing_resume_miss = r
                out.append((
                    "MED", "ingest_cache_miss",
                    f"streamed-ingest cache MISS on resume (expected "
                    f"{r.get('expected_key', '?')}, got "
                    f"{r.get('actual_key', '?')}, "
                    f"{r.get('rebinned', 0)} chunk(s) re-binned) — a "
                    f"re-bin the checkpoint manifest should have "
                    f"prevented"))
            elif event == "prefetch" and r.get("prefetch"):
                self._ing_prefetches += 1
                self._ing_windows += int(r.get("windows", 0))
                self._ing_overlap_s += float(r.get("overlap_s", 0.0))
                if ("ingest_prefetch_stalled" not in self._fired and
                        self._ing_windows >= 8 and
                        self._ing_overlap_s < 1e-5):
                    self._fired.add("ingest_prefetch_stalled")
                    out.append((
                        "MED", "ingest_prefetch_stalled",
                        f"stream prefetch overlap ~0 across "
                        f"{self._ing_windows} upload windows with "
                        f"double-buffering enabled — window prep is "
                        f"serializing behind the device copies "
                        f"(stream_host_budget_mb too small? prefetch "
                        f"thread starved?)"))
        elif rtype == "pager":
            if r.get("event") == "flush":
                self._pg_flushes += 1
                self._pg_pages += int(r.get("pages", 0))
                self._pg_overlap_s += float(r.get("overlap_s", 0.0))
                if ("pager_no_overlap" not in self._fired and
                        self._pg_pages >= 16 and
                        self._pg_overlap_s < 1e-5):
                    self._fired.add("pager_no_overlap")
                    out.append((
                        "MED", "pager_no_overlap",
                        f"device-block pager served {self._pg_pages} "
                        f"pages with prefetch overlap ~0 — page prep "
                        f"is serializing behind the histogram passes "
                        f"(prefetch thread disabled or starved, or "
                        f"hbm_budget_mb so small every page misses) — "
                        f"paging is costing full fetch latency per "
                        f"page"))
        elif rtype == "checkpoint" and r.get("event") == "fallback":
            out.append((
                "HIGH", "ckpt_fallback",
                f"checkpoint candidate rejected "
                f"(corrupt/truncated): "
                f"{str(r.get('error', '?'))[:160]}"))
        elif rtype == "recovery" and r.get("event") == "escalate":
            out.append((
                "HIGH", "escalate",
                f"elastic recovery ESCALATED "
                f"({r.get('reason', '?')})"))
        return out

    # -- run-level aggregates (the triage report's historical text) ---
    def summary_anomalies(self) -> List[Tuple[str, str]]:
        out: List[Tuple[str, str]] = []
        if self._rt_requests >= 20:
            n = self._rt_requests
            if self._rt_hedges > 0.20 * n:
                out.append(("MED", f"router hedge rate "
                                   f"{self._rt_hedges}/{n} requests "
                                   f"(> 20%) — the tail-latency hedge "
                                   f"is a rescue path, not a steady "
                                   f"state; a backend is consistently "
                                   f"slow"))
            if self._rt_shed > 0.05 * n:
                out.append(("HIGH", f"router budget-shed rate "
                                    f"{self._rt_shed}/{n} requests "
                                    f"(> 5%) — admission budgets are "
                                    f"turning real traffic away; "
                                    f"raise route_rows_per_s or add "
                                    f"replicas"))
        if self._ex_compiles:
            out.append(("MED", f"explanation lane compiled at steady "
                               f"state: {self._ex_compiles:.0f} XLA "
                               f"compile(s) across "
                               f"{self._ex_requests} served explain "
                               f"request(s) — the zero-steady-state-"
                               f"compile contract extends to "
                               f"/explain; check the publish warm-up "
                               f"bucket set and the shap engine's "
                               f"LRU capacity"))
        for obj in sorted(self._slo_worst):
            r = self._slo_worst[obj]
            status = r.get("status", "")
            if status in ("", "ok", "scrape_error"):
                continue
            sev = "MED" if status == "slow_burn" else "HIGH"
            reacted = (f"; autoscaler took {self._as_actions} "
                       f"action(s)" if self._as_actions else
                       "; autoscaler took no action")
            out.append((sev, f"SLO objective {obj} worst state "
                             f"{status.upper()} (burn fast/slow "
                             f"{float(r.get('burn_fast', 0.0)):.1f}x/"
                             f"{float(r.get('burn_slow', 0.0)):.1f}x, "
                             f"budget remaining "
                             f"{float(r.get('budget_remaining', 0.0)):.0%})"
                             f"{reacted}"))
        if self._as_degraded:
            out.append(("MED", f"autoscaler degraded to no-op on "
                               f"{self._as_degraded} control step(s) — "
                               f"the fleet kept serving, unsteered"))
        if self._ing_quarantines:
            out.append(("HIGH", f"streamed ingest quarantined "
                                f"{self._ing_quarantines} chunk(s) — "
                                f"transient-read retries exhausted or "
                                f"deterministic parse failures; the "
                                f"retry run only owes the quarantined "
                                f"chunks (every other one is "
                                f"published)"))
        if self._ing_resume_miss is not None:
            r = self._ing_resume_miss
            out.append(("MED", f"streamed-ingest cache miss on resume "
                               f"(expected {r.get('expected_key', '?')}"
                               f", got {r.get('actual_key', '?')}) — "
                               f"the checkpoint manifest recorded a "
                               f"published cache this resume re-binned "
                               f"anyway"))
        if self._ing_prefetches and self._ing_windows >= 8 and \
                self._ing_overlap_s < 1e-5:
            out.append(("MED", f"stream prefetch overlap ~0 across "
                               f"{self._ing_windows} host->device "
                               f"upload windows with double-buffering "
                               f"enabled — the window prep cost is "
                               f"fully serialized again (mirrors the "
                               f"pipelining-disabled rule)"))
        if self._pg_flushes and self._pg_pages >= 16 and \
                self._pg_overlap_s < 1e-5:
            out.append(("MED", f"device-block pager overlap ~0 across "
                               f"{self._pg_pages} served pages — the "
                               f"out-of-core page loop ran with fetch "
                               f"latency fully exposed (no prefetch "
                               f"overlap was ever measured)"))
        if self._ws_bad is not None:
            r0, b0, r1, b1, ff = self._ws_bad
            out.append(("MED", f"2-D weak-scaling per-axis anomaly: "
                               f"feature-axis collective bytes grew "
                               f"from {b0:.0f} B/iter ({r0}x{ff}) to "
                               f"{b1:.0f} B/iter ({r1}x{ff}) as the "
                               f"data axis widened — the tile merge is "
                               f"O(F) and routing shrinks as 1/R, so "
                               f"this traffic should be flat or "
                               f"falling in R"))
        if self._ss_late:
            out.append(("HIGH", f"superstep retrace storm: "
                                f"{self._ss_late:.0f} "
                                f"XLA compiles ({self._ss_secs:.1f}s) on "
                                f"repeated same-k super-steps — the fused "
                                f"scan should compile once per block "
                                f"size"))
        if self._iter_late:
            out.append(("HIGH", f"retrace storm: {self._iter_late:.0f} XLA "
                                f"compiles ({self._iter_secs:.1f}s) AFTER "
                                f"iteration {WARMUP_ITERS} — steady state "
                                f"should re-run cached programs"))
        if self._overlap_total:
            stalled = self._overlap_stalled
            if stalled > self._overlap_total / 2:
                out.append(("MED", f"superstep pipelining silently "
                                   f"disabled: {stalled}/"
                                   f"{self._overlap_total} "
                                   f"fused blocks show ~zero fetch "
                                   f"overlap at pipeline_depth > 0 — "
                                   f"every block is draining the "
                                   f"in-flight queue (learning_rates "
                                   f"schedule? eligibility flapping?), "
                                   f"so the per-block fetch RTT is "
                                   f"un-hidden again"))
        for seg in self._segs:
            backend = seg["backend"]
            if not backend or backend in ("cpu", "unknown", "?"):
                continue
            if seg["ss_last"]:
                sk, reason = seg["ss_last"]
            else:
                sk = seg["tier"].get("split_kernel")
                reason = (seg["tier"].get("gates") or {}).get("split")
            if sk == "xla" and reason and \
                    "split_kernel=xla" not in reason:
                out.append(("MED", f"split kernel fell back to XLA on a "
                                   f"{backend} backend: {reason} — the "
                                   f"on-chip split scan is off, every "
                                   f"grow level scans its histograms "
                                   f"in XLA"))
                break
        return out
