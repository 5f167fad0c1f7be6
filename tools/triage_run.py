"""Triage a telemetry run: schema lint, anomaly scan, run-vs-run diff.

Reads the schema-versioned JSONL a training/inference run wrote
(``telemetry_file=<path>``, ``utils/telemetry.py``) and prints the
top phase / retrace / tier anomalies — the "is the chip down or is the
code broken?" readout round 5 didn't have.

    python tools/triage_run.py RUN.jsonl                 # triage
    python tools/triage_run.py RUN.jsonl --baseline PRIOR.jsonl
    python tools/triage_run.py RUN.jsonl --check         # schema lint
    python tools/triage_run.py RUN.jsonl --check --quiet # CI gate
    python tools/triage_run.py RUN.jsonl --follow        # live tail

``--check`` exits non-zero on any malformed record (CI's schema gate);
``--baseline`` compares per-iteration phase medians against a prior
run's JSONL and ranks the regressions; ``--follow`` tails a LIVE
stream and prints anomalies the moment their rule trips — the same
online rule evaluator (``lightgbm_tpu/obs/rules.py``) the in-process
flight recorder (``obs/flight.py``) triggers captures from, so the
offline report, the live tail and the capture triggers can never
disagree about what counts as an anomaly.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from lightgbm_tpu.obs import rules as obs_rules  # noqa: E402
from lightgbm_tpu.utils.telemetry import (  # noqa: E402
    lint_file, read_records)

# re-exported from the shared rule module (obs/rules.py) — the one
# definition of steady-state warmup and fused-block compile exemption
WARMUP_ITERS = obs_rules.WARMUP_ITERS
_superstep_warmups = obs_rules.superstep_warmups


def _median(vals):
    vals = sorted(vals)
    return vals[len(vals) // 2] if vals else 0.0


def _block_k(r):
    """Iterations a record stands for: 1, or k for a fused superstep."""
    return max(int(r.get("k", 1)), 1) if r.get("type") == "superstep" \
        else 1


def phase_medians(records):
    """{phase: median ms/iter} over the run's iteration records.
    A fused ``superstep`` record carries a whole K-iteration block:
    its phase deltas are normalized by k and weighted k-fold, so the
    median stays a per-iteration figure."""
    acc = {}
    for r in records:
        if r.get("type") not in ("iteration", "superstep"):
            continue
        k = _block_k(r)
        for name, ms in (r.get("phases_ms") or {}).items():
            acc.setdefault(name, []).extend([float(ms) / k] * k)
    return {name: _median(vals) for name, vals in acc.items()}


def iter_durations(records):
    """Per-iteration wall times; a superstep record expands to k
    entries of duration/k — the K-fold drop in per-iteration time the
    fused path delivers must read as throughput, not as an anomaly."""
    out = []
    for r in records:
        if r.get("type") not in ("iteration", "superstep"):
            continue
        k = _block_k(r)
        out.extend([float(r.get("duration_ms", 0.0)) / k] * k)
    return out


def scan_anomalies(records):
    """Ordered (severity, message) anomaly list for one run.

    The compile/pipelining/split-kernel rules live in the SHARED rule
    module (``obs/rules.py`` — the flight recorder and ``--follow``
    evaluate them online); this function renders their run-level
    aggregates and keeps the offline-only statistics (weak scaling,
    spike checks, subsystem rollup scans) local."""
    out = []
    scanner = obs_rules.OnlineScanner()
    for r in records:
        scanner.feed(r)
    out.extend(scanner.summary_anomalies())
    # weak-scaling regression: sharded super-steps at DIFFERENT mesh
    # sizes in one run (the weak-scale bench grid, or a resumed run on
    # a wider mesh) whose per-iteration time grows with the shard
    # count while per-shard collective bytes stay ~constant — the
    # dispatch/host-sync overhead signature WEAKSCALE.json measured
    # through r05, which the single-program sharded scan exists to
    # kill.  Ignores each mesh identity's compile-bearing warmup
    # blocks (_superstep_warmups).
    by_shards = {}
    for r, warm in _superstep_warmups(records):
        if warm or "num_shards" not in r:
            continue
        d = int(r["num_shards"])
        k = _block_k(r)
        ent = by_shards.setdefault(d, {"iter_ms": [], "bytes": []})
        ent["iter_ms"].append(float(r.get("duration_ms", 0.0)) / k)
        ent["bytes"].append(float(r.get("collective_bytes", 0.0)) / k)
    if len(by_shards) >= 2:
        lo_d, hi_d = min(by_shards), max(by_shards)
        t_lo = _median(by_shards[lo_d]["iter_ms"])
        t_hi = _median(by_shards[hi_d]["iter_ms"])
        b_lo = _median(by_shards[lo_d]["bytes"])
        b_hi = _median(by_shards[hi_d]["bytes"])
        bytes_flat = b_lo <= 0 or abs(b_hi - b_lo) <= 0.25 * b_lo
        if t_lo > 0 and t_hi > 1.5 * t_lo and bytes_flat:
            out.append(("HIGH", f"weak-scaling regression: "
                                f"{t_hi / t_lo:.1f}x per-iteration "
                                f"time from {lo_d} to {hi_d} shards at "
                                f"~constant per-shard collective bytes "
                                f"({b_hi / 1e3:.0f} KB/iter) — "
                                f"per-shard dispatch or host-sync "
                                f"overhead, not the wire (expect flat "
                                f"on one real device per shard; a "
                                f"core-oversubscribed dryrun mesh "
                                f"timeshares compute and trips this "
                                f"by design)"))
    # steady-state per-iteration durations: unfused warmup iterations
    # AND the first superstep of each block size are compile-bearing
    # by design — only repeats count toward the spike check.  The two
    # populations are judged SEPARATELY: a mixed run (fused blocks
    # plus a few legitimate unfused iterations after an eligibility
    # drift) would otherwise read the unfused iterations as spikes
    # against the K-fold-lower fused median.
    steady_unfused = [
        float(r.get("duration_ms", 0.0)) for r in records
        if r.get("type") == "iteration"
        and r.get("iter", 0) >= WARMUP_ITERS]
    steady_fused = {}          # per (learner, shards): different mesh
    for r, warm in _superstep_warmups(records):  # sizes are different
        if warm:                                 # cost populations
            continue
        k = _block_k(r)
        mesh = (r.get("learner", ""), int(r.get("num_shards", 1)))
        steady_fused.setdefault(mesh, []).extend(
            [float(r.get("duration_ms", 0.0)) / k] * k)
    pops = [("iteration", steady_unfused)]
    for (learner, shards), vals in sorted(steady_fused.items()):
        label = "fused per-iteration" if not learner else \
            f"fused per-iteration ({learner}x{shards})"
        pops.append((label, vals))
    for label, steady in pops:
        if len(steady) <= WARMUP_ITERS:
            continue
        med = _median(steady)
        worst = max(steady)
        if med > 0 and worst > 3 * med:
            out.append(("MED", f"{label} time spike: worst steady "
                               f"{worst:.0f} ms vs median "
                               f"{med:.0f} ms"))
    preds = [r for r in records if r.get("type") == "predict"]
    if preds:
        cache = preds[-1].get("cache") or {}
        if cache.get("evictions", 0) > 0:
            out.append(("MED", f"predict compile-cache thrash: "
                               f"{cache['evictions']} evictions "
                               f"(predict_cache_slots too small for "
                               f"the serving shape mix)"))
    serves = [r for r in records if r.get("type") == "serve"
              and r.get("status") != "swap"]
    if serves:
        n = len(serves)
        bad = sum(1 for r in serves
                  if r.get("status") in ("shed", "timeout", "rejected"))
        if bad and bad / n > 0.05:
            out.append(("MED", f"serving under pressure: {bad}/{n} "
                               f"requests shed/timed-out/rejected — "
                               f"raise serve_queue_rows or add "
                               f"serve_workers, or the clients must "
                               f"honor retry-after"))
        occ = [r["occupancy"] for r in serves
               if r.get("status") == "ok" and "occupancy" in r]
        if occ and len(occ) >= 20 and sum(occ) / len(occ) < 0.05:
            out.append(("MED", f"serve batch occupancy "
                               f"{sum(occ) / len(occ):.3f} — batches "
                               f"are nearly all padding; shrink "
                               f"serve_max_batch_rows or raise "
                               f"serve_batch_wait_ms"))
    fleet = [r for r in records if r.get("type") == "fleet"]
    if fleet:
        skips = [r for r in fleet if r.get("event") == "publish_skip"]
        corrupt = [r for r in skips if r.get("reason") == "manifest"]
        canary = [r for r in skips if r.get("reason") == "canary"]
        if corrupt:
            out.append(("HIGH", f"deploy pipeline produced "
                                f"{len(corrupt)} CORRUPT snapshot(s) "
                                f"the watcher refused to publish; "
                                f"last: {corrupt[-1].get('path', '?')} "
                                f"({str(corrupt[-1].get('error', '?'))[:120]})"))
        if canary:
            out.append(("MED", f"{len(canary)} snapshot(s) failed "
                               f"canary scoring and were not "
                               f"published; last: "
                               f"{canary[-1].get('path', '?')} "
                               f"({str(canary[-1].get('error', '?'))[:120]})"))
        rollbacks = [r for r in fleet if r.get("event") == "rollback"]
        if rollbacks:
            last = rollbacks[-1]
            out.append(("HIGH", f"deploy ROLLED BACK {len(rollbacks)} "
                                f"time(s): {last.get('from_id', '?')} "
                                f"-> {last.get('to_id', '?')} "
                                f"({last.get('reason', '?')}: "
                                f"{str(last.get('detail', ''))[:120]})"))
        circuits = [r for r in fleet if r.get("event") == "circuit_open"]
        if circuits:
            out.append(("HIGH", f"replica circuit breaker OPEN on "
                                f"slot(s) "
                                f"{sorted({r.get('slot') for r in circuits})}"
                                f" — fleet is degraded (crash loop?)"))
        restarts = [r for r in fleet
                    if r.get("event") == "replica_restart"]
        if restarts:
            out.append(("MED", f"{len(restarts)} replica restart(s) — "
                               f"replicas crashed or hung under "
                               f"supervision"))
        unverified = [r for r in fleet
                      if r.get("event") == "publish_unverified"]
        if unverified:
            out.append(("MED", f"{len(unverified)} deploy(s) closed "
                               f"their observation window UNVERIFIED "
                               f"(too little traffic for a verdict); "
                               f"last: "
                               f"{unverified[-1].get('model_id', '?')}"))
        errors = [r for r in fleet if r.get("event") == "watch_error"]
        if errors:
            out.append(("MED", f"{len(errors)} watcher error(s); "
                               f"last: "
                               f"{str(errors[-1].get('error', '?'))[:140]}"))
    routers = [r for r in records if r.get("type") == "router"]
    if routers:
        # rate-based router rules (hedge > 20% MED, budget-shed > 5%
        # HIGH) come from the shared scanner's summary above; the
        # breaker scan is offline-only rollup detail
        opens = [r for r in routers if r.get("event") == "breaker_open"]
        if opens:
            out.append(("HIGH", f"router circuit breaker OPENED "
                                f"{len(opens)} time(s); backends: "
                                f"{sorted({r.get('backend', '?') for r in opens})}"
                                f" — a backend failed repeatedly and "
                                f"left the balancer rotation"))
        upstream = [r for r in routers
                    if r.get("event") == "request" and
                    r.get("status") in ("upstream", "no_backend",
                                        "timeout")]
        reqs = [r for r in routers if r.get("event") == "request"]
        if upstream and len(upstream) / max(len(reqs), 1) > 0.01:
            out.append(("HIGH", f"router failed to mask "
                                f"{len(upstream)}/{len(reqs)} "
                                f"requests (upstream/no_backend/"
                                f"timeout > 1%) — retries + hedging "
                                f"ran out of healthy backends or "
                                f"budget"))
    recov = [r for r in records if r.get("type") == "recovery"]
    if recov:
        remeshes = [r for r in recov if r.get("event") == "remesh"]
        if len(remeshes) >= 2:
            path = " -> ".join(
                [str(remeshes[0].get("from_shards", "?"))] +
                [str(r.get("to_shards", "?")) for r in remeshes])
            out.append(("HIGH", f"repeated re-mesh: {len(remeshes)} "
                                f"shard-loss recoveries in ONE run "
                                f"({path} shards) — the fleet is "
                                f"shedding shards faster than one "
                                f"preemption; check the slice health "
                                f"before trusting the wall clock"))
        elif remeshes:
            r = remeshes[-1]
            out.append(("MED", f"elastic re-mesh: "
                               f"{r.get('from_shards', '?')} -> "
                               f"{r.get('to_shards', '?')} shards at "
                               f"iteration {r.get('iter', '?')} "
                               f"({r.get('cause', '?')}) — training "
                               f"continued bit-exactly on the "
                               f"survivors"))
        escal = [r for r in recov if r.get("event") == "escalate"]
        if escal:
            out.append(("HIGH", f"elastic recovery ESCALATED "
                                f"({escal[-1].get('reason', '?')}) — "
                                f"the run failed loudly into the "
                                f"checkpoint restart story"))
        failed = [r for r in recov
                  if r.get("event") == "remesh_failed"]
        if failed:
            out.append(("MED", f"{len(failed)} re-mesh attempt(s) "
                               f"failed and recovery degraded to a "
                               f"narrower mesh; last: "
                               f"{str(failed[-1].get('error', '?'))[:120]}"))
    cont = [r for r in records if r.get("type") == "continual"]
    if cont:
        batches = [r for r in cont if r.get("event") == "batch"]
        quar = [r for r in cont if r.get("event") == "quarantine"]
        consumed = len(batches) + len(quar)
        if quar and consumed and len(quar) / consumed > 0.1:
            by_reason = {}
            for r in quar:
                by_reason[r.get("reason", "?")] = \
                    by_reason.get(r.get("reason", "?"), 0) + 1
            out.append(("HIGH", f"continual quarantine rate "
                                f"{len(quar)}/{consumed} batches "
                                f"({', '.join(f'{k}:{v}' for k, v in sorted(by_reason.items()))})"
                                f" — the ingest feed is degrading, "
                                f"not the trainer"))
        nonfin = [r for r in cont if r.get("event") == "nonfinite"]
        if nonfin:
            last = nonfin[-1]
            out.append(("HIGH", f"numerical-health guard tripped "
                                f"{len(nonfin)} time(s): non-finite "
                                f"training state at iteration "
                                f"{last.get('iter', '?')} "
                                f"({last.get('phase', '?')}) — bad "
                                f"input got past ingest validation"))
        stalls = [r for r in cont if r.get("event") == "stall_restart"]
        if stalls:
            out.append(("MED", f"{len(stalls)} stalled train step(s) "
                               f"abandoned by the watchdog and "
                               f"restarted from the last snapshot "
                               f"(worst {max(float(r.get('stalled_s', 0.0)) for r in stalls):.1f}s "
                               f"silent)"))
        errors = [r for r in cont if r.get("event") == "batch_error"]
        if errors:
            out.append(("MED", f"{len(errors)} continual train "
                               f"attempt(s) raised and retried from "
                               f"the last snapshot; last: "
                               f"{str(errors[-1].get('error', '?'))[:120]}"))
        unknown = [r for r in cont
                   if r.get("event") == "fault_unknown_point"]
        if unknown:
            pts = sorted({r.get("point", "?") for r in unknown})
            out.append(("MED", f"fault spec names unregistered "
                               f"point(s) {pts} — the chaos scenario "
                               f"armed NOTHING (typo?)"))
    ckpts = [r for r in records if r.get("type") == "checkpoint"]
    if ckpts:
        fallbacks = [r for r in ckpts if r.get("event") == "fallback"]
        if fallbacks:
            out.append(("HIGH", f"checkpoint fallback: {len(fallbacks)} "
                                f"candidate(s) rejected "
                                f"(corrupt/truncated) — loader fell "
                                f"back to an older snapshot; last: "
                                f"{fallbacks[-1].get('error', '?')}"))
        save_ms = sum(float(r.get("duration_ms", 0.0)) for r in ckpts
                      if r.get("event") == "save")
        train_ms = sum(float(r.get("duration_ms", 0.0)) for r in records
                       if r.get("type") in ("iteration", "superstep"))
        if train_ms > 0 and save_ms > 0.05 * train_ms:
            out.append(("MED", f"checkpoint save overhead "
                               f"{100 * save_ms / train_ms:.1f}% of "
                               f"train wall time ({save_ms:.0f} of "
                               f"{train_ms:.0f} ms) — raise "
                               f"snapshot_freq or shrink keep_last_n"))
    return out


def triage(records, baseline=None):
    lines = []
    # a bare recorder emits a placeholder run_start ("backend":
    # "unknown") before a booster adopts it and emits the real one —
    # prefer the first header carrying a tier decision
    starts = [r for r in records if r.get("type") == "run_start"]
    start = next((r for r in starts if r.get("tier")),
                 starts[0] if starts else {})
    end = next((r for r in reversed(records)
                if r.get("type") == "run_end"), None)
    tier = start.get("tier") or {}
    lines.append(f"backend     : {start.get('backend', '?')} "
                 f"{start.get('device_kind', '')}".rstrip())
    if tier:
        lines.append(f"tier        : {tier.get('tier')} "
                     f"(learner={tier.get('learner')}, "
                     f"routed={tier.get('routed')}, "
                     f"c2f={tier.get('c2f')}, "
                     f"quantize={tier.get('quantize')})")
        for name, why in sorted((tier.get("gates") or {}).items()):
            lines.append(f"  gate      : {name:<12s} rejected: {why}")
    durs = iter_durations(records)
    if durs:
        lines.append(f"iterations  : {len(durs)}  median "
                     f"{_median(durs):.1f} ms/iter")
    supersteps = [r for r in records if r.get("type") == "superstep"]
    if supersteps:
        ks = sorted({int(r.get("k", 1)) for r in supersteps})
        fused_iters = sum(_block_k(r) for r in supersteps)
        lines.append(f"supersteps  : {len(supersteps)} fused blocks "
                     f"(k={'/'.join(str(k) for k in ks)}), covering "
                     f"{fused_iters} iterations")
        sharded = [r for r in supersteps if "num_shards" in r]
        if sharded:
            # a 2-D (data2d) mesh prints its full RxF shape — the
            # shard count alone cannot tell a 4x2 from a 2x4 cell
            def _mesh_label(r):
                shape = r.get("mesh_shape") or []
                if len(shape) == 2:
                    return (f"{r.get('learner', '?')}x"
                            f"{'x'.join(str(int(s)) for s in shape)}")
                return f"{r.get('learner', '?')}x{int(r['num_shards'])}"
            meshes = sorted({_mesh_label(r) for r in sharded})
            cb = sum(float(r.get("collective_bytes", 0.0))
                     for r in sharded)
            co = sum(float(r.get("collective_ops", 0.0))
                     for r in sharded)
            lines.append(
                f"  sharded   : {', '.join(meshes)} — "
                f"{cb / 1e6:.1f} MB / {co:.0f} collective ops inside "
                f"the fused scans (per-shard estimate)")
    meds = phase_medians(records)
    total = sum(meds.values()) or 1.0
    for name, ms in sorted(meds.items(), key=lambda kv: -kv[1])[:8]:
        lines.append(f"  phase     : {name:<24s} {ms:10.1f} ms/iter "
                     f"({100 * ms / total:4.1f}%)")
    if end is not None:
        s = end.get("summary") or {}
        lines.append(f"compiles    : "
                     f"{s.get('xla_compiles', 0):.0f} "
                     f"({s.get('xla_compile_secs', 0.0):.1f}s), "
                     f"traces {s.get('jax_traces', 0):.0f}")
        if s.get("predicts"):
            lines.append(
                f"predicts    : {s['predicts']:.0f} calls, "
                f"{s.get('predict_rows', 0):.0f} rows, cache "
                f"{s.get('predict_cache_hits', 0):.0f}h/"
                f"{s.get('predict_cache_misses', 0):.0f}m/"
                f"{s.get('predict_cache_evictions', 0):.0f}e")
        if s.get("collective_bytes"):
            lines.append(f"collectives : "
                         f"{s['collective_bytes'] / 1e6:.1f} MB moved "
                         f"(estimate)")
        if s.get("ckpt_saves") or s.get("ckpt_loads") or \
                s.get("ckpt_fallbacks"):
            reasons = {}
            for r in records:
                if r.get("type") == "checkpoint" and \
                        r.get("event") == "save":
                    reasons[r.get("reason", "?")] = \
                        reasons.get(r.get("reason", "?"), 0) + 1
            rs = "/".join(f"{k}:{v}" for k, v in sorted(reasons.items()))
            lines.append(
                f"checkpoints : {s.get('ckpt_saves', 0):.0f} saves "
                f"({rs or '-'}, {s.get('ckpt_bytes', 0) / 1e6:.2f} MB, "
                f"{s.get('ckpt_save_ms', 0.0):.0f} ms), "
                f"{s.get('ckpt_loads', 0):.0f} loads "
                f"({s.get('ckpt_load_ms', 0.0):.0f} ms), "
                f"{s.get('ckpt_fallbacks', 0):.0f} fallbacks")
        if any(s.get(k) for k in ("recovery_detects",
                                  "recovery_remeshes",
                                  "recovery_reshards",
                                  "recovery_escalations")):
            remesh_recs = [r for r in records
                           if r.get("type") == "recovery" and
                           r.get("event") == "remesh"]
            path = ""
            if remesh_recs:
                path = (" (" + " -> ".join(
                    [str(remesh_recs[0].get("from_shards", "?"))] +
                    [str(r.get("to_shards", "?"))
                     for r in remesh_recs]) + " shards)")
            lines.append(
                f"elastic     : "
                f"{s.get('recovery_detects', 0):.0f} shard-failure "
                f"detections, {s.get('recovery_remeshes', 0):.0f} "
                f"re-meshes{path}, "
                f"{s.get('recovery_reshards', 0):.0f} resume "
                f"re-shards, {s.get('recovery_escalations', 0):.0f} "
                f"escalations")
        if any(s.get(k) for k in ("fleet_publishes", "fleet_skips",
                                  "fleet_rollbacks", "fleet_restarts",
                                  "fleet_replica_starts",
                                  "fleet_circuit_opens")):
            lines.append(
                f"fleet       : "
                f"{s.get('fleet_replica_starts', 0):.0f} replica "
                f"starts, {s.get('fleet_restarts', 0):.0f} restarts, "
                f"{s.get('fleet_circuit_opens', 0):.0f} circuit-opens, "
                f"{s.get('fleet_publishes', 0):.0f} publishes "
                f"({s.get('fleet_publish_verified', 0):.0f} verified), "
                f"{s.get('fleet_skips', 0):.0f} skips, "
                f"{s.get('fleet_rollbacks', 0):.0f} rollbacks")
        if s.get("ingest_runs") or s.get("ingest_chunk_reads") or \
                s.get("ingest_quarantines"):
            lines.append(
                f"ingest      : "
                f"{s.get('ingest_chunk_reads', 0):.0f} chunk reads "
                f"({s.get('ingest_rows', 0):.0f} rows), "
                f"{s.get('ingest_cache_writes', 0):.0f} cache writes "
                f"({s.get('ingest_cached_bytes', 0) / 1e6:.2f} MB), "
                f"{s.get('ingest_cache_hits', 0):.0f} chunk cache "
                f"hits, {s.get('ingest_rebins', 0):.0f} re-bins, "
                f"{s.get('ingest_mapper_fits', 0):.0f} mapper fits "
                f"({s.get('ingest_prelude_hits', 0):.0f} prelude "
                f"hits), {s.get('ingest_quarantines', 0):.0f} "
                f"quarantined, {s.get('ingest_backoffs', 0):.0f} "
                f"backoffs, prefetch overlap "
                f"{s.get('ingest_prefetch_overlap_s', 0.0):.3f}s over "
                f"{s.get('ingest_prefetch_windows', 0):.0f} windows")
        if s.get("pager_pages"):
            lines.append(
                f"pager       : "
                f"{s.get('pager_pages', 0):.0f} pages served "
                f"({s.get('pager_bytes', 0) / 1e6:.2f} MB), "
                f"{s.get('pager_stalls', 0):.0f} serve stalls, "
                f"prefetch overlap "
                f"{s.get('pager_overlap_s', 0.0):.3f}s, inline wait "
                f"{s.get('pager_wait_s', 0.0):.3f}s")
        if s.get("continual_batches") or s.get("continual_quarantines"):
            mean_ms = (s.get("continual_batch_ms", 0.0) /
                       max(s.get("continual_batches", 0), 1))
            lines.append(
                f"continual   : "
                f"{s.get('continual_batches', 0):.0f} batches "
                f"({s.get('continual_rows', 0):.0f} rows, mean "
                f"{mean_ms:.0f} ms/batch), "
                f"{s.get('continual_quarantines', 0):.0f} quarantined, "
                f"{s.get('continual_backoffs', 0):.0f} read backoffs, "
                f"{s.get('continual_stall_restarts', 0):.0f} stall "
                f"restarts, "
                f"{s.get('continual_nonfinite', 0):.0f} non-finite "
                f"aborts, {s.get('continual_resumes', 0):.0f} resumes")
        if s.get("router_requests"):
            lines.append(
                f"router      : {s['router_requests']:.0f} requests "
                f"({s.get('router_rows', 0):.0f} rows), p50/p95/p99 "
                f"{s.get('router_total_ms_p50', 0):.1f}/"
                f"{s.get('router_total_ms_p95', 0):.1f}/"
                f"{s.get('router_total_ms_p99', 0):.1f} ms, "
                f"{s.get('router_retries', 0):.0f} retries, "
                f"{s.get('router_hedges', 0):.0f} hedges "
                f"({s.get('router_hedge_wins', 0):.0f} wins), "
                f"{s.get('router_shed', 0):.0f} shed, "
                f"{s.get('router_breaker_opens', 0):.0f} breaker-opens")
        if s.get("serve_requests"):
            lines.append(
                f"serve       : {s['serve_requests']:.0f} requests "
                f"({s.get('serve_rows', 0):.0f} rows), p50/p95/p99 "
                f"{s.get('serve_total_ms_p50', 0):.1f}/"
                f"{s.get('serve_total_ms_p95', 0):.1f}/"
                f"{s.get('serve_total_ms_p99', 0):.1f} ms, "
                f"{s.get('serve_shed', 0):.0f} shed / "
                f"{s.get('serve_timeout', 0):.0f} timeout / "
                f"{s.get('serve_rejected', 0):.0f} rejected, "
                f"occupancy {s.get('serve_mean_occupancy', 0):.2f}, "
                f"{s.get('serve_swaps', 0):.0f} swaps")
        if s.get("slo_evals"):
            # newest result per objective = the engine's final verdict
            last = {}
            for r in records:
                if r.get("type") == "slo" and r.get("objective"):
                    last[str(r["objective"])] = r
            line = (f"slo         : {s['slo_evals']:.0f} evals over "
                    f"{len(last)} objective(s)")
            if last:
                worst = max(last.values(),
                            key=lambda r: r.get("burn_fast", 0.0))
                lowest = min(last.values(),
                             key=lambda r: r.get("budget_remaining",
                                                 1.0))
                line += (f", worst burn "
                         f"{float(worst.get('burn_fast', 0.0)):.1f}x "
                         f"({worst.get('objective')}), budget left "
                         f"{float(lowest.get('budget_remaining', 1.0)):.0%} "
                         f"({lowest.get('objective')})")
            bad = [f"{k.split('slo_', 1)[1]} {v:.0f}"
                   for k, v in sorted(s.items())
                   if k.startswith("slo_") and k not in
                   ("slo_evals",) and v]
            if bad:
                line += ", states: " + ", ".join(bad)
            lines.append(line)
        if s.get("autoscale_actions") or s.get("autoscale_degraded"):
            parts = [f"{k.split('autoscale_', 1)[1]} {v:.0f}"
                     for k, v in sorted(s.items())
                     if k.startswith("autoscale_") and
                     k != "autoscale_actions" and v]
            lines.append(
                f"autoscale   : {s.get('autoscale_actions', 0):.0f} "
                f"action(s)" + (f" ({', '.join(parts)})" if parts
                                else ""))
    anomalies = scan_anomalies(records)
    lines.append("anomalies   : " + ("none" if not anomalies else ""))
    for sev, msg in anomalies:
        lines.append(f"  [{sev}] {msg}")
    if baseline is not None:
        lines.append("")
        lines.append("vs baseline:")
        base_meds = phase_medians(baseline)
        base_durs = iter_durations(baseline)
        if durs and base_durs:
            a, b = _median(durs), _median(base_durs)
            lines.append(f"  iteration : {a:.1f} vs {b:.1f} ms/iter "
                         f"({'+' if a >= b else ''}{100 * (a - b) / max(b, 1e-9):.1f}%)")
        deltas = []
        for name in set(meds) | set(base_meds):
            a = meds.get(name, 0.0)
            b = base_meds.get(name, 0.0)
            deltas.append((abs(a - b), name, a, b))
        for _, name, a, b in sorted(deltas, reverse=True)[:6]:
            pct = 100 * (a - b) / max(b, 1e-9)
            lines.append(f"  phase     : {name:<24s} {a:9.1f} vs "
                         f"{b:9.1f} ms/iter ({'+' if pct >= 0 else ''}"
                         f"{pct:.1f}%)")
        base_tier = next((r.get("tier") for r in baseline
                          if r.get("type") == "run_start"), None) or {}
        if tier and base_tier and tier.get("tier") != base_tier.get("tier"):
            lines.append(f"  [HIGH] TIER CHANGED: {base_tier.get('tier')} "
                         f"-> {tier.get('tier')} (check the gates above)")
    return "\n".join(lines)


def follow(path, idle_timeout_s=0.0, poll_s=0.25, out=sys.stdout):
    """Tail a live telemetry JSONL and print anomalies AS THEY FIRE
    (the online half of the shared rule evaluator, ``obs/rules.py``).
    Waits for the file to appear; a partially-written trailing line is
    re-read on the next poll (the writer appends whole lines, so only
    the tail can be torn).  Exits after ``idle_timeout_s`` with no new
    data (0 = run until interrupted).  Returns the number of instant
    anomalies printed."""
    scanner = obs_rules.OnlineScanner()
    n_fired = 0
    n_records = 0
    t_idle = time.monotonic()
    f = None
    try:
        while True:
            if f is None:
                try:
                    f = open(path)
                    print(f"following {path} ...", file=out, flush=True)
                except OSError:
                    if idle_timeout_s > 0 and \
                            time.monotonic() - t_idle > idle_timeout_s:
                        print(f"no file after {idle_timeout_s:.0f}s: "
                              f"{path}", file=out)
                        return n_fired
                    time.sleep(poll_s)
                    continue
            where = f.tell()
            line = f.readline()
            if not line or not line.endswith("\n"):
                f.seek(where)              # torn tail: retry whole line
                if idle_timeout_s > 0 and \
                        time.monotonic() - t_idle > idle_timeout_s:
                    break
                time.sleep(poll_s)
                continue
            t_idle = time.monotonic()
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            n_records += 1
            for sev, code, msg in scanner.feed(rec):
                n_fired += 1
                stamp = time.strftime("%H:%M:%S")
                print(f"{stamp} [{sev}] {code}: {msg}", file=out,
                      flush=True)
            if rec.get("type") == "capture":
                stamp = time.strftime("%H:%M:%S")
                print(f"{stamp} [CAPTURE] {rec.get('trigger', '?')} "
                      f"-> {rec.get('path', '?')}", file=out,
                      flush=True)
    except KeyboardInterrupt:
        pass
    finally:
        if f is not None:
            f.close()
    print(f"followed {n_records} records, {n_fired} anomalies fired",
          file=out, flush=True)
    return n_fired


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("run", help="telemetry JSONL to triage")
    ap.add_argument("--baseline", help="prior run's JSONL to diff against")
    ap.add_argument("--check", action="store_true",
                    help="schema-lint only; exit 1 on malformed records")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress OK output (CI mode)")
    ap.add_argument("--follow", action="store_true",
                    help="tail the (possibly still-growing) JSONL and "
                         "print anomalies as they fire")
    ap.add_argument("--follow-timeout", type=float, default=0.0,
                    help="with --follow: exit after this many seconds "
                         "without new records (0 = until Ctrl-C)")
    args = ap.parse_args(argv)

    if args.follow:
        follow(args.run, idle_timeout_s=args.follow_timeout)
        return 0

    if args.check:
        n, errs = lint_file(args.run)
        if errs:
            print(f"{args.run}: {n} records, {len(errs)} schema "
                  f"errors:")
            for e in errs[:20]:
                print(f"  {e}")
            return 1
        if not args.quiet:
            print(f"{args.run}: {n} records, schema OK "
                  f"(all records valid, version pinned)")
        return 0

    records = read_records(args.run)
    baseline = read_records(args.baseline) if args.baseline else None
    print(triage(records, baseline))
    return 0


if __name__ == "__main__":
    sys.exit(main())
