"""Regenerate ``docs/Benchmarks.md`` deterministically from artifacts.

The document once drifted because it was written from memory.  This
tool makes it a pure function of the checked-in ``BENCH_*_cpu.json``
and ``WEAKSCALE.json`` artifacts (CPU-container count and harness
runs) so it can never be hand-edited into drift again.  Training on
the chip has no artifact yet: the section says "not measured on the
present chip" until the benchmark of ROADMAP Speed 0 exists.

    python tools/render_benchmarks.py            # rewrite the doc
    python tools/render_benchmarks.py --check    # byte-compare, CI gate
    python tools/render_benchmarks.py --stdout
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC = os.path.join(ROOT, "docs", "Benchmarks.md")

HEADER_NOTE = (
    "<!-- GENERATED by tools/render_benchmarks.py from BENCH_*.json; "
    "do not hand-edit — rerun the tool (CI --check pins this). -->\n")


def fmt(v, nd=2):
    if v is None:
        return "—"
    return f"{v:.{nd}f}".rstrip("0").rstrip(".") if nd else f"{v:.0f}"


def fmt_rate(rows_per_s):
    v = rows_per_s / 1000.0
    return f"{v:.0f}k" if v >= 20 else f"{v:.1f}k"


def predict_section(p):
    if not p:
        return []
    lines = [
        "## Batch inference (flattened jitted engine vs per-tree loop)",
        "",
        "The ensemble-flattened inference engine (`ops/predict.py`,",
        "`docs/Design.md` \"Inference engine\") replaces the per-tree "
        "numpy loop",
        "behind every `predict*` surface.  Measured on the **CPU "
        "backend**",
        f"({p.get('env', '?')}, {p.get('date', '?')}, "
        f"`{p.get('source', '?')}`),",
        f"{p.get('forest', '')};",
        f"engine output matches the per-tree loop to <= "
        f"{p.get('max_abs_delta', 0):g}:", "",
        "| rows x trees | per-tree loop | flattened engine | speedup |",
        "|---|---|---|---|"]
    for c in p.get("cells", []):
        rows, trees = c["rows"], c["trees"]
        loop_s, eng_s = c["loop_s"], c["engine_s"]
        label = f"{rows // 1000}k x {trees}" if rows < 1_000_000 \
            else f"{rows // 1_000_000}M x {trees}"
        cell = (f"| {label} | {fmt(loop_s, 3)} s "
                f"({fmt_rate(rows / loop_s)} rows/s) | "
                f"{fmt(eng_s, 3)} s ({fmt_rate(rows / eng_s)} rows/s) | "
                f"{loop_s / eng_s:.1f}x |")
        if c.get("acceptance"):
            cell = (f"| **{label}** (acceptance shape) | "
                    f"**{fmt(loop_s, 3)} s** "
                    f"({fmt_rate(rows / loop_s)} rows/s) | "
                    f"**{fmt(eng_s, 3)} s** "
                    f"({fmt_rate(rows / eng_s)} rows/s) | "
                    f"**{loop_s / eng_s:.1f}x** |")
        lines.append(cell)
    lines += [
        "",
        "`bench.py` records the same comparison on its trained primary "
        "booster",
        "per run (`predict_engine_rows_per_s` / "
        "`predict_loop_rows_per_s` /",
        "`predict_engine_speedup`).  Accumulation-order caveat: the "
        "engine sums",
        "each 32-tree chunk with a k-strided reshape-sum before adding "
        "chunks in",
        "order, so raw scores differ from the strictly tree-by-tree "
        "loop at the",
        f"~1e-13 relative level (tolerance pinned at "
        f"{p.get('parity_tolerance_pinned', 1e-12):g} in",
        "`tests/test_predict_engine.py`).", ""]
    return lines


def superstep_section(s):
    if not s:
        return []
    budget = s.get("device_call_budget") or {}
    lines = [
        "## Fused boosting super-steps (`fused_iters`)",
        "",
        "One jitted `lax.scan` runs K boosting iterations on device "
        "(gradients,",
        "sampling mask, tree build, score update; donated score "
        "carry) and the",
        "K trees' split records return in ONE packed transfer — "
        "`O(iters/K)`",
        "Python dispatches and host syncs instead of `O(iters)`.",
        "Bit-exact against the per-iteration path "
        "(`tests/test_superstep.py`).",
        f"Measured on the **CPU backend** ({s.get('env', '?')}, "
        f"{s.get('date', '?')},",
        f"`{s.get('source', '?')}`), {s.get('shape', '')}:", "",
        "| variant | s/iter (min block mean) | device calls/iter | "
        "compiles in window |",
        "|---|---|---|---|"]
    for c in s.get("cells", []):
        k = c["fused_iters"]
        label = "per-iteration path" if k <= 1 else f"fused K={k}"
        calls = "~5" if k <= 1 else fmt(c.get("dispatches_per_iter"), 2)
        comp = "—" if k <= 1 else \
            f"{c.get('measured_xla_compiles_all_fused', 0):d}"
        lines.append(f"| {label} | {fmt(c['iter_s'], 3)} | {calls} | "
                     f"{comp} |")
    tiny = s.get("dispatch_bound_cells") or []
    if len(tiny) == 2:
        t0, t1 = tiny
        lines += [
            "",
            f"Dispatch-bound pair ({t1.get('shape', '')}): "
            f"**{fmt(t0['iter_s'] * 1e3, 1)} ms/iter unfused vs "
            f"{fmt(t1['iter_s'] * 1e3, 1)} ms/iter fused "
            f"({fmt(t0['iter_s'] / t1['iter_s'], 2)}x)** — the "
            "per-iteration host cost the fused path amortizes;",
            "the 5000-row cells are device-compute-bound on this CPU, "
            "so their",
            "wall clock is parity by physics while the device-call "
            "budget drops",
            f"{('%d of %d expected fused device calls observed' % (budget.get('observed_fused_device_calls', 0), budget.get('expected_fused_device_calls', 0)))} "
            "(2 per K-iteration block: the scan dispatch + the packed "
            "fetch).", ""]
    else:
        lines.append("")
    pl = s.get("pipelined") or {}
    if pl.get("cells"):
        lines += [
            "### Async block pipelining (`superstep_pipeline_depth`)",
            "",
            "Block K+1's scan is dispatched BEFORE block K's stacked "
            "split records",
            "are fetched, so the one device->host round-trip per "
            "block hides behind the",
            "next block's device compute.  Bit-exact with depth 0 "
            "(`tests/test_pipeline.py`);",
            "the device-call budget stays **2 per K-block at every "
            "depth** (hard-asserted",
            f"by the harness).  {pl.get('shape', '')}:", "",
            "| pipeline depth | s/iter (min window mean) | "
            "`superstep/fetch` ms/iter | fetch wall hidden (ms/iter) "
            "| device calls/block |",
            "|---|---|---|---|---|"]
        for c in pl["cells"]:
            calls = fmt(c.get("dispatches_per_block", 0) +
                        c.get("fetches_per_block", 0), 0)
            lines.append(
                f"| {c['pipeline_depth']}"
                f"{' (off)' if c['pipeline_depth'] == 0 else ''} | "
                f"{fmt(c['iter_s'], 5)} | "
                f"{fmt(c['fetch_ms_per_iter'], 2)} | "
                f"{fmt(c['fetch_wall_hidden_ms'], 2)} | {calls} |")
        lines += [
            "",
            "On this CPU container the fetch is host-memory-fast, so "
            "the hidden wall",
            "says nothing about the chip, where the overlap is not "
            "measured.  Per-block telemetry "
            "(`fetch_overlap_s`,",
            "`pipeline_depth`) makes the overlap auditable; "
            "`triage_run.py` flags",
            "depth > 0 with ~zero overlap as pipelining silently "
            "disabled.", ""]
    pg = s.get("paged") or {}
    if pg.get("cells"):
        lines += [
            "### Paged super-step (`paged_training`, `io/pager.py`)",
            "",
            "The device-block pager serves the binned matrix page by "
            "page from host",
            "memory through `jax.pure_callback` INSIDE the one "
            "compiled scan — so the",
            "host-side device-call budget stays **2 per K-block at "
            "any page count**",
            "(hard-asserted per page-rows variant by the harness; "
            "byte-parity with the",
            f"resident path is `tests/test_pager.py`).  "
            f"{pg.get('shape', '')}:", "",
            "| page layout | s/iter (min window mean) | device "
            "calls/block | pages served | vs resident |",
            "|---|---|---|---|---|"]
        for c in pg["cells"]:
            calls = fmt(c.get("dispatches_per_block", 0) +
                        c.get("fetches_per_block", 0), 0)
            layout = "resident" if not c.get("n_pages") else \
                (f"{fmt(c['n_pages'], 0)} pages x "
                 f"{fmt(c['page_rows'], 0)} rows")
            lines.append(
                f"| {layout} | {fmt(c['iter_s'], 5)} | {calls} | "
                f"{fmt(c.get('pages_served', 0), 0)} | "
                f"{fmt(c.get('slowdown_vs_resident', 1), 2)}x |")
        lines += [
            "",
            "The wall-clock column prices the callback serve "
            "machinery on host RAM",
            "(see the out-of-core section's caveat); the budget "
            "column is the point:",
            "paging changes where the bytes live, never how often "
            "the host touches",
            "the device.", ""]
    sx = s.get("split") or {}
    if sx.get("superstep", {}).get("cells"):
        op = sx.get("op") or {}
        ss_ = sx["superstep"]
        lines += [
            "### Best-split kernel (`split_kernel`)",
            "",
            "The best-split pass runs as a Pallas kernel family "
            "fused with the",
            "histogram build (`ops/split.py`): the batched histogram "
            "kernels scan",
            "their own accumulated (leaf, feature-tile) histogram "
            "while it is still",
            "VMEM-resident (fused epilogue) and the "
            "subtraction-trick children go",
            "through a standalone per-(leaf, feature-tile) scan "
            "kernel with a",
            "two-stage tile-then-global argmax — the full "
            "(leaves x F x B x 3)",
            "histogram is never round-tripped through HBM between "
            "build and scan.",
            "Split choice is bit-identical to the XLA scan "
            "(`tests/test_split_kernel.py`).",
            f"Op-level A/B ({op.get('shape', '?')}):", "",
            "| pass | s/pass | dispatches/pass |",
            "|---|---|---|",
            f"| histogram → (HBM) → split, two programs | "
            f"{fmt(op.get('unfused_s_per_pass', 0), 4)} | 2 |",
            f"| fused histogram→split, one program | "
            f"{fmt(op.get('fused_s_per_pass', 0), 4)} | 1 |",
            "",
            f"End-to-end budget pin ({ss_.get('shape', '')}; "
            f"hard-asserted by the harness):", "",
            "| split_kernel | s/iter | device calls per K-block |",
            "|---|---|---|"]
        for c in ss_["cells"]:
            calls = fmt(c.get("dispatches_per_block", 0) +
                        c.get("fetches_per_block", 0), 0)
            lines.append(f"| {c['split_kernel']} | "
                         f"{fmt(c['iter_s'], 5)} | {calls} |")
        lines += [
            "",
            "CPU wall is compute-parity by physics (host RAM is one "
            "memory, and",
            "`split_kernel=pallas` on CPU runs the Pallas "
            "INTERPRETER — a correctness",
            "lane, not a fast path); the structural win is the "
            "dispatch column and,",
            "on TPU, the per-wave histogram HBM write+read-back the "
            "fused epilogue",
            "deletes.  An XLA fallback on a TPU backend is flagged "
            "by `triage_run.py`",
            "(superstep records carry `split_kernel` + "
            "`split_fallback`).", ""]
    sb = s.get("sharded_device_call_budget") or {}
    if sb:
        match = "MATCHES" if sb.get("matches_serial_fused") \
            else "DOES NOT MATCH"
        lines += [
            f"Sharded fused super-step (data-parallel over "
            f"{sb.get('num_shards', '?')} shards,",
            "`docs/Distributed.md`): "
            f"{sb.get('observed_fused_device_calls', 0)} of "
            f"{sb.get('expected_fused_device_calls', 0)} expected "
            "device calls observed —",
            f"the device-call budget per K-block **{match}** the "
            "serial fused path",
            "(the strategy collectives ride INSIDE the one compiled "
            "scan; the",
            "pre-refactor per-call path issued ~5 dispatches per "
            "shard per iteration).", ""]
    return lines


def weakscale_section(w):
    if not w:
        return []
    curve = w.get("curve") or []
    if not curve:
        return []
    pts = {c["shards"]: c for c in curve}
    lo = pts[min(pts)]
    ideal = lo["iter_s"]
    cores = int(w.get("cores", 0) or 0)
    rps = curve[0].get("rows_per_shard", "?")
    lines = [
        "## Weak scaling (sharded fused super-steps, "
        "`docs/Distributed.md`)",
        "",
        f"Per-iteration time at a FIXED {rps} rows/shard as the "
        "data-parallel mesh",
        "widens — the curve an ideal weak-scaling system keeps flat.  "
        "The sharded",
        "learners now run INSIDE the one compiled fused `lax.scan` "
        "(collectives",
        "in-program, 2 device calls per K-iteration block at any mesh "
        "size), so the",
        "per-shard dispatch overhead that degraded this curve through "
        "r05 is gone;",
        f"generated by `{w.get('source', '?')}` "
        f"(learner `{w.get('learner', '?')}`, "
        f"K={w.get('fused_iters', '?')}):", "",
        "| shards | s/iter (wall) | vs ideal (flat) | CPU s/shard-iter "
        "| collective KB/iter (per shard) | device calls/iter |",
        "|---|---|---|---|---|---|"]
    for c in curve:
        lines.append(
            f"| {c['shards']} | {fmt(c['iter_s'], 4)} | "
            f"{fmt(c['iter_s'] / max(ideal, 1e-9), 2)}x | "
            f"{fmt(c.get('cpu_s_per_shard_iter'), 4)} | "
            f"{fmt(c.get('collective_bytes', 0) / 1e3, 1)} | "
            f"{fmt(c.get('device_calls_per_iter'), 2)} |")
    # ASCII curve + ideal line: wall time per iteration by mesh size
    lines += ["", "```", "s/iter vs shards (#### measured, | ideal "
                         "flat line at the 1-shard point)"]
    top = max(c["iter_s"] for c in curve)
    width = 44
    for c in curve:
        bar = max(int(round(c["iter_s"] / top * width)), 1)
        ideal_col = max(int(round(ideal / top * width)), 1)
        row = "#" * bar
        if ideal_col >= len(row):
            row = row + " " * (ideal_col - len(row)) + "|"
        else:
            row = row[:ideal_col - 1] + "|" + row[ideal_col:]
        lines.append(f"D={c['shards']:<3d} {row} {c['iter_s']:.3f}s")
    lines.append("```")
    ratio_cpu = w.get("flat_ratio_cpu_per_shard")
    ratio_sharded = w.get("flat_ratio_cpu_per_shard_sharded")
    ratio_wall = w.get("flat_ratio_wall")
    tail = []
    if ratio_sharded is not None:
        sharded = sorted(d for d in pts if d > 1)
        verdict = ("flat within the +/-20% bar (<= 1.2)"
                   if ratio_sharded <= 1.2 else
                   f"{fmt(ratio_sharded, 2)}x exceeds the +/-20% bar "
                   f"(<= 1.2) on this 2-core dryrun sample — rerun on "
                   f"a quiet host before reading it as a regression")
        tail.append(
            f"Per-shard CPU cost across the SHARDED mesh sizes "
            f"({sharded[0]} -> {sharded[-1]} shards) is "
            f"**{fmt(ratio_sharded, 2)}x** — the per-shard-cost-O(1)-"
            f"in-D weak-scaling pin, {verdict}.")
    if ratio_cpu is not None:
        tail.append(
            f"Including the 1-shard serial anchor the ratio is "
            f"{fmt(ratio_cpu, 2)}x: the serial program runs NO "
            f"collectives, so the first sharded point pays the "
            f"one-time collective-compute cost on top of the flat "
            f"scaling law.")
    if ratio_wall is not None and cores and max(pts) > cores:
        tail.append(
            f"Wall-clock grows {fmt(ratio_wall, 1)}x because this "
            f"dryrun timeshares {max(pts)} virtual devices over "
            f"{cores} core(s) — total compute grows with the mesh "
            f"while the cores do not; on one real chip per shard the "
            f"wall curve is the per-shard-CPU curve.  Methodology in "
            f"`docs/Distributed.md`.")
    if tail:
        lines += ["", "  ".join(tail)]
    g2 = w.get("grid2d") or {}
    grid = g2.get("grid") or []
    if grid:
        rps2 = grid[0].get("rows_per_shard", "?")
        lines += [
            "",
            "### The second axis: the 2-D `data2d` (data x feature) "
            "grid",
            "",
            f"Fixed {g2.get('devices', '?')} devices, fixed {rps2} "
            "rows per ROW shard, sweeping how the devices factor into "
            "R x F.  Every cell",
            "moves the same per-device row block; what changes is the "
            "collective schedule",
            "— the `data`-axis histogram reduction covers only the "
            "device's feature tile",
            "(O(1/F) bytes), the `feature`-axis merge stays O(F) and "
            "its routing term",
            "shrinks as 1/R.  Device calls stay flat at 2 per "
            "K-iteration block on every",
            "shape:", "",
            "| mesh (R x F) | s/iter (wall) | CPU s/shard-iter | "
            "`data`-axis KB/iter | `feature`-axis KB/iter | device "
            "calls/iter |",
            "|---|---|---|---|---|---|"]
        for c in grid:
            r, f = c["shape"]
            axb = c.get("collective_bytes_axis") or {}
            lines.append(
                f"| {r} x {f} | {fmt(c['iter_s'], 4)} | "
                f"{fmt(c.get('cpu_s_per_shard_iter'), 4)} | "
                f"{fmt(axb.get('data', 0) / 1e3, 1)} | "
                f"{fmt(axb.get('feature', 0) / 1e3, 1)} | "
                f"{fmt(c.get('device_calls_per_iter'), 2)} |")
        ratio = g2.get("data_axis_bytes_ratio")
        ideal2 = g2.get("data_axis_ideal_ratio")
        if ratio is not None:
            lines += [
                "",
                f"`data`-axis bytes at the widest feature axis are "
                f"**{fmt(ratio, 3)}x** the F=1 (pure data-parallel "
                f"schedule) cell against an ideal 1/F ratio of "
                f"{fmt(ideal2, 3)} — the O(F·B/F_axis) cut the 2-D "
                f"factoring exists for.  Wall time on this "
                f"oversubscribed virtual mesh timeshares all shapes "
                f"over the same cores and is not the acceptance "
                f"series (see the caveat above)."]
    lines.append("")
    return lines


def serve_section(v):
    if not v:
        return []
    cfg = v.get("config") or {}
    lines = [
        "## Online serving (micro-batching scheduler over the engine)",
        "",
        "The serving subsystem (`lightgbm_tpu/serve/`, "
        "`docs/Serving.md`) coalesces",
        "concurrent requests into the engine's pre-warmed power-of-two "
        "row buckets",
        "(max batch " + fmt(cfg.get("max_batch_rows"), 0) + " rows), "
        "with admission control and",
        "atomic hot-swap.  Measured on the **CPU backend** "
        f"({v.get('env', '?')}, {v.get('date', '?')},",
        f"`{v.get('source', '?')}`), {v.get('forest', '')}, mixed "
        f"request sizes 1-{fmt(cfg.get('rows_max'), 0)} rows, "
        f"{fmt(cfg.get('requests'), 0)} requests per cell:", "",
        "| scenario | p50 | p99 | req/s | rows/s | batch occupancy | "
        "steady-state compiles |", "|---|---|---|---|---|---|---|"]
    for c in v.get("cells", []):
        label = c.get("label", "?")
        if c.get("hot_swaps"):
            label += f" ({c['hot_swaps']} mid-run swap, " \
                     f"{c.get('failed_requests', 0)} failed)"
        lines.append(
            f"| {label} | {fmt(c.get('p50_ms'), 1)} ms | "
            f"{fmt(c.get('p99_ms'), 1)} ms | "
            f"{fmt(c.get('req_per_s'), 1)} | "
            f"{fmt_rate(c.get('rows_per_s', 0))} | "
            f"{fmt(c.get('mean_occupancy'), 2)} | "
            f"{c.get('steady_xla_compiles', 0):d} |")
    lines += [
        "",
        "Steady-state serving records ZERO `backend_compile` events "
        "across the",
        "mixed-size request stream (every servable bucket kernel is "
        "compiled at",
        "publish time, and hot-swap pre-warms BEFORE the atomic "
        "pointer swap —",
        "pinned by `tests/test_serve.py` via the telemetry compile "
        "counters).", ""]
    return lines


def explain_section(x):
    if not x:
        return []
    cfg = x.get("config") or {}
    cells = {c.get("label"): c for c in x.get("cells", [])}
    fast = cells.get("single-row fastpath") or {}
    slow = cells.get("single-row bucketed") or {}
    lines = [
        "## Serve-time explanations + single-row fast path",
        "",
        "The explanation engine (`ops/shap.py`, `docs/Serving.md` "
        "\"Explanation",
        "lane\") runs path-dependent TreeSHAP as a jitted device "
        "program behind",
        "`POST /v1/<model>/explain`, micro-batched in its own lane "
        "(explain and",
        "predict rows never share a device batch) with every bucket "
        "pre-compiled",
        "at publish time.  Measured on the **CPU backend** "
        f"({x.get('env', '?')}, {x.get('date', '?')},",
        f"`{x.get('source', '?')}`), {x.get('forest', '')}, "
        f"{fmt(cfg.get('requests'), 0)} requests per cell:", "",
        "| scenario | p50 | p99 | req/s | rows/s | batch occupancy | "
        "steady-state compiles |", "|---|---|---|---|---|---|---|"]
    for c in x.get("cells", []):
        if c.get("kind") != "explain":
            continue
        lines.append(
            f"| {c.get('label', '?')} | {fmt(c.get('p50_ms'), 1)} ms "
            f"| {fmt(c.get('p99_ms'), 1)} ms | "
            f"{fmt(c.get('req_per_s'), 1)} | "
            f"{fmt(c.get('rows_per_s'), 0)} | "
            f"{fmt(c.get('mean_occupancy'), 2)} | "
            f"{c.get('steady_xla_compiles', 0):d} |")
    lines += [
        "",
        "Single-row predict latency: the occupancy-routed fast path "
        "(a pinned",
        "tiny-bucket scorer that skips the micro-batch wait, "
        "bit-identical",
        "output — `tests/test_shap_engine.py`) against the same "
        "requests forced",
        "through the full bucketed path:", "",
        "| path | p50 | p99 | req/s | fastpath batches | steady-state "
        "compiles |", "|---|---|---|---|---|---|",
        f"| single-row fastpath | {fmt(fast.get('p50_ms'), 2)} ms | "
        f"{fmt(fast.get('p99_ms'), 2)} ms | "
        f"{fmt(fast.get('req_per_s'), 1)} | "
        f"{fmt(fast.get('fastpath_batches'), 0)} | "
        f"{fast.get('steady_xla_compiles', 0):d} |",
        f"| single-row bucketed | {fmt(slow.get('p50_ms'), 2)} ms | "
        f"{fmt(slow.get('p99_ms'), 2)} ms | "
        f"{fmt(slow.get('req_per_s'), 1)} | 0 | "
        f"{slow.get('steady_xla_compiles', 0):d} |",
        "",
        f"Fast-path p50 is **{fmt(x.get('fastpath_p50_speedup'), 2)}x**"
        " below the bucketed path (the artifact's",
        "binding acceptance pin), and every cell above — including "
        "the explain",
        "lane under 8-way mixed-size concurrency — records ZERO "
        "steady-state XLA",
        "compiles: publish-time warmup pre-compiles the explain "
        "bucket ladder and",
        "the fast-path scorers exactly like the predict buckets "
        "(`warmup_info`),",
        "pinned by the 504-distinct-size concurrent acceptance test "
        "in", "`tests/test_shap_engine.py`.", ""]
    return lines


def router_section(r):
    if not r:
        return []
    cfg = r.get("config") or {}
    pins = r.get("pins") or {}
    shed = r.get("shed") or {}
    lines = [
        "## Routing front (retrying/hedging router over the replica "
        "fleet)",
        "",
        "The router (`lightgbm_tpu/serve/router.py`, "
        "`docs/Routing.md`) balances over",
        "the fleet with live health/draining/fingerprint awareness, "
        "masks backend",
        "failures (bounded retries + tail-latency hedging + circuit "
        "breakers) and",
        "sheds overload via per-model admission budgets.  Measured on "
        f"the **CPU backend** ({r.get('env', '?')}, "
        f"{r.get('date', '?')}, `{r.get('source', '?')}`),",
        f"{r.get('forest', '')}, {fmt(cfg.get('threads'), 0)} clients "
        f"x {fmt(cfg.get('rows_per_request'), 0)}-row requests, "
        f"{fmt(cfg.get('requests'), 0)} per cell; brownout = "
        f"{cfg.get('brownout', '?')}:", "",
        "| scenario | p50 | p99 | req/s | rows/s | hedges (wins) | "
        "dropped |", "|---|---|---|---|---|---|---|"]
    for c in r.get("cells", []):
        hedges = "—" if "hedges" not in c else \
            f"{c['hedges']} ({c.get('hedge_wins', 0)})"
        lines.append(
            f"| {c.get('label', '?')} | {fmt(c.get('p50_ms'), 1)} ms "
            f"| {fmt(c.get('p99_ms'), 1)} ms | "
            f"{fmt(c.get('req_per_s'), 1)} | "
            f"{fmt_rate(c.get('rows_per_s', 0))} | {hedges} | "
            f"{c.get('dropped', 0):d} |")
    verdict = "hedging bounded the brownout p99" \
        if pins.get("hedge_bounds_p99") else \
        "hedging did NOT bound the brownout p99 on this run"
    lines += [
        "",
        f"Acceptance pins (recorded in the artifact): {verdict}; "
        f"{fmt(shed.get('shed_structured'), 0)} budget-shed requests "
        f"were ALL structured",
        "429s (`code=backpressure` + `retry_after_ms` + `Retry-After` "
        "header,",
        f"{fmt(shed.get('shed_unstructured'), 0)} unstructured); zero "
        "requests dropped through the router in",
        "any cell — including the mid-run deploy, where the router "
        "never touches a",
        "stale-fingerprint replica (the chaos e2e "
        "`tools/chaos_router.py` drives the",
        "same pins through replica SIGKILL and a mid-run multi-model "
        "publish).", ""]
    return lines


def autoscale_section(a):
    if not a:
        return []
    cfg = a.get("config") or {}
    slo = cfg.get("slo") or {}
    asc = cfg.get("autoscale") or {}
    pins = a.get("pins") or {}
    cells = {c.get("label"): c for c in a.get("cells", [])}
    grow = cells.get("surge -> grow reaction") or {}
    drain = cells.get("idle -> drain hysteresis") or {}
    seq = cells.get("decision timeline (active)") or {}
    over = cells.get("evaluate() overhead") or {}
    lines = [
        "## SLO engine + closed-loop autoscaler (control plane)",
        "",
        "The burn-rate SLO engine (`lightgbm_tpu/obs/slo.py`) and the "
        "closed-loop",
        "fleet autoscaler (`lightgbm_tpu/serve/autoscaler.py`, "
        "`docs/Serving.md`)",
        "driven by injected clocks against a scripted error stream — "
        "no sleeping, so",
        "the reaction arithmetic is exact.  Measured on the **CPU "
        f"backend** ({a.get('env', '?')}, {a.get('date', '?')}, "
        f"`{a.get('source', '?')}`); {a.get('forest', '')}.  "
        f"Windows {slo.get('windows_s', '?')} s, grow threshold "
        f"{fmt(asc.get('grow_burn'), 1)}x burn on fast+mid, drain "
        f"after {fmt(asc.get('drain_idle_s'), 0)} s sustained idle:",
        "",
        "| cell | value |",
        "|---|---|",
        f"| surge -> grow reaction | "
        f"{fmt(grow.get('grow_reaction_s'), 0)} s (mid window "
        f"{fmt(grow.get('window_mid_s'), 0)} s is the binding "
        f"signal) |",
        f"| first drain after surge end | "
        f"{fmt(drain.get('first_drain_after_surge_end_s'), 0)} s "
        f"(hysteresis {fmt(drain.get('drain_idle_s'), 0)} s idle, "
        f"{fmt(drain.get('drain_cooldown_s'), 0)} s cooldown) |",
        f"| decisions / actuations (active run) | "
        f"{fmt(seq.get('decisions'), 0)} / {fmt(seq.get('actions'), 0)}"
        f" |",
        f"| evaluate() overhead | p50 {fmt(over.get('p50_ms'), 3)} ms, "
        f"p99 {fmt(over.get('p99_ms'), 3)} ms over "
        f"{fmt(over.get('evaluations'), 0)} control steps |",
        "",
        "Acceptance pins (recorded in the artifact): "
        + ("grow landed within the mid burn window"
           if pins.get("grow_within_mid_window") else
           "grow MISSED the mid burn window") + "; "
        + ("every drain respected the sustained-idle hysteresis and "
           "cooldown" if pins.get("drain_respects_hysteresis") else
           "a drain violated the hysteresis") + "; "
        + ("dry-run replayed an identical decision sequence with zero "
           "actuations" if pins.get("dry_run_parity") else
           "dry-run DIVERGED from the active decisions") + "; "
        + ("the fleet returned to min replicas"
           if pins.get("drained_back_to_min") else
           "the fleet did NOT return to min replicas")
        + ".  The chaos e2e (`tools/chaos_router.py --autoscale`) "
        "drives the same",
        "policy against a REAL process fleet: surge -> grow, brownout "
        "-> admission",
        "retune before the budget exhausts, restore on burn clear, "
        "idle -> drain,",
        "and a fault-wedged controller that leaves the fleet serving.",
        ""]
    return lines


def ckpt_section(c):
    if not c:
        return []
    cfg = c.get("config") or {}
    lines = [
        "## Checkpoint/resume (fault-tolerant training, "
        "`lightgbm_tpu/ckpt/`)",
        "",
        "Atomic, content-hashed training checkpoints every "
        "`snapshot_freq`",
        "iterations plus a SIGTERM best-effort final one; "
        "`resume_from=` continues",
        "BIT-exactly (trees, scores, RNG streams — "
        "`tests/test_checkpoint.py`).",
        f"Measured on the **CPU backend** ({c.get('env', '?')}, "
        f"{c.get('date', '?')},",
        f"`{c.get('source', '?')}`), {c.get('forest', '')}, snapshot "
        f"every {fmt(cfg.get('snapshot_freq'), 0)} iterations:", "",
        "| path | save (mean) | snapshot size | save overhead | load "
        "| resume warmup | resume compiles |",
        "|---|---|---|---|---|---|---|"]
    for cell in c.get("cells", []):
        lines.append(
            f"| {cell.get('label', '?')} | "
            f"{fmt(cell.get('save_ms_mean'), 1)} ms | "
            f"{fmt(cell.get('ckpt_bytes', 0) / 1e3, 1)} KB | "
            f"{fmt(cell.get('save_overhead_pct'), 2)}% | "
            f"{fmt(cell.get('load_ms'), 1)} ms | "
            f"{fmt(cell.get('resume_warmup_s'), 2)} s | "
            f"{cell.get('resume_xla_compiles', 0):d} |")
    lines += [
        "",
        "Save overhead is the checkpoint wall time as a fraction of "
        "training",
        "wall time (`triage_run.py` flags runs past 5%).  Resume "
        "warmup is the",
        "in-process continuation bill (dataset rebuild + restore + 5 "
        "iterations)",
        "and its compile count is the resume-SPECIFIC delta — a "
        "fresh replacement",
        "machine additionally pays the normal first-run compiles.  A "
        "mid-fused-block",
        "snapshot aligns to the served boundary via the exact rewind "
        "replay, so the",
        "fused path checkpoints at any `snapshot_freq`.", ""]
    return lines


def continual_section(c):
    if not c:
        return []
    cfg = c.get("config") or {}
    lines = [
        "## Continual training (ingest -> train -> validate -> "
        "publish, `lightgbm_tpu/cont/`)",
        "",
        "The continual daemon (`task=continual`, `docs/Continual.md`) "
        "tails a batch",
        "directory, gates every batch through the validation pipeline "
        "(schema /",
        "non-finite / drift), extends or refits the model, checkpoints "
        "(PR 5), and",
        "hands snapshots to the serve watcher for canary-validated "
        "publish (PR 6).",
        f"Measured on the **CPU backend** ({c.get('env', '?')}, "
        f"{c.get('date', '?')},",
        f"`{c.get('source', '?')}`), {c.get('forest', '')}:", "",
        "| cell | batch (train+ckpt) | validate | validate overhead "
        "| publish (manifest+canary+swap) | batch-to-publish |",
        "|---|---|---|---|---|---|"]
    for cell in c.get("cells", []):
        lines.append(
            f"| {cell.get('label', '?')} | "
            f"{fmt(cell.get('batch_ms_mean'), 1)} ms | "
            f"{fmt(cell.get('validate_ms_mean'), 2)} ms | "
            f"{fmt(cell.get('validate_overhead_pct'), 2)}% | "
            f"{fmt(cell.get('publish_ms'), 1)} ms | "
            f"{fmt(cell.get('batch_to_publish_ms'), 1)} ms |")
    lines += [
        "",
        "Batch time is the steady-state ingest->extend/refit->"
        "checkpoint wall per",
        f"accepted batch (first warmup batch excluded, "
        f"{fmt(cfg.get('rows'), 0)} rows x "
        f"{fmt(cfg.get('features'), 0)} features,",
        f"{fmt(cfg.get('rounds'), 0)} rounds per extend batch).  "
        "Refit batches recalibrate leaf",
        "values only (no new trees), so their cost is the gradient "
        "replay, not",
        "tree building.  Publish is the watcher's full gate: manifest "
        "hash verify +",
        "canary scoring + flatten/swap into a cold server; a warm "
        "fleet amortizes",
        "the flatten.  The chaos e2e (`tools/chaos_continual.py`, "
        "CI) pins the",
        "robustness half: quarantine accounting, SIGKILL/SIGTERM "
        "resume",
        "bit-exactness, and zero invalid models published.", ""]
    return lines


def ingest_section(g):
    if not g:
        return []
    cells = g.get("cells") or {}
    bp = cells.get("bin_pass") or {}
    cl = cells.get("cache_load") or {}
    up = cells.get("upload") or {}
    tr = cells.get("train") or {}
    cfg = g.get("config") or {}
    lines = [
        "## Out-of-core streaming ingest (`io/stream.py` + "
        "`io/cache.py`)",
        "",
        "The streamed data plane (`stream_ingest=true`, "
        "`docs/Streaming.md`): raw",
        "rows read chunk-by-chunk off an mmap source, binned once "
        "into the",
        "crash-safe content-keyed cache, and uploaded to the device "
        "training",
        "layout in budgeted double-buffered windows.  Measured on the "
        f"**CPU backend** ({g.get('env', '?')}, {g.get('date', '?')},",
        f"`{g.get('source', '?')}`), {g.get('forest', '')}:", "",
        "| cell | wall | throughput / note |",
        "|---|---|---|",
        f"| streamed bin pass ({fmt(bp.get('chunks'), 0)} chunks) | "
        f"{fmt(bp.get('wall_s'), 2)} s | "
        f"{fmt(bp.get('raw_mb_per_s'), 1)} MB/s raw in, "
        f"{fmt(bp.get('cache_write_mb_per_s'), 1)} MB/s cache out |",
        f"| cache load + full sha256 verify | "
        f"{fmt(cl.get('wall_s'), 3)} s | "
        f"{fmt(cl.get('verify_mb_per_s'), 1)} MB/s verified |",
        f"| host->device upload ({fmt(up.get('windows'), 0)} x "
        f"{fmt(up.get('window_rows'), 0)}-row windows) | "
        f"{fmt(up.get('on_ms'), 1)} ms (double-buffered) vs "
        f"{fmt(up.get('off_ms'), 1)} ms (serial) | "
        f"{fmt(100 * (up.get('overlap_fraction') or 0), 1)}% of window "
        f"prep hidden under device copies |",
        f"| train, streamed vs resident ({fmt(tr.get('rounds'), 0)} "
        f"rounds) | {fmt(tr.get('streamed_wall_s'), 2)} s vs "
        f"{fmt(tr.get('resident_wall_s'), 2)} s | "
        f"{fmt(tr.get('streamed_over_resident'), 3)}x — identical "
        f"device program, byte-identical model |",
        "",
        f"The binned cache ({fmt(bp.get('cache_write_mb'), 2)} MB for "
        f"{fmt(cfg.get('rows'), 0)} x "
        f"{fmt(cfg.get('features'), 0)}) is the residency unit: host "
        "RAM holds only",
        "chunk-sized staging windows, so \"rows must fit in RAM\" "
        "becomes \"rows",
        "must fit on disk\".  On the 2-core container the "
        "double-buffer thread's",
        "scheduling overhead exceeds the sub-millisecond \"device "
        "copies\" it",
        "hides (on > off above) — the prefetch window exists for a "
        "real",
        "host->device copy, whose cost on the present chip is not "
        "measured",
        "(`stream_prefetch=false` keeps the serial path).",
        "The robustness half is pinned by `tools/chaos_ingest.py` in "
        "CI: SIGKILL",
        "mid-binning resumes with zero mapper re-fits, a corrupt or "
        "truncated",
        "chunk re-bins alone, and the resumed model is byte-identical "
        "to the",
        "in-memory oracle.", ""]
    return lines


def paged_section(d):
    if not d:
        return []
    cells = d.get("cells") or []
    budget = d.get("device_call_budget") or {}
    pins = d.get("pins") or {}
    cfg = d.get("config") or {}
    lines = [
        "## Out-of-core on-device training (`io/pager.py`)",
        "",
        "The device-block pager (`paged_training`, `hbm_budget_mb`, "
        "`docs/Streaming.md`):",
        "the binned training matrix lives in host memory and row "
        "pages are served",
        "into the fused super-step through `jax.pure_callback` with "
        "double-buffered",
        "prefetch — the paged model is BYTE-IDENTICAL to the resident "
        "one.  Measured",
        f"on the **CPU backend** ({d.get('env', '?')}, "
        f"{d.get('date', '?')}, `{d.get('source', '?')}`),",
        f"{d.get('forest', '')}:", "",
        "| cell | wall | pages | vs resident |",
        "|---|---|---|---|"]
    for c in cells:
        if c.get("n_pages"):
            pages = (f"{fmt(c.get('n_pages'), 0)} x "
                     f"{fmt(c.get('page_rows'), 0)} rows, "
                     f"{fmt(c.get('pages_served'), 0)} served "
                     f"({fmt(c.get('paged_mb'), 0)} MB)")
            ratio = f"{fmt(c.get('wall_over_resident'), 3)}x"
        else:
            pages = "resident (no paging)"
            ratio = "1.0x"
        lines.append(f"| {c.get('label', '?')} | "
                     f"{fmt(c.get('wall_s'), 2)} s | {pages} | "
                     f"{ratio} |")
    page_counts = [c.get("n_pages") for c in
                   (budget.get("cells") or []) if c.get("n_pages")]
    ok = budget.get("budget_ok_at_all_page_counts")
    lines += [
        "",
        f"Device-call budget: the fused super-step stays at "
        f"**{fmt(budget.get('device_call_budget_per_block'), 0)} "
        f"host->device calls per K-block** with paging active "
        f"(hard-asserted at {', '.join(fmt(p, 0) for p in page_counts)}"
        f" pages{' — held' if ok else ' — BROKEN'}): page serves are",
        "`pure_callback`s INSIDE the one compiled scan, so paging "
        "changes where the",
        "bytes live, not how often the host touches the device "
        "(`tools/prof_superstep.py`",
        "pins the same budget in `BENCH_superstep_cpu.json`).",
        "Byte-parity pins: explicit `paged_page_rows` "
        f"{'held' if pins.get('byte_identical_paged_vs_resident') else 'BROKEN'}, "
        f"`hbm_budget_mb` auto (budget "
        f"{fmt(cfg.get('auto_hbm_budget_mb'), 2)} MB -> "
        f"{'paged, held' if pins.get('byte_identical_auto_vs_resident') else 'BROKEN'}).",
        "",
        "Honest caveat: host RAM backs both the \"device\" buffers "
        "and the page store",
        "on this container, so the paged slowdown prices the "
        "callback serve machinery",
        "only and the overlap columns are milliseconds of "
        "trivially-cheap prep — the",
        "HBM-ceiling win (training sets larger than device memory) "
        "is the ROADMAP",
        "real-hardware item.  The crash half is pinned by "
        "`tools/chaos_pager.py` in CI:",
        "SIGKILL mid-page-stream resumes byte-identical, a writeback "
        "fault is absorbed,",
        "and a fetch fault fails loudly via the poison contract "
        "instead of training on",
        "garbage.", ""]
    return lines


def obs_section(o):
    if not o:
        return []
    lines = [
        "## Observability-plane overhead (`lightgbm_tpu/obs/`)",
        "",
        "The observability plane's optional half (`docs/"
        "Observability.md`:",
        "Prometheus `/metrics` registry + telemetry-counter mirror + "
        "armed",
        "flight-recorder ring) measured against the always-on half "
        "(telemetry",
        "JSONL + span tagging, one contextvar read per record — "
        "present in BOTH",
        "cells) on the",
        f"**CPU backend** ({o.get('env', '?')}, {o.get('date', '?')}, "
        f"`{o.get('source', '?')}`),",
        f"interleaved off/on cells, {fmt(o.get('reps'), 0)} reps "
        f"each:", "",
        "| cell | plane off | plane on | overhead | rep spread |",
        "|---|---|---|---|---|"]
    for cell in o.get("cells", []):
        if cell.get("cell") == "train":
            off = f"{fmt(cell.get('off_s'), 2)} s"
            on = f"{fmt(cell.get('on_s'), 2)} s"
            label = (f"train ({fmt(cell.get('rows'), 0)} rows x "
                     f"{fmt(cell.get('rounds'), 0)} rounds)")
        else:
            off = f"{fmt_rate(cell.get('off_rps'))}/s req"
            on = f"{fmt_rate(cell.get('on_rps'))}/s req"
            label = (f"serve ({fmt(cell.get('requests'), 0)} "
                     f"sequential {fmt(cell.get('rows_per_req'), 0)}"
                     f"-row requests)")
        lines.append(f"| {label} | {off} | {on} | "
                     f"{fmt(cell.get('overhead_pct'), 2)}% | "
                     f"±{fmt(cell.get('spread_pct'), 1)}% |")
    worst = max((abs(float(c.get("overhead_pct", 0.0)))
                 for c in o.get("cells", [])), default=0.0)
    noise = max((float(c.get("spread_pct", 0.0))
                 for c in o.get("cells", [])), default=0.0)
    verdict = ("within the container's rep-to-rep noise floor"
               if worst <= noise else
               "above the rep-to-rep noise floor — re-examine before "
               "shipping")
    lines += [
        "",
        f"Worst-cell overhead {fmt(worst, 2)}% against a "
        f"±{fmt(noise, 1)}% off-cell spread — {verdict}.",
        "The serve cell is the plane's worst case by construction "
        "(sequential",
        "sub-millisecond requests, so per-request counter/histogram "
        "updates and",
        "the mirror hook are maximally amplified); batched or "
        "concurrent traffic",
        "amortizes the same fixed cost over more rows.  The "
        "flight-recorder ring",
        "is one deque append per record until a trigger fires.", ""]
    return lines


def sweep_section(s):
    if not s:
        return []
    lines = [
        "## Many-model battery (sweeps + k-fold CV, "
        "`lightgbm_tpu/models/battery.py`)",
        "",
        "B independent boosters train as ONE compiled program: the "
        "binned matrix",
        "is resident once and per-model state (scores, learning "
        "rates, PRNG keys,",
        "fold weights) stacks on a vmapped model axis — so a "
        "hyperparameter sweep",
        "over traced params costs ONE XLA compile however many "
        "members it has,",
        "and every member is byte-equal to the same params trained "
        "solo",
        "(`tests/test_sweep.py`).  Measured on the **CPU backend** "
        f"({s.get('env', '?')},",
        f"{s.get('date', '?')}, `{s.get('source', '?')}`), "
        f"{s.get('forest', '')}:", "",
        "| B | battery | models/s | solo (1/process) | models/s | "
        "speedup | solo (warm loop) | speedup | compiles |",
        "|---|---|---|---|---|---|---|---|---|"]
    for cell in s.get("cells", []):
        lines.append(
            f"| {cell.get('B', '?')} | "
            f"{fmt(cell.get('battery_wall_s'), 2)} s | "
            f"{fmt(cell.get('battery_models_per_s'), 3)} | "
            f"{fmt(cell.get('solo_proc_wall_s'), 2)} s | "
            f"{fmt(cell.get('solo_proc_models_per_s'), 3)} | "
            f"**{fmt(cell.get('speedup'), 2)}x** | "
            f"{fmt(cell.get('solo_warm_wall_s'), 2)} s | "
            f"{fmt(cell.get('speedup_warm'), 2)}x | "
            f"{cell.get('xla_compiles', '?')} |")
    lines += [
        "",
        "`solo (1/process)` is how sequential sweep drivers actually "
        "run trainings",
        "— one process per candidate, each paying JAX init + its own "
        "compiles;",
        "those are exactly the costs the battery amortizes.  `solo "
        "(warm loop)`",
        "shares one process and a warm compile cache — the floor a "
        "perfectly-cached",
        "sequential driver could hit; on this 1-core CPU the device "
        "compute is the",
        "same work either way, so that ratio hovers near 1 and the "
        "device-side win",
        "(dispatch amortization, model axis over spare devices via "
        "`sweep_shard_models`) only shows on real accelerators.  "
        "`compiles` is the",
        "whole battery's XLA compile count — the single-compile "
        "contract",
        "(`retraces_per_model > 0` is a triage anomaly).", ""]
    return lines


def render(root=ROOT):
    ppath = os.path.join(root, "BENCH_predict_cpu.json")
    pdata = None
    if os.path.exists(ppath):
        with open(ppath) as f:
            pdata = json.load(f)
    spath = os.path.join(root, "BENCH_superstep_cpu.json")
    sdata = None
    if os.path.exists(spath):
        with open(spath) as f:
            sdata = json.load(f)
    vpath = os.path.join(root, "BENCH_serve_cpu.json")
    vdata = None
    if os.path.exists(vpath):
        with open(vpath) as f:
            vdata = json.load(f)
    xpath = os.path.join(root, "BENCH_explain_cpu.json")
    xdata = None
    if os.path.exists(xpath):
        with open(xpath) as f:
            xdata = json.load(f)
    rpath = os.path.join(root, "BENCH_router_cpu.json")
    rdata = None
    if os.path.exists(rpath):
        with open(rpath) as f:
            rdata = json.load(f)
    cpath = os.path.join(root, "BENCH_ckpt_cpu.json")
    cdata = None
    if os.path.exists(cpath):
        with open(cpath) as f:
            cdata = json.load(f)
    copath = os.path.join(root, "BENCH_continual_cpu.json")
    codata = None
    if os.path.exists(copath):
        with open(copath) as f:
            codata = json.load(f)
    gpath = os.path.join(root, "BENCH_ingest_cpu.json")
    gdata = None
    if os.path.exists(gpath):
        with open(gpath) as f:
            gdata = json.load(f)
    pgpath = os.path.join(root, "BENCH_paged_cpu.json")
    pgdata = None
    if os.path.exists(pgpath):
        with open(pgpath) as f:
            pgdata = json.load(f)
    opath = os.path.join(root, "BENCH_obs_cpu.json")
    odata = None
    if os.path.exists(opath):
        with open(opath) as f:
            odata = json.load(f)
    wpath = os.path.join(root, "WEAKSCALE.json")
    wdata = None
    if os.path.exists(wpath):
        with open(wpath) as f:
            wdata = json.load(f)
    swpath = os.path.join(root, "BENCH_sweep_cpu.json")
    swdata = None
    if os.path.exists(swpath):
        with open(swpath) as f:
            swdata = json.load(f)
    apath = os.path.join(root, "BENCH_autoscale_cpu.json")
    adata = None
    if os.path.exists(apath):
        with open(apath) as f:
            adata = json.load(f)

    lines = [HEADER_NOTE]
    lines += [
        "# Benchmark results", "",
        "## Training on the chip", "",
        "Not measured on the present chip.  The reference's bar for "
        "the Higgs",
        "shape (10.5M x 28, 255 leaves, 255 bins, 500 iterations) is "
        "**238.5 s**",
        "on 2x E5-2670v3 (`BASELINE.md`).  `python chip_smoke.py` "
        "proves the",
        "training path starts and learns on one TPU v5e chip; its "
        "wall time is a",
        "smoke timing, not a benchmark number.  Chip measurements "
        "live in",
        "`PERF_LEDGER.jsonl` and are explained in `PERF.md`.", "",
        "Every section below is a **CPU-container** run: it pins "
        "counts and harness",
        "contracts (device calls per block, compiles in the window, "
        "parity) and its",
        "wall-clock columns say nothing about the chip.", ""]
    lines += superstep_section(sdata)
    lines += weakscale_section(wdata)
    lines += predict_section(pdata)
    lines += serve_section(vdata)
    lines += explain_section(xdata)
    lines += router_section(rdata)
    lines += autoscale_section(adata)
    lines += ckpt_section(cdata)
    lines += continual_section(codata)
    lines += ingest_section(gdata)
    lines += paged_section(pgdata)
    lines += sweep_section(swdata)
    lines += obs_section(odata)
    lines += [
        "## Protocol notes", "",
        "- A/B decisions are valid only from interleaved measurements "
        "in one",
        "  process on the chip; the bench exits non-zero when it "
        "finds no chip or",
        "  a phase fails.",
        "- Training runs emit schema-versioned telemetry JSONL "
        "(`telemetry_file=`",
        "  param, `tools/triage_run.py`) attributing time to phases, "
        "compiles and",
        "  collectives.  A phase is a profiler annotation "
        "(`ltpu.<phase>`, on the",
        "  device trace's clock) and a pair of process counters "
        "(`phase_secs/<phase>`,",
        "  `phase_calls/<phase>`); `phases_ms` is their diff over the "
        "record, and compile",
        "  and trace seconds are kept by program "
        "(`xla_compile_secs/jit(superstep)`).",
        "- `tools/check_routed_kernels.py` and "
        "`tools/check_tpu_integration.py`",
        "  validate the Pallas kernels against their segsum oracles "
        "on the chip;",
        "  the CPU suite pins the oracles against brute force.", ""]
    text = "\n".join(lines)
    while "\n\n\n" in text:
        text = text.replace("\n\n\n", "\n\n")
    return text


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="compare against docs/Benchmarks.md; exit 1 "
                         "on drift")
    ap.add_argument("--stdout", action="store_true")
    args = ap.parse_args(argv)
    text = render()
    if args.stdout:
        sys.stdout.write(text)
        return 0
    if args.check:
        on_disk = ""
        if os.path.exists(DOC):
            with open(DOC) as f:
                on_disk = f.read()
        if on_disk != text:
            print("docs/Benchmarks.md DRIFTED from the checked-in "
                  "artifacts; rerun: python tools/render_benchmarks.py")
            import difflib
            for line in list(difflib.unified_diff(
                    on_disk.splitlines(), text.splitlines(),
                    "docs/Benchmarks.md", "rendered", lineterm=""))[:40]:
                print(line)
            return 1
        print("docs/Benchmarks.md matches the artifacts byte-for-byte")
        return 0
    with open(DOC, "w") as f:
        f.write(text)
    print("wrote", DOC)
    return 0


if __name__ == "__main__":
    sys.exit(main())
