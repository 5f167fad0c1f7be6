"""CPU microbench for the fused training super-step (fused_iters).

Measures, on the CPU backend, the per-iteration wall time and the
device-interaction budget of the fused K-iteration ``lax.scan`` path
against the per-iteration (pipelined) path on the same synthetic
binary-classification shape, and writes the ``BENCH_superstep_cpu.json``
artifact ``tools/render_benchmarks.py`` renders into
``docs/Benchmarks.md`` — the same generated-from-artifacts discipline
as ``BENCH_predict_cpu.json``.

The budget numbers come from the telemetry counters the driver
increments (``superstep_dispatches`` = the one jitted scan call per
block, ``superstep_fetches`` = the one packed device->host transfer
per block) plus the packed-record dispatch; the per-iteration path
issues ~5 device calls per iteration (gradients, bagging draw, build
dispatch, score update, record fetch/pack).

A PIPELINED cell (``superstep_pipeline_depth`` 0/1/2 at K=8 on the
dispatch-bound shape) measures the fetch overlap — the
``superstep/fetch`` phase wall that disappears when block K+1's
dispatch goes out before block K's stacked-record fetch — and
HARD-asserts the healthy-path device-call budget stays 2 per K-block
at every depth (pipelining reorders the dispatch/fetch pair, it never
adds calls).

A SHARDED cell (``--shards``, default 8 virtual host devices on CPU)
runs the data-parallel learner through the same fused scan — UNDER
the elastic shard-loss supervisor (``parallel/elastic.py``) — and
pins that its per-block device-call budget MATCHES the serial fused
path: the single-program property `docs/Distributed.md` documents
(the pre-refactor per-call path issued ~5 dispatches per shard per
iteration, the WEAKSCALE.json degradation), and the elastic
heartbeat/watchdog detection riding it at zero extra device calls.

A 2-D SHARDED cell runs ``tree_learner=data2d`` over a (data x
feature) mesh (R x 2 of the same virtual devices) through the same
fused scan and HARD-asserts the identical 2-calls-per-K-block budget:
the per-axis collective factoring changes what moves on the wire, not
how often the host touches the device.

A PAGED cell (``paged_training=on``, the device-block pager of
``io/pager.py``) re-pins the same budget with the binned matrix
served page by page from host memory: page serves ride
``jax.pure_callback`` INSIDE the compiled scan, so the host-side
device-call budget stays 2 per K-block at ANY page count —
hard-asserted per page-rows variant.

    JAX_PLATFORMS=cpu python tools/prof_superstep.py            # write
    JAX_PLATFORMS=cpu python tools/prof_superstep.py --stdout
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "BENCH_superstep_cpu.json")


def measure(variants=(1, 4, 8), n_rows=5_000, n_feat=28, reps=6,
            block=8, learner="serial", num_shards=0, elastic=False,
            mesh_shape=None, extra_params=None):
    """Interleaved A/B: one booster per ``fused_iters`` variant, then
    round-robin 8-iteration blocks across them — the same-process
    interleaving discipline docs/Benchmarks.md's protocol notes
    require (this container's clock jitters 20-40% minute to minute,
    so back-to-back runs measure the machine, not the code).  One
    block = one whole fused super-step, so a dispatch amortizes over
    exactly its serves; min block mean is the steady-state estimate."""
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils import telemetry

    rng = np.random.RandomState(0)
    X = rng.randn(n_rows, n_feat).astype(np.float32)
    y = (X[:, 0] + 0.4 * rng.randn(n_rows) > 0).astype(np.float32)
    mesh = None
    if learner not in ("serial", "data2d") and num_shards > 1:
        import jax
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:num_shards]), ("shard",))
    boosters = {}
    for k in variants:
        params = {"objective": "binary",
                  "num_leaves": 15 if n_rows > 2500 else 7,
                  "max_bin": 63, "verbose": -1, "metric": "None",
                  "num_iterations": 10_000,  # no tail block in-window
                  "tree_learner": learner,
                  "fused_iters": k}
        if extra_params:
            params.update(extra_params)
        if learner == "data2d":
            # the 2-D learner builds its own (data x feature) mesh
            # from the shape spec — no 1-D mesh handed in
            params["num_machines"] = num_shards
            params["mesh_shape"] = "x".join(str(s) for s in mesh_shape)
        d = lgb.Dataset(X, label=y, params=params)
        d.construct()
        bst = lgb.Booster(params=params, train_set=d, mesh=mesh)
        step = bst.update
        if elastic and (mesh is not None or learner == "data2d"):
            # the sharded cell runs under the elastic supervisor
            # (parallel/elastic.py): the healthy-path budget pin below
            # covers the SUPERVISED path — detection must cost zero
            # device calls
            from lightgbm_tpu.parallel import ElasticSupervisor
            step = ElasticSupervisor(bst).update
        # warmup covers the XLA compiles: iteration 0 (unfused bias
        # iteration) plus the first whole fused block
        for _ in range(1 + max(k, 1)):
            step()
        boosters[k] = (bst, step)
    mins = {k: [] for k in variants}
    base_c = telemetry.counters_snapshot()
    for _ in range(reps):
        for k in variants:
            _, step = boosters[k]
            t0 = time.time()
            for _ in range(block):
                step()
            mins[k].append((time.time() - t0) / block)
    end_c = telemetry.counters_snapshot()

    def delta(key):
        return end_c.get(key, 0.0) - base_c.get(key, 0.0)

    iters_per_variant = reps * block
    n_fused = sum(1 for k in variants if k > 1)
    cells = []
    for k in variants:
        fused_blocks = iters_per_variant // k if k > 1 else 0
        cells.append({
            "fused_iters": k,
            "iters_measured": iters_per_variant,
            "iter_s": round(min(mins[k]), 5),
            "iter_s_mean": round(sum(mins[k]) / reps, 5),
            # the counters are process-wide; per-variant attribution is
            # exact because block size k fixes each variant's share
            "dispatches_per_iter": round(2.0 / k, 3) if k > 1 else None,
            "measured_xla_compiles_all_fused": int(
                delta("xla_compiles")) if k > 1 else None,
        })
    total_expected = sum(2 * (iters_per_variant // k)
                         for k in variants if k > 1)
    observed = int(delta("superstep_dispatches") +
                   delta("superstep_fetches"))
    return cells, {"expected_fused_device_calls": total_expected,
                   "observed_fused_device_calls": observed,
                   "n_fused_variants": n_fused}


def measure_pipelined(depths=(0, 1, 2), K=8, n_rows=2_000, n_feat=10,
                      reps=6, block=8):
    """Async block pipelining A/B on the dispatch-bound shape: one
    booster per ``superstep_pipeline_depth``, interleaved 8-update
    windows (window == one whole K=8 block, so every window is one
    dispatch + one fetch at steady state).  Reports per-depth steady
    wall, the ``superstep/fetch`` phase wall (the stall the pipeline
    exists to hide — at depth > 0 the block has been computing since
    its dispatch one serve-cycle earlier, so the fetch waits only for
    the residual), and HARD-asserts the healthy-path device-call
    budget stays 2 per K-block at any depth."""
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils import profiling, telemetry

    rng = np.random.RandomState(0)
    X = rng.randn(n_rows, n_feat).astype(np.float32)
    y = (X[:, 0] + 0.4 * rng.randn(n_rows) > 0).astype(np.float32)
    boosters = {}
    for depth in depths:
        params = {"objective": "binary", "num_leaves": 7,
                  "max_bin": 63, "verbose": -1, "metric": "None",
                  "num_iterations": 10_000, "fused_iters": K,
                  "superstep_pipeline_depth": depth}
        d = lgb.Dataset(X, label=y, params=params)
        d.construct()
        bst = lgb.Booster(params=params, train_set=d)
        # warmup ends exactly on a block boundary (1 bias iteration +
        # one whole block), pre-seeding the in-flight queue — every
        # measured window is then exactly one steady-state block
        for _ in range(1 + K):
            bst.update()
        boosters[depth] = bst
    mins = {d: [] for d in depths}
    fetch_ms = {d: [] for d in depths}
    calls = {d: [0, 0] for d in depths}
    for _ in range(reps):
        for depth in depths:
            bst = boosters[depth]
            ph0 = profiling.snapshot()
            c0 = telemetry.counters_snapshot()
            t0 = time.time()
            for _ in range(block):
                bst.update()
            mins[depth].append((time.time() - t0) / block)
            c1 = telemetry.counters_snapshot()
            fetch_ms[depth].append(
                profiling.delta_ms(ph0).get("superstep/fetch", 0.0) /
                block)
            calls[depth][0] += int(c1.get("superstep_dispatches", 0) -
                                   c0.get("superstep_dispatches", 0))
            calls[depth][1] += int(c1.get("superstep_fetches", 0) -
                                   c0.get("superstep_fetches", 0))
    cells = []
    blocks = reps * block // K
    for depth in depths:
        disp, fet = calls[depth]
        # the pin this cell exists for: pipelining reorders the
        # dispatch/fetch pair, it NEVER adds device calls — 2 per
        # K-block at any depth
        assert disp == blocks and fet == blocks, (
            f"device-call budget broken at pipeline_depth={depth}: "
            f"{disp} dispatches / {fet} fetches over {blocks} blocks "
            f"(expected {blocks}/{blocks})")
        cells.append({
            "pipeline_depth": depth,
            "fused_iters": K,
            "iter_s": round(min(mins[depth]), 6),
            "iter_s_mean": round(sum(mins[depth]) / reps, 6),
            "fetch_ms_per_iter": round(min(fetch_ms[depth]), 4),
            "dispatches_per_block": round(disp / blocks, 3),
            "fetches_per_block": round(fet / blocks, 3),
        })
    base = cells[0]
    for c in cells:
        c["speedup_vs_unpipelined"] = round(
            base["iter_s"] / max(c["iter_s"], 1e-9), 2)
        c["fetch_wall_hidden_ms"] = round(
            max(base["fetch_ms_per_iter"] - c["fetch_ms_per_iter"],
                0.0), 4)
    return {
        "shape": f"{n_rows} x {n_feat} binary, 7 leaves, K={K}, "
                 f"interleaved min-of-{reps} {block}-update windows",
        "device_call_budget_per_block": 2,
        "budget_ok_at_all_depths": True,
        "cells": cells,
    }


def measure_paged(page_rows_variants=(256, 64), K=8, n_rows=2_000,
                  n_feat=10, reps=6, block=8):
    """Out-of-core cell: the device-block pager serves the binned
    feature matrix page by page from host memory, yet the fused
    super-step's HOST-SIDE device-call budget must not move — page
    serves ride ``jax.pure_callback`` INSIDE the one compiled scan,
    so they are never dispatches.  One resident booster plus one
    paged booster per page-count variant, interleaved 8-update
    windows; HARD-asserts 2 calls per K-block at EVERY page count
    (the pin re-pinned here per ISSUE 19: paging changes where the
    bytes live, not how often the host touches the device)."""
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils import telemetry

    rng = np.random.RandomState(0)
    X = rng.randn(n_rows, n_feat).astype(np.float32)
    y = (X[:, 0] + 0.4 * rng.randn(n_rows) > 0).astype(np.float32)
    variants = [None] + list(page_rows_variants)  # None == resident
    boosters, n_pages = {}, {}
    for pr in variants:
        params = {"objective": "binary", "num_leaves": 7,
                  "max_bin": 63, "verbose": -1, "metric": "None",
                  "num_iterations": 10_000, "fused_iters": K}
        if pr is not None:
            params["paged_training"] = "on"
            params["paged_page_rows"] = pr
        d = lgb.Dataset(X, label=y, params=params)
        d.construct()
        bst = lgb.Booster(params=params, train_set=d)
        pager = bst._gbdt._pager
        if pr is None:
            assert pager is None, "resident baseline built a pager"
            n_pages[pr] = 0
        else:
            assert pager is not None, (
                f"paged_training=on at page_rows={pr} did not build "
                f"a pager (eligibility gate regressed?)")
            n_pages[pr] = int(pager.plan.n_pages)
            assert n_pages[pr] >= 3, (
                f"page_rows={pr} yields only {n_pages[pr]} pages — "
                f"shape too small to exercise the paged lane")
        for _ in range(1 + K):
            bst.update()
        boosters[pr] = bst
    mins = {pr: [] for pr in variants}
    calls = {pr: [0, 0] for pr in variants}
    for _ in range(reps):
        for pr in variants:
            bst = boosters[pr]
            c0 = telemetry.counters_snapshot()
            t0 = time.time()
            for _ in range(block):
                bst.update()
            mins[pr].append((time.time() - t0) / block)
            c1 = telemetry.counters_snapshot()
            calls[pr][0] += int(c1.get("superstep_dispatches", 0) -
                                c0.get("superstep_dispatches", 0))
            calls[pr][1] += int(c1.get("superstep_fetches", 0) -
                                c0.get("superstep_fetches", 0))
    cells = []
    blocks = reps * block // K
    for pr in variants:
        disp, fet = calls[pr]
        # the ISSUE-19 pin: page serves are pure_callbacks inside the
        # compiled scan, NOT dispatches — the budget stays 2 per
        # K-block whether the matrix is resident or split 32 ways
        assert disp == blocks and fet == blocks, (
            f"paged device-call budget broken at page_rows={pr} "
            f"({n_pages[pr]} pages): {disp} dispatches / {fet} "
            f"fetches over {blocks} blocks (expected "
            f"{blocks}/{blocks})")
        stats = {}
        if pr is not None:
            stats = boosters[pr]._gbdt._pager.stats()
            assert stats.get("pages", 0) > 0, (
                f"page_rows={pr}: pager built but zero pages served")
        cells.append({
            "page_rows": pr, "n_pages": n_pages[pr],
            "fused_iters": K,
            "iter_s": round(min(mins[pr]), 6),
            "iter_s_mean": round(sum(mins[pr]) / reps, 6),
            "dispatches_per_block": round(disp / blocks, 3),
            "fetches_per_block": round(fet / blocks, 3),
            "pages_served": int(stats.get("pages", 0)),
            "prefetch_overlap_s": round(
                float(stats.get("overlap_s", 0.0)), 4),
        })
    base = cells[0]
    for c in cells:
        c["slowdown_vs_resident"] = round(
            c["iter_s"] / max(base["iter_s"], 1e-9), 2)
    return {
        "shape": f"{n_rows} x {n_feat} binary, 7 leaves, K={K}, "
                 f"interleaved min-of-{reps} {block}-update windows",
        "device_call_budget_per_block": 2,
        "budget_ok_at_all_page_counts": True,
        "note": "CPU slowdown is the honest host-callback cost on a "
                "2-core container (host RAM serves both sides); the "
                "TPU-side win — training sets larger than HBM — is "
                "the ROADMAP real-hardware item",
        "cells": cells,
    }


def measure_split(reps=6, n_rows=2_048, n_feat=10, n_bins=16, W=8,
                  inner=16):
    """Best-split cell: the histogram→split producer/consumer pair
    FUSED into one compiled program vs dispatched as two.

    Two sub-cells:

    - ``op``: the op-level A/B on the DISPATCH-BOUND shape (the same
      discipline as the dispatch_bound superstep cells: big shapes
      are compute-parity on CPU by physics) — unfused runs the
      batched histogram pass and the best-split scan as TWO jitted
      calls (the (W, F, B, 3) histogram round-trips through a
      host-visible buffer between them, the boundary the Pallas fused
      epilogue deletes on TPU), fused runs them as ONE jitted
      program.  The CPU-measurable saving is the second dispatch +
      histogram materialization; the TPU-side win is the full HBM
      round-trip.
    - ``superstep``: end-to-end budget pin — training with
      split_kernel=pallas (the interpret-mode CPU lane: correctness +
      budget, NOT kernel speed) must keep the fused super-step at
      exactly 2 device calls per K-block, same as split_kernel=xla.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.ops.histogram import histogram_segsum_multi
    from lightgbm_tpu.ops.split import SplitParams, find_best_split

    rng = np.random.RandomState(0)
    bins = rng.randint(0, n_bins - 1,
                       size=(n_feat, n_rows)).astype(np.uint8)
    vals = np.stack([rng.randn(n_rows), np.abs(rng.randn(n_rows)),
                     np.ones(n_rows)], -1).astype(np.float32)
    sel = rng.randint(-1, W, size=n_rows).astype(np.int32)
    nb = jnp.full(n_feat, n_bins, jnp.int32)
    mt = jnp.zeros(n_feat, jnp.int32)
    sp = SplitParams(max_bin=n_bins, min_data_in_leaf=5, any_cat=False,
                     any_missing=False)
    parents = np.zeros((W, 3), np.float32)
    for w in range(W):
        m = sel == w
        parents[w] = [vals[m, 0].sum(), vals[m, 1].sum(), m.sum()]
    ic, fm = jnp.zeros(n_feat, bool), jnp.ones(n_feat, bool)

    @jax.jit
    def hist_pass(bt, v, s):
        return histogram_segsum_multi(bt, v, s, n_bins, W)

    def split_scan(h, par):
        return jax.vmap(lambda hh, pp: find_best_split(
            hh, pp, nb, mt, ic, fm, sp))(h, par)

    split_jit = jax.jit(split_scan)

    @jax.jit
    def fused(bt, v, s, par):
        return split_scan(hist_pass(bt, v, s), par)

    bt, v, s = (jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(sel))
    par = jnp.asarray(parents)
    # warmup compiles
    jax.block_until_ready(split_jit(hist_pass(bt, v, s), par))
    jax.block_until_ready(fused(bt, v, s, par))
    t_un, t_fu = [], []
    for _ in range(reps):
        t0 = time.time()
        for _ in range(inner):
            h = jax.block_until_ready(hist_pass(bt, v, s))
            jax.block_until_ready(split_jit(h, par))
        t_un.append((time.time() - t0) / inner)
        t0 = time.time()
        for _ in range(inner):
            jax.block_until_ready(fused(bt, v, s, par))
        t_fu.append((time.time() - t0) / inner)
    op_cell = {
        "shape": f"{n_rows} x {n_feat} x {n_bins} bins, {W} leaf "
                 f"lanes, interleaved min-of-{reps}",
        "unfused_s_per_pass": round(min(t_un), 6),
        "fused_s_per_pass": round(min(t_fu), 6),
        "dispatches_per_pass": {"unfused": 2, "fused": 1},
        "speedup": round(min(t_un) / max(min(t_fu), 1e-9), 3),
        "note": "CPU wall is compute-parity by physics (host RAM is "
                "one memory; the XLA CPU scan reads the histogram "
                "from cache either way) — the structural win is the "
                "dispatch column (2 -> 1) and, on TPU, the "
                "(W,F,B,3) HBM write+read-back between the passes "
                "that the fused epilogue deletes (the r04 profile's "
                "per-wave histogram fetch); TPU wall validation is "
                "the ROADMAP real-hardware item",
    }

    # end-to-end device-call budget pin at K=4 per split engine
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils import telemetry
    K, n_tr = 4, 1_500
    X = rng.randn(n_tr, 10).astype(np.float32)
    y = (X[:, 0] + 0.4 * rng.randn(n_tr) > 0).astype(np.float32)
    cells = []
    for sk in ("xla", "pallas"):
        params = {"objective": "binary", "num_leaves": 7,
                  "max_bin": 63, "verbose": -1, "metric": "None",
                  "num_iterations": 10_000, "fused_iters": K,
                  "split_kernel": sk}
        d = lgb.Dataset(X, label=y, params=params)
        d.construct()
        bst = lgb.Booster(params=params, train_set=d)
        for _ in range(1 + K):
            bst.update()
        walls = []
        c0 = telemetry.counters_snapshot()
        for _ in range(reps):
            t0 = time.time()
            for _ in range(2 * K):
                bst.update()
            walls.append((time.time() - t0) / (2 * K))
        c1 = telemetry.counters_snapshot()
        blocks = reps * 2
        disp = int(c1.get("superstep_dispatches", 0) -
                   c0.get("superstep_dispatches", 0))
        fet = int(c1.get("superstep_fetches", 0) -
                  c0.get("superstep_fetches", 0))
        # the acceptance pin: the fused path (and the xla baseline)
        # stays at 2 device calls per K-block — the split engine
        # changes WHAT runs inside the one compiled scan, never how
        # many times the host touches the device
        assert disp == blocks and fet == blocks, (
            f"split_kernel={sk}: {disp}/{fet} calls over {blocks} "
            f"blocks (expected {blocks}/{blocks})")
        cells.append({
            "split_kernel": sk,
            "fused_iters": K,
            "iter_s": round(min(walls), 6),
            "dispatches_per_block": round(disp / blocks, 3),
            "fetches_per_block": round(fet / blocks, 3),
            "tier_split_kernel":
                bst._gbdt.tier_decision["split_kernel"],
        })
    return {
        "op": op_cell,
        "superstep": {
            "shape": f"{n_tr} x 10 binary, 7 leaves, K={K}",
            "device_call_budget_per_block": 2,
            "budget_ok": True,
            "note": "split_kernel=pallas on CPU runs the interpret "
                    "lane (correctness + budget pin, not kernel "
                    "speed); TPU wall-clock is the ROADMAP "
                    "real-hardware item",
            "cells": cells,
        },
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--stdout", action="store_true")
    ap.add_argument("--rows", type=int, default=5_000)
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--split-only", action="store_true",
                    help="re-measure only the best-split cell and "
                         "merge it into the existing artifact")
    ap.add_argument("--shards", type=int, default=8,
                    help="mesh width for the sharded fused cell "
                         "(virtual host devices forced on CPU)")
    args = ap.parse_args(argv)

    # the sharded cell needs the virtual mesh BEFORE the first jax
    # backend init (same contract as tests/conftest.py); unconditional
    # — the flag only affects the host platform, and gating it on an
    # exact JAX_PLATFORMS match silently dropped the sharded cell (and
    # its matches_serial_fused pin) from the artifact on hosts where
    # cpu is auto-detected rather than requested
    from lightgbm_tpu.utils.env import force_host_platform_devices
    force_host_platform_devices(args.shards)
    import jax
    if args.split_only:
        # fast path: refresh ONLY the best-split cell, preserving the
        # other cells of an existing artifact
        split_cell = measure_split(reps=args.reps)
        out = {}
        if os.path.exists(OUT):
            with open(OUT) as f:
                out = json.load(f)
        out["split"] = split_cell
        out["date"] = time.strftime("%Y-%m-%d")
        text = json.dumps(out, indent=2)
        if args.stdout:
            print(text)
            return 0
        with open(OUT, "w") as f:
            f.write(text + "\n")
        print("wrote", OUT, "(split cell only)")
        return 0
    cells, budget = measure(n_rows=args.rows, reps=args.reps)
    base = cells[0]["iter_s"]
    for c in cells:
        c["speedup_vs_unfused"] = round(base / max(c["iter_s"], 1e-9), 2)
    # dispatch-bound pair: a shape small enough that per-iteration
    # host dispatch work is NOT hidden behind device compute — the
    # per-iteration host cost the fused path exists to amortize (the
    # 5000-row cells above are device-compute-bound on CPU, so their
    # wall clock is parity by physics)
    tiny, _ = measure(variants=(1, 8), n_rows=2_000, n_feat=10,
                      reps=args.reps)
    tbase = tiny[0]["iter_s"]
    for c in tiny:
        c["speedup_vs_unfused"] = round(tbase / max(c["iter_s"], 1e-9),
                                        2)
        c["shape"] = "2000 x 10, 7 leaves (dispatch-bound)"
    # SHARDED fused super-step: the data-parallel learner rides the
    # same K-iteration scan under shard_map, so its device-call budget
    # per block must MATCH the serial fused path (2 calls per K
    # iterations — one scan dispatch, one packed fetch), not the 5K
    # per-shard dispatches of the pre-refactor per-call path.  Runs on
    # the virtual host mesh when >= 2 devices are exposed.
    sharded_cells, sharded_budget = [], None
    D = min(len(jax.devices()), args.shards)
    if D >= 2:
        sharded_cells, sharded_budget = measure(
            variants=(8,), n_rows=2_048 * D, n_feat=10, reps=args.reps,
            learner="data", num_shards=D, elastic=True)
        for c in sharded_cells:
            c["shape"] = (f"{2048 * D} x 10, data-parallel over "
                          f"{D} shards, elastic-supervised")
        sharded_budget["num_shards"] = D
        sharded_budget["supervised_elastic"] = True
        sharded_budget["matches_serial_fused"] = (
            sharded_budget["observed_fused_device_calls"] ==
            sharded_budget["expected_fused_device_calls"])
    # 2-D SHARDED cell: tree_learner=data2d over a (data x feature)
    # mesh rides the SAME fused scan — the per-axis collective
    # factoring (histogram psum over "data" only, tile merge + routing
    # over "feature") must not change how many times the host touches
    # the device, so its budget is HARD-asserted at 2 per K-block
    sharded2d_cells, sharded2d_budget = [], None
    if D >= 4:
        r2, f2 = D // 2, 2
        sharded2d_cells, sharded2d_budget = measure(
            variants=(8,), n_rows=2_048 * r2, n_feat=10,
            reps=args.reps, learner="data2d", num_shards=D,
            mesh_shape=(r2, f2), elastic=True)
        for c in sharded2d_cells:
            c["shape"] = (f"{2048 * r2} x 10, data2d over a "
                          f"{r2}x{f2} (data x feature) mesh, "
                          f"elastic-supervised")
        sharded2d_budget["num_shards"] = D
        sharded2d_budget["mesh_shape"] = [r2, f2]
        sharded2d_budget["supervised_elastic"] = True
        sharded2d_budget["matches_serial_fused"] = (
            sharded2d_budget["observed_fused_device_calls"] ==
            sharded2d_budget["expected_fused_device_calls"])
        assert sharded2d_budget["matches_serial_fused"], (
            f"2-D mesh device-call budget broken: "
            f"{sharded2d_budget['observed_fused_device_calls']} calls "
            f"observed, "
            f"{sharded2d_budget['expected_fused_device_calls']} "
            f"expected (2 per K-block on the {r2}x{f2} mesh)")
    # ASYNC BLOCK PIPELINING cell (superstep_pipeline_depth): the
    # per-block fetch overlapped behind the next block's dispatch,
    # with the 2-calls-per-K-block budget hard-asserted at every depth
    pipelined = measure_pipelined(reps=args.reps)
    # PAGED cell (device-block pager): page serves are pure_callbacks
    # inside the one compiled scan, so the budget is hard-asserted at
    # 2 per K-block at every page count (re-pinned per page geometry)
    paged = measure_paged(reps=args.reps)
    # BEST-SPLIT cell (split_kernel): fused histogram→split vs the
    # two-dispatch pair + the 2-calls-per-K-block pin per engine
    split_cell = measure_split(reps=args.reps)
    out = {
        "metric": "fused_superstep_vs_periter_cpu",
        "unit": "s/iter",
        "backend": jax.default_backend(),
        "date": time.strftime("%Y-%m-%d"),
        "source": "JAX_PLATFORMS=cpu python tools/prof_superstep.py",
        "env": os.environ.get("BENCH_ENV", "2-core CPU container"),
        "shape": f"{args.rows} x 28 binary, 15 leaves, 63 bins, "
                 f"interleaved min-of-{args.reps} 8-iteration block "
                 f"means",
        "device_call_budget": budget,
        "cells": cells,
        "dispatch_bound_cells": tiny,
        "pipelined": pipelined,
        "paged": paged,
        "split": split_cell,
    }
    if sharded_cells:
        out["sharded_cells"] = sharded_cells
        out["sharded_device_call_budget"] = sharded_budget
    if sharded2d_cells:
        out["sharded2d_cells"] = sharded2d_cells
        out["sharded2d_device_call_budget"] = sharded2d_budget
    text = json.dumps(out, indent=2)
    if args.stdout:
        print(text)
        return 0
    with open(OUT, "w") as f:
        f.write(text + "\n")
    print("wrote", OUT)
    return 0


if __name__ == "__main__":
    sys.exit(main())
