"""benchmark/run.py: one cell of the training benchmark, one run.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Finds ``workloads/<cell>.json`` and through it the configuration, the
traffic mix and the per-layer metrics (harness/cells.py); makes the
data from ``--seed``; builds the trainer, warms up the cell's own
programs (iteration 0 and one block), and hands the same trainer to a
window of ``--seconds``; then holds what the trainer produced against
the plain reference (harness/reference.py).  The last line of stdout is
the result; the numbers compared stand beside their limits on the last
lines of stderr and last in the result.

It needs a TPU with as many chips as the cell asks for, and exits
non-zero with no result line where JAX finds none."""
from __future__ import annotations

import time

T_START = time.time()

import argparse     # noqa: E402
import json         # noqa: E402
import math         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402
import tempfile     # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from harness import cells, datagen, readers, reference, work, xplane  # noqa: E402
from harness import trainer as trainer_mod  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def acquire_chip(chips: int):
    """Refuse anything but a TPU with the chips the cell asks for."""
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"platform={d.platform} device_kind={d.device_kind} "
        f"device_count={len(devs)} jax={jax.__version__}")
    if d.platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU, JAX found platform "
                         f"{d.platform!r}; there is no CPU fallback")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, "
                         f"JAX found {len(devs)}")
    if os.environ.get("LTPU_PALLAS_INTERPRET"):
        raise SystemExit("benchmark: LTPU_PALLAS_INTERPRET is set: the "
                         "kernels would run interpreted")


def _finite(v):
    """A number as JSON can carry it: None where it is not finite."""
    return v if math.isfinite(v) else None


def check_tier(cell: cells.Cell, tier: dict) -> None:
    log(f"tier record: {json.dumps(tier, sort_keys=True)}")
    want = cell.workload["expect_tier"]
    bad = {k: (tier.get(k), v) for k, v in want.items() if tier.get(k) != v}
    if bad:
        raise SystemExit(f"benchmark: the tier record differs from "
                         f"{cell.name}'s file (got, expected): {bad}")


class Window:
    """Drives ``step`` block by block and keeps the clock of every
    block.  A block is ``fused_iters`` iterations where the job fuses,
    else one iteration."""

    def __init__(self, tr, cell: cells.Cell):
        self.tr = tr
        self.block = cell.block
        self.fused = cell.block > 1
        self.attempted = 0
        self.block_seconds = []

    def slowest_s_per_iter(self) -> float:
        """The slowest fused block, or the slowest run of 8 unfused
        iterations (of all, where there are fewer), an iteration."""
        if self.fused:
            return max(self.block_seconds) / self.block
        b = self.block_seconds
        k = min(8, len(b))
        return max(sum(b[i:i + k]) for i in range(len(b) - k + 1)) / k

    def run_block(self) -> None:
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.block"):
            for _ in range(self.block):
                self.attempted += 1
                self.tr.step()      # one that raises ends the run
        self.block_seconds.append(time.perf_counter() - t0)

    def land(self) -> None:
        """The unfused loop holds its newest tree back by one call:
        fetch it, so that host and device stand at the same iteration."""
        if not self.fused:
            self.tr.trees_done()


def warmup_steps(cell: cells.Cell) -> int:
    """Iterations of the warm-up after iteration 0: one block where the
    job fuses, else the three steps the reference can follow."""
    return cell.block if cell.block > 1 else 3


def traced_metrics(trace_dir: str, describe_trace: str):
    """Read the profiler's trace, then delete it: (events by plane and
    the host's spans, busy and window seconds)."""
    try:
        path = xplane.find_xplane(trace_dir)
        if describe_trace:
            os.makedirs(os.path.dirname(describe_trace) or ".",
                        exist_ok=True)
            with open(describe_trace, "w") as f:
                f.write(xplane.describe(path))
        tdata = xplane.load(path)
        if describe_trace:
            with open(describe_trace + ".events.json", "w") as f:
                json.dump(xplane.excerpt(tdata), f)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    b = xplane.busy(tdata)
    if b is None or not b["busy_s"] > 0:
        raise SystemExit("benchmark: the trace holds no device operation")
    return tdata, b


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             rows: int = 0, describe_trace: str = "") -> dict:
    """Everything of a run but the look for a chip."""
    import jax
    spans = {}
    trainer_mod.configure_jax(log)
    trainer_mod.build_native_binner(log)
    reference.native.lib()
    cfg = cell.config
    n = int(rows or cfg["rows"])
    features = int(cfg["features"])
    dev = jax.devices()[0]
    c_start = trainer_mod.counters()

    t0 = time.time()
    x, y, group = datagen.make(n, features, cfg["data"], seed, cell.root)
    spans["data_gen_s"] = time.time() - t0
    tmp = tempfile.mkdtemp(prefix="bench-")
    try:
        tr = trainer_mod.Trainer(
            cell.params, x, y,
            os.path.join(tmp, "telemetry.jsonl") if trace else None,
            group=group)
        spans["data_prep_s"] = time.time() - t0
        queries = "" if group is None else f", {len(group)} queries"
        log(f"data: {n} x {features} from seed {seed}: generated in "
            f"{spans['data_gen_s']:.1f} s, binned and uploaded by "
            f"{spans['data_prep_s']:.1f} s; mean label "
            f"{float(y.mean()):.4f}{queries}")
        # the plan is whole once the booster is built: a trainer that
        # lands on another tier stops here, before the warm-up
        check_tier(cell, tr.tier())
        win = Window(tr, cell)

        # warm-up: iteration 0, then one block: every program of the
        # window compiles here, and the trainer goes on into the window
        t0 = time.time()
        tr.step()
        warm = warmup_steps(cell)
        for _ in range(warm):
            tr.step()
        warm_trees = 1 + warm
        spans["warmup_s"] = time.time() - t0
        c_warm = trainer_mod.counters()
        log(f"warm-up: {1 + warm} iterations in {spans['warmup_s']:.1f} s; "
            f"compile requests {c_warm.get('xla_compiles', 0):.0f} "
            f"({c_warm.get('xla_compile_secs', 0.0):.1f} s), persistent "
            f"cache hits {c_warm.get('jax_cache_hits', 0):.0f}, misses "
            f"{c_warm.get('jax_cache_misses', 0):.0f}")

        trace_dir = os.path.join(tmp, "trace")
        traced_iters = 0
        if trace:
            # the profiler starts while a block is on the device; one
            # block not counted brings host and device to a boundary
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # annotations only: bench.*, ltpu.*
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            win.run_block()
            win.land()
            win.block_seconds.clear()
            warm_trees += win.block
            win.attempted = 0
        hist0 = tr.hist_passes()
        c_open = trainer_mod.counters()
        setup_s = time.time() - T_START
        t_open = time.perf_counter()
        if trace:
            want = int(cell.traffic.get("trace_blocks", 1))
            with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
                for _ in range(want):
                    win.run_block()
                win.land()
            traced_iters = want * win.block
            jax.profiler.stop_trace()
            log(f"trace: {want} blocks, stopped "
                f"{time.perf_counter() - t_open:.1f} s into the window")
        while time.perf_counter() - t_open < seconds or not win.block_seconds:
            win.run_block()
        win.land()      # the clock stops when the last tree is fetched
        window_s = time.perf_counter() - t_open
        if trace:
            # the profiler's stop is inside the clock: leave it out of
            # what the blocks themselves took
            window_s = sum(win.block_seconds)
        c_close = trainer_mod.counters()
        hist1 = tr.hist_passes()
        iterations = win.attempted

        produced = tr.produced()          # drains the block in flight
        stats = dev.memory_stats() or {}
        peak = int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in jax.devices()[:int(cell.workload["chips"])]))
        tier = tr.tier()
        tr.close()
        del tr, win.tr
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise

    window_trees = produced.trees[warm_trees:warm_trees + iterations]
    short = sum(1 for t in window_trees
                if t.num_leaves < 2
                or not bool(np.isfinite(t.leaf_value).all()))
    failed = short + (iterations - len(window_trees))
    log(f"window: {iterations} iterations in {window_s:.3f} s "
        f"({len(win.block_seconds)} blocks of {win.block}); trees "
        f"{len(produced.trees)}; peak device memory {peak} bytes of "
        f"{stats.get('bytes_limit', 0)}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": iterations, "failed": failed,
              "metrics": {}, "device": device}
    if not trace:
        result["metrics"] = {
            "setup_s": {"value": setup_s, "unit": "s"},
            "train_s_per_iter": {"value": window_s / iterations,
                                 "unit": "s/iter"}}
    else:
        tdata, b = traced_metrics(trace_dir, describe_trace)
        device.update(b)
        quantities = {
            "traced_iterations": traced_iters,
            "traced_window_s": b["window_s"],
            "window_iterations": iterations,
            "window_trees": len(window_trees),
            "hist_passes": None if hist1 is None else hist1 - (hist0 or 0),
            "block_s_per_iter_max": win.slowest_s_per_iter(),
            "idle_pct": 100.0 * (1.0 - b["busy_s"] / b["window_s"]),
            "peak_gib": peak / 2 ** 30,
        }
        ctx = {"spans": spans, "quantities": quantities, "trace": tdata,
               "counters": {"setup": (c_start, c_open),
                            "window": (c_open, c_close)},
               "traced_trees":
                   produced.trees[warm_trees:warm_trees + traced_iters],
               "features": features, "rows": n,
               "peaks": work.peaks_for(dev.device_kind)
               if dev.platform == "tpu" else {}}
        result["metrics"] = readers.read_all(cell.metrics, ctx)
        result["breakdown"] = {"device_ops": xplane.top_ops(tdata),
                               "idle_gaps": xplane.idle_gaps(tdata)}
    shutil.rmtree(tmp, ignore_errors=True)

    # the comparison, once the window has closed and the peak is read
    t0 = time.time()
    steps = int(cell.workload.get("reference_steps", 3))
    numbers = reference.compare(produced, x, y, cell.params, seed, steps,
                                log, group, cell.root)
    log(f"reference: {steps} steps compared in {time.time() - t0:.1f} s")
    limits = cell.workload["limits"]
    checks = {"failed_iterations": [failed, 0]}
    for name, limit in limits.items():
        checks[name] = [_finite(numbers[name]), limit]
    result["observed"] = {k: _finite(v) for k, v in numbers.items()
                          if k not in limits}
    result["correct"] = all(v is not None and v <= lim
                            for v, lim in checks.values())
    result["tier"] = tier["tier"]
    result["checks"] = checks
    log(f"observed, not compared: {result['observed']}")
    for name, (v, lim) in checks.items():
        log(f"check {name}: {v!r} (limit {lim!r})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rows", type=int, default=0,
                    help="sizing probe only: rows instead of the "
                         "configuration's")
    ap.add_argument("--describe-trace", default="",
                    help="write the trace's planes, lines and names here, "
                         "and an excerpt of its events beside it")
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    log(f"cell {cell.name}: {cell.workload['why']}")
    acquire_chip(int(cell.workload["chips"]))
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      args.rows, args.describe_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
