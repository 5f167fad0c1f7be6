"""bench.py CPU smoke: the driver runs the bench at every round end —
a bench that crashes (bad section code, API drift) silently costs the
round its artifact.  This pins that `python bench.py` completes on the
CPU backend and emits a parsable JSON line with the contract fields.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(env_extra, *args, timeout=600):
    env = dict(os.environ)
    env.update({"PYTHONPATH": ""})
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=REPO)


def test_bench_cpu_smoke(tmp_path):
    tele = str(tmp_path / "bench_tele.jsonl")
    out = _run_bench({"JAX_PLATFORMS": "cpu", "BENCH_ROWS": "60000",
                      "BENCH_MEAS_ITERS": "3", "BENCH_TELEMETRY": tele},
                     timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [l for l in out.stdout.strip().splitlines()
             if l.startswith("{")]
    assert lines, out.stdout[-2000:]
    d = json.loads(lines[-1])
    assert d["metric"] == "higgs_shape_train_time_500iter"
    # fused super-step contract row: present, with the compile pin
    # (0 compiles in the measured window after the first block)
    assert d.get("fused4_measured_xla_compiles") == 0, \
        d.get("fused_error")
    assert "fused4_mean_iter_s" in d
    assert d["unit"] == "s"
    assert d["value"] > 0
    assert "vs_baseline" in d
    assert d["backend"] == "cpu"
    assert d.get("auc_holdout") is None or d["auc_holdout"] > 0.5
    # batch-inference rows (flattened engine vs per-tree loop)
    assert d.get("predict_engine_rows_per_s", 0) > 0, \
        d.get("predict_bench_error")
    assert d.get("predict_loop_rows_per_s", 0) > 0
    # self-diagnosis: compile-count deltas + telemetry summary rows
    primary = d["primary_variant"]
    assert f"{primary}_measured_xla_compiles" in d
    assert d.get("telemetry_summary", {}).get("iterations", 0) > 0
    # the run's JSONL exists and is schema-valid
    from lightgbm_tpu.utils.telemetry import lint_file
    n, errs = lint_file(tele)
    assert errs == [] and n > 0


@pytest.mark.parametrize("flag", [None, "--serve-only",
                                  "--weakscale-only"])
def test_bench_absent_platform_exits_nonzero(flag, tmp_path):
    """A requested accelerator that is not there is a non-zero exit
    from every entry point — no CPU fallback, no artifact written."""
    out = _run_bench({"JAX_PLATFORMS": "tpu",   # no TPU in this image
                      "BENCH_WEAKSCALE_OUT": str(tmp_path / "ws.json")},
                     *([flag] if flag else []), timeout=300)
    assert out.returncode != 0, out.stdout[-2000:]
    assert not [l for l in out.stdout.splitlines() if l.startswith("{")]
    assert not (tmp_path / "ws.json").exists()


def test_bench_failed_phase_exits_nonzero():
    """A phase after the primary that raises leaves its ``*_error``
    key in the JSON and the process exits non-zero."""
    out = _run_bench({"JAX_PLATFORMS": "cpu", "BENCH_ROWS": "20000",
                      "BENCH_MEAS_ITERS": "1", "BENCH_SERVE": "0",
                      "BENCH_TELEMETRY": "0",
                      "BENCH_FUSED_ITERS": "not-a-number"})
    assert out.returncode != 0, out.stdout[-2000:]
    d = json.loads([l for l in out.stdout.splitlines()
                    if l.startswith("{")][-1])
    assert "fused_error" in d, sorted(d)
    assert "fused_error" in out.stderr


def test_bench_weakscale_writes_curve(tmp_path):
    """`--weakscale-only` regenerates the WEAKSCALE artifact: a
    shards x fixed-rows-per-shard grid on the host-platform mesh with
    wall/per-shard-CPU/device-call series and a lint-clean telemetry
    JSONL carrying the in-scan collective counters."""
    ws = tmp_path / "ws.json"
    tele = tmp_path / "ws_tele.jsonl"
    out = _run_bench({"JAX_PLATFORMS": "cpu",
                      "BENCH_WEAKSCALE_SHARDS": "2",
                      "BENCH_WEAKSCALE_ROWS": "512",
                      "BENCH_WEAKSCALE_ITERS": "8",
                      "BENCH_WEAKSCALE_REPS": "1",
                      "BENCH_WEAKSCALE_OUT": str(ws),
                      "BENCH_WEAKSCALE_TELEMETRY": str(tele)},
                     "--weakscale-only")
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(ws.read_text())
    assert d["metric"] == "weak_scaling_fixed_rows_per_shard"
    shards = [c["shards"] for c in d["curve"]]
    assert shards == [1, 2]
    for c in d["curve"]:
        assert c["iter_s"] > 0
        assert c["cpu_s_per_shard_iter"] > 0
        # the fused-scan device-call budget: 2 per K-iteration block
        # at ANY mesh size (the single-program property)
        assert c["device_calls_per_iter"] == pytest.approx(
            2.0 / d["fused_iters"])
    assert d["curve"][1]["collective_bytes"] > 0
    from lightgbm_tpu.utils.telemetry import lint_file
    n, errs = lint_file(str(tele))
    assert errs == [] and n > 0
