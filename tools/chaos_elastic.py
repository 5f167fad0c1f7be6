"""Elastic mesh-training chaos e2e: the acceptance harness for
shard-loss detection -> exact rewind -> re-mesh -> bit-exact recovery
(``parallel/elastic.py``, ``GBDT.remesh``, cross-width checkpoint
resume; ``docs/Distributed.md``).

One run drives the mesh-sharded fused training path through the
failure modes a pod-scale job on preemptible slices actually meets,
on the forced 8-device CPU mesh:

- **injected collective HANG of one shard mid-fused-block**
  (``mesh.collective:hang``): the dispatch blocks the way a lost peer
  stalls the rendezvous; the collective-stall watchdog abandons it,
  training re-meshes 8 -> 7 and continues;
- **injected collective ERROR** (``mesh.collective:error``): the
  dispatch raises the way XLA surfaces a dead peer; same recovery;
- **SIGKILL of the process hosting a shard** mid-fused-block: nothing
  graceful runs — the restart finds only 4 devices (the surviving
  slice), reads the mesh topology the checkpoint manifest recorded,
  RE-SHARDS and resumes bit-exactly at the new width;
- **healthy path**: supervision is invisible — byte-identical model,
  2 device calls per K-block;
- **shard death with a whole block IN FLIGHT**
  (``superstep_pipeline_depth=2``): the fault fires on a dispatch
  while earlier blocks are dispatched-but-unfetched — the abort
  restores the fence across every outstanding block's
  RNG/quantization-stream draws and recovery is still bit-exact.

Hard asserts (exit nonzero on any failure):

1. each recovered model is BYTE-identical to an uninterrupted run
   over the surviving mesh from the shared boundary (the clean
   remesh/resume continuation — data-parallel float psums make
   cross-width PREFIXES differ in low bits by physics, so the oracle
   shares the prefix; see docs/Distributed.md);
2. the SIGKILL restart's model equals BOTH the subprocess clean-resume
   oracle and the in-process ``remesh()`` continuation — checkpoint
   restore at a new width and live re-mesh are the same transition;
3. recovery records (detect/remesh/reshard) account for every event,
   the telemetry is schema-clean, triage raises the repeated-re-mesh
   HIGH anomaly for the doubly-degraded stream and NO retrace-storm
   anomaly (the post-re-mesh recompile is exempt warmup);
4. the healthy-path device-call budget stays 2 per K-block and the
   supervised model is byte-identical to the unsupervised run.

Usage::

    JAX_PLATFORMS=cpu python tools/chaos_elastic.py \
        --workdir chaos_elastic_work --telemetry elastic_telemetry.jsonl \
        --out chaos_elastic.json
"""
import argparse
import glob
import json
import os
import shutil
import signal
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
from lightgbm_tpu.utils.env import force_host_platform_devices  # noqa: E402

force_host_platform_devices(8)

import numpy as np  # noqa: E402

N_ROWS = 601      # not divisible by the mesh width (padded-row paths)
N_FEAT = 8
ROUNDS = 10
CHECKS = []


def check(name, ok, detail=""):
    CHECKS.append({"name": name, "ok": bool(ok), "detail": str(detail)})
    print(f"[{'OK' if ok else 'FAIL'}] {name}"
          + (f" — {detail}" if detail and not ok else ""), flush=True)
    return bool(ok)


def make_data():
    rng = np.random.RandomState(0)
    X = rng.random_sample((N_ROWS, N_FEAT))
    y = (X[:, 0] + 0.5 * (X[:, 1] > 0.5) +
         0.1 * rng.randn(N_ROWS) > 0.7).astype(float)
    return X, y


def base_params(rounds=ROUNDS, **kw):
    p = {"objective": "binary", "num_leaves": 15, "verbose": -1,
         "metric": "None", "tree_learner": "data", "fused_iters": 4,
         "num_iterations": rounds}
    p.update(kw)
    return p


def mesh_of(width):
    import jax
    return jax.sharding.Mesh(np.asarray(jax.devices()[:width]),
                             ("shard",))


def train(X, y, rounds=ROUNDS, width=8, resume=None, **kw):
    import lightgbm_tpu as lgb
    p = base_params(rounds, **kw)
    d = lgb.Dataset(X, label=y, params=p)
    return lgb.train(p, d, verbose_eval=False, mesh=mesh_of(width),
                     resume_from=resume)


def oracle_remesh_at(X, y, boundary, to_shards, rounds=ROUNDS):
    """Uninterrupted continuation oracle: 8-wide to the boundary, one
    clean remesh, uninterrupted to the end."""
    import jax
    import lightgbm_tpu as lgb
    p = base_params(rounds)
    d = lgb.Dataset(X, label=y, params=p)
    d.construct()
    b = lgb.Booster(params=p, train_set=d, mesh=mesh_of(8))
    while b._gbdt.completed_iterations() < boundary:
        b.update()
    b._gbdt.remesh(num_shards=to_shards)
    while b._gbdt.completed_iterations() < rounds:
        b.update()
    return b.model_to_string()


def recovery_records(telemetry):
    out = []
    try:
        with open(telemetry) as f:
            for line in f:
                line = line.strip()
                if line and '"type": "recovery"' in line:
                    out.append(json.loads(line))
    except OSError:
        pass
    return out


# The SIGKILL scenario's training subprocess: the device width comes
# from the environment, standing in for "the surviving slice after a
# host died" — a restarted pod job sees fewer devices, reads the mesh
# topology the manifest recorded, and re-shards.
_TRAIN_SCRIPT = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
from lightgbm_tpu.utils.env import force_host_platform_devices
force_host_platform_devices(int(os.environ["LTPU_ELASTIC_DEVICES"]))
import numpy as np
import lightgbm_tpu as lgb

cfg = json.load(open(sys.argv[1]))
d = np.load(cfg["data"])
params = cfg["params"]
ds = lgb.Dataset(d["X"], label=d["y"], params=params)
bst = lgb.train(params, ds, verbose_eval=False, resume_from="auto")
bst.save_model(cfg["model_out"])
tele = getattr(bst._gbdt, "_telemetry", None)
if tele is not None:
    tele.close(log=False)
"""


def spawn_train(workdir, tag, devices, ck_root, telemetry, data_npz,
                rounds=12):
    cfg = {
        "data": data_npz,
        "model_out": os.path.join(workdir, f"model_{tag}.txt"),
        "params": base_params(
            rounds, checkpoint_dir=ck_root, snapshot_freq=2,
            keep_last_n=8, telemetry_file=telemetry),
    }
    cfg_path = os.path.join(workdir, f"train_{tag}.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    script = os.path.join(workdir, "elastic_train.py")
    if not os.path.exists(script):
        with open(script, "w") as f:
            f.write(_TRAIN_SCRIPT)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # the harness's OWN 8-device XLA flag must not leak into the
    # subprocess (force_host_platform_devices is first-writer-wins):
    # the "surviving slice" has to really see its own device count
    flags = " ".join(
        tok for tok in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in tok)
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=flags,
               LTPU_ELASTIC_DEVICES=str(devices),
               PYTHONPATH=repo + os.pathsep +
               os.environ.get("PYTHONPATH", ""))
    return subprocess.Popen([sys.executable, script, cfg_path], env=env)


def wait_for(pred, timeout, what):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(0.2)
    print(f"TIMEOUT waiting for {what}", flush=True)
    return False


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workdir", default="chaos_elastic_work")
    ap.add_argument("--telemetry", default="elastic_telemetry.jsonl")
    ap.add_argument("--out", default="chaos_elastic.json")
    args = ap.parse_args(argv)

    workdir = os.path.abspath(args.workdir)
    if os.path.isdir(workdir):
        shutil.rmtree(workdir)
    os.makedirs(workdir)
    telemetry = os.path.abspath(args.telemetry)
    if os.path.exists(telemetry):
        os.remove(telemetry)

    from lightgbm_tpu.utils import faults
    from lightgbm_tpu.utils import telemetry as _telemetry
    from lightgbm_tpu.utils.telemetry import lint_file

    X, y = make_data()
    ok = True

    # ---- phase 1: collective HANG of one shard mid-fused-block ------
    print("== phase 1: injected collective hang (stall watchdog) ==",
          flush=True)
    faults.reset()
    faults.configure("mesh.collective:hang@2")
    bst = train(X, y, elastic_training=True,
                elastic_stall_timeout_s=4.0, telemetry_file=telemetry)
    bst._gbdt._telemetry.close(log=False)
    faults.clear()
    faults.reset()
    recov = recovery_records(telemetry)
    ok &= check("phase1: hang detected + re-meshed",
                [r["event"] for r in recov] == ["detect", "remesh"] and
                recov[0]["cause"] == "hang" and
                recov[1]["to_shards"] == 7, str(recov))
    ok &= check("phase1: training completed on the survivors",
                bst._gbdt._dist.num_shards == 7 and
                bst._gbdt.iter == ROUNDS)
    boundary = recov[1]["iter"] if len(recov) > 1 else 0
    ok &= check("phase1: model BYTE-identical to the uninterrupted "
                "run over the surviving mesh",
                bst.model_to_string() ==
                oracle_remesh_at(X, y, boundary, 7))

    # ---- phase 2: collective ERROR (dead peer) ----------------------
    print("== phase 2: injected collective error (dead peer) ==",
          flush=True)
    # fault ordinals are process-wide hit counts and phase 1's parity
    # oracle dispatched fused blocks too — re-zero before arming
    faults.reset()
    faults.configure("mesh.collective:error@3")
    bst2 = train(X, y, elastic_training=True, telemetry_file=telemetry)
    bst2._gbdt._telemetry.close(log=False)
    faults.clear()
    faults.reset()
    recov2 = recovery_records(telemetry)[len(recov):]
    ok &= check("phase2: error detected + re-meshed",
                [r["event"] for r in recov2] == ["detect", "remesh"]
                and recov2[0]["cause"] == "error", str(recov2))
    boundary2 = recov2[1]["iter"] if len(recov2) > 1 else 0
    ok &= check("phase2: model BYTE-identical to the uninterrupted "
                "run over the surviving mesh",
                bst2.model_to_string() ==
                oracle_remesh_at(X, y, boundary2, 7))

    # ---- phase 3: SIGKILL mid-fused-block, restart on 4 devices -----
    print("== phase 3: SIGKILL -> restart on the surviving (4-device) "
          "slice ==", flush=True)
    data_npz = os.path.join(workdir, "data.npz")
    np.savez(data_npz, X=X, y=y)
    ck_root = os.path.join(workdir, "ck")
    sub_tele = os.path.join(workdir, "subprocess_telemetry.jsonl")
    proc = spawn_train(workdir, "victim", 8, ck_root, sub_tele,
                       data_npz)
    # snapshot_freq=2, fused_iters=4: ckpt_00000006 is provably
    # mid-run and mid-fused-block territory; SIGKILL there
    ok &= check("phase3: mid-run snapshot appeared",
                wait_for(lambda: os.path.isdir(
                    os.path.join(ck_root, "ckpt_00000006")), 600,
                    "ckpt_00000006"))
    proc.kill()
    proc.wait(timeout=60)
    # freeze the pre-restart lineage for the clean-resume oracle
    oracle_root = os.path.join(workdir, "ck_oracle")
    shutil.copytree(ck_root, oracle_root)
    proc = spawn_train(workdir, "restart", 4, ck_root, sub_tele,
                       data_npz)
    rc = proc.wait(timeout=900)
    ok &= check("phase3: 4-device restart completed", rc == 0,
                f"rc={rc}")
    reshards = [r for r in recovery_records(sub_tele)
                if r.get("event") == "reshard"]
    ok &= check("phase3: restart re-sharded from the manifest's "
                "recorded 8-shard topology",
                len(reshards) == 1 and
                reshards[0]["from_shards"] == 8 and
                reshards[0]["to_shards"] == 4, str(reshards))
    proc = spawn_train(workdir, "oracle", 4, oracle_root,
                       os.path.join(workdir, "oracle_telemetry.jsonl"),
                       data_npz)
    rc = proc.wait(timeout=900)
    ok &= check("phase3: clean-resume oracle completed", rc == 0,
                f"rc={rc}")
    restart_text = open(os.path.join(workdir, "model_restart.txt")).read()
    oracle_text = open(os.path.join(workdir, "model_oracle.txt")).read()
    ok &= check("phase3: restarted model BYTE-identical to the "
                "uninterrupted resume on the surviving slice",
                restart_text == oracle_text)
    # cross-machinery pin: live remesh() == checkpoint restore at the
    # new width.  Resume the frozen lineage in THIS (8-device) process
    # onto an explicit 4-wide mesh.
    newest = sorted(glob.glob(os.path.join(oracle_root, "ckpt_*")))[-1]
    inproc = train(X, y, rounds=12, width=4, resume=newest,
                   checkpoint_dir=os.path.join(workdir, "ck_inproc"),
                   snapshot_freq=2, keep_last_n=8)
    ok &= check("phase3: in-process cross-width resume equals the "
                "subprocess restart",
                inproc.model_to_string() == restart_text)

    # ---- phase 4: healthy-path budget + supervision is a no-op ------
    print("== phase 4: healthy path (budget + byte-identity) ==",
          flush=True)
    c0 = _telemetry.counters_snapshot()
    sup = train(X, y, rounds=9, elastic_training=True)
    c1 = _telemetry.counters_snapshot()
    plain = train(X, y, rounds=9)
    # 9 rounds = 1 unfused bias iteration + 2 fused blocks of 4 ->
    # exactly 2 scan dispatches + 2 packed fetches
    disp = c1["superstep_dispatches"] - c0.get("superstep_dispatches", 0)
    fet = c1["superstep_fetches"] - c0.get("superstep_fetches", 0)
    ok &= check("phase4: healthy-path device-call budget is 2 per "
                "K-block under supervision",
                disp == 2 and fet == 2, f"dispatches={disp} fetches={fet}")
    ok &= check("phase4: supervised healthy run byte-identical to "
                "unsupervised", sup.model_to_string() ==
                plain.model_to_string())

    # ---- phase 5: shard death with a whole block IN FLIGHT ----------
    # (async pipelining, superstep_pipeline_depth=2): the fault fires
    # on a dispatch while earlier blocks are dispatched-but-unfetched
    # — the abort must restore the fence across EVERY outstanding
    # block's RNG/quantization-stream consumption and recover
    # bit-exactly from the served boundary
    print("== phase 5: collective error with in-flight pipelined "
          "blocks ==", flush=True)
    seen = len(recovery_records(telemetry))
    faults.reset()
    # ordinal 3 = the third block's dispatch, which (at depth 2) goes
    # out while blocks 1 and 2 are still unfetched in the queue
    faults.configure("mesh.collective:error@3")
    bst5 = train(X, y, elastic_training=True, telemetry_file=telemetry,
                 superstep_pipeline_depth=2)
    bst5._gbdt._telemetry.close(log=False)
    faults.clear()
    faults.reset()
    recov5 = recovery_records(telemetry)[seen:]
    ok &= check("phase5: in-flight-block failure detected + re-meshed",
                [r["event"] for r in recov5] == ["detect", "remesh"]
                and recov5[0]["cause"] == "error" and
                recov5[1]["to_shards"] == 7, str(recov5))
    ok &= check("phase5: training completed with the queue drained",
                bst5._gbdt.iter == ROUNDS and bst5._gbdt._sq == [])
    boundary5 = recov5[1]["iter"] if len(recov5) > 1 else 0
    ok &= check("phase5: model BYTE-identical to the uninterrupted "
                "run over the surviving mesh (queued blocks discarded "
                "losslessly)",
                bst5.model_to_string() ==
                oracle_remesh_at(X, y, boundary5, 7))

    # ---- telemetry: lint + triage anomalies -------------------------
    n, errs = lint_file(telemetry)
    ok &= check("elastic telemetry schema-clean", not errs,
                "; ".join(errs[:3]))
    print(f"telemetry: {n} records", flush=True)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from triage_run import scan_anomalies  # noqa: E402
    from lightgbm_tpu.utils.telemetry import read_records
    anomalies = scan_anomalies(read_records(telemetry))
    ok &= check("triage flags the doubly-degraded stream as a HIGH "
                "repeated-re-mesh anomaly",
                any(sev == "HIGH" and "repeated re-mesh" in msg
                    for sev, msg in anomalies), str(anomalies))
    ok &= check("post-re-mesh recompiles are warmup, not a retrace "
                "storm",
                not any("retrace storm" in msg for _, msg in anomalies),
                str(anomalies))

    result = {"ok": bool(ok), "checks": CHECKS}
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
    n_ok = sum(1 for c in CHECKS if c["ok"])
    print(f"chaos elastic: {n_ok}/{len(CHECKS)} checks passed -> "
          f"{args.out}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
