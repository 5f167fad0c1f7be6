"""Read the numbers that decide ``correct`` on many seeds in one
process, to set their limits from (PERF.md, section 2).

    python3 benchmark/calibrate.py --cells a,b --seeds 1,2,3 \
        [--program 1] [--control 1] [--faults 1] [--rows N]

For every seed the data are made once; then for every cell: the
trainer is driven through its warm-up (the steps the reference
follows) and compared (``--program``); the reference, cut to the cell's
control precision, is put in the trainer's place and compared
(``--control``); and so is the reference with each fault planted
(``--faults``).  The control and the faults are host code and touch no
device.  One JSON line a reading, on stdout."""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from harness import cells, datagen, reference  # noqa: E402

FAULTS = ("state_unchanged", "half_batch", "altered_answer")


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", type=int, default=0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--rows", type=int, default=0)
    args = ap.parse_args()
    the_cells = [cells.load_cell(c) for c in args.cells.split(",")]
    if args.program:
        import run as run_mod
        from harness import trainer as trainer_mod
        run_mod.acquire_chip(1)
        trainer_mod.configure_jax(log)
        trainer_mod.build_native_binner(log)
    reference.native.lib()
    made = None
    for seed in map(int, args.seeds.split(",")):
        for cell in the_cells:
            cfg = cell.config
            n = int(args.rows or cfg["rows"])
            key = (cfg["name"], n, seed)
            if made is None or made[0] != key:
                made = None
                gc.collect()
                made = (key, datagen.make(n, int(cfg["features"]),
                                          cfg["data"], seed))
            x, y, group = made[1]
            steps = int(cell.workload.get("reference_steps", 3))
            if args.program:
                t0 = time.time()
                tr = trainer_mod.Trainer(cell.params, x, y, group=group)
                run_mod.check_tier(cell, tr.tier())
                for _ in range(1 + run_mod.warmup_steps(cell)):
                    tr.step()
                produced = tr.produced()
                tr.close()
                del tr
                gc.collect()
                t1 = time.time()
                nums = reference.compare(produced, x, y, cell.params, seed,
                                         steps, log, group)
                emit(cell=cell.name, seed=seed, who="program", numbers=nums,
                     train_s=round(t1 - t0, 1),
                     compare_s=round(time.time() - t1, 1))
            runs = []
            if args.control:
                c = cell.workload["control"]
                runs.append((f"control:{c['hist']}/{c['leaf']}",
                             dict(hist_precision=c["hist"],
                                  leaf_precision=c["leaf"])))
            if args.faults:
                runs += [(f"fault:{f}", dict(fault=f)) for f in FAULTS]
            for who, kw in runs:
                t0 = time.time()
                produced = reference.train_in_place(x, y, cell.params,
                                                    steps, seed, group=group,
                                                    **kw)
                nums = reference.compare(produced, x, y, cell.params, seed,
                                         steps, log, group)
                emit(cell=cell.name, seed=seed, who=who, numbers=nums,
                     took_s=round(time.time() - t0, 1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
