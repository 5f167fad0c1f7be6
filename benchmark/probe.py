"""The sizing probe and the other many-runs calls of the builder: runs
``run.py`` several times, one process after another, from a parent that
stays off JAX (a chip belongs to one process, and a peak is a
process's own).

    python3 benchmark/probe.py <out-dir> <plan.json>

``plan.json`` is a list of argument lists for run.py.  Every run's
stderr and result go to ``<out-dir>/<index>-<workload>.log``; the
result lines are printed together at the end."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    out_dir, plan_path = sys.argv[1], sys.argv[2]
    os.makedirs(out_dir, exist_ok=True)
    with open(plan_path) as f:
        plan = json.load(f)
    print(f"compile cache: {os.environ.get('JAX_COMPILATION_CACHE_DIR') or os.path.join(os.path.dirname(HERE), '.jax_cache')}; "
          f"hits and misses are on each run's warm-up line", flush=True)
    lines = []
    for i, args in enumerate(plan):
        name = args[args.index("--workload") + 1]
        log_path = os.path.join(out_dir, f"{i:02d}-{name}.log")
        t0 = time.time()
        with open(log_path, "w") as logf:
            p = subprocess.run([sys.executable, os.path.join(HERE, "run.py"),
                                *map(str, args)], stdout=subprocess.PIPE,
                               stderr=logf, text=True)
        took = time.time() - t0
        last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
        with open(log_path, "a") as logf:
            logf.write(f"\nexit {p.returncode} after {took:.1f} s\n{last}\n")
        with open(log_path) as logf:
            tail = [ln for ln in logf.read().splitlines()
                    if "cpu_aot_loader" not in ln][-22:]
        print(f"--- run {i} {' '.join(map(str, args))}: exit "
              f"{p.returncode} after {took:.1f} s", flush=True)
        print("\n".join(ln[:400] for ln in tail), flush=True)
        lines.append((args, p.returncode, took, last))
    print("=== results", flush=True)
    for args, rc, took, last in lines:
        print(json.dumps({"args": args, "rc": rc, "took_s": round(took, 1),
                          "result": json.loads(last) if last.startswith("{")
                          else last[:300]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
