"""Each compile request counted as what it came to (utils/telemetry.py
``install_jax_hooks``): a fresh compile, or a load from the persistent
compile cache, by program, with lowering timed.  The persistent cache
is process-wide, so the requests are made in a fresh process with a
cache directory of their own."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one program, compiled fresh, then served by the persistent cache
# after the in-process caches are cleared; the input is made with
# numpy so that no other program compiles in between
SCRIPT = r"""
import json, sys
import jax
import jax.numpy as jnp
import numpy as np
from lightgbm_tpu.utils import telemetry

jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
at_import = telemetry.counters_snapshot()
telemetry.install_jax_hooks()
c0 = telemetry.counters_snapshot()

def f(x):
    return jnp.cumsum(x * 3.0) - 1.0

x = np.arange(7.0, dtype=np.float32)
jax.block_until_ready(jax.jit(f)(x))
c1 = telemetry.counters_snapshot()
jax.clear_caches()
jax.block_until_ready(jax.jit(f)(x))
c2 = telemetry.counters_snapshot()
print(json.dumps({"at_import": at_import, "installed": c0,
                  "first": c1, "second": c2}))
"""

NEW = ("xla_fresh_compiles", "xla_fresh_compile_secs", "xla_cache_loads",
       "xla_cache_load_secs", "jax_lower_secs")


@pytest.fixture(scope="module")
def snaps(tmp_path_factory):
    cache = str(tmp_path_factory.mktemp("jax_cache"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, "-c", SCRIPT, cache],
                       capture_output=True, text=True, env=env,
                       timeout=300, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _grown(lo, hi):
    return {k: v - lo.get(k, 0.0) for k, v in hi.items()
            if v != lo.get(k, 0.0)}


def test_new_counters_stand_at_zero_once_installed(snaps):
    assert not set(NEW) & set(snaps["at_import"])
    assert {k: snaps["installed"][k] for k in NEW} == dict.fromkeys(NEW, 0.0)


def test_first_request_is_a_fresh_compile(snaps):
    g = _grown(snaps["installed"], snaps["first"])
    assert g["xla_compiles/jit(f)"] == 1
    assert g["xla_fresh_compiles/jit(f)"] == 1
    assert g["xla_fresh_compile_secs/jit(f)"] == \
        g["xla_compile_secs/jit(f)"] > 0
    assert "xla_cache_loads" not in g and "xla_cache_load_secs" not in g
    # the outcome that did not happen is there, at 0
    assert snaps["first"]["xla_cache_loads/jit(f)"] == 0.0
    assert snaps["first"]["xla_cache_load_secs/jit(f)"] == 0.0


def test_request_after_clearing_the_caches_is_a_load(snaps):
    g = _grown(snaps["first"], snaps["second"])
    assert g["xla_compiles/jit(f)"] == 1
    assert g["xla_cache_loads/jit(f)"] == 1
    assert g["xla_cache_load_secs/jit(f)"] == \
        pytest.approx(g["xla_compile_secs/jit(f)"], rel=1e-12)
    assert g["xla_cache_load_secs/jit(f)"] > 0
    assert "xla_fresh_compiles" not in g
    assert "xla_fresh_compiles/jit(f)" not in g
    assert g["jax_cache_hits"] == g["xla_cache_loads"]


@pytest.mark.parametrize("step", [("installed", "first"),
                                  ("first", "second"),
                                  ("installed", "second")],
                         ids=["fresh", "load", "both"])
def test_outcomes_add_up_to_the_requests(snaps, step):
    lo, hi = (snaps[s] for s in step)
    g = {k: hi.get(k, 0.0) - lo.get(k, 0.0) for k in hi}
    assert g["xla_cache_loads"] + g["xla_fresh_compiles"] == \
        g["xla_compiles"] >= 1
    assert g["xla_cache_load_secs"] + g["xla_fresh_compile_secs"] == \
        pytest.approx(g["xla_compile_secs"], rel=1e-6)
    for fun in {k.split("/", 1)[1] for k in hi
                if k.startswith("xla_compiles/")}:
        assert g[f"xla_cache_loads/{fun}"] + \
            g[f"xla_fresh_compiles/{fun}"] == g[f"xla_compiles/{fun}"]


def test_lowering_is_timed_by_program(snaps):
    for lo, hi in (("installed", "first"), ("first", "second")):
        g = _grown(snaps[lo], snaps[hi])
        assert g["jax_lower_secs/jit(f)"] > 0
        assert g["jax_lower_secs"] >= g["jax_lower_secs/jit(f)"]
