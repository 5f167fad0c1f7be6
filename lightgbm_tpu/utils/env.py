"""Process-environment helpers: virtual host devices, the
multi-host runtime, the Pallas interpret lane, the compile cache."""
from __future__ import annotations

import os


def force_host_platform_devices(n: int) -> None:
    """Append ``--xla_force_host_platform_device_count=n`` to
    ``XLA_FLAGS`` so the CPU platform exposes ``n`` virtual devices —
    the mesh the sharded tests/benches run on.  Must be called BEFORE
    the first jax import; no-op when the flag is already present (an
    explicit operator choice wins) or ``n <= 1``.  The flag only
    affects the host platform, so it is safe to set even when an
    accelerator backend ends up selected."""
    flags = os.environ.get("XLA_FLAGS", "")
    if n <= 1 or "xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={int(n)}"
    ).strip()


def maybe_init_distributed() -> bool:
    """Env-gated multi-host entry: join the JAX distributed runtime
    when the ``LTPU_COORDINATOR`` env triple is set, no-op otherwise.

    A multi-host launcher exports::

        LTPU_COORDINATOR=host0:12355   # coordinator (process 0)
        LTPU_NUM_PROCESSES=4
        LTPU_PROCESS_ID=<rank>         # or LTPU_MACHINE_RANK

    and every process calls this (the driver does, before building any
    mesh) — afterwards ``jax.devices()`` spans all hosts, so the 1-D
    learners' meshes and the data2d 2-D mesh factor over the GLOBAL
    device set.  Single-host runs (no ``LTPU_COORDINATOR``) return
    False without importing jax.  Idempotent: a runtime already joined
    with the same topology is a no-op; a different topology raises
    (``parallel.distributed.init_distributed``).  Malformed env values
    raise — a silent single-host fallback would train at the wrong
    scale (docs/Distributed.md).
    """
    coordinator = os.environ.get("LTPU_COORDINATOR", "")
    if not coordinator:
        return False
    n = int(os.environ.get("LTPU_NUM_PROCESSES", "1"))
    if n <= 1:
        return False
    rank = os.environ.get("LTPU_PROCESS_ID",
                          os.environ.get("LTPU_MACHINE_RANK"))
    if rank is None:
        raise RuntimeError(
            "LTPU_COORDINATOR is set but neither LTPU_PROCESS_ID nor "
            "LTPU_MACHINE_RANK names this process's rank")
    from ..parallel.distributed import init_distributed
    timeout = os.environ.get("LTPU_INIT_TIMEOUT_S")
    init_distributed(coordinator, n, int(rank),
                     timeout_s=int(timeout) if timeout else None)
    return True


def pallas_interpret_forced() -> bool:
    """True when the ``LTPU_PALLAS_INTERPRET`` env lane is armed: every
    Pallas kernel runs under ``pl.pallas_call(..., interpret=True)``
    AND the driver treats the backend as kernel-capable, so the whole
    kernel tier (histogram passes, routed kernels, the best-split
    scan) executes on a CPU-only host — the tier-1 parity lane for
    code paths that otherwise need a real TPU.  Interpreter-mode wall
    time measures the interpreter, not the kernel; this is a
    correctness lane, never a benchmark."""
    return os.environ.get("LTPU_PALLAS_INTERPRET", "") not in ("", "0")


def pallas_interpret() -> bool:
    """Interpret-mode decision for a ``pl.pallas_call`` site: the env
    lane above, or a CPU default backend (Mosaic kernels cannot
    compile there, so a direct kernel call on CPU — e.g.
    ``split_kernel=pallas`` under ``JAX_PLATFORMS=cpu`` — always runs
    interpreted).  Read at trace time; jit caches key on shapes/static
    args only, so flip the env before the first kernel trace."""
    if pallas_interpret_forced():
        return True
    import jax
    return jax.default_backend() == "cpu"


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
    and this sets nothing.  Otherwise the cache goes to
    ``<checkout>/.jax_cache`` (the root io/native.py derives for
    ``cpp/``): a fixed path, because the path is part of the cache
    key's lookup and a directory that moves never hits.  Called once
    from each entry point (``engine.train``, the CLI, ``serve.Server``,
    ``chip_smoke.py``); calling it again changes nothing."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if env_dir:
        return env_dir
    import jax
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
