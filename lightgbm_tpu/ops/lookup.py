"""Small-table row lookup: ``vals[leaf_idx]`` for (N,) indices.

XLA's gather lowers this to ~sub-GB/s element loads on TPU — measured
160-200 ms for 10.5M rows from a 255-entry table, a hidden tax on
EVERY boosting iteration's score update (the reference's
``ScoreUpdater::AddScore`` is a trivial indexed add on CPU,
``score_updater.hpp:17``).  The Pallas kernel instead streams the index
vector once and resolves each row with an unrolled select-chain against
the table's scalars — pure VPU work, ~2-3 orders faster.

Gated to tables ≤ 512 entries (the unroll is the table size); larger
tables fall back to ``jnp.take``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..utils.env import pallas_interpret, pallas_interpret_forced

__all__ = ["take_small", "MAX_LOOKUP_TABLE"]

MAX_LOOKUP_TABLE = 512


def _lookup_kernel(idx_ref, vals_ref, out_ref, *, table: int):
    idx = idx_ref[...].astype(jnp.int32)     # narrow storage widened
    acc = jnp.zeros_like(out_ref)            # (1, T) f32
    for l in range(table):
        acc = jnp.where(idx == l, vals_ref[0, l], acc)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block",))
def _take_small_pallas(vals: jax.Array, idx: jax.Array,
                       block: int = 16384) -> jax.Array:
    import jax.experimental.pallas as pl

    (L,) = vals.shape
    n = idx.shape[0]
    n_pad = (n + block - 1) // block * block
    # keep a narrow (uint8) index vector narrow — it is the kernel's
    # dominant read; the kernel widens per tile
    ix = idx if jnp.issubdtype(idx.dtype, jnp.integer) \
        else idx.astype(jnp.int32)
    if n_pad != n:
        ix = jnp.pad(ix, (0, n_pad - n))
    Lp = (L + 127) // 128 * 128
    vt = jnp.pad(vals.astype(jnp.float32), (0, Lp - L))[None, :]

    out = pl.pallas_call(
        functools.partial(_lookup_kernel, table=L),
        grid=(n_pad // block,),
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, Lp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        interpret=pallas_interpret(),
    )(ix[None, :], vt)
    return out[0, :n]


def take_small(vals: jax.Array, idx: jax.Array) -> jax.Array:
    """``vals[idx]`` with the TPU-friendly kernel when applicable.

    A concrete ``idx`` placed over several devices (the parallel
    learners' leaf vector) runs the kernel under ``shard_map`` with
    ``idx``'s own sharding: XLA cannot partition a Mosaic kernel by
    itself ("Mosaic kernels cannot be automatically partitioned").
    Pass the mesh-placed array whole and slice the result: an uneven
    slice of it comes back replicated, and every device then looks up
    every row.  A traced ``idx`` is already inside the caller's
    ``shard_map`` (or on one device)."""
    if not (vals.ndim == 1 and vals.shape[0] <= MAX_LOOKUP_TABLE and
            (jax.default_backend() != "cpu" or pallas_interpret_forced())):
        return jnp.take(vals, idx)
    if isinstance(idx, jax.core.Tracer) or \
            len(idx.sharding.device_set) == 1:
        return _take_small_pallas(vals, idx)
    sharding = idx.sharding
    if not isinstance(sharding, jax.sharding.NamedSharding):
        return jnp.take(vals, idx)     # XLA partitions its own gather
    return _lookup_over(sharding.mesh, sharding.spec)(vals, idx)


@functools.lru_cache(maxsize=8)
def _lookup_over(mesh, spec):
    """The lookup kernel over ``mesh``, ``idx`` laid out by ``spec``
    and the table replicated; cached so repeated calls reuse one jit."""
    return jax.jit(jax.shard_map(
        _take_small_pallas, mesh=mesh, check_vma=False,
        in_specs=(jax.sharding.PartitionSpec(), spec), out_specs=spec))
