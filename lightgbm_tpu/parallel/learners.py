"""Parallel tree-learner builders: ``shard_map`` wrappers around the
device growth loop.

Maps ``tree_learner={data,feature,voting}`` (``tree_learner.cpp:9-33``)
onto a 1-D named mesh, and ``tree_learner=data2d`` onto a 2-D
``Mesh((R, F), ("data", "feature"))`` — rows sharded down one axis,
feature tiles across the other, with the collective schedule factored
per axis (see :mod:`lightgbm_tpu.ops.grow`).  The growth loop itself
(:func:`lightgbm_tpu.ops.grow.build_tree`) contains the per-strategy
collectives; this module owns mesh construction, sharding specs, and
the feature-axis padding the block-cyclic layouts need.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np

from ..ops.grow import (GROW_COUNTERS, DistConfig, GrowParams,
                        build_tree)
from ..utils.log import Log

AXIS_NAME = "shard"
DATA_AXIS = "data"
FEAT_AXIS = "feature"


def resolve_num_shards(config, mesh=None) -> int:
    """How many ways to shard: an explicit mesh wins; otherwise all
    GLOBAL devices, capped by ``num_machines`` when the user set it.

    When the config carries a reference-style multi-machine topology
    (``machines=`` + ``num_machines>1``, ``config.h:729-744``) and the
    distributed runtime is not up yet, it is initialized here — after
    which ``jax.devices()`` spans every machine.  Initialization
    failures raise; a silent single-node fallback would train at the
    wrong scale."""
    import jax
    if mesh is not None:
        return int(np.prod(mesh.devices.shape))
    machines = getattr(config, "machines", "")
    if not machines and getattr(config, "machine_list_filename", ""):
        with open(config.machine_list_filename) as f:
            machines = f.read()  # newline-separated host:port lines
    if config.num_machines > 1 and machines:
        from .distributed import init_from_machines, is_initialized
        if not is_initialized() and jax.process_count() == 1:
            init_from_machines(machines, config.local_listen_port,
                               config.time_out, config.num_machines)
    n = len(jax.devices())
    if config.num_machines > 1 and jax.process_count() == 1:
        # single-process mesh emulation: num_machines caps the shards
        n = min(n, config.num_machines)
    return n


def make_mesh_for(num_shards: int):
    """A 1-D mesh over the first ``num_shards`` local devices.
    Raises when fewer devices are visible — silently returning a
    narrower mesh than requested is exactly the opaque-placement
    failure mode cross-width resume used to die with (a snapshot
    taken on a wider mesh restores fine on a narrower host; the mesh
    just has to SAY it is narrower — ``docs/Distributed.md``)."""
    import jax
    devices = jax.devices()
    if len(devices) < num_shards:
        raise ValueError(
            f"requested a {num_shards}-shard mesh but only "
            f"{len(devices)} device(s) are visible — pass the real "
            f"device count (resume re-shards checkpointed state to "
            f"any width automatically; see docs/Distributed.md)")
    return jax.sharding.Mesh(np.asarray(devices[:num_shards]),
                             (AXIS_NAME,))


def parse_mesh_shape(spec) -> tuple:
    """``'4x2'`` / ``'4,2'`` / ``(4, 2)`` -> ``(4, 2)`` — the
    ``mesh_shape`` config value as a (rows, feature-tiles) pair."""
    if isinstance(spec, (tuple, list)):
        toks = [str(s) for s in spec]
    else:
        import re
        toks = [t for t in re.split(r"[x*,()\s]+", str(spec).strip())
                if t]
    if len(toks) != 2:
        raise ValueError(
            f"mesh_shape must name exactly two axes as 'RxF', got "
            f"{spec!r}")
    r, f = int(toks[0]), int(toks[1])
    if r < 1 or f < 1:
        raise ValueError(f"mesh_shape axes must be positive, got "
                         f"({r}, {f})")
    return (r, f)


def factor_mesh_shape(n: int) -> tuple:
    """Default (R, F) factorization of ``n`` devices when the user set
    ``tree_learner=data2d`` without ``mesh_shape``: the largest
    feature-axis divisor <= sqrt(n), rows get the rest (8 -> 4x2).
    Rows usually outnumber features by orders of magnitude, so the row
    axis gets the larger factor; the feature axis still earns its
    O(1/F_axis) histogram-byte cut."""
    fx = 1
    for d in range(1, int(np.sqrt(n)) + 1):
        if n % d == 0:
            fx = d
    return (n // fx, fx)


def make_mesh_2d(mesh_shape) -> "jax.sharding.Mesh":
    """A 2-D ``(rows, features)`` mesh over the first R*F local
    devices, axes named ``("data", "feature")``.  Raises when fewer
    devices are visible — same no-silent-narrowing contract as
    :func:`make_mesh_for`."""
    import jax
    r, f = (int(s) for s in mesh_shape)
    if r < 1 or f < 1:
        raise ValueError(f"mesh_shape must be positive, got ({r}, {f})")
    need = r * f
    devices = jax.devices()
    if len(devices) < need:
        raise ValueError(
            f"requested a {r}x{f} mesh ({need} devices) but only "
            f"{len(devices)} device(s) are visible — pass a shape the "
            f"host can satisfy (resume re-shards checkpointed state to "
            f"any shape automatically; see docs/Distributed.md)")
    return jax.sharding.Mesh(
        np.asarray(devices[:need]).reshape(r, f), (DATA_AXIS, FEAT_AXIS))


def pad_rows_for(kind: str, num_shards: int, n: int, base: int = 1) -> int:
    """Rows must split evenly over the mesh (and per-shard row count
    must honor the histogram kernel's block size).  ``num_shards`` is
    the ROW-axis size — the 2-D learner passes R, not R*F."""
    step = base if kind in ("feature", "serial", "") \
        else base * num_shards
    return (n + step - 1) // step * step


def pad_features_for(kind: str, num_shards: int, f: int) -> int:
    """Features must split evenly for the feature-block layouts.
    ``num_shards`` is the FEATURE-axis size — the 2-D learner passes
    F, not R*F."""
    if kind in ("voting", "serial", ""):
        return f
    d = num_shards
    return (f + d - 1) // d * d


class DistributedBuilder:
    """Callable with :func:`build_tree`'s signature that runs it SPMD.

    Inputs arrive as GLOBAL (host-shaped) arrays; ``jit`` + ``shard_map``
    split them onto the mesh per the learner's specs and reassemble the
    outputs (split records replicated, ``leaf_idx`` row-sharded for the
    data/voting learners).
    """

    def __init__(self, kind: str, params: GrowParams, num_shards: int,
                 mesh=None, mesh_shape=None, pager=None):
        import jax
        from jax.sharding import PartitionSpec as P

        if kind not in ("data", "feature", "voting", "data2d"):
            raise ValueError(f"unknown parallel tree_learner {kind!r}")
        self.kind = kind
        self.num_shards = num_shards
        R = P()
        if kind == "data2d":
            if mesh is not None:
                if len(mesh.devices.shape) != 2:
                    raise ValueError(
                        f"tree_learner=data2d shards over a 2-D "
                        f"(data, feature) mesh; got axes "
                        f"{mesh.axis_names}")
                shape = tuple(int(s) for s in mesh.devices.shape)
            else:
                shape = tuple(int(s) for s in (
                    mesh_shape if mesh_shape
                    else factor_mesh_shape(num_shards)))
                mesh = make_mesh_2d(shape)
            if shape[0] * shape[1] != num_shards:
                raise ValueError(
                    f"mesh_shape {shape[0]}x{shape[1]} does not factor "
                    f"the {num_shards} shards")
            self.mesh = mesh
            axis, feat_axis = self.mesh.axis_names
            self.row_shards, self.feat_shards = shape
            self.params = dataclasses.replace(
                params, dist=DistConfig(kind=kind, axis=axis,
                                        num_shards=self.row_shards,
                                        top_k=params.dist.top_k,
                                        feat_axis=feat_axis,
                                        feat_shards=self.feat_shards))
            # xt is (F, N): feature tiles down axis 0, row blocks down
            # axis 1 — each device holds an R-th of rows x an F-th of
            # features; descriptors shard with the tiles, per-row state
            # with the row blocks
            xt_spec = P(feat_axis, axis)
            row_spec, feat_spec = P(axis), P(feat_axis)
            leaf_idx_spec = P(axis)
        else:
            self.mesh = mesh if mesh is not None \
                else make_mesh_for(num_shards)
            if len(self.mesh.axis_names) != 1:
                raise ValueError(
                    f"tree learner {kind!r} shards over a 1-D mesh; "
                    f"got axes {self.mesh.axis_names}")
            axis = self.mesh.axis_names[0]
            feat_axis = None
            self.row_shards = num_shards if kind in ("data", "voting") \
                else 1
            self.feat_shards = num_shards if kind == "feature" else 1
            self.params = dataclasses.replace(
                params, dist=DistConfig(kind=kind, axis=axis,
                                        num_shards=num_shards,
                                        top_k=params.dist.top_k))

            S = P(axis)
            if kind == "feature":
                xt_spec, row_spec, feat_spec = P(axis, None), R, S
                leaf_idx_spec = R
            else:  # data | voting: rows sharded, features whole
                xt_spec, row_spec, feat_spec = P(None, axis), S, R
                leaf_idx_spec = S
        # the sharding contract, exposed for (a) mesh-resident placement
        # of the training tensors (device_put once, no per-call
        # resharding) and (b) the fused sharded super-step
        # (models/gbdt.py wraps its K-iteration scan in shard_map with
        # these same specs)
        self.axis = axis
        self.feat_axis = feat_axis
        self.xt_spec, self.row_spec, self.feat_spec = (xt_spec, row_spec,
                                                       feat_spec)

        out_specs = {k: R for k in (
            "leaf", "feature", "threshold", "default_left", "is_cat",
            "gain", "left_stats", "right_stats", "left_mask", "valid",
            "leaf_values", "leaf_values_final", "leaf_stats",
            "n_leaves")}
        if self.params.split.has_monotone:
            for k in ("rec_left_min", "rec_left_max",
                      "rec_right_min", "rec_right_max"):
                out_specs[k] = R
        # mirror build_tree's do_spec predicate exactly: a spec for an
        # absent output is a pytree-structure error at call time
        do_spec = (self.params.speculate > 1 and
                   self.params.use_hist_pool and
                   not self.params.forced and
                   kind in ("data", "feature", "voting") and
                   self.params.wave)
        if do_spec:
            for k in GROW_COUNTERS:
                out_specs[k] = R
        if self.params.quantize:
            out_specs["leaf_stats_exact"] = R
        out_specs["leaf_idx"] = leaf_idx_spec

        # device-block pager (io/pager.py): the per-tree dispatch
        # substitutes the PagedXt view for the sharded xt operand —
        # the slot keeps a replicated dummy so the call signature
        # stays build_tree's, and each program instance pages its own
        # (f_loc, n_loc) block through axis-indexed callbacks
        self.pager_view = pager.view(kind, axis, feat_axis) \
            if pager is not None else None
        view = self.pager_view
        if view is not None:
            xt_spec = R

        def fn(xt, grad, hess, mask, fmask, nb, mt, cat, qk):
            if view is not None:
                # trace-time operand swap; build_tree_impl runs
                # un-jitted here because the whole shard_map is
                # already under jit and PagedXt is not a pytree leaf
                from ..ops.grow import build_tree_impl
                return build_tree_impl(view, grad, hess, mask, fmask,
                                       nb, mt, cat, self.params,
                                       quant_key=qk)
            return build_tree(xt, grad, hess, mask, fmask, nb, mt, cat,
                              self.params, quant_key=qk)
        sharded = jax.shard_map(
            fn, mesh=self.mesh, check_vma=False,
            in_specs=(xt_spec, row_spec, row_spec, row_spec, feat_spec,
                      feat_spec, feat_spec, feat_spec, R),
            out_specs=out_specs)
        self._call = jax.jit(sharded)

    # ------------------------------------------------------------------
    def shardings(self):
        """NamedShardings for the persistent training tensors.  The
        driver ``device_put``s the binned matrix / masks / descriptors
        with these ONCE at construction so every dispatch (per-tree or
        fused super-step) runs on mesh-resident buffers instead of
        re-sharding host-placed arrays per call — the per-shard
        dispatch overhead WEAKSCALE.json measured."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        m = self.mesh
        return {"xt": NamedSharding(m, self.xt_spec),
                "row": NamedSharding(m, self.row_spec),
                "feat": NamedSharding(m, self.feat_spec),
                "rep": NamedSharding(m, P()),
                # per-row state with leading axes (the (k, n_pad) score
                # carry, the (K, n_pad) stacked leaf index): rows as
                # "row" lays them, whatever leads whole on every device
                "rows2d": NamedSharding(m, P(None, *self.row_spec))}

    def pad_rows(self, n: int, base: int = 1) -> int:
        return pad_rows_for(self.kind, max(self.row_shards, 1), n, base)

    def pad_features(self, f: int) -> int:
        shards = self.feat_shards if self.kind == "data2d" \
            else self.num_shards
        return pad_features_for(self.kind, shards, f)

    def __call__(self, xt, grad, hess, sample_mask, feature_mask,
                 num_bins, missing_type, is_cat, params=None,
                 quant_key=None):
        import jax
        # params is baked in at construction (signature-compatible with
        # the jitted serial build_tree); reject a drifting override
        # instead of silently training with stale parameters
        if params is not None and \
                dataclasses.replace(params, dist=self.params.dist) != \
                self.params:
            raise ValueError(
                "DistributedBuilder was constructed with different "
                "GrowParams; rebuild the builder to change them")
        if quant_key is None:
            quant_key = jax.random.PRNGKey(0)
        return self._call(xt, grad, hess, sample_mask, feature_mask,
                          num_bins, missing_type, is_cat, quant_key)
