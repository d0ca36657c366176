"""Pipeline parallelism for TRAINING: GPipe microbatch schedule as a
shard_map + ppermute program over a ``pp`` mesh axis.

The reference's pipeline parallelism is implicit (SURVEY.md §2.9: its
whole runtime is a software pipeline; multi-model graphs are
stage-parallel across frames). For inference this framework mirrors that
with per-stage device pinning (backends/jax_backend.py custom=device:N).
This module is the training-side counterpart: model stages live on
different chips (params sharded over ``pp``), microbatches stream
through the stages, and activations hop stage→stage over ICI via
``ppermute`` — the classic GPipe schedule expressed as one jittable SPMD
program (every stage runs the same code; validity masking replaces
data-dependent control flow, so XLA compiles a static graph).

Schedule: with P stages and M microbatches, the scan runs M+P-1 ticks;
stage s processes microbatch m = t - s at tick t (bubble ticks compute
masked garbage — the standard trade for a static schedule).
"""
from __future__ import annotations

from typing import Any, Callable


def stack_stage_params(params_list) -> Any:
    """Stack per-stage param pytrees along a leading stage axis (to be
    sharded P("pp", ...))."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params_list)


def mesh_from_assignment(assignment, num_stages: int, axis: str = "pp",
                         devices=None):
    """Build the ``pp`` mesh for a planner-produced stage→device
    assignment: stage ``s`` runs on ``devices[assignment[s]]``.

    ``assignment`` is a sequence of device indices (one per stage,
    distinct) or a ``runtime.placement.PlacementPlan`` — the planner's
    stage order IS the pipeline stage order, so its per-stage device
    indices transfer directly. ``devices`` defaults to ``jax.devices()``
    (the same farm ``runtime/placement.py`` assigns over).
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    if hasattr(assignment, "stages"):  # a PlacementPlan
        assignment = [st.device for st in assignment.stages]
    assignment = [int(i) for i in assignment]
    if len(assignment) != num_stages:
        raise ValueError(
            f"pipeline: assignment has {len(assignment)} stages, "
            f"expected {num_stages}")
    if len(set(assignment)) != num_stages:
        raise ValueError(
            f"pipeline: assignment {assignment} reuses a device — GPipe "
            "stages need one chip each (params + activations resident)")
    devices = list(devices if devices is not None else jax.devices())
    for i in assignment:
        if not 0 <= i < len(devices):
            raise ValueError(
                f"pipeline: assignment index {i} out of range "
                f"({len(devices)} devices)")
    return Mesh(np.array([devices[i] for i in assignment]), (axis,))


def make_pipeline(stage_fn: Callable, num_stages: int, mesh=None,
                  axis: str = "pp", assignment=None,
                  devices=None) -> Callable:
    """Build ``run(stacked_params, microbatches) -> outputs``.

    * ``stage_fn(stage_params, x) -> y`` — one stage's forward, shapes
      preserved (y feeds the next stage);
    * ``stacked_params`` — leaves with leading axis ``num_stages``,
      sharded over ``axis`` (see stack_stage_params);
    * ``microbatches`` — (M, mb, ...) input, replicated over ``axis``;
    * returns (M, mb, ...) final-stage outputs (replicated).

    Stage→device mapping comes from ``mesh`` (hand-built, the classic
    path) OR ``assignment`` (a planner-produced device-index list or
    ``runtime.placement.PlacementPlan`` — see
    :func:`mesh_from_assignment`); exactly one of the two.

    Differentiable end-to-end: jax.grad flows back through the scan and
    the ppermutes (reverse-mode is the opposite rotation).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    if (mesh is None) == (assignment is None):
        raise ValueError("pipeline: pass exactly one of mesh= or "
                         "assignment= (a hand mesh OR a planner-produced "
                         "stage->device assignment)")
    if assignment is not None:
        mesh = mesh_from_assignment(assignment, num_stages, axis=axis,
                                    devices=devices)
    if dict(mesh.shape).get(axis) != num_stages:
        raise ValueError(
            f"pipeline: mesh axis '{axis}' size must equal num_stages "
            f"({num_stages}); mesh has {dict(mesh.shape)}")
    perm_fwd = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    def _run(stacked_params, xs):
        M = xs.shape[0]
        stage = jax.lax.axis_index(axis)
        # shard_map hands each stage its params slice (leading axis 1)
        params = jax.tree_util.tree_map(lambda p: p[0], stacked_params)
        zeros = jnp.zeros_like(xs[0])

        def tick(carry, t):
            prev_out, ys = carry
            # activations hop to the next stage; stage 0's recv is garbage
            # and never selected
            recv = jax.lax.ppermute(prev_out, axis, perm_fwd)
            m = t - stage
            m_idx = jnp.clip(m, 0, M - 1)
            valid = (m >= 0) & (m < M)
            inp = jnp.where(stage == 0, jnp.take(xs, m_idx, axis=0), recv)
            out = stage_fn(params, inp)
            out = jnp.where(valid, out, zeros)
            # last stage records its finished microbatch
            write = valid & (stage == num_stages - 1)
            upd = jax.lax.dynamic_update_index_in_dim(ys, out, m_idx, 0)
            ys = jnp.where(write, upd, ys)
            return (out, ys), None

        # jax tracks varying-manual-axes: the carry becomes pp-varying
        # after the first ppermute, so the init is declared varying too
        init = jax.tree_util.tree_map(
            lambda a: jax.lax.pcast(a, (axis,), to="varying"),
            (zeros, jnp.zeros_like(xs)))
        (_, ys), _ = jax.lax.scan(
            tick, init, jnp.arange(M + num_stages - 1))
        # only the last stage's ys is real — replicate it to all stages
        mask = (stage == num_stages - 1).astype(ys.dtype)
        return jax.lax.psum(ys * mask, axis)

    # P("pp") is a pytree-prefix spec: every param leaf leads with pp
    return shard_map(_run, mesh=mesh, in_specs=(P(axis), P()),
                     out_specs=P())
