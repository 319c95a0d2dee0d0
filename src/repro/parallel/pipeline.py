"""Pipeline-parallel execution of planner-chosen splits (shard_map + ppermute).

This is the runtime counterpart of :func:`repro.core.planner.plan_pipeline`:
the beam-search split plan assigns contiguous layer ranges to pipeline
stages; this module executes them as a GPipe-style microbatch pipeline on
a mesh axis ("stage" locally, the "pod" axis in the production mesh),
rotating microbatch activations between stages with
``jax.lax.ppermute`` — the collective whose cost the paper's Eq. 7 models
(the inter-device activation hop).

Execution model (standard collective-pipelining formulation):
  * stage s holds the stacked params of its layer range (uneven plans are
    padded with identity blocks to the max stage depth);
  * M microbatches stream through S stages over M + S - 1 ticks;
  * each tick: every stage applies its blocks to its resident microbatch,
    then ppermute rotates the ring (stage s -> s+1), stage 0 injects the
    next microbatch and stage S-1 emits a finished one.

The per-tick ppermute payload is exactly ``boundary_act_bytes`` of the
plan — the quantity the beam-search objective minimizes; EXPERIMENTS.md
§Perf uses this correspondence for the planner-quality benchmark.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.planner import SplitPlan


def stage_assignment(plan: SplitPlan, n_layers: int) -> list[tuple[int, int]]:
    """[(first, last)] 0-indexed inclusive layer ranges per stage."""
    bounds = [0, *plan.splits, n_layers]
    return [(bounds[i], bounds[i + 1] - 1) for i in range(len(bounds) - 1)]


def pad_stage_params(stacked_params, ranges, max_depth: int):
    """Slice the (L, ...) stacked block params into (S, max_depth, ...)
    per-stage stacks, padding short stages with zeros + an identity mask."""
    stages = []
    masks = []
    for (a, b) in ranges:
        depth = b - a + 1
        sl = jax.tree.map(lambda t: t[a : b + 1], stacked_params)
        if depth < max_depth:
            sl = jax.tree.map(
                lambda t: jnp.concatenate(
                    [t, jnp.zeros((max_depth - depth, *t.shape[1:]), t.dtype)]),
                sl)
        stages.append(sl)
        masks.append(jnp.arange(max_depth) < depth)
    stage_stack = jax.tree.map(lambda *ts: jnp.stack(ts), *stages)
    return stage_stack, jnp.stack(masks)  # (S, max_depth, ...), (S, max_depth)


def pipelined_forward(
    block_apply: Callable,  # (layer_params, x) -> x
    stage_params,  # (S, depth, ...) stacked, stage axis sharded over mesh axis
    layer_mask: jax.Array,  # (S, depth) bool — identity for padded layers
    microbatches: jax.Array,  # (M, mb, ...) activations entering stage 0
    *,
    mesh: Mesh,
    axis: str = "stage",
) -> jax.Array:
    """Run the microbatch pipeline; returns (M, mb, ...) outputs of the
    last stage. Pure collective implementation: one ppermute per tick."""
    S = mesh.shape[axis]
    M = microbatches.shape[0]
    n_ticks = M + S - 1

    def stage_fn(stage_p, mask, mb):
        # runs per-stage under shard_map: leading stage axis is local (=1)
        stage_p = jax.tree.map(lambda t: t[0], stage_p)
        mask = mask[0]
        sidx = jax.lax.axis_index(axis)

        def apply_stage(x):
            def body(h, inp):
                lp, m = inp
                h2 = block_apply(lp, h)
                return jnp.where(m, h2, h), None

            x, _ = jax.lax.scan(body, x, (stage_p, mask))
            return x

        buf = jnp.zeros_like(mb[0])  # resident activation
        outputs = jnp.zeros_like(mb)

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 injects microbatch t (if any remain)
            inject = jnp.where(t < M, t, M - 1)
            buf = jnp.where(sidx == 0, mb[inject], buf)
            buf = apply_stage(buf)
            # last stage emits microbatch t - (S - 1)
            emit_t = t - (S - 1)
            do_emit = (sidx == S - 1) & (emit_t >= 0)
            outputs = jax.lax.cond(
                do_emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, buf, jnp.maximum(emit_t, 0), 0),
                lambda o: o,
                outputs)
            # rotate ring: s -> s+1 (the Eq.7-priced activation hop)
            buf = jax.lax.ppermute(
                buf, axis, [(i, (i + 1) % S) for i in range(S)])
            return (buf, outputs), None

        (buf, outputs), _ = jax.lax.scan(
            tick, (buf, outputs), jnp.arange(n_ticks, dtype=jnp.int32))
        # outputs live on the last stage; broadcast via psum of masked value
        outputs = jnp.where(sidx == S - 1, outputs, jnp.zeros_like(outputs))
        return jax.lax.psum(outputs, axis)

    # the resident buffer starts replicated and turns stage-varying after
    # the first ppermute, so the scan carry has no single replication type
    # (check_vma off); after the psum every stage holds the same outputs,
    # so the result is returned replicated (out_specs=P())
    fn = shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, layer_mask, microbatches)


def run_pipeline(plan: SplitPlan, block_apply, stacked_params, n_layers: int,
                 microbatches: jax.Array, mesh: Mesh, axis: str = "stage"):
    """Convenience wrapper: plan -> padded stage stacks -> pipelined run."""
    ranges = stage_assignment(plan, n_layers)
    max_depth = max(b - a + 1 for a, b in ranges)
    stage_stack, mask = pad_stage_params(stacked_params, ranges, max_depth)
    return pipelined_forward(block_apply, stage_stack, mask, microbatches,
                             mesh=mesh, axis=axis)
