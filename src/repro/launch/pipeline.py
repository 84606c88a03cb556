"""Pipeline parallelism: GPipe microbatching over the "pod" axis.

At multi-pod scale the inter-pod links are the slow dimension; PP turns
them into point-to-point activation hops instead of full DP gradient
reductions.  The period-stacked layer parameters shard NATURALLY over
the pipe axis (leading ``n_periods`` axis → ``n_periods/S`` local
periods per stage), so no parameter surgery is needed.

Schedule: classic GPipe — ``n_micro + S − 1`` ticks; stage ``s``
processes microbatch ``t − s`` at tick ``t``; activations hop stage→
stage+1 via ``jax.lax.ppermute`` each tick.  The backward pipeline falls
out of jax autodiff (ppermute transposes to the reverse hop); per-tick
``jax.checkpoint`` keeps in-flight activation memory to
O(n_micro · microbatch).

Scope: decoder-only single-position-plan archs (olmo/qwen3/chatglm —
``period_plan`` length 1); embedding runs on stage 0, unembed + CE on
the last stage, loss psum'd.  Demonstrated and equivalence-tested in
tests/test_pipeline.py; measured vs the DP baseline in EXPERIMENTS §PP.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models.lm import (ArchConfig, period_plan, _embed, _head,
                         _run_periods, _train_mixer, cross_entropy)

Params = dict[str, Any]

__all__ = ["build_pp_loss", "pp_param_specs"]

PIPE_AXIS = "pod"


def pp_param_specs(params: Params) -> Params:
    """shard_map in_specs: layer stacks split over the pipe axis on their
    leading period axis; embed/unembed/norms replicated."""
    def spec_for(path_leaf):
        return None
    specs: Params = {}
    for k, v in params.items():
        if k.startswith("pos"):
            specs[k] = jax.tree.map(
                lambda leaf: P(PIPE_AXIS, *([None] * (leaf.ndim - 1))), v)
        else:
            specs[k] = jax.tree.map(lambda leaf: P(), v)
    return specs


def build_pp_loss(cfg: ArchConfig, n_stages: int, n_micro: int):
    """Returns loss_fn(params, batch) running the GPipe schedule inside a
    shard_map over the pipe axis.  Requires:
    * single-position period plan (plan length 1);
    * n_periods % n_stages == 0; global batch % n_micro == 0."""
    plan, n_periods = period_plan(cfg)
    assert len(plan) == 1, "PP demo supports single-position plans"
    assert n_periods % n_stages == 0

    def stage_stack(stack_local, x, positions):
        """Run this stage's local periods (scan over n_periods/S)."""
        x, _, _ = _run_periods(cfg, plan, {"pos0": stack_local}, x,
                               _train_mixer(cfg, positions), train=True)
        return x

    def local_fn(params, tokens, labels, stage_ids):
        # tokens/labels: (B_global, S) replicated over the pipe axis;
        # stage_ids: (n_stages,) split over it → this shard's (1,) slice
        # is the stage index.  (An input, not lax.axis_index: axis_index
        # inside partial-manual shard_map lowers to a PartitionId op
        # older XLA SPMD pipelines reject.)
        stage = stage_ids[0]
        b, s = tokens.shape
        mb = b // n_micro
        positions = jnp.broadcast_to(jnp.arange(s)[None], (mb, s))

        def embed_micro(m):
            toks = jax.lax.dynamic_slice_in_dim(tokens, m * mb, mb, 0)
            return _embed(cfg, params, toks)

        def tail_loss(x, m):
            lbl = jax.lax.dynamic_slice_in_dim(labels, m * mb, mb, 0)
            return cross_entropy(_head(cfg, params, x), lbl)

        d = cfg.d_model
        carry_in = jnp.zeros((mb, s, d), params["embed"]["e"].dtype)
        loss_acc = jnp.zeros((), jnp.float32)

        def tick(state, t):
            carry_in, loss_acc = state
            m_here = t - stage                  # microbatch index at stage
            active = (m_here >= 0) & (m_here < n_micro)
            m_safe = jnp.clip(m_here, 0, n_micro - 1)
            # stage 0 ingests a fresh microbatch; others take the hop-in
            x = jnp.where(stage == 0, embed_micro(m_safe), carry_in)
            y = stage_stack(params["stack_local"], x, positions)
            # last stage: CE on its active ticks
            is_last = stage == n_stages - 1
            lm = tail_loss(y, m_safe)
            loss_acc = loss_acc + jnp.where(
                active & is_last, lm, 0.0)
            # hop activations to the next stage
            carry_out = jax.lax.ppermute(
                y, PIPE_AXIS,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (carry_out, loss_acc), None

        (carry_in, loss_acc), _ = jax.lax.scan(
            tick, (carry_in, loss_acc), jnp.arange(n_micro + n_stages - 1))
        # every stage returns the same global mean loss
        total = jax.lax.psum(loss_acc, PIPE_AXIS)
        return total / n_micro

    def loss_fn(params, batch, mesh):
        # split the layer stack over the pipe axis; rest replicated
        stack = params["pos0"]
        other = {k: v for k, v in params.items() if k != "pos0"}
        in_specs = (
            {**{k: jax.tree.map(lambda _: P(), v) for k, v in other.items()},
             "stack_local": jax.tree.map(
                 lambda leaf: P(PIPE_AXIS, *([None] * (leaf.ndim - 1))),
                 stack)},
            P(), P(), P(PIPE_AXIS))
        # manual ONLY over the pipe axis — data/model stay under the
        # partitioner (the inner stage compute keeps its DP/TP sharding)
        fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                           out_specs=P(), check_vma=False,
                           axis_names=frozenset({PIPE_AXIS}))
        return fn({**other, "stack_local": stack},
                  batch["tokens"], batch["labels"],
                  jnp.arange(n_stages, dtype=jnp.int32))

    return loss_fn
