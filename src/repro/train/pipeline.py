"""Pipeline parallelism over the ``pod`` axis (GPipe-style, looped SPMD).

At 2 pods the multi-pod mesh's outer axis can either replicate (outer DP —
the dry-run default) or **pipeline**: each pod holds half the depth and
microbatch activations stream pod0 -> pod1 through ``ppermute`` — turning
the cross-pod traffic from a full gradient all-reduce into boundary
activations (B_micro × S × d per tick), which is the standard reason to
pipeline across the slow inter-pod links.

Schedule: the looped/collective formulation (as in praxis/MaxText pipeline
layers).  All stages run the SAME program for ``M + stages − 1`` ticks; at
tick t, stage 0 injects microbatch t (or zeros in the drain phase), every
stage applies its half of the periods, and boundary activations rotate
forward one stage.  The last stage's head+loss contributions are collected
where valid (``t ≥ stages − 1``).  ``jax.grad`` differentiates through the
whole schedule — ``ppermute`` transposes to the reverse rotation, giving
the backward drain automatically.

Scope (documented): homogeneous decoder-only patterns (no enc-dec / vlm
prefix), depth split evenly across stages.  Used by the dry-run as the
``pp2`` §Perf alternative for the multi-pod mesh, and validated numerically
against the sequential loss in ``tests/test_pipeline.py`` (2 host devices,
subprocess).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import model as model_lib
from ..models.layers import apply_norm
from ..models.model import _apply_block  # same block code as the assembly

__all__ = ["pipeline_loss_fn", "make_pp_loss_for_mesh"]


def _run_periods(params_periods, x, cfg, positions, vary=()):
    """Apply this stage's stacked periods (scan, rematted like forward)."""
    aux0 = _pvary(jnp.zeros((1,), jnp.float32), vary)

    def period_fn(carry, pp):
        h, aux = carry
        for bi, blk in enumerate(cfg.pattern):
            h, aux = _apply_block(
                pp[f"b{bi}"], blk, h, cfg, positions, None, aux
            )
        return (h, aux), None

    fn = jax.checkpoint(period_fn) if cfg.remat else period_fn
    (x, aux), _ = jax.lax.scan(fn, (x, aux0), params_periods)
    return x, aux


def _pvary(x, axes):
    """Mark a constant as varying over the manual axes (shard_map vma typing
    requires scan carries to have consistent varying sets)."""
    if not axes:
        return x
    return jax.lax.pcast(x, tuple(axes), to="varying")


def pipeline_loss_fn(params, batch, cfg, *, stages: int, microbatches: int,
                     axis: str = "pod", all_axes: Tuple[str, ...] = ()):
    """Per-shard pipelined loss.  MUST run inside ``shard_map`` over a mesh
    that has ``axis``; ``params['periods']`` leaves carry this stage's
    n_periods/stages slice (leading dim already divided)."""
    stage = jax.lax.axis_index(axis)
    tokens, labels = batch["tokens"], batch["labels"]
    b, s = tokens.shape
    m = microbatches
    assert b % m == 0
    mb_tokens = tokens.reshape(m, b // m, s)
    mb_labels = labels.reshape(m, b // m, s)
    positions = jnp.broadcast_to(
        jnp.arange(s, dtype=jnp.int32)[None], (b // m, s)
    )
    d = cfg.d_model
    ticks = m + stages - 1
    perm = [(i, (i + 1) % stages) for i in range(stages)]

    def head_loss(x, labels_mb):
        x = apply_norm(x, params["final_norm"], cfg.norm)
        logits = model_lib._head(params, x, cfg)
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(labels_mb, 0)[..., None], axis=-1
        )[..., 0]
        mask = (labels_mb >= 0).astype(jnp.float32)
        # [1]-shaped sums, like every float accumulator of the shard_map body
        return (
            jnp.sum((logz - tgt) * mask).reshape(1),
            jnp.sum(mask).reshape(1),
        )

    def tick(carry, t):
        buf, loss_sum, tok_sum, aux_sum = carry
        # stage 0 injects microbatch t during the fill phase; other stages
        # consume the rotated boundary activations.
        inj_idx = jnp.clip(t, 0, m - 1)
        injected = jnp.take(params["embed"], mb_tokens[inj_idx], axis=0)
        injected = injected.astype(cfg.dtype)
        x = jnp.where(stage == 0, injected, buf)
        y, aux = _run_periods(params["periods"], x, cfg, positions, vary)
        # last stage: microbatch (t - stages + 1) finishes at tick t
        out_idx = jnp.clip(t - (stages - 1), 0, m - 1)
        lsum, ntok = head_loss(y, mb_labels[out_idx])
        valid = (
            (stage == stages - 1) & (t >= stages - 1) & (t - (stages - 1) < m)
        ).astype(jnp.float32).reshape(1)
        loss_sum = loss_sum + valid * lsum
        tok_sum = tok_sum + valid * ntok
        aux_sum = aux_sum + aux / ticks
        buf = jax.lax.ppermute(y, axis, perm)
        return (buf, loss_sum, tok_sum, aux_sum), None

    vary = tuple(all_axes) or (axis,)
    buf0 = _pvary(jnp.zeros((b // m, s, d), cfg.dtype), vary)
    zero = _pvary(jnp.zeros((1,), jnp.float32), vary)
    (buf, loss_sum, tok_sum, aux_sum), _ = jax.lax.scan(
        tick, (buf0, zero, zero, zero), jnp.arange(ticks)
    )
    # total over stages (only the last stage contributed); mean per token
    loss_sum = jax.lax.psum(loss_sum, axis)
    tok_sum = jax.lax.psum(tok_sum, axis)
    aux_sum = jax.lax.psum(aux_sum, axis) / stages
    nm = model_lib.num_moe_layers(cfg)
    ce = loss_sum / jnp.maximum(tok_sum, 1.0)
    total = ce + (cfg.router_aux * aux_sum / nm if nm else 0.0)
    return total[0]  # rank-1 accumulators squeeze only at the very end


def _stage_slice_specs(params_abs, mesh: Mesh, policy, axis: str = "pod"):
    """Shardings for PP: periods' leading (depth) dim over ``axis``; other
    leaves follow the normal policy rules."""
    from .. import sharding as shd

    base = shd.param_specs(params_abs, policy)

    def fix(path, spec_leaf, abs_leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if "periods" in names:
            old = spec_leaf.spec
            rest = tuple(old)[1:] if len(tuple(old)) >= 1 else ()
            # drop any use of `axis` elsewhere in the spec (depth owns it)
            rest = tuple(
                None if (a == axis or (isinstance(a, tuple) and axis in a))
                else a
                for a in rest
            )
            return NamedSharding(mesh, P(axis, *rest))
        return spec_leaf

    return jax.tree_util.tree_map_with_path(fix, base, params_abs)


def make_pp_loss_for_mesh(cfg, mesh: Mesh, policy, batch_abs,
                          *, microbatches: int, axis: str = "pod"):
    """shard_map-wrapped pipelined loss + its in_shardings.

    Returns (fn(params, batch) -> scalar, (param_shardings, batch_shardings))
    where the params pytree is the FULL model (depth dim sharded over
    ``axis`` = each stage stores only its slice).
    """
    from .. import sharding as shd

    stages = mesh.shape[axis]
    assert cfg.n_periods % stages == 0, (cfg.n_periods, stages)
    # the pipeline owns ``axis``: batch parallelism must not use it
    policy = shd.ShardingPolicy(
        mesh, policy.rules.override(batch="data")
    )
    params_abs = model_lib.abstract_params(cfg)
    param_sh = _stage_slice_specs(params_abs, mesh, policy, axis)
    batch_sh = shd.batch_specs(batch_abs, policy)

    param_specs = jax.tree.map(lambda s: s.spec, param_sh)
    batch_specs_ = jax.tree.map(lambda s: s.spec, batch_sh)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(param_specs, batch_specs_),
        out_specs=P(),
    )
    def fn(params, batch):
        # constrain() must be inert per-shard: shard_map already fixes layout
        with shd.use_policy(None):
            loss = pipeline_loss_fn(
                params, batch, cfg, stages=stages,
                microbatches=microbatches, axis=axis,
                all_axes=tuple(mesh.axis_names),
            )
            # mean over the data-parallel shards too
            other = tuple(a for a in mesh.axis_names if a != axis)
            return jax.lax.pmean(loss, other) if other else loss

    return fn, (param_sh, batch_sh)
