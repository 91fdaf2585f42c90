"""Stream-planned pipeline-parallel training (GPipe schedule over
shard_map + collective_permute).

The PipelinePlan (core/planner.py) fixes the layer->stage allocation and
microbatch count; this executor materializes it: the 'pipe' mesh axis holds
one stage per device group, activations flow stage-to-stage with ppermute,
and jax.grad differentiates straight through the pipeline (the reverse
schedule emerges from AD — ppermute's transpose is the reversed ppermute).

Supports uniform dense decoder archs (gqa mixers with glu/gelu ffn).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import transformer as tfm
from repro.models.module import is_spec, spec_tree_map

F32 = jnp.float32


def stage_stacked_specs(cfg: ArchConfig, n_stages: int):
    """Param specs with layers grouped (n_stages, L/stage, ...), stage axis
    sharded along 'pipe'."""
    import dataclasses
    from repro.models.zoo import build_param_specs
    specs = build_param_specs(cfg)
    per = cfg.n_layers // n_stages

    def regroup(s):
        return dataclasses.replace(
            s, shape=(n_stages, per) + s.shape[1:],
            axes=(("pipe",) + (s.axes[1:] if s.axes else (None,) * (len(s.shape) - 1))
                  if True else None))

    specs["layers"] = spec_tree_map(regroup, specs["layers"])
    return specs


def make_pipeline_loss(cfg: ArchConfig, mesh, *, n_stages: int,
                       n_microbatches: int, axis: str = "pipe"):
    """Returns loss(params, batch) with pipeline parallelism over `axis`.

    params['layers'] leaves: (n_stages, L/stage, ...) sharded on `axis`;
    embed / final_norm / lm_head replicated.
    batch: tokens (B, S), labels (B, S); B % n_microbatches == 0.
    """
    per_stage = cfg.n_layers // n_stages

    def loss_fn(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        B, S = tokens.shape
        mb = B // n_microbatches
        tok_mb = tokens.reshape(n_microbatches, mb, S)
        lab_mb = labels.reshape(n_microbatches, mb, S)

        def stage_fn(layers, embed, final_norm_scale, head, tok_mb, lab_mb):
            # layers: (1, per_stage, ...) local slice -> squeeze stage dim
            layers = jax.tree.map(lambda a: a[0], layers)
            stage = jax.lax.axis_index(axis)
            positions = jnp.arange(S)[None, :]

            def block_stack(x):
                def body(x, lp):
                    x, _, _ = tfm.apply_layer(cfg, lp, x, positions, mesh=None)
                    return x, None
                x, _ = jax.lax.scan(body, x, layers)
                return x

            n_steps = n_microbatches + n_stages - 1
            buf = jnp.zeros((mb, S, cfg.d_model), cfg.dtype)
            # (1,)-shaped, not scalar: scalar f32 carries become scalar
            # residuals of the shard_map body, which older jax's
            # partial-eval cannot assign residual axis-names to
            loss_acc = jnp.zeros((1,), F32)

            def step(carry, t):
                x_prev, loss_acc = carry
                # receive activation from the previous stage
                x_in = jax.lax.ppermute(
                    x_prev, axis,
                    [(i, (i + 1) % n_stages) for i in range(n_stages)])
                # stage 0 injects microbatch t (if in range)
                m_idx = jnp.clip(t, 0, n_microbatches - 1)
                fresh = jnp.take(params_embed_holder[0],
                                 jax.lax.dynamic_index_in_dim(
                                     tok_mb, m_idx, 0, keepdims=False),
                                 axis=0)
                x = jnp.where(stage == 0, fresh.astype(cfg.dtype), x_in)
                active_in = (t - stage >= 0) & (t - stage < n_microbatches)
                y = block_stack(x)
                y = jnp.where(active_in, y, x)
                # last stage computes the loss for its finished microbatch
                is_last = stage == n_stages - 1
                m_done = jnp.clip(t - (n_stages - 1), 0, n_microbatches - 1)
                h = tfm.rmsnorm(y, final_norm_scale) if cfg.norm == "rms" else y
                lab = jax.lax.dynamic_index_in_dim(lab_mb, m_done, 0,
                                                   keepdims=False)
                l = tfm.chunked_ce_loss(h, head, lab, block=min(512, S))
                use = is_last & (t - (n_stages - 1) >= 0)
                loss_acc = loss_acc + jnp.where(use, l, 0.0)
                return (y, loss_acc), None

            params_embed_holder = (embed,)
            (x, loss_acc), _ = jax.lax.scan(
                step, (buf, loss_acc), jnp.arange(n_steps))
            # only the last stage holds a nonzero loss; emit per-stage
            # values (device-varying out_spec) and reduce outside the
            # shard_map — replicated scalar outputs are not transposable
            # under older jax's shard_map, a psum here breaks jax.grad
            return jnp.where(stage == n_stages - 1, loss_acc, 0.0)

        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        per_stage = jax.shard_map(
            stage_fn, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(axis), params["layers"]),
                      P(), P(), P(), P(), P()),
            out_specs=P(axis), check_vma=False,
        )(params["layers"], params["embed"],
          params["final_norm"]["scale"], head, tok_mb, lab_mb)
        return per_stage.sum() / n_microbatches

    return loss_fn
