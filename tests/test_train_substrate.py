"""Training substrate: optimizer, microbatching, gradient compression,
checkpointing, data determinism, fault tolerance, pipeline parallelism."""
import os

import jax
from jax.sharding import AxisType
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, reduce_config
from repro.models.module import init_from_specs
from repro.models.zoo import build_param_specs
from repro.train import checkpoint as ckpt
from repro.train.data import DataConfig, TokenStream
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state
from repro.train.train_step import (TrainStepConfig, compress_grads,
                                    init_train_state, make_train_step)


_needs_zstandard = pytest.mark.skipif(
    ckpt.zstandard is None,
    reason="optional 'zstandard' not installed (checkpoint compression)")


def _mesh(shape=(2, 4), names=("data", "model")):
    return jax.make_mesh(shape, names,
                         axis_types=(AxisType.Auto,) * len(names))


def _tiny():
    cfg = reduce_config(ARCHS["llama3.2-3b"], n_layers=2, d_model=64,
                        n_heads=2, d_ff=128, vocab=256)
    params = init_from_specs(build_param_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _tiny_batch(cfg, B=4, S=32, seed=1):
    key = jax.random.PRNGKey(seed)
    return {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab),
            "labels": jax.random.randint(key, (B, S), 0, cfg.vocab)}


def test_train_loss_decreases():
    cfg, params = _tiny()
    mesh = _mesh()
    scfg = TrainStepConfig(opt=AdamWConfig(lr=3e-3, warmup_steps=2,
                                           total_steps=30))
    step = jax.jit(make_train_step(cfg, mesh, scfg), donate_argnums=(0, 1))
    state = init_train_state(cfg, params, scfg)
    data = TokenStream(DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8))
    losses = []
    with jax.set_mesh(mesh):
        for i in range(25):
            batch = {k: jnp.asarray(v) for k, v in data.global_batch(i).items()}
            params, state, m = step(params, state, batch)
            losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1


def test_microbatch_equivalence():
    """Grad accumulation over microbatches == single-shot gradients."""
    cfg, params = _tiny()
    mesh = _mesh()
    batch = _tiny_batch(cfg, B=4)
    outs = {}
    for mb in (1, 2):
        scfg = TrainStepConfig(microbatches=mb, remat=False,
                               opt=AdamWConfig(lr=1e-3))
        step = make_train_step(cfg, mesh, scfg)
        with jax.set_mesh(mesh):
            p2, _, m = step(jax.tree.map(jnp.copy, params),
                            init_train_state(cfg, params, scfg), batch)
        outs[mb] = (p2, float(m["loss"]))
    # loss averages match; updated params close
    assert abs(outs[1][1] - outs[2][1]) < 5e-2
    for a, b in zip(jax.tree.leaves(outs[1][0]), jax.tree.leaves(outs[2][0])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=0.1,
                                   atol=5e-3)


def test_grad_compress_error_feedback():
    """Error feedback keeps the accumulated compressed grads unbiased."""
    g = {"w": jnp.array([0.3e-2, -1.7e-2, 0.9e-2])}
    ef = {"w": jnp.zeros(3)}
    total_deq = jnp.zeros(3)
    for _ in range(64):
        deq, ef = compress_grads(g, ef)
        total_deq = total_deq + deq["w"]
    avg = total_deq / 64
    np.testing.assert_allclose(np.asarray(avg), np.asarray(g["w"]),
                               rtol=2e-2, atol=1e-5)


def test_adamw_step_and_clip():
    params = {"w": jnp.ones((4, 4))}
    grads = {"w": jnp.full((4, 4), 100.0)}  # should be clipped
    state = init_opt_state(params)
    cfg = AdamWConfig(lr=1e-2, clip_norm=1.0, warmup_steps=0, total_steps=10)
    p2, s2, m = adamw_update(cfg, params, grads, state)
    assert float(m["grad_norm"]) > 1.0
    assert int(s2["step"]) == 1
    assert np.all(np.asarray(p2["w"]) < np.asarray(params["w"]))


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

@_needs_zstandard
def test_checkpoint_roundtrip_and_reshard(tmp_path):
    cfg, params = _tiny()
    tree = {"params": params, "step": jnp.int32(7)}
    path = ckpt.save(str(tmp_path), 7, tree)
    assert os.path.isdir(path)
    assert ckpt.latest_step(str(tmp_path)) == 7
    # restore onto a different mesh sharding
    mesh = _mesh((4, 2))
    from repro.sharding.rules import tree_shardings
    sh = {"params": tree_shardings(build_param_specs(cfg), mesh),
          "step": None}
    restored = ckpt.restore(str(tmp_path), 7, like_tree=tree, shardings=sh)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))


@_needs_zstandard
def test_checkpoint_atomic_no_partial(tmp_path):
    cfg, params = _tiny()
    ckpt.save(str(tmp_path), 1, {"p": params})
    # a .tmp dir must never be visible as a checkpoint
    assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path))


def test_data_pipeline_deterministic_and_shardable():
    cfg = DataConfig(vocab=512, seq_len=16, global_batch=8, seed=3)
    ds = TokenStream(cfg)
    a = ds.global_batch(5)
    b = ds.global_batch(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = ds.global_batch(6)
    assert not np.array_equal(a["tokens"], c["tokens"])
    # shards are deterministic slices of the same step
    s0 = ds.batch(5, shard=0, n_shards=2)
    s0b = ds.batch(5, shard=0, n_shards=2)
    np.testing.assert_array_equal(s0["tokens"], s0b["tokens"])
    # labels are next-token shifted
    seq = np.concatenate([a["tokens"][:, :1], a["labels"]], axis=1)
    np.testing.assert_array_equal(seq[:, 1:], a["labels"])


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

@_needs_zstandard
def test_resume_or_init(tmp_path):
    from repro.train.fault_tolerance import resume_or_init
    tree = {"x": jnp.arange(4)}
    got, step = resume_or_init(str(tmp_path), lambda: tree)
    assert step == 0
    ckpt.save(str(tmp_path), 12, tree)
    got, step = resume_or_init(str(tmp_path), lambda: tree, like_tree=tree)
    assert step == 12
    np.testing.assert_array_equal(np.asarray(got["x"]), np.arange(4))


def test_elastic_replan_smaller_pod():
    from repro.train.fault_tolerance import replan_after_failure
    cfg = ARCHS["llama3.2-3b"]
    plan_full = replan_after_failure(cfg, SHAPES["train_4k"], 256,
                                     n_stages=4, n_microbatches=8)
    plan_small = replan_after_failure(cfg, SHAPES["train_4k"], 192,
                                      n_stages=4, n_microbatches=8)
    assert plan_small.n_stages * plan_small.chips_per_stage == 192
    assert plan_small.est_step_s >= plan_full.est_step_s * 0.95


def test_straggler_mitigation_ga_rebalances():
    from repro.train.fault_tolerance import replan_with_straggler
    cfg = ARCHS["llama3.2-3b"]
    base, mitigated, per_stage = replan_with_straggler(
        cfg, SHAPES["train_4k"], n_stages=4, chips_per_stage=8,
        n_microbatches=8, slow_stage=0, slowdown=3.0)
    assert mitigated <= base * 1.001          # GA never worse
    assert per_stage.sum() == cfg.n_layers
    assert per_stage[0] <= per_stage[1:].max()  # slow stage got <= layers


# ---------------------------------------------------------------------------
# pipeline parallelism
# ---------------------------------------------------------------------------

def test_pipeline_loss_matches_reference():
    from repro.models.zoo import train_loss
    from repro.train.pipeline import make_pipeline_loss
    cfg = reduce_config(ARCHS["llama3.2-3b"], n_layers=4)
    mesh = jax.make_mesh((2, 2), ("pipe", "data"),
                         axis_types=(AxisType.Auto,) * 2)
    params = init_from_specs(build_param_specs(cfg), jax.random.PRNGKey(0))
    batch = _tiny_batch(cfg, B=4, S=32)
    with jax.set_mesh(mesh):
        ref = train_loss(cfg, params, batch, mesh=mesh, remat=False)
        p2 = dict(params)
        p2["layers"] = jax.tree.map(
            lambda a: a.reshape((2, 2) + a.shape[1:]), params["layers"])
        loss_fn = make_pipeline_loss(cfg, mesh, n_stages=2, n_microbatches=2)
        lp = loss_fn(p2, batch)
        grads = jax.grad(loss_fn)(p2, batch)
    assert abs(float(ref) - float(lp)) < 1e-3
    gn = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32))))
             for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0
