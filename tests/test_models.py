"""Model zoo: per-arch smoke tests (reduced configs, one fwd/train step on
CPU, asserting shapes + finiteness), chunked-vs-scan equivalences, MoE
semantics, decode-vs-full-forward consistency."""
import jax
from jax.sharding import AxisType
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduce_config
from repro.models.module import init_from_specs, init_sharded
from repro.models.zoo import (build_cache_specs, build_param_specs,
                              decode_step, prefill, train_loss)

MESH = None


def mesh():
    global MESH
    if MESH is None:
        MESH = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
    return MESH


def _batch(cfg, B=2, S=32, key=jax.random.PRNGKey(7)):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab),
             "labels": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.family == "encdec":
        batch["enc_embeds"] = jax.random.normal(
            key, (B, cfg.enc["enc_len"], cfg.d_model), cfg.dtype)
    if cfg.rope == "mrope":
        pos = jnp.broadcast_to(jnp.arange(S)[None, None], (3, B, S))
        batch["mrope_positions"] = pos
    return batch


@pytest.mark.parametrize("arch", list(ARCHS))
def test_arch_smoke_train_step(arch):
    """Reduced same-family config: one forward/train step, finite loss."""
    cfg = reduce_config(ARCHS[arch])
    params = init_from_specs(build_param_specs(cfg), jax.random.PRNGKey(0))
    batch = _batch(cfg)
    with jax.set_mesh(mesh()):
        loss = train_loss(cfg, params, batch, mesh=mesh(), remat=False)
    assert jnp.isfinite(loss) and 3.0 < float(loss) < 12.0


@pytest.mark.parametrize("arch", list(ARCHS))
def test_arch_smoke_prefill_decode(arch):
    cfg = reduce_config(ARCHS[arch])
    params = init_from_specs(build_param_specs(cfg), jax.random.PRNGKey(0))
    B, S = 2, 32
    batch = _batch(cfg, B, S)
    batch.pop("labels")
    caches = init_from_specs(build_cache_specs(cfg, B, S + 4),
                             jax.random.PRNGKey(1))
    with jax.set_mesh(mesh()):
        logits, caches = prefill(cfg, params, batch, caches, mesh=mesh())
        enc_out = None
        if cfg.family == "encdec":
            from repro.models import encdec
            enc_out = encdec.encode(cfg, params, batch["enc_embeds"],
                                    mesh=mesh())
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        logits2, _ = decode_step(cfg, params, tok, caches, jnp.int32(S),
                                 mesh=mesh(), enc_out=enc_out)
    assert logits.shape == (B, cfg.vocab)
    assert jnp.isfinite(logits).all() and jnp.isfinite(logits2).all()


@pytest.mark.parametrize("arch", ["llama3.2-3b", "granite-34b",
                                  "deepseek-v2-236b"])
def test_prefill_then_decode_matches_full_forward(arch):
    """Greedy continuation from (prefill + decode) == slicing a longer
    teacher-forced forward pass (KV-cache correctness)."""
    import dataclasses
    cfg = reduce_config(ARCHS[arch])
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    if cfg.moe:
        # capacity drops depend on batch composition; a no-drop factor makes
        # prefill+decode bitwise-comparable with the teacher-forced pass
        cfg = dataclasses.replace(cfg, moe=dict(cfg.moe, capacity_factor=16.0))
    params = init_from_specs(build_param_specs(cfg), jax.random.PRNGKey(0),
                             dtype_override=jnp.float32)
    B, S = 1, 16
    key = jax.random.PRNGKey(3)
    toks = jax.random.randint(key, (B, S + 1), 0, cfg.vocab)
    m = mesh()
    with jax.set_mesh(m):
        # full forward over S+1 tokens -> logits at position S-1 and S
        from repro.models import transformer as tfm
        x, _, _ = tfm.decoder_forward(cfg, params, toks, mesh=m)
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        full_logits = jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                                 head.astype(jnp.float32))
        # prefill S tokens, then decode token S
        caches = init_from_specs(build_cache_specs(cfg, B, S + 4),
                                 jax.random.PRNGKey(1),
                                 dtype_override=jnp.float32)
        lg_pre, caches = prefill(cfg, params, {"tokens": toks[:, :S]}, caches,
                                 mesh=m)
        lg_dec, _ = decode_step(cfg, params, toks[:, S:S + 1], caches,
                                jnp.int32(S), mesh=m)
    np.testing.assert_allclose(np.asarray(lg_pre), np.asarray(
        full_logits[:, S - 1]), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(lg_dec), np.asarray(
        full_logits[:, S]), rtol=2e-3, atol=2e-3)


def test_ssd_chunked_vs_scan_oracle():
    from repro.models.ssm import ssd_chunked, ssd_scan_oracle
    key = jax.random.PRNGKey(0)
    ks = [jax.random.fold_in(key, i) for i in range(5)]
    B, S, H, P, N = 2, 96, 3, 16, 8
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    y1, s1 = ssd_chunked(x, dt, A, Bm, Cm, chunk=32)
    y2, s2 = ssd_scan_oracle(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4,
                               atol=1e-4)


def test_rwkv_chunked_vs_scan_oracle():
    from repro.models.rwkv import rwkv6_chunked, rwkv6_scan_oracle
    key = jax.random.PRNGKey(1)
    ks = [jax.random.fold_in(key, i) for i in range(5)]
    B, S, H, K = 2, 64, 2, 16
    r = jax.random.normal(ks[0], (B, S, H, K))
    k = jax.random.normal(ks[1], (B, S, H, K))
    v = jax.random.normal(ks[2], (B, S, H, K))
    logw = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H, K))) - 0.5
    u = jax.random.normal(ks[4], (H, K)) * 0.1
    o1, s1 = rwkv6_chunked(r, k, v, logw, u, chunk=16)
    o2, s2 = rwkv6_scan_oracle(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4,
                               atol=1e-4)


def test_moe_capacity_matches_dense_when_unconstrained():
    """With generous capacity, the capacity MoE == dense one-hot reference."""
    from repro.models.layers import moe_ffn, moe_specs
    m = mesh()
    specs = moe_specs(16, 8, n_routed=8, n_shared=1, dtype=jnp.float32)
    params = init_from_specs(specs, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 8, 16))
    with jax.set_mesh(m):
        out_cap, _ = moe_ffn(params, x, top_k=2, mesh=m, dp_axes=("data",),
                             impl="capacity", capacity_factor=8.0)
        out_rag, _ = moe_ffn(params, x, top_k=2, mesh=m, dp_axes=("data",),
                             impl="ragged")
    np.testing.assert_allclose(np.asarray(out_cap), np.asarray(out_rag),
                               rtol=1e-4, atol=1e-4)


def test_mrope_sections_rotate_independently():
    from repro.models.layers import apply_mrope, apply_rope
    B, S, H, D = 1, 8, 2, 32
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    same = apply_mrope(x, jnp.stack([pos, pos, pos]), sections=(8, 4, 4),
                       theta=1e4)
    plain = apply_rope(x, pos, theta=1e4)
    np.testing.assert_allclose(np.asarray(same), np.asarray(plain),
                               rtol=1e-5, atol=1e-5)
    # different position streams must change the result
    diff = apply_mrope(x, jnp.stack([pos, pos * 2, pos]), sections=(8, 4, 4),
                       theta=1e4)
    assert not np.allclose(np.asarray(diff), np.asarray(plain))


def test_init_sharded_places_leaves_by_their_axes():
    """Launchers materialize parameters sharded by their logical axes:
    the values of `init_from_specs` (the fused float32 draw may round a
    last bfloat16 bit the other way: 2**-7 relative), split across the
    mesh."""
    from repro.sharding.rules import tree_shardings
    cfg = reduce_config(ARCHS["deepseek-moe-16b"])
    specs = build_param_specs(cfg)
    key = jax.random.PRNGKey(0)
    want = init_from_specs(specs, key)
    got = init_sharded(specs, key, mesh())
    for w, g, sh in zip(jax.tree.leaves(want), jax.tree.leaves(got),
                        jax.tree.leaves(tree_shardings(specs, mesh()))):
        assert g.sharding.is_equivalent_to(sh, g.ndim)
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), rtol=2**-7)
    assert any(len({s.index for s in g.addressable_shards}) > 1
               for g in jax.tree.leaves(got))
