"""Shared model layers: norms, rotary embeddings (RoPE / M-RoPE), blocked
(FlashAttention-style memory-efficient) attention, MLA, GLU MLPs, and the
fine-grained MoE layer (sort + jax.lax.ragged_dot grouped GEMM, expert-TP via
shard_map).

Everything is pure-functional over param dicts produced from ParamSpec trees
(see module.py). Attention math accumulates in fp32; weights/activations are
bf16 by default.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.module import ParamSpec

F32 = jnp.float32
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps: float = 1e-5):
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def layernorm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * scale + bias


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 1e4) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=F32) / head_dim))


def apply_rope(x, positions, theta: float = 1e4):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                       # (D/2,)
    angles = positions[..., None].astype(F32) * freqs        # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                      # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions_thw, sections=(16, 24, 24), theta: float = 1e6):
    """Qwen2-VL M-RoPE: head_dim/2 frequency slots split into (t, h, w)
    sections, each rotated by its own position stream.

    x: (B, S, H, D); positions_thw: (3, B, S).
    """
    d = x.shape[-1]
    assert sum(sections) == d // 2, (sections, d)
    freqs = rope_frequencies(d, theta)                       # (D/2,)
    # build per-slot positions by section
    sec_ids = jnp.repeat(jnp.arange(3), jnp.array(sections),
                         total_repeat_length=d // 2)          # (D/2,)
    pos = positions_thw.astype(F32)                           # (3, B, S)
    pos_per_slot = jnp.take(pos, sec_ids, axis=0)             # (D/2, B, S)
    angles = jnp.einsum("fbs,f->bsf", pos_per_slot, freqs)    # (B, S, D/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# blocked (memory-efficient) attention — the pure-jnp XLA path; the Pallas
# flash kernel (repro.kernels.flash_attention) is the TPU-optimized twin.
# ---------------------------------------------------------------------------

def blocked_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                      block_kv: int = 1024, bias=None):
    """Online-softmax attention over KV blocks (O(S) memory).

    q: (B, S, Hq, D); k, v: (B, T, Hkv, D) with Hq % Hkv == 0.
    bias: optional (B, 1, S, T) additive mask bias.
    """
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    Dv = v.shape[-1]                     # may differ from D (e.g. MLA)
    G = Hq // Hkv
    bq = min(block_q, S)
    bk = min(block_kv, T)
    # pad ragged sequence lengths (e.g. whisper's 1500 frames) to full blocks;
    # padded kv positions are masked below, padded q rows are sliced off
    S_orig, T_orig = S, T
    pad_q = (-S) % bq
    pad_k = (-T) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        S += pad_q
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        T += pad_k
    nq, nk = S // bq, T // bk
    scale = 1.0 / math.sqrt(D)

    # NOTE (perf): K/V stay scan-INVARIANT and are dynamic-sliced inside the
    # body. Feeding reshaped/transposed (nk, B, bk, ...) tensors as scan xs
    # makes GSPMD re-all-gather the full K/V every block step (measured:
    # 3.3 TB/device of all-gathers on deepseek-67b prefill_32k); slicing the
    # original batch-sharded (B, T, H, D) layout is collective-free.
    qh = q.reshape(B, S, Hkv, G, D)

    def q_block(qi):
        q_blk = jax.lax.dynamic_slice_in_dim(qh, qi * bq, bq, axis=1)
        m0 = jnp.full((B, Hkv, G, bq), NEG_INF, F32)
        l0 = jnp.zeros((B, Hkv, G, bq), F32)
        a0 = jnp.zeros((B, Hkv, G, bq, Dv), F32)

        def kv_step(carry, kj):
            m, l, acc = carry
            k_blk = jax.lax.dynamic_slice_in_dim(k, kj * bk, bk, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, kj * bk, bk, axis=1)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk,
                           preferred_element_type=F32) * scale
            if pad_k:
                kpos = kj * bk + jnp.arange(bk)
                s = jnp.where(kpos[None, :] < T_orig, s, NEG_INF)
            if causal:
                qpos = qi * bq + jnp.arange(bq)
                kpos = kj * bk + jnp.arange(bk)
                s = jnp.where(qpos[:, None] >= kpos[None, :], s, NEG_INF)
            if bias is not None:
                qpos = qi * bq + jnp.arange(bq)
                kpos = kj * bk + jnp.arange(bk)
                s = s + jax.lax.dynamic_slice(
                    bias, (0, 0, qi * bq, kj * bk), (B, 1, bq, bk)
                )[:, :, None, :, :].astype(F32)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=F32)
            return (m_new, l, acc), None

        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]          # (B,Hkv,G,bq,D)
        return jnp.moveaxis(out, 3, 1).astype(q.dtype)        # (B,bq,Hkv,G,D)

    def scan_q(carry, qi):
        return carry, q_block(qi)

    _, outs = jax.lax.scan(scan_q, (), jnp.arange(nq))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, Hkv, G, Dv)
    out = out.reshape(B, S, Hq, Dv)
    return out[:, :S_orig] if pad_q else out


def decode_attention(q, k_cache, v_cache, cur_len):
    """Single-token decode: q (B, 1, Hq, D) against a KV cache (B, T, Hkv, D)
    of which the first `cur_len` positions are valid."""
    B, _, Hq, D = q.shape
    _, T, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bthd->bhgt", qg, k_cache,
                   preferred_element_type=F32) / math.sqrt(D)
    valid = (jnp.arange(T) < cur_len)[None, None, None, :]
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgt,bthd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=F32)
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


def decode_attention_kv_sharded(q, k_cache, v_cache, cur_len, mesh,
                                kv_axis=("data",)):
    """Long-context decode with the KV cache sharded along its sequence dim
    across `kv_axis` (flash-decoding style distributed split-KV): each shard
    computes partial (max, sum, acc) softmax statistics which are merged with
    cross-shard collectives. Exact (same result as decode_attention)."""
    B, _, Hq, D = q.shape
    _, T, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    ax = kv_axis if len(kv_axis) > 1 else kv_axis[0]

    def local_fn(q, kc, vc, cur_len):
        Tl = kc.shape[1]
        shard = jax.lax.axis_index(ax)
        base = shard * Tl
        qg = q.reshape(B, Hkv, G, D)
        s = jnp.einsum("bhgd,bthd->bhgt", qg, kc,
                       preferred_element_type=F32) / math.sqrt(D)
        valid = (base + jnp.arange(Tl) < cur_len)[None, None, None, :]
        s = jnp.where(valid, s, NEG_INF)
        m = s.max(axis=-1)                                    # (B,Hkv,G)
        p = jnp.exp(s - m[..., None])
        l = p.sum(axis=-1)
        acc = jnp.einsum("bhgt,bthd->bhgd", p.astype(vc.dtype), vc,
                         preferred_element_type=F32)
        # merge partial softmax stats across KV shards
        m_all = jax.lax.pmax(m, ax)
        corr = jnp.exp(m - m_all)
        l_all = jax.lax.psum(l * corr, ax)
        acc_all = jax.lax.psum(acc * corr[..., None], ax)
        out = acc_all / jnp.maximum(l_all, 1e-30)[..., None]
        return out.reshape(B, 1, Hq, D).astype(q.dtype)

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P(None, ax, None, None), P(None, ax, None, None), P()),
        out_specs=P(), check_vma=False,
    )(q, k_cache, v_cache, cur_len)


# ---------------------------------------------------------------------------
# MLP / GLU
# ---------------------------------------------------------------------------

def glu_mlp_specs(d_model: int, d_ff: int, dtype=jnp.bfloat16):
    return {
        "gate": ParamSpec((d_model, d_ff), dtype, ("embed", "mlp")),
        "up": ParamSpec((d_model, d_ff), dtype, ("embed", "mlp")),
        "down": ParamSpec((d_ff, d_model), dtype, ("mlp", "embed")),
    }


def glu_mlp(params, x):
    h = jax.nn.silu(x @ params["gate"]) * (x @ params["up"])
    return h @ params["down"]


def gelu_mlp_specs(d_model: int, d_ff: int, dtype=jnp.bfloat16):
    return {
        "in": ParamSpec((d_model, d_ff), dtype, ("embed", "mlp")),
        "in_b": ParamSpec((d_ff,), dtype, (None,), init="zeros"),
        "out": ParamSpec((d_ff, d_model), dtype, ("mlp", "embed")),
        "out_b": ParamSpec((d_model,), dtype, (None,), init="zeros"),
    }


def gelu_mlp(params, x):
    h = jax.nn.gelu(x @ params["in"] + params["in_b"], approximate=True)
    return h @ params["out"] + params["out_b"]


# ---------------------------------------------------------------------------
# fine-grained MoE (DeepSeekMoE): shared + routed experts, top-k routing,
# sort + ragged_dot grouped GEMM, expert weights tensor-parallel on 'model'.
# ---------------------------------------------------------------------------

def moe_specs(d_model: int, d_ff_expert: int, n_routed: int, n_shared: int,
              dtype=jnp.bfloat16):
    specs = {
        "router": ParamSpec((d_model, n_routed), jnp.float32, ("embed", None),
                            scale=0.02),
        "gate": ParamSpec((n_routed, d_model, d_ff_expert), dtype,
                          (None, "embed", "mlp")),
        "up": ParamSpec((n_routed, d_model, d_ff_expert), dtype,
                        (None, "embed", "mlp")),
        "down": ParamSpec((n_routed, d_ff_expert, d_model), dtype,
                          (None, "mlp", "embed")),
    }
    if n_shared:
        specs["shared"] = glu_mlp_specs(d_model, d_ff_expert * n_shared, dtype)
    return specs


def moe_ffn(params, x, *, top_k: int, mesh, dp_axes=("pod", "data"),
            tp_axis: str = "model", impl: str = "capacity",
            capacity_factor: float = 1.25):
    """x: (B, S, D) -> (out, aux_loss). Token-local routing; expert weights
    sharded on d_ff across `tp_axis` (expert tensor parallelism -> one psum
    per MoE layer).

    impl='capacity' (default): GShard-style fixed-capacity scatter/gather
    dispatch + batched expert GEMMs — shape-static, compiles to proportional
    FLOPs on every backend. Tokens beyond an expert's capacity are dropped
    (aux loss drives balance).
    impl='ragged': sort + jax.lax.ragged_dot grouped GEMM — exact (no drops);
    best on TPU where ragged_dot has a native kernel.
    """
    B, S, D = x.shape
    E = params["router"].shape[1]
    has_shared = "shared" in params
    dp = tuple(a for a in dp_axes if a in mesh.axis_names)
    if dp and (B % math.prod(mesh.shape[a] for a in dp) != 0):
        dp = ()                      # tiny batches (long-context decode)
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    has_tp = tp_axis in mesh.axis_names
    tp = tp_axis if has_tp else None

    def local_fn(x, router, wg, wu, wd, *shared):
        Bl, Sl, _ = x.shape
        n = Bl * Sl
        xf = x.reshape(n, D)
        logits = xf.astype(F32) @ router                      # (n, E)
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(probs, top_k)              # (n, k)
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
        flat_e = topi.reshape(-1)                             # (n*k,) token-major
        group_sizes = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)

        if impl == "capacity":
            C = max(8, int(math.ceil(n * top_k * capacity_factor / E)))
            # rank of each (token, slot) within its expert, via argsort
            order = jnp.argsort(flat_e)
            sorted_e = flat_e[order]
            idx = jnp.arange(n * top_k)
            is_start = jnp.concatenate(
                [jnp.ones((1,), bool), sorted_e[1:] != sorted_e[:-1]])
            group_start = jax.lax.associative_scan(
                jnp.maximum, jnp.where(is_start, idx, 0))
            rank_sorted = idx - group_start
            rank = jnp.zeros_like(flat_e).at[order].set(rank_sorted)
            ok = rank < C
            rank_c = jnp.minimum(rank, C - 1)
            tok = jnp.arange(n * top_k) // top_k
            contrib = jnp.where(ok[:, None], jnp.take(xf, tok, axis=0), 0)
            buf = jnp.zeros((E, C, D), xf.dtype).at[flat_e, rank_c].add(contrib)
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * \
                jnp.einsum("ecd,edf->ecf", buf, wu)           # (E, C, F_loc)
            y_buf = jnp.einsum("ecf,efd->ecd", h, wd)
            y = y_buf[flat_e, rank_c] * jnp.where(ok, 1.0, 0.0)[:, None]
            w_slot = topv.reshape(-1).astype(F32)
            out = jnp.sum(
                (y.astype(F32) * w_slot[:, None]).reshape(n, top_k, D), axis=1)
        else:  # ragged: sort tokens by expert, grouped GEMM, unsort
            order = jnp.argsort(flat_e)
            tok = order // top_k
            xs = jnp.take(xf, tok, axis=0)                    # (n*k, D) sorted
            h = jax.nn.silu(jax.lax.ragged_dot(xs, wg, group_sizes)) * \
                jax.lax.ragged_dot(xs, wu, group_sizes)
            y = jax.lax.ragged_dot(h.astype(xs.dtype), wd, group_sizes)
            w_sorted = topv.reshape(-1)[order].astype(F32)
            out = jnp.zeros((n, D), F32).at[tok].add(
                y.astype(F32) * w_sorted[:, None])

        if has_shared:
            sg, su, sd = shared
            hs = jax.nn.silu(xf @ sg) * (xf @ su)
            out = out + (hs @ sd).astype(F32)
        if has_tp:
            # reduce activations in bf16 (dots already accumulated fp32
            # locally); halves expert-TP wire bytes
            out = jax.lax.psum(out.astype(x.dtype), tp_axis)
        # switch-style load-balance aux loss
        frac = group_sizes.astype(F32) / jnp.maximum(n * top_k, 1)
        imp = probs.mean(axis=0)
        aux = E * jnp.sum(frac * imp)
        if dp:
            aux = jax.lax.pmean(aux, dp if len(dp) > 1 else dp[0])
        return out.reshape(Bl, Sl, D).astype(x.dtype), aux

    shared_args = ()
    shared_specs = ()
    if has_shared:
        shared_args = (params["shared"]["gate"], params["shared"]["up"],
                       params["shared"]["down"])
        shared_specs = (P(None, tp), P(None, tp), P(tp, None))

    out, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dp_spec, None, None), P(),
                  P(None, None, tp), P(None, None, tp),
                  P(None, tp, None)) + shared_specs,
        out_specs=(P(dp_spec, None, None), P()),
        check_vma=False,
    )(x, params["router"], params["gate"], params["up"], params["down"],
      *shared_args)
    return out, aux
