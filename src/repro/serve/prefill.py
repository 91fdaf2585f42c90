"""Prefill graphs of published LLM configurations for Stream's explorer.

`mla_moe_prefill` builds the prefill of one prompt through a latent-attention
(MLA) + mixture-of-experts decoder (`ArchConfig` with `mixer="mla"`,
`ffn="moe"`, as DeepSeek-V2) as a Stream `Workload` at the config's widths.
Token rows run along OY (OX = 1), so Stream's row bands split the prompt
into token bands and layer fusion streams them through the fabric. Unlike
`repro.serve.workloads`' generic graphs, nothing stands in for an operand:

* attention's scores (Q.K^T) and context (P.V) are `matmul` layers whose
  operand B is the key / value output of the kv up-projection, read over
  the causal prefix of each query band (`Layer.causal`), so a band's MACs
  and the K/V it keeps live grow with its position;
* every routed expert is a pair of layers (gate+up, down) over its own
  routed token rows (`Layer.rows`), fed by the dispatch (the normed rows
  and the router's scores of its tokens) and gathered back per token by
  the weighted combine.

Per layer (arXiv:2405.04434, Sec. 2.1 and 2.2; no q LoRA, as
`q_lora_rank` null): RMSNorm; q_proj d -> H x (nope + rope); kv_a_proj
d -> lora + rope, RMSNorm on the lora part, kv_b_proj lora -> H x (nope +
v); RoPE on the rope parts of q and k; scores, softmax, context; o_proj;
residual add; RMSNorm; then the dense FFN (gate+up d -> 2 x d_ff, SiLU.mul,
down) for the first `first_dense_layers` layers, else the router d -> E,
the shared experts (one gate+up d -> 2 x n_shared x d_ff_expert and its
down), the routed experts and the combine, and the residual add.
RMSNorm, RoPE, softmax and SiLU.mul are SIMD `pool` layers, the adds and
the combine `add` layers. The shared and routed experts apply SiLU.mul to
their gate+up output as their down projection reads it (the down layer
reads both halves), so each expert is two layers. The embedding lookup and
the LM head are left out: the prompt's embeddings are read from DRAM.

Routing is fixed data drawn from a seed (`route_tokens`): no weights or
prompt decide it here.
"""
from __future__ import annotations

import numpy as np

from repro.core.workload import Workload

ROUTING_SKEW = 0.3   # expert popularity ~ rank ** -ROUTING_SKEW


def route_tokens(n_tokens: int, n_experts: int, top_k: int, seed: int,
                 layer: int) -> list[np.ndarray]:
    """Per expert, the sorted token ids routed to it in MoE layer `layer`:
    each token takes `top_k` distinct experts, drawn without replacement
    in proportion to a popularity ~ rank ** -0.3 under a permutation of
    the experts drawn afresh per layer (Gumbel top-k). Max/mean load is
    about 2.4 at 64 experts, top 6.

        >>> loads = [len(r) for r in route_tokens(64, 8, 2, 0, 1)]
        >>> sum(loads)
        128
    """
    rng = np.random.default_rng([seed, layer])
    rank = np.empty(n_experts)
    rank[rng.permutation(n_experts)] = np.arange(1, n_experts + 1)
    logp = -ROUTING_SKEW * np.log(rank)
    keys = logp[None, :] + rng.gumbel(size=(n_tokens, n_experts))
    top = np.argpartition(-keys, top_k - 1, axis=1)[:, :top_k]
    chosen = np.zeros((n_tokens, n_experts), dtype=bool)
    chosen[np.arange(n_tokens)[:, None], top] = True
    return [np.flatnonzero(chosen[:, e]) for e in range(n_experts)]


def mla_moe_prefill(cfg, seq_len: int, *, n_layers: int | None = None,
                    seed: int = 0) -> Workload:
    """The prefill of one `seq_len`-token prompt through the first
    `n_layers` (default all) layers of an MLA + MoE `cfg`, 8-bit operands.
    MoE layer i routes with `route_tokens(seq_len, E, k, seed, i)`; an
    expert that no token picks has no layers.

        >>> from repro.configs.deepseek_v2_lite import CONFIG
        >>> w = mla_moe_prefill(CONFIG, 64, n_layers=2)
        >>> [w.layers[i].name for i in range(8, 11)]
        ['L0.scores', 'L0.softmax', 'L0.context']
        >>> w.layers[8].op, w.layers[8].causal, w.layers[8].dims["C"]
        ('matmul', 'K', 192)
    """
    if cfg.mixer != "mla" or cfg.ffn != "moe":
        raise ValueError(f"{cfg.name}: an MLA + MoE config is needed")
    mla, moe = cfg.mla, cfg.moe
    d, h, t = cfg.d_model, cfg.n_heads, seq_len
    nope, rope, v, lora = (mla["qk_nope"], mla["qk_rope"], mla["v_dim"],
                           mla["kv_lora"])
    n_layers = cfg.n_layers if n_layers is None else n_layers
    w = Workload(f"{cfg.name}.prefill{seq_len}x{n_layers}")
    bits = 8

    def gemm(name, src, k, c, **kw):
        return w.add(name, "conv", {"B": 1, "K": k, "C": c, "OY": kw.pop(
            "oy", t), "OX": 1, "FY": 1, "FX": 1}, inputs=src, bits=bits, **kw)

    def simd(name, src, k, **kw):
        return w.add(name, "pool", {"B": 1, "K": k, "OY": kw.pop("oy", t),
                                    "OX": 1, "FY": 1, "FX": 1},
                     inputs=src, bits=bits, **kw)

    def add(name, src, k, **kw):
        return w.add(name, "add", {"B": 1, "K": k, "OY": t, "OX": 1},
                     inputs=src, bits=bits, **kw)

    x = simd("embed", (), d)          # the prompt's embeddings, from DRAM
    for i in range(n_layers):
        p = f"L{i}."
        n1 = simd(p + "norm", (x,), d)
        q = gemm(p + "q_proj", (n1,), h * (nope + rope), d)
        kv_a = gemm(p + "kv_a_proj", (n1,), lora + rope, d)
        kv_n = simd(p + "kv_norm", (kv_a,), lora, reads=((0, lora),))
        kv_b = gemm(p + "kv_b_proj", (kv_n,), h * (nope + v), lora)
        q_pe = simd(p + "q_rope", (q,), h * rope,
                    reads=((h * nope, h * (nope + rope)),))
        k_pe = simd(p + "k_rope", (kv_a,), rope,
                    reads=((lora, lora + rope),))
        # Q = [q_nope | rope(q_pe)] per head; K = [k_nope | rope(k_pe)],
        # the rope key shared by all heads; V from kv_b's second half
        scores = w.add(p + "scores", "matmul",
                       {"B": h, "K": t, "C": nope + rope, "OY": t, "OX": 1},
                       inputs=(q, q_pe, kv_b, k_pe), roles="aabb",
                       reads=((0, h * nope), None, (0, h * nope), None),
                       causal="K", bits=bits)
        probs = w.add(p + "softmax", "pool",
                      {"B": h, "K": t, "OY": t, "OX": 1, "FY": 1, "FX": 1},
                      inputs=(scores,), causal="K", bits=bits)
        ctx = w.add(p + "context", "matmul",
                    {"B": h, "K": v, "C": t, "OY": t, "OX": 1},
                    inputs=(probs, kv_b), roles="ab",
                    reads=(None, (h * nope, h * (nope + v))), causal="C",
                    bits=bits)
        o = gemm(p + "o_proj", (ctx,), d, h * v, reads=((0, v),))
        x_attn = add(p + "attn_res", (o, x), d)
        n2 = simd(p + "ffn_norm", (x_attn,), d)
        if i < moe["first_dense_layers"]:
            f = moe["d_ff_dense"]
            gu = gemm(p + "gate_up", (n2,), 2 * f, d)
            act = simd(p + "silu_mul", (gu,), f, reads=((0, 2 * f),))
            ffn = gemm(p + "down", (act,), d, f)
        else:
            ffn = _moe(p, moe, n2, gemm, add, d,
                       route_tokens(t, moe["n_routed"], moe["top_k"], seed,
                                    i))
        x = add(p + "ffn_res", (ffn, x_attn), d)
    return w


def _moe(p: str, moe: dict, n2: int, gemm, add, d: int,
         routes: list[np.ndarray]) -> int:
    """Router, shared experts, routed experts over their routed rows and the
    weighted combine; returns the combine. The gate+up layers come first,
    then the downs: a fused stack holds one weight layer per core, so
    consecutive experts on different cores can share one."""
    e, fe = moe["n_routed"], moe["d_ff_expert"]
    fs = moe["n_shared"] * fe
    router = gemm(p + "router", (n2,), e, d)
    s_gu = gemm(p + "shared.gate_up", (n2,), 2 * fs, d)
    used = [x for x in range(e) if len(routes[x])]
    gus = [gemm(f"{p}expert{x}.gate_up", (n2, router), 2 * fe, d,
                reads=((0, d), (0, e)), rows=routes[x],
                oy=len(routes[x])) for x in used]
    s_down = gemm(p + "shared.down", (s_gu,), d, fs, reads=((0, 2 * fs),))
    downs = [gemm(f"{p}expert{x}.down", (gu,), d, fe, reads=((0, 2 * fe),),
                  rows=routes[x], oy=len(routes[x]))
             for x, gu in zip(used, gus)]
    src = (s_down, *downs, router)
    return add(p + "combine", src, d,
               reads=((0, d),) * (1 + len(downs)) + ((0, e),))
