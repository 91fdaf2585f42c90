"""jit'd public wrappers for the Pallas kernels.

`interpret=None` resolves through `repro.backend.pallas_interpret`: the
Pallas interpreter on the CPU, native compilation everywhere else. Each op
has a pure-jnp oracle in ref.py; tests sweep shapes/dtypes asserting
allclose.
"""
from __future__ import annotations

import functools

import jax

from repro.backend import pallas_interpret
from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.moe_gemm import moe_gemm
from repro.kernels.rmsnorm import rmsnorm_fwd
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.kernels.ssd_scan import ssd_scan


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, block_q=256, block_kv=256,
                    interpret=None):
    interpret = pallas_interpret() if interpret is None else interpret
    return flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                               block_kv=block_kv, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_kv", "interpret"))
def decode_attention(q, k, v, cur_len, *, block_kv=512, interpret=None):
    interpret = pallas_interpret() if interpret is None else interpret
    return decode_attention_fwd(q, k, v, cur_len, block_kv=block_kv,
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def grouped_expert_gemm(x, w, *, block_m=128, block_n=128, block_k=128,
                        interpret=None):
    interpret = pallas_interpret() if interpret is None else interpret
    return moe_gemm(x, w, block_m=block_m, block_n=block_n, block_k=block_k,
                    interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, scale, *, eps=1e-5, block_rows=256, interpret=None):
    interpret = pallas_interpret() if interpret is None else interpret
    return rmsnorm_fwd(x, scale, eps=eps, block_rows=block_rows,
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba2_ssd(x, dt, A, Bm, Cm, *, chunk=64, interpret=None):
    interpret = pallas_interpret() if interpret is None else interpret
    return ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_wkv(r, k, v, logw, u, *, chunk=32, interpret=None):
    interpret = pallas_interpret() if interpret is None else interpret
    return rwkv6_scan(r, k, v, logw, u, chunk=chunk, interpret=interpret)
