"""Kernel micro-benches: Pallas (interpreted on the CPU, compiled on a TPU;
correctness + blocking structure, NOT wall-clock) vs the pure-jnp
reference path, plus the analytic VMEM footprint per BlockSpec choice
(what the Stream planner's Step-3 analogue reasons about)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _vmem_bytes_flash(bq, bk, d, dtype_bytes=2):
    # q + k + v blocks + fp32 scratch (m, l, acc)
    return (bq * d + 2 * bk * d) * dtype_bytes + (2 * bq + bq * d) * 4


def run(report=print):
    report("== Pallas kernel block sweeps (VMEM footprints) ==")
    B, H, S, D = 1, 2, 512, 128
    q = jax.random.normal(KEY, (B, H, S, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, H, S, D), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, H, S, D), jnp.float32)
    want = ref.flash_attention_ref(q, k, v)
    out_rows = []
    report(f"{'kernel':16s} {'blocks':>12s} {'VMEM(KB)':>9s} {'max err':>10s}")
    for bq, bk in ((128, 128), (256, 256), (128, 512)):
        out = ops.flash_attention(q, k, v, block_q=bq, block_kv=bk)
        err = float(jnp.abs(out - want).max())
        vm = _vmem_bytes_flash(bq, bk, D) / 1024
        report(f"{'flash_attn':16s} {f'{bq}x{bk}':>12s} {vm:9.1f} {err:10.2e}")
        out_rows.append(("flash", bq, bk, vm, err))

    qd = q[:, :, 0, :]
    wantd = ref.decode_attention_ref(qd, k, v, 400)
    for bk in (128, 256, 512):
        out = ops.decode_attention(qd, k, v, jnp.int32(400), block_kv=bk)
        err = float(jnp.abs(out - wantd).max())
        report(f"{'decode_attn':16s} {f'1x{bk}':>12s} "
               f"{(2 * bk * D * 2 + D * 4) / 1024:9.1f} {err:10.2e}")

    E, C, K, N = 4, 128, 256, 128
    x = jax.random.normal(KEY, (E, C, K), jnp.float32) * 0.2
    w = jax.random.normal(jax.random.fold_in(KEY, 3), (E, K, N), jnp.float32) * 0.2
    wantm = ref.moe_gemm_ref(x, w)
    for bm, bn, bkk in ((64, 64, 64), (128, 128, 128)):
        out = ops.grouped_expert_gemm(x, w, block_m=bm, block_n=bn,
                                      block_k=bkk)
        err = float(jnp.abs(out - wantm).max() / jnp.abs(wantm).max())
        report(f"{'moe_gemm':16s} {f'{bm}x{bn}x{bkk}':>12s} "
               f"{(bm * bkk + bkk * bn) * 2 / 1024 + bm * bn * 4 / 1024:9.1f} "
               f"{err:10.2e}")
    return out_rows


if __name__ == "__main__":
    run()
