"""Pallas TPU kernel: blocked online-softmax attention (FlashAttention).

Beyond-paper optimization for the LM stack: the assigned architectures'
prefill path is attention-FLOP dominated at 32k context; a blocked online
softmax keeps the working set in VMEM (Bq x Dh, Bk x Dh, Bq x Bk tiles)
instead of materializing the [L, L] score matrix in HBM.

Supports causal masking, sliding windows (h2o-danube / zamba2 long
context), and GQA (kv heads broadcast outside the kernel).

Grid = (B*H, num_q_blocks, num_k_blocks); the running (m, l, acc) state
lives in VMEM scratch and persists across the k-block inner loop.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import resolve_interpret

NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, block_q: int, block_k: int, causal: bool, window: int, lq: int, lk: int, scale: float,
):
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions (decode alignment: query i sits at lk - lq + i)
    q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + (lk - lq)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    run = True
    s = jnp.dot(
        q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32).T,
        preferred_element_type=jnp.float32,
    ) * scale  # [bq, bk]
    mask = k_pos < lk
    if causal:
        mask = jnp.logical_and(mask, k_pos <= q_pos)
    if window > 0:
        mask = jnp.logical_and(mask, k_pos > q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, 0]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_cur[:, None])
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
    )
    m_ref[:, 0] = m_cur

    @pl.when(kb == nk - 1)
    def _fini():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, :, :] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret")
)
def flash_attention_call(
    q: jnp.ndarray,  # [B, H, Lq, Dh]
    k: jnp.ndarray,  # [B, Hkv, Lk, Dh]
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    b, h, lq, dh = q.shape
    hkv = k.shape[1]
    if hkv != h:  # GQA: broadcast kv heads (outside the kernel)
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
    lk = k.shape[2]
    block_q = min(block_q, max(8, 1 << (lq - 1).bit_length()))
    block_k = min(block_k, max(8, 1 << (lk - 1).bit_length()))
    lq_pad = ((lq + block_q - 1) // block_q) * block_q
    lk_pad = ((lk + block_k - 1) // block_k) * block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, lq_pad - lq), (0, 0))).reshape(b * h, lq_pad, dh)
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, lk_pad - lk), (0, 0))).reshape(b * h, lk_pad, dh)
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, lk_pad - lk), (0, 0))).reshape(b * h, lk_pad, dh)
    grid = (b * h, lq_pad // block_q, lk_pad // block_k)
    scale = 1.0 / float(dh) ** 0.5

    out = pl.pallas_call(
        functools.partial(
            _kernel, block_q=block_q, block_k=block_k, causal=causal,
            window=window, lq=lq, lk=lk, scale=scale,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, qb, kb: (bh, qb, 0)),
            pl.BlockSpec((1, block_k, dh), lambda bh, qb, kb: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, dh), lambda bh, qb, kb: (bh, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda bh, qb, kb: (bh, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, lq_pad, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qp, kp, vp)
    return out.reshape(b, h, lq_pad, dh)[:, :, :lq, :]
