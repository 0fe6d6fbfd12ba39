"""Jit'd public wrappers around the Pallas kernels.

These handle padding/sorting conventions so callers (the DSL back-end, the
MoE layer) see clean semantics; the underlying kernels keep hardware-shaped
contracts (tile multiples, sorted streams).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve a Pallas ``interpret`` argument; ``None`` means auto.

    Auto interprets on hosts without a TPU (CPU CI) and compiles the kernel
    on a TPU, so a caller that leaves the argument out never runs the
    interpreter on the chip without asking for it.
    """
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _pad_to(x: jnp.ndarray, n: int, value) -> jnp.ndarray:
    pad = n - x.shape[0]
    if pad <= 0:
        return x
    return jnp.concatenate([x, jnp.full((pad,) + x.shape[1:], value, x.dtype)])


@functools.partial(jax.jit, static_argnames=("n_out", "op", "interpret", "u", "et"))
def shuffle_reduce(
    vals: jnp.ndarray,
    idx: jnp.ndarray,
    n_out: int,
    op: str = "+",
    *,
    interpret: Optional[bool] = None,
    u: int = 512,
    et: int = 1024,
) -> jnp.ndarray:
    """Scatter-reduce (unsorted) updates into ``n_out`` bins via the Pallas
    shuffle kernel. Matches ``ref.shuffle_reduce_ref`` exactly."""
    from .shuffle_reduce import shuffle_reduce_sorted

    n = vals.shape[0]
    et = min(et, max(128, 1 << (max(1, n) - 1).bit_length()))
    u = min(u, max(128, 1 << (max(1, n_out) - 1).bit_length()))
    perm = jnp.argsort(idx)  # the shuffle-routing decision
    idx_s = idx[perm].astype(jnp.int32)
    vals_s = vals[perm]
    n_pad = ((n + et - 1) // et) * et
    from .ref import _identity

    idx_s = _pad_to(idx_s, n_pad, jnp.int32(2**31 - 1))
    vals_s = _pad_to(vals_s, n_pad, _identity(op, vals.dtype))
    out = shuffle_reduce_sorted(
        vals_s, idx_s, n_out=n_out, op=op, u=u, et=et, interpret=interpret
    )
    return out[:n_out]


@functools.partial(jax.jit, static_argnames=("n_out", "op", "interpret", "u", "et"))
def shuffle_reduce_batched(
    vals: jnp.ndarray,
    idx: jnp.ndarray,
    n_out: int,
    op: str = "+",
    *,
    interpret: Optional[bool] = None,
    u: int = 512,
    et: int = 1024,
) -> jnp.ndarray:
    """Batched scatter-reduce: ``[K, N]`` update lanes into ``[K, n_out]``.

    One Pallas launch serves the whole batch: each lane's destinations are
    offset into a private bin range (``idx + k * n_out``) and the flattened
    ``[K * N]`` stream reduces into ``K * n_out`` bins — the multi-query
    analogue of the shuffle network, with the batch axis materialized as
    extra output partitions instead of extra launches. ``idx`` may be
    shared (``[N]``, e.g. a fixed dst array) or per-lane (``[K, N]``).
    Row ``k`` of the result equals ``shuffle_reduce(vals[k], idx[k], n_out,
    op)`` — bit-identical for min/max and integer reductions; float sums
    can differ in the last ulp where the flattened stream's tile boundaries
    regroup the additions.
    """
    k, n = vals.shape
    idx = jnp.broadcast_to(idx, (k, n)) if idx.ndim == 1 else idx
    offsets = (jnp.arange(k, dtype=jnp.int32) * n_out)[:, None]
    flat_idx = (idx.astype(jnp.int32) + offsets).reshape(-1)
    out = shuffle_reduce(
        vals.reshape(-1), flat_idx, k * n_out, op, interpret=interpret, u=u, et=et
    )
    return out.reshape(k, n_out)


@functools.partial(jax.jit, static_argnames=("n_out", "apply_op", "reduce_op", "interpret"))
def edge_stream_batched(
    src_vals: jnp.ndarray,
    weights: jnp.ndarray,
    dst: jnp.ndarray,
    active: jnp.ndarray,
    n_out: int,
    apply_op: str = "add",
    reduce_op: str = "min",
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Batched fused edge pipeline: ``[K, E]`` gathered operands in ONE kernel.

    Same bin-offset flattening as :func:`shuffle_reduce_batched`: the K
    per-query edge streams concatenate into one sorted stream whose
    destination ids index ``K * n_out`` partitions, so the whole batch
    costs one gather->apply->shuffle->reduce launch. ``weights`` / ``dst``
    / ``active`` may each be shared (``[E]``) or per-lane (``[K, E]``).
    Row ``k`` equals ``edge_stream(src_vals[k], ..., n_out, ...)`` —
    bit-identical for min/max and integer reductions; float sums can
    differ in the last ulp where tile boundaries regroup the additions.
    """
    k, n = src_vals.shape
    weights = jnp.broadcast_to(weights, (k, n)) if weights.ndim == 1 else weights
    dst = jnp.broadcast_to(dst, (k, n)) if dst.ndim == 1 else dst
    active = jnp.broadcast_to(active, (k, n)) if active.ndim == 1 else active
    offsets = (jnp.arange(k, dtype=jnp.int32) * n_out)[:, None]
    flat_dst = (dst.astype(jnp.int32) + offsets).reshape(-1)
    out = edge_stream(
        src_vals.reshape(-1), weights.reshape(-1), flat_dst, active.reshape(-1),
        k * n_out, apply_op, reduce_op, interpret=interpret,
    )
    return out.reshape(k, n_out)


@functools.partial(jax.jit, static_argnames=("n_out", "apply_op", "reduce_op", "interpret"))
def edge_stream(
    src_vals: jnp.ndarray,
    weights: jnp.ndarray,
    dst: jnp.ndarray,
    active: jnp.ndarray,
    n_out: int,
    apply_op: str = "add",
    reduce_op: str = "min",
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused gather->apply->shuffle->reduce edge pipeline (paper Fig. 4)."""
    from .edge_stream import edge_stream_call

    return edge_stream_call(
        src_vals, weights, dst, active, n_out=n_out, apply_op=apply_op,
        reduce_op=reduce_op, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("capacity", "interpret"))
def moe_gather(
    tokens_sorted: jnp.ndarray,
    group_offsets: jnp.ndarray,
    group_sizes: jnp.ndarray,
    capacity: int,
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Capacity-binned expert gather via the Pallas dispatch kernel."""
    from .moe_dispatch import moe_gather_call

    return moe_gather_call(
        tokens_sorted, group_offsets, group_sizes, capacity, interpret=interpret
    )


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Blocked online-softmax attention (beyond-paper LM hot-spot kernel)."""
    from .flash_attention import flash_attention_call

    return flash_attention_call(
        q, k, v, causal=causal, window=window, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
