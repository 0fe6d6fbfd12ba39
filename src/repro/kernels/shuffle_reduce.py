"""Pallas TPU kernel: binned conflict-free scatter-reduce ("shuffle").

The TPU-native re-formulation of the paper's data-shuffling network
(Fig. 7(c)): instead of routing updates through a hardware shuffle into
banked URAM, updates are **sorted by destination once** (the routing
decision, done by the caller) and the kernel reduces each destination
partition in VMEM:

* grid = (P, T): P output partitions x T input tiles;
* the output block (one partition of width ``u``) stays VMEM-resident for
  the whole inner ``t`` loop — the URAM accumulator analogue;
* input tiles are streamed HBM->VMEM; with sorted input, a partition only
  overlaps a contiguous tile range ``[tile_lo[p], tile_hi[p]]``. The tile
  index map **clamps** to that range (scalar-prefetched), so out-of-range
  grid steps re-reference the same block (no DMA) and skip compute via
  ``pl.when`` — the streaming cost is O(N), not O(P*N);
* within a tile, the reduction is conflict-free: an explicit one-hot
  contraction — ``onehot.T @ vals`` on the MXU for float sums, a masked
  broadcast reduce on the VPU for min/max/int — replacing the FPGA's
  RAW-resolver + banked reduce.

Validated against ``ref.shuffle_reduce_ref`` in interpret mode (CPU).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import resolve_interpret
from . import ref

NEG = {"min": "max", "max": "min"}


def _identity(op: str, dtype) -> jnp.ndarray:
    dtype = jnp.dtype(dtype)
    if op == "+":
        return jnp.asarray(0, dtype)
    if op == "min":
        return jnp.asarray(
            jnp.iinfo(dtype).max if jnp.issubdtype(dtype, jnp.integer) else jnp.inf, dtype
        )
    if op == "max":
        return jnp.asarray(
            jnp.iinfo(dtype).min if jnp.issubdtype(dtype, jnp.integer) else -jnp.inf, dtype
        )
    raise ValueError(op)


def _kernel(tile_lo_ref, tile_hi_ref, idx_ref, val_ref, out_ref, *, op: str, u: int, et: int):
    p = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.full((1, u), _identity(op, out_ref.dtype))

    in_range = jnp.logical_and(t >= tile_lo_ref[p], t <= tile_hi_ref[p])

    @pl.when(in_range)
    def _accum():
        idx = idx_ref[0, :]  # [et] global destination ids (sorted)
        vals = val_ref[0, :]  # [et]
        local = idx - p * u
        lanes = jax.lax.broadcasted_iota(jnp.int32, (et, u), 1)
        onehot = local[:, None] == lanes  # [et, u]
        if op == "+" and jnp.issubdtype(out_ref.dtype, jnp.floating):
            # MXU path: one-hot contraction
            contrib = jnp.dot(
                onehot.astype(out_ref.dtype)[:, :].T, vals.astype(out_ref.dtype),
                preferred_element_type=jnp.float32,
            ).astype(out_ref.dtype)
            out_ref[0, :] = out_ref[0, :] + contrib
        else:
            ident = _identity(op, out_ref.dtype)
            spread = jnp.where(onehot, vals[:, None].astype(out_ref.dtype), ident)
            if op == "+":
                contrib = jnp.sum(spread, axis=0)
                out_ref[0, :] = out_ref[0, :] + contrib
            elif op == "min":
                contrib = jnp.min(spread, axis=0)
                out_ref[0, :] = jnp.minimum(out_ref[0, :], contrib)
            else:
                contrib = jnp.max(spread, axis=0)
                out_ref[0, :] = jnp.maximum(out_ref[0, :], contrib)


@functools.partial(
    jax.jit, static_argnames=("n_out", "op", "u", "et", "interpret")
)
def shuffle_reduce_sorted(
    vals: jnp.ndarray,
    idx_sorted: jnp.ndarray,
    *,
    n_out: int,
    op: str = "+",
    u: int = 512,
    et: int = 1024,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Reduce sorted (idx, val) update streams into ``n_out`` bins.

    Inputs must be padded so ``len % et == 0`` and invalid lanes must carry
    an out-of-range index (>= n_out) with identity values.
    Returns an array of length ``n_out_padded`` (multiple of ``u``) whose
    untouched entries hold the reduction identity; callers slice to n_out.
    """
    n = vals.shape[0]
    assert n % et == 0, "pad the update stream to a tile multiple"
    n_out_pad = ((n_out + u - 1) // u) * u
    n_tiles = n // et
    n_parts = n_out_pad // u

    # scalar prefetch: first/last tile overlapping each partition
    tile_of = idx_sorted // u  # partition of each update
    first_in_tile = tile_of[:: et]  # [T] partition of each tile's first lane
    tmp = jnp.minimum(tile_of, n_parts - 1)
    last_in_tile = tmp[et - 1 :: et]
    parts = jnp.arange(n_parts, dtype=jnp.int32)
    # tile t overlaps partition p iff first_in_tile[t] <= p <= last_in_tile[t]
    tile_lo = jnp.searchsorted(last_in_tile, parts, side="left").astype(jnp.int32)
    tile_hi = (
        jnp.searchsorted(first_in_tile, parts, side="right").astype(jnp.int32) - 1
    )
    tile_lo = jnp.minimum(tile_lo, n_tiles - 1)
    tile_hi = jnp.clip(tile_hi, 0, n_tiles - 1)

    def idx_map_in(p, t, lo_ref, hi_ref):
        return (0, jnp.clip(t, lo_ref[p], hi_ref[p]))

    def idx_map_out(p, t, lo_ref, hi_ref):
        return (0, p)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_parts, n_tiles),
        in_specs=[
            pl.BlockSpec((1, et), idx_map_in),
            pl.BlockSpec((1, et), idx_map_in),
        ],
        out_specs=pl.BlockSpec((1, u), idx_map_out),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, op=op, u=u, et=et),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, n_out_pad), vals.dtype),
        interpret=resolve_interpret(interpret),
    )(tile_lo, tile_hi, idx_sorted[None, :], vals[None, :])
    return out[0]
