"""Pallas TPU kernel: MoE token dispatch — the paper's shuffle engine
applied to expert routing.

MoE dispatch *is* a graph-shuffle problem: tokens are update tuples keyed
by expert id; conflict-free capacity binning is destination-partitioned
reduction. The routing (argsort by expert) happens once outside; this
kernel performs the capacity-binned gather with **block-aligned group
offsets carried via scalar prefetch**, so on real TPUs the index map is a
static DMA schedule (a Megablocks-style layout, expressed with the paper's
machinery).

Contract:
* ``tokens_sorted``: [T, D] tokens sorted by expert id (padded rows zero);
* ``group_offsets``: [E] start row per expert, **multiples of block_c**;
* ``group_sizes``: [E] live token count per expert (<= capacity);
* output: [E, C, D] with zero padding beyond each group size.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import resolve_interpret


def _kernel(off_ref, size_ref, tok_ref, out_ref, *, block_c: int, d: int):
    e = pl.program_id(0)
    c = pl.program_id(1)
    base = c * block_c
    count = size_ref[e] - base  # live rows in this block
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_c, d), 0)
    live = rows < count
    out_ref[0, :, :] = jnp.where(live, tok_ref[:, :], 0)


@functools.partial(jax.jit, static_argnames=("capacity", "block_c", "interpret"))
def moe_gather_call(
    tokens_sorted: jnp.ndarray,
    group_offsets: jnp.ndarray,
    group_sizes: jnp.ndarray,
    capacity: int,
    *,
    block_c: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    t, d = tokens_sorted.shape
    e = group_offsets.shape[0]
    block_c = min(block_c, capacity)
    assert capacity % block_c == 0
    n_blocks = capacity // block_c
    # tokens must be padded so any offset+capacity window is in range
    t_pad = ((t + capacity + block_c - 1) // block_c) * block_c
    if t_pad > t:
        tokens_sorted = jnp.concatenate(
            [tokens_sorted, jnp.zeros((t_pad - t, d), tokens_sorted.dtype)]
        )

    def im_tok(e_i, c_i, off, size):
        return (off[e_i] // block_c + c_i, 0)

    def im_out(e_i, c_i, off, size):
        return (e_i, c_i, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(e, n_blocks),
        in_specs=[pl.BlockSpec((block_c, d), im_tok)],
        out_specs=pl.BlockSpec((1, block_c, d), im_out),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_c=block_c, d=d),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, capacity, d), tokens_sorted.dtype),
        interpret=resolve_interpret(interpret),
    )(
        group_offsets.astype(jnp.int32),
        group_sizes.astype(jnp.int32),
        tokens_sorted,
    )
    return out
