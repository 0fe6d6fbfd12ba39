"""Pallas TPU kernel: fused edge pipeline (Burst Read -> Apply -> Shuffle ->
Reduce -> Burst Write), the whole of paper Fig. 4 step 1-6 as one kernel.

Layout contract (prepared by the caller / DSL back-end):
* edges are sorted by destination (the static shuffle routing);
* the source-side operand is pre-gathered into a stream (``src_vals``) —
  on TPU the hub-cache split makes this gather cheap: hot vertices hit a
  VMEM-resident prefix, cold ones are bulk HBM gathers;
* the kernel streams (src_vals, weights, dst, active) tiles HBM->VMEM
  (automatically double-buffered: the Burst Read + pipelining optimization),
  applies the edge operation, and reduces conflict-free into the
  VMEM-resident destination partition via a one-hot contraction.

Grid = (P, T) with clamped tile index maps exactly as in shuffle_reduce.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import resolve_interpret
from .ref import _identity


def _kernel(
    tile_lo_ref, tile_hi_ref,
    sv_ref, w_ref, dst_ref, act_ref, out_ref,
    *, apply_op: str, reduce_op: str, u: int, et: int,
):
    p = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.full((1, u), _identity(reduce_op, out_ref.dtype))

    in_range = jnp.logical_and(t >= tile_lo_ref[p], t <= tile_hi_ref[p])

    @pl.when(in_range)
    def _accum():
        sv = sv_ref[0, :]
        w = w_ref[0, :]
        dst = dst_ref[0, :]
        act = act_ref[0, :]
        # -- Edge Operation (user apply function) --
        if apply_op == "add":
            upd = sv + w
        elif apply_op == "mul":
            upd = sv * w
        else:  # 'src'
            upd = sv
        ident = _identity(reduce_op, out_ref.dtype)
        upd = jnp.where(act, upd.astype(out_ref.dtype), ident)
        # -- Shuffle + RAW-free Reduce --
        local = dst - p * u
        lanes = jax.lax.broadcasted_iota(jnp.int32, (et, u), 1)
        onehot = local[:, None] == lanes
        if reduce_op == "+" and jnp.issubdtype(out_ref.dtype, jnp.floating):
            masked = jnp.where(onehot, upd[:, None], 0).astype(jnp.float32)
            out_ref[0, :] = out_ref[0, :] + jnp.sum(masked, axis=0).astype(out_ref.dtype)
        else:
            spread = jnp.where(onehot, upd[:, None], ident)
            if reduce_op == "+":
                out_ref[0, :] = out_ref[0, :] + jnp.sum(spread, axis=0)
            elif reduce_op == "min":
                out_ref[0, :] = jnp.minimum(out_ref[0, :], jnp.min(spread, axis=0))
            else:
                out_ref[0, :] = jnp.maximum(out_ref[0, :], jnp.max(spread, axis=0))


@functools.partial(
    jax.jit,
    static_argnames=("n_out", "apply_op", "reduce_op", "u", "et", "interpret"),
)
def edge_stream_call(
    src_vals: jnp.ndarray,
    weights: jnp.ndarray,
    dst: jnp.ndarray,
    active: jnp.ndarray,
    *,
    n_out: int,
    apply_op: str = "add",
    reduce_op: str = "min",
    u: int = 512,
    et: int = 1024,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    n = src_vals.shape[0]
    et = min(et, max(128, 1 << (max(1, n) - 1).bit_length()))
    u = min(u, max(128, 1 << (max(1, n_out) - 1).bit_length()))
    # sort by destination: the static shuffle routing
    perm = jnp.argsort(dst)
    sv, w, ds, ac = src_vals[perm], weights[perm], dst[perm].astype(jnp.int32), active[perm]
    n_pad = ((n + et - 1) // et) * et
    big = jnp.int32(2**31 - 1)

    def pad(x, v):
        if n_pad == n:
            return x
        return jnp.concatenate([x, jnp.full((n_pad - n,), v, x.dtype)])

    sv = pad(sv, 0)
    w = pad(w, 0)
    ds = pad(ds, big)
    ac = pad(ac, False)

    n_out_pad = ((n_out + u - 1) // u) * u
    n_tiles = n_pad // et
    n_parts = n_out_pad // u
    tile_of = ds // u
    first_in_tile = tile_of[::et]
    last_in_tile = jnp.minimum(tile_of, n_parts - 1)[et - 1 :: et]
    parts = jnp.arange(n_parts, dtype=jnp.int32)
    tile_lo = jnp.minimum(
        jnp.searchsorted(last_in_tile, parts, side="left").astype(jnp.int32), n_tiles - 1
    )
    tile_hi = jnp.clip(
        jnp.searchsorted(first_in_tile, parts, side="right").astype(jnp.int32) - 1,
        0,
        n_tiles - 1,
    )

    def im_in(p, t, lo, hi):
        return (0, jnp.clip(t, lo[p], hi[p]))

    def im_out(p, t, lo, hi):
        return (0, p)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_parts, n_tiles),
        in_specs=[
            pl.BlockSpec((1, et), im_in),
            pl.BlockSpec((1, et), im_in),
            pl.BlockSpec((1, et), im_in),
            pl.BlockSpec((1, et), im_in),
        ],
        out_specs=pl.BlockSpec((1, u), im_out),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, apply_op=apply_op, reduce_op=reduce_op, u=u, et=et),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, n_out_pad), src_vals.dtype),
        interpret=resolve_interpret(interpret),
    )(tile_lo, tile_hi, sv[None, :], w[None, :], ds[None, :], ac[None, :])
    return out[0, :n_out]
