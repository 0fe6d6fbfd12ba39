"""Kronecker (R-MAT / Graph500) graphs, generated on the device from a seed.

Each arc picks one quadrant of the adjacency matrix per bit of the vertex
id, with probabilities A, B, C and D = 1 - A - B - C: the recursion of
Leskovec et al. that Graph500 and ThunderGP's rmat-19-32 use. Optional
steps follow the Graph500 specification: a random permutation of the
vertex ids (so hubs are not the low ids), a shuffle of the edge list, and
both directions of every edge for an undirected graph.

The graph is drawn with ``jax.random`` in one jitted call and copied to
the host once; the same seed gives the same arcs on every platform.
Configuration keys read (``graph`` in ``bench/configs/<name>.json``):
``scale``, ``edge_factor``, ``a``, ``b``, ``c``, ``permute`` and
``undirected``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.arcs import Arcs


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A threefry key from any whole number (``jax.random.key`` keeps only
    the low 32 bits of a larger seed)."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


@functools.partial(jax.jit, static_argnames=(
    "scale", "m", "a", "b", "c", "permute", "undirected"))
def _draw(key, *, scale, m, a, b, c, permute, undirected):
    k_bits, k_perm, k_shuf = jax.random.split(key, 3)

    def bit(i, carry):
        src, dst = carry
        r = jax.random.uniform(jax.random.fold_in(k_bits, i), (m,))
        down = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        right = r >= a + b
        return (src | (down.astype(jnp.int32) << i),
                dst | (right.astype(jnp.int32) << i))

    zero = jnp.zeros((m,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, bit, (zero, zero))
    if permute:
        perm = jax.random.permutation(k_perm, 1 << scale).astype(jnp.int32)
        src, dst = perm[src], perm[dst]
        order = jax.random.permutation(k_shuf, m)
        src, dst = src[order], dst[order]
    if undirected:
        src, dst = jnp.concatenate([src, dst]), jnp.concatenate([dst, src])
    return src, dst


def generate(spec: dict, seed: int) -> Arcs:
    scale = int(spec["scale"])
    m = int(spec["edge_factor"]) << scale
    key = seed_key(seed)
    src, dst = _draw(
        key, scale=scale, m=m, a=float(spec["a"]), b=float(spec["b"]),
        c=float(spec["c"]), permute=bool(spec["permute"]),
        undirected=bool(spec["undirected"]),
    )
    src, dst = jax.device_get((src, dst))
    return Arcs(1 << scale, np.asarray(src, np.int32),
                np.asarray(dst, np.int32))
