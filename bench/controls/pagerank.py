"""Control of the PageRank comparison: the reference in bfloat16.

The configuration states float32 ranks; the nearest precision below is
bfloat16. Ranks, contributions and their sums are held in bfloat16 on the
device, in the same arc order as the float64 reference."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def answer(arcs, params: dict) -> np.ndarray:
    iters, damp = int(params["iters"]), float(params["damp"])
    n = arcs.n
    bf = jnp.bfloat16
    src, dst = jnp.asarray(arcs.src), jnp.asarray(arcs.dst)
    inv_deg = (1.0 / jnp.maximum(jnp.asarray(arcs.out_degree, jnp.float32),
                                 1.0)).astype(bf)

    @jax.jit
    def run(src, dst, inv_deg):
        def step(_, rank):
            contrib = jax.ops.segment_sum((rank * inv_deg)[src], dst,
                                          num_segments=n)
            return (jnp.asarray((1.0 - damp) / n, bf)
                    + jnp.asarray(damp, bf) * contrib).astype(bf)

        return jax.lax.fori_loop(0, iters, step, jnp.full((n,), 1.0 / n, bf))

    return np.asarray(jax.device_get(run(src, dst, inv_deg)), np.float64)
