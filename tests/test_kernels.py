"""Pallas kernel tests: shape/dtype sweeps + hypothesis property tests,
all asserting allclose against the pure-jnp ref.py oracles (interpret
mode — the kernel bodies execute on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


# --------------------------------------------------------------------------
# shuffle_reduce
# --------------------------------------------------------------------------


@pytest.mark.parametrize("n,v", [(64, 16), (1000, 300), (4096, 512), (513, 1024), (7, 5)])
@pytest.mark.parametrize("op", ["+", "min", "max"])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_shuffle_reduce_sweep(n, v, op, dtype):
    idx = jnp.asarray(RNG.integers(0, v, n).astype(np.int32))
    vals = jnp.asarray(RNG.integers(-50, 50, n).astype(dtype))
    got = ops.shuffle_reduce(vals, idx, v, op)
    want = ref.shuffle_reduce_ref(vals, idx, v, op)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 2000),
    v=st.integers(1, 700),
    op=st.sampled_from(["+", "min", "max"]),
    seed=st.integers(0, 2**31 - 1),
)
def test_shuffle_reduce_property(n, v, op, seed):
    r = np.random.default_rng(seed)
    idx = jnp.asarray(r.integers(0, v, n).astype(np.int32))
    vals = jnp.asarray(r.normal(size=n).astype(np.float32))
    got = ops.shuffle_reduce(vals, idx, v, op)
    want = ref.shuffle_reduce_ref(vals, idx, v, op)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=1e-5)


def test_shuffle_reduce_empty_bins():
    """Bins receiving no update hold the reduction identity."""
    idx = jnp.asarray([2, 2, 2], jnp.int32)
    vals = jnp.asarray([1.0, 2.0, 3.0], jnp.float32)
    out = np.asarray(ops.shuffle_reduce(vals, idx, 5, "min"))
    assert out[2] == 1.0 and np.isinf(out[0]) and np.isinf(out[4])


def test_interpret_default_follows_the_backend():
    """Left out, ``interpret`` compiles on a TPU and interprets elsewhere,
    by the same rule as ``Target.interpret_effective``."""
    from repro.core.target import Target

    auto = jax.default_backend() != "tpu"
    assert ops.resolve_interpret(None) is auto
    assert Target().interpret_effective is auto
    assert ops.resolve_interpret(True) is True
    assert ops.resolve_interpret(False) is False


# --------------------------------------------------------------------------
# edge_stream (fused gather->apply->shuffle->reduce)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("e,v", [(128, 32), (3000, 400), (5000, 123)])
@pytest.mark.parametrize("apply_op", ["add", "mul", "src"])
@pytest.mark.parametrize("reduce_op", ["+", "min", "max"])
def test_edge_stream_sweep(e, v, apply_op, reduce_op):
    sv = jnp.asarray(RNG.normal(size=e).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=e).astype(np.float32))
    dst = jnp.asarray(RNG.integers(0, v, e).astype(np.int32))
    act = jnp.asarray(RNG.random(e) < 0.4)
    got = ops.edge_stream(sv, w, dst, act, v, apply_op, reduce_op)
    want = ref.edge_stream_ref(sv, w, dst, act, v, apply_op, reduce_op)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(e=st.integers(1, 1500), v=st.integers(1, 300), seed=st.integers(0, 2**31 - 1))
def test_edge_stream_property(e, v, seed):
    r = np.random.default_rng(seed)
    sv = jnp.asarray(r.normal(size=e).astype(np.float32))
    w = jnp.asarray(r.normal(size=e).astype(np.float32))
    dst = jnp.asarray(r.integers(0, v, e).astype(np.int32))
    act = jnp.asarray(r.random(e) < 0.5)
    got = ops.edge_stream(sv, w, dst, act, v, "add", "min")
    want = ref.edge_stream_ref(sv, w, dst, act, v, "add", "min")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------
# moe dispatch
# --------------------------------------------------------------------------


@pytest.mark.parametrize("e,c,d,bc", [(8, 256, 64, 128), (4, 128, 32, 128), (16, 512, 128, 128)])
def test_moe_gather_sweep(e, c, d, bc):
    sizes = np.minimum(RNG.multinomial(e * c // 2, np.ones(e) / e), c).astype(np.int32)
    aligned = ((sizes + bc - 1) // bc) * bc
    offs = np.zeros(e, np.int32)
    offs[1:] = np.cumsum(aligned)[:-1]
    tbuf = int(offs[-1] + aligned[-1])
    tok = jnp.asarray(RNG.normal(size=(tbuf, d)).astype(np.float32))
    got = ops.moe_gather(tok, jnp.asarray(offs), jnp.asarray(sizes), c)
    want = ref.moe_gather_ref(tok, jnp.asarray(offs), jnp.asarray(sizes), c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_moe_scatter_roundtrip():
    e, c, d = 4, 128, 32
    sizes = jnp.asarray([100, 17, 0, 128], jnp.int32)
    offs = jnp.asarray([0, 128, 256, 384], jnp.int32)
    tok = jnp.asarray(RNG.normal(size=(640, d)).astype(np.float32))
    binned = ref.moe_gather_ref(tok, offs, sizes, c)
    back = ref.moe_scatter_ref(binned, offs, sizes, 640)
    # rows inside groups round-trip; padding rows are zero
    for ei in range(e):
        o, s = int(offs[ei]), int(sizes[ei])
        np.testing.assert_allclose(np.asarray(back[o : o + s]), np.asarray(tok[o : o + s]))


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------


@pytest.mark.parametrize("b,h,hkv,lq,lk,dh", [
    (1, 2, 2, 64, 64, 32),
    (2, 4, 2, 128, 128, 64),
    (1, 4, 1, 1, 256, 64),  # decode shape
    (1, 2, 2, 100, 100, 32),  # ragged
])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 48)])
def test_flash_attention_sweep(b, h, hkv, lq, lk, dh, causal, window):
    q = jnp.asarray(RNG.normal(size=(b, h, lq, dh)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(b, hkv, lk, dh)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(b, hkv, lk, dh)).astype(np.float32))
    got = ops.flash_attention(q, k, v, causal=causal, window=window, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    b, h, l, dh = 1, 2, 128, 64
    q = jnp.asarray(RNG.normal(size=(b, h, l, dh)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(b, h, l, dh)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(b, h, l, dh)), jnp.bfloat16)
    got = ops.flash_attention(q, k, v, causal=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=3e-2, atol=3e-2
    )
