"""Training substrate tests: optimizers, schedules, microbatching,
quantization properties, straggler monitor, data determinism."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import smoke_config
from repro.data import SyntheticLM
from repro.distributed.compression import StragglerMonitor
from repro.models import Model
from repro.train import OptConfig, init_state, make_train_step
from repro.train import optimizer as opt_mod


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config("qwen3-0.6b")
    m = Model(cfg, dtype=jnp.float32)
    params = m.init(jax.random.PRNGKey(0))
    data = SyntheticLM(cfg, seq_len=64, global_batch=8, seed=0)
    return cfg, m, params, data


@pytest.mark.parametrize("quantized", [False, True])
def test_training_reduces_loss(setup, quantized):
    cfg, m, params, data = setup
    ocfg = OptConfig(lr=3e-3, warmup_steps=5, total_steps=60, quantized=quantized)
    st_ = init_state(params, ocfg)
    ts = jax.jit(make_train_step(m, ocfg, n_microbatches=2))
    p = params
    l0 = lN = None
    for i in range(30):
        b = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
        p, st_, metrics = ts(p, st_, b)
        if i == 0:
            l0 = float(metrics["loss"])
        lN = float(metrics["loss"])
    assert lN < l0 - 0.2, f"no learning: {l0} -> {lN}"


def test_microbatch_equivalence(setup):
    """Accumulated microbatch gradients == single-shot gradients on the
    same global batch (Adam's sqrt(v) step-1 sensitivity makes post-update
    params ill-conditioned for comparison, so compare the grads)."""
    cfg, m, params, data = setup
    b = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    grad_fn = jax.jit(jax.grad(lambda p, mb: m.loss(p, mb)[0]))
    g1 = grad_fn(params, b)
    nm = 4
    mbs = jax.tree.map(
        lambda x: x.reshape((nm, x.shape[0] // nm) + x.shape[1:]), b
    )
    acc = jax.tree.map(lambda p: np.zeros(p.shape, np.float32), params)
    for i in range(nm):
        gi = grad_fn(params, jax.tree.map(lambda x: x[i], mbs))
        acc = jax.tree.map(lambda a, g: a + np.asarray(g, np.float32), acc, gi)
    acc = jax.tree.map(lambda g: g / nm, acc)
    for a, b_ in zip(jax.tree.leaves(g1), jax.tree.leaves(acc)):
        np.testing.assert_allclose(np.asarray(a), b_, rtol=1e-3, atol=1e-6)


def test_lr_schedule_shape():
    ocfg = OptConfig(lr=1e-3, warmup_steps=10, total_steps=100, min_lr_ratio=0.1)
    lrs = [float(opt_mod.lr_schedule(ocfg, jnp.int32(s))) for s in [0, 5, 10, 50, 100]]
    assert lrs[0] == 0.0
    assert lrs[1] == pytest.approx(5e-4)
    assert lrs[2] == pytest.approx(1e-3, rel=0.2)
    assert lrs[3] < lrs[2]
    assert lrs[4] == pytest.approx(1e-4, rel=0.05)


def test_grad_clipping():
    ocfg = OptConfig(lr=1.0, grad_clip=1.0, warmup_steps=0, total_steps=1,
                     weight_decay=0.0)
    params = {"w": jnp.zeros((4,), jnp.float32)}
    grads = {"w": jnp.full((4,), 100.0)}
    st_ = init_state(params, ocfg)
    new_p, _ = opt_mod.apply_updates(params, grads, st_, ocfg)
    # clipped global norm = 1 -> per-element grad 0.5 -> adam update ~ lr
    assert np.all(np.isfinite(np.asarray(new_p["w"])))
    assert np.abs(np.asarray(new_p["w"])).max() < 2.0


@settings(max_examples=30, deadline=None)
@given(
    n=st.integers(1, 2000),
    power=st.sampled_from([2, 4]),
    seed=st.integers(0, 2**31 - 1),
    scale=st.floats(1e-6, 1e4),
)
def test_quantization_error_bound(n, power, seed, scale):
    """Nonlinear int8 code: per-element error <= power/127 * blockmax."""
    r = np.random.default_rng(seed)
    x = (r.normal(size=n) * scale).astype(np.float32)
    if power == 4:
        x = np.abs(x)
    q, s = opt_mod._quant(jnp.asarray(x), power=power)
    back = np.asarray(opt_mod._dequant(q, s, x.shape, power=power))
    blocks = opt_mod._blocks(jnp.asarray(x))
    bmax = np.maximum(np.asarray(jnp.max(jnp.abs(blocks), axis=1)), 1e-20)
    tol = (power / 127.0) * np.repeat(bmax, opt_mod.QBLOCK)[:n] + 1e-12
    assert (np.abs(back - x) <= tol).all()


def test_quantization_preserves_sign_and_zero():
    x = jnp.asarray([-1.0, 0.0, 1e-9, 5.0], jnp.float32)
    q, s = opt_mod._quant(x, power=2)
    back = np.asarray(opt_mod._dequant(q, s, x.shape, power=2))
    assert back[0] < 0 and back[1] == 0 and back[3] > 0


def test_straggler_monitor():
    mon = StragglerMonitor(threshold=2.0, warmup=3)
    for _ in range(10):
        assert not mon.record(1.0)
    assert mon.record(5.0)  # 5x EWMA -> flagged
    assert mon.flags[-1]["action"] == "rebalance-or-replace"
    assert not mon.record(1.0)  # EWMA not poisoned by the straggler
    assert mon.ewma == pytest.approx(1.0, rel=0.05)


def test_data_determinism_and_restart_safety(setup):
    cfg, _, _, _ = setup
    d1 = SyntheticLM(cfg, 32, 4, seed=3)
    d2 = SyntheticLM(cfg, 32, 4, seed=3)
    b1 = d1.batch(17)
    b2 = d2.batch(17)  # a "restarted job" regenerating step 17
    for k in b1:
        np.testing.assert_array_equal(b1[k], b2[k])
    b3 = d1.batch(18)
    assert not np.array_equal(b1["labels"], b3["labels"])


def test_data_sharding_partitions_batch(setup):
    cfg, _, _, _ = setup
    d = SyntheticLM(cfg, 32, 8, seed=4)
    full_rows = [d.batch(5, shard=s, shards=4)["labels"] for s in range(4)]
    assert all(r.shape[0] == 2 for r in full_rows)
    # distinct shards see distinct data
    assert not np.array_equal(full_rows[0], full_rows[1])
