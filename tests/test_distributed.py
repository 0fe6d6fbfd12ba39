"""Multi-device tests (subprocess with forced host device counts):
sharding specs, distributed graph engine, compressed all-reduce,
sharded train step, and a small dry-run cell."""
import numpy as np
import pytest

from repro.distributed import sharding as shardlib


# --------------------------------------------------------------------------
# pure spec logic (no devices needed)
# --------------------------------------------------------------------------


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_spec_for_divisibility_fallback():
    mesh = _FakeMesh({"data": 4, "model": 8})
    s = shardlib.spec_for(mesh, (16, 24), ("embed", "mlp"))
    assert s == __import__("jax").sharding.PartitionSpec("data", "model")
    s2 = shardlib.spec_for(mesh, (16, 27), ("embed", "mlp"))  # 27 % 8 != 0
    assert s2[1] is None
    s3 = shardlib.spec_for(mesh, (15, 24), ("embed", "mlp"))  # 15 % 4 != 0
    assert s3[0] is None


def test_spec_for_no_duplicate_axis():
    mesh = _FakeMesh({"data": 4, "model": 8})
    s = shardlib.spec_for(mesh, (8, 16, 24), ("experts", "embed", "mlp"))
    flat = [a for a in s if a is not None]
    exp = []
    for a in flat:
        exp += [a] if isinstance(a, str) else list(a)
    assert len(exp) == len(set(exp))


def test_spec_for_missing_mesh_axis():
    mesh = _FakeMesh({"data": 4})  # no 'model' axis (e.g. DP-only mesh)
    s = shardlib.spec_for(mesh, (16, 24), ("embed", "mlp"))
    assert s == __import__("jax").sharding.PartitionSpec("data", None)


# --------------------------------------------------------------------------
# multi-device subprocess tests
# --------------------------------------------------------------------------


def test_distributed_graph_push(subproc):
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.graph import generators
from repro.core.dist_engine import partition_graph, make_push_step
g = generators.power_law(300, 2500, seed=5, weighted=True)
mesh = jax.make_mesh((8,), ("data",))
dg = partition_graph(g, mesh)
deg = np.maximum(g.out_degree, 1).astype(np.float32)
rank = np.random.default_rng(0).random(g.n_vertices).astype(np.float32)
prop = np.zeros(dg.n_vertices_padded, np.float32); prop[:g.n_vertices] = rank / deg
step = make_push_step(dg, lambda sv, w: sv, "+")
with mesh:
    out = np.asarray(step(jnp.asarray(prop)))
want = np.zeros_like(prop)
np.add.at(want, g.dst, rank[g.src] / deg[g.src])
np.testing.assert_allclose(out[:g.n_vertices], want[:g.n_vertices], rtol=1e-4)
step2 = make_push_step(dg, lambda sv, w: sv + w, "min")
sp = np.full(dg.n_vertices_padded, np.inf, np.float32)
sp[:g.n_vertices] = np.random.default_rng(1).integers(0, 50, g.n_vertices)
with mesh:
    out2 = np.asarray(step2(jnp.asarray(sp)))
want2 = np.full_like(sp, np.inf)
np.minimum.at(want2, g.dst, sp[g.src] + g.weights)
np.testing.assert_allclose(out2[:g.n_vertices], want2[:g.n_vertices], rtol=1e-5)
print("dist push ok")
"""
    )
    assert "dist push ok" in out


def test_compressed_allreduce(subproc):
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.distributed.compression import compressed_allreduce
mesh = jax.make_mesh((8,), ("data",))
x = np.random.default_rng(0).normal(size=(8, 64)).astype(np.float32)
def f(shard):
    return compressed_allreduce(shard[0], "data")[None]
g = shard_map(f, mesh=mesh, in_specs=(P("data", None),), out_specs=P("data", None))
with mesh:
    got = np.asarray(jax.jit(g)(jnp.asarray(x)))
want = x.mean(axis=0)
# int8 compression: ~1% relative error on the mean is acceptable
err = np.abs(got - want[None]).max() / (np.abs(want).max() + 1e-9)
assert err < 0.05, err
print("compressed ar ok", err)
"""
    )
    assert "compressed ar ok" in out


def test_sharded_train_step_matches_single_device(subproc):
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import smoke_config
from repro.models import Model
from repro.models.layers import set_sharding_rules
from repro.distributed import sharding as shardlib
from repro.train import OptConfig, init_state, make_train_step
from repro.data import SyntheticLM

cfg = smoke_config('qwen3-0.6b')
ocfg = OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
data = SyntheticLM(cfg, 32, 8, seed=0)
batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}

# single device reference
m1 = Model(cfg, dtype=jnp.float32)
p1 = m1.init(jax.random.PRNGKey(0))
s1 = init_state(p1, ocfg)
p1b, _, met1 = jax.jit(make_train_step(m1, ocfg))(p1, s1, batch)

# 2x4 mesh
mesh = jax.make_mesh((2, 4), ("data", "model"))
set_sharding_rules({k: shardlib._present(mesh, v) for k, v in shardlib.LOGICAL_RULES.items()}, dict(mesh.shape))
m2 = Model(cfg, dtype=jnp.float32)
p2 = m2.init(jax.random.PRNGKey(0))
psh = shardlib.shardings_of(mesh, shardlib.param_pspecs(mesh, jax.eval_shape(lambda: p2), m2.param_specs()))
with mesh:
    p2 = jax.tree.map(lambda x, s: jax.device_put(x, s), p2, psh)
    s2 = init_state(p2, ocfg)
    p2b, _, met2 = jax.jit(make_train_step(m2, ocfg))(p2, s2, batch)
assert abs(float(met1['loss']) - float(met2['loss'])) < 2e-3, (float(met1['loss']), float(met2['loss']))
for a, b in zip(jax.tree.leaves(p1b), jax.tree.leaves(p2b)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-2, atol=5e-4)
print("sharded == single ok")
""",
        devices=8,
        timeout=420,
    )
    assert "sharded == single ok" in out


@pytest.mark.slow
def test_dryrun_small_cell(subproc):
    """One real dry-run cell on a reduced 4x4 host mesh equivalent —
    exercises the production dryrun code path end-to-end."""
    out = subproc(
        """
import repro.launch.dryrun as dr
res = dr.run_cell('xlstm-125m', 'decode_32k', multi_pod=False, phase='gate', verbose=False)
assert res.get('ok'), res
print('cell ok', res['gate']['memory_analysis'].get('argument_size_in_bytes', 0) > 0)
""",
        devices=512,
        timeout=420,
    )
    assert "cell ok" in out


def test_perf_toggles_numerically_equivalent(subproc):
    """The §Perf sharding toggles (chunked attention, 2D batchxseq
    sharding) must not change results under SPMD."""
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import smoke_config
from repro.models import Model
from repro.models.layers import set_sharding_rules
from repro.distributed import sharding as shardlib

cfg = smoke_config('qwen2-vl-2b')
mesh = jax.make_mesh((2, 4), ("data", "model"))
toks = jax.random.randint(jax.random.PRNGKey(0), (4, 64), 0, cfg.vocab_size)
batch = {"embeds": jax.random.normal(jax.random.PRNGKey(1), (4, 64, cfg.d_model), jnp.float32)}

outs = {}
for name, mkw, seq_rule in [
    ("naive", dict(), None),
    ("chunked", dict(attn_impl="chunked"), None),
    ("sp2d", dict(attn_seq_parallel=True), "model"),
]:
    rules = dict(shardlib.LOGICAL_RULES)
    if seq_rule:
        rules["seq"] = seq_rule
    set_sharding_rules({k: shardlib._present(mesh, v) for k, v in rules.items()},
                       dict(mesh.shape))
    m = Model(cfg, dtype=jnp.float32, **mkw)
    if name == "chunked":
        m.attn_impl = "chunked"
        # exercise the chunk path: chunk smaller than seq
        import repro.models.attention as A
    params = m.init(jax.random.PRNGKey(2))
    psh = shardlib.shardings_of(mesh, shardlib.param_pspecs(mesh, jax.eval_shape(lambda: params), m.param_specs()))
    with mesh:
        p = jax.tree.map(lambda x, s: jax.device_put(x, s), params, psh)
        logits, _ = jax.jit(m.forward)(p, batch)
        outs[name] = np.asarray(logits)
    set_sharding_rules(None)

np.testing.assert_allclose(outs["chunked"], outs["naive"], rtol=2e-4, atol=2e-5)
np.testing.assert_allclose(outs["sp2d"], outs["naive"], rtol=2e-4, atol=2e-5)
print("toggles equivalent ok")
""",
        devices=8,
        timeout=420,
    )
    assert "toggles equivalent ok" in out
