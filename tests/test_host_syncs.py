"""Host syncs and kernel names: what a profiler trace of a run can be read by.

* every device->host read of the interpreters goes through one helper and
  is counted in ``EngineStats.host_syncs`` / ``host_sync_bytes``, exactly:
  the counts below are derived from the program's host loop (levels or
  iterations) and the properties read back, for the sequential engine and
  the batch engine, with tracing off;
* every jitted kernel carries its Graphitron name, so its HLO module reads
  ``jit_gt_<kernel>_<full|subset|batched>``, ``jit_gt_frontier_build`` or
  ``jit_gt_msbfs_step``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import telemetry
from repro.algorithms import sources
from repro.batch.engine import BatchEngine
from repro.batch.msbfs import msbfs_step
from repro.core import backend
from repro.core.accelerator import _scalar_specs, _state_specs
from repro.graph import generators

BFS_PROPS = 4  # old_level, new_level, tuple, activeVertex
PAGERANK_PROPS = 3  # rank, contrib, deg
ROOTS = [0, 3, 17]


@pytest.fixture(scope="module")
def graph():
    return generators.power_law(300, 2400, seed=2)


@pytest.fixture(autouse=True)
def untraced():
    telemetry.disable()
    yield
    telemetry.disable()


def _int_bytes(n):
    return 4 * n


# --------------------------------------------------------------------------
# the sequential engine
# --------------------------------------------------------------------------


@pytest.mark.parametrize("lowering", ["plain", "library"])
@pytest.mark.parametrize("root", ROOTS)
def test_bfs_host_syncs_exact(graph, lowering, root):
    program = repro.compile(sources.BFS_ECP)
    if lowering == "plain":
        session = program.bind(graph)
    else:
        session = program.lower(repro.Target(),
                                shape=repro.GraphShape.of(graph)).bind(graph)
    res = session.run(root=root)
    st = res.stats
    n_v = session.backend.engine.graph.n_vertices
    levels = st.host_iterations
    # per level: the EdgeTraversal frontier mask (old_level, int32 [V]) and
    # `frontier_size = activeVertex[0]`; then every property read back
    assert st.host_syncs == 2 * levels + BFS_PROPS
    assert st.host_sync_bytes == (levels * _int_bytes(n_v) + levels * 4
                                  + BFS_PROPS * _int_bytes(n_v))
    assert st.kernel_launches["EdgeTraversal"] == levels


@pytest.mark.parametrize("iters", [1, 5])
def test_pagerank_host_syncs_exact(graph, iters):
    res = repro.compile(sources.PAGERANK).bind(graph).run(iters=iters)
    st = res.stats
    n_v = res.properties["rank"].shape[0]
    # the loop reads nothing back: only the final properties
    assert st.host_iterations == iters
    assert st.host_syncs == PAGERANK_PROPS
    assert st.host_sync_bytes == PAGERANK_PROPS * _int_bytes(n_v)


def test_host_syncs_count_per_run_not_per_session(graph):
    session = repro.compile(sources.PAGERANK).bind(graph)
    a = session.run(iters=2)
    b = session.run(iters=2)
    assert a.stats is not b.stats
    assert a.stats.host_syncs == b.stats.host_syncs == PAGERANK_PROPS


@pytest.mark.parametrize("batched", [False, True], ids=["engine", "batch"])
def test_readback_is_inside_the_run_wall_time(graph, monkeypatch, batched):
    # the run ends when the answer is on the host: every read, the result
    # readback last, happens before wall_time_s is taken
    from repro.batch import engine as batch_mod
    from repro.core import engine as engine_mod

    seen = []
    real = engine_mod.host_read

    def spy(stats, value, reason):
        seen.append((reason, stats.wall_time_s))
        return real(stats, value, reason)

    monkeypatch.setattr(engine_mod, "host_read", spy)
    monkeypatch.setattr(batch_mod, "host_read", spy)
    program = repro.compile(sources.PAGERANK)
    if batched:
        res = program.bind_batch(graph).run_many([{"iters": 2}] * 2)[0]
    else:
        res = program.bind(graph).run(iters=2)
    assert [r for r, _ in seen] == ["result"] * PAGERANK_PROPS
    assert all(wall == 0.0 for _, wall in seen)
    assert res.stats.wall_time_s > 0


# --------------------------------------------------------------------------
# the batch engine
# --------------------------------------------------------------------------


def test_batched_bfs_vmap_host_syncs_exact(graph):
    k = len(ROOTS)
    res = repro.compile(sources.BFS_ECP).bind_batch(graph, msbfs=False) \
        .run_many([{"root": r} for r in ROOTS])
    st = res[0].stats
    assert BatchEngine.MSBFS_NAME not in st.kernel_launches
    n_v = res[0].properties["old_level"].shape[0]
    iters = st.host_iterations
    # per iteration one [K] scalar read (`activeVertex[0]`); batched
    # launches stream every arc, so no frontier mask; then [K, V] results
    assert st.host_syncs == iters + BFS_PROPS
    assert st.host_sync_bytes == iters * _int_bytes(k) \
        + BFS_PROPS * _int_bytes(k * n_v)


def test_batched_bfs_msbfs_host_syncs_exact(graph):
    k = len(ROOTS)
    res = repro.compile(sources.BFS_ECP).bind_batch(graph) \
        .run_many([{"root": r} for r in ROOTS])
    st = res[0].stats
    assert BatchEngine.MSBFS_NAME in st.kernel_launches
    n_v = res[0].properties["old_level"].shape[0]
    steps = st.host_iterations
    # per packed step one `any new` flag (bool); the levels [K, V] once;
    # then the reconstructed [K, V] properties
    assert st.host_syncs == steps + 1 + BFS_PROPS
    assert st.host_sync_bytes == steps + _int_bytes(k * n_v) \
        + BFS_PROPS * _int_bytes(k * n_v)


def test_batched_pagerank_host_syncs_exact(graph):
    k = 4
    res = repro.compile(sources.PAGERANK).bind_batch(graph) \
        .run_many([{"iters": 3}] * k)
    st = res[0].stats
    n_v = res[0].properties["rank"].shape[0]
    assert st.host_syncs == PAGERANK_PROPS
    assert st.host_sync_bytes == PAGERANK_PROPS * _int_bytes(k * n_v)


def test_msbfs_first_touch_counts_as_compile(graph):
    # a fresh K compiles the packed step: timed into compile_time_s
    # through the engine's warm-key registry, and warm on the next batch
    k = 5
    session = repro.compile(sources.BFS_ECP).bind_batch(graph)
    sets = [{"root": r} for r in range(k)]
    first = session.run_many(sets)[0].stats
    again = session.run_many(sets)[0].stats
    assert BatchEngine.MSBFS_NAME in first.kernel_launches
    assert first.compile_time_s > 0
    warm = session.engine.engine._warm_keys
    assert any(key[:2] == ("msbfs", k) for key in warm)
    assert again.compile_time_s == 0.0


# --------------------------------------------------------------------------
# kernel names in the lowered programs
# --------------------------------------------------------------------------


def _module_name(lowered) -> str:
    first = lowered.as_text().splitlines()[0]
    return first.split("@", 1)[1].split(" ", 1)[0]


@pytest.fixture(scope="module")
def bfs_library(graph):
    acc = repro.compile(sources.BFS_ECP).lower(
        repro.Target(), shape=repro.GraphShape.of(graph))
    return acc.library


@pytest.mark.parametrize("src", [sources.BFS_ECP, sources.PAGERANK],
                         ids=["bfs", "pagerank"])
def test_generic_full_kernels_named(graph, src):
    acc = repro.compile(src).lower(repro.Target(),
                                   shape=repro.GraphShape.of(graph))
    lib = acc.library
    gb = backend.gb_array_specs(lib.shape.n_vertices, lib.shape.n_edges)
    state = _state_specs(lib.module, lib.shape)
    assert lib._generic
    for name, g in lib._generic.items():
        scal = _scalar_specs(lib.module, lib.module.kernels[name])
        assert _module_name(g.jit_full.lower(gb, state, scal)) \
            == f"jit_gt_{name}_full"


def test_generic_subset_and_batched_named(graph, bfs_library):
    lib = bfs_library
    g = lib._generic["EdgeTraversal"]
    gb = backend.gb_array_specs(lib.shape.n_vertices, lib.shape.n_edges)
    state = _state_specs(lib.module, lib.shape)
    scal = _scalar_specs(lib.module, lib.module.kernels["EdgeTraversal"])
    pad = 1024
    batch = (jax.ShapeDtypeStruct((pad,), jnp.int32),) * 2 + (
        jax.ShapeDtypeStruct((pad,), jnp.float32),
        jax.ShapeDtypeStruct((pad,), jnp.int32),
        jax.ShapeDtypeStruct((pad,), jnp.bool_),
    )
    assert _module_name(g.jit_subset.lower(gb, state, scal, batch)) \
        == "jit_gt_EdgeTraversal_subset"
    lib.batched_for("EdgeTraversal",
                    backend._graph_bindings(graph, lib.module, lib.target))
    k = 2
    kstate = {n: jax.ShapeDtypeStruct((k,) + s.shape, s.dtype)
              for n, s in state.items()}
    kscal = {n: jax.ShapeDtypeStruct((k,), s.dtype) for n, s in scal.items()}
    assert _module_name(g.jit_batched.lower(gb, kstate, kscal)) \
        == "jit_gt_EdgeTraversal_batched"


@pytest.mark.parametrize("which", ["full", "subset", "batched"])
def test_plain_lowering_kernels_named(graph, which):
    session = repro.compile(sources.BFS_ECP).bind(graph)
    eng = session.backend.engine
    lk = eng._kernel("EdgeTraversal")
    state = {n: jax.ShapeDtypeStruct(a.shape, a.dtype)
             for n, a in eng.state.items()}
    scal = {s: jax.ShapeDtypeStruct((), jnp.int32) for s in ("level",)}
    if which == "full":
        lowered = lk.run_full.lower(state, scal)
    elif which == "subset":
        pad = 1024
        batch = (jax.ShapeDtypeStruct((pad,), jnp.int32),) * 2 + (
            jax.ShapeDtypeStruct((pad,), jnp.float32),
            jax.ShapeDtypeStruct((pad,), jnp.int32),
            jax.ShapeDtypeStruct((pad,), jnp.bool_),
        )
        lowered = lk.run_subset.lower(state, scal, batch)
    else:
        fn = backend.lower_kernel_batched(lk)
        kstate = {n: jax.ShapeDtypeStruct((2,) + s.shape, s.dtype)
                  for n, s in state.items()}
        kscal = {n: jax.ShapeDtypeStruct((2,), s.dtype)
                 for n, s in scal.items()}
        lowered = fn.lower(kstate, kscal)
    assert _module_name(lowered) == f"jit_gt_EdgeTraversal_{which}"


def test_frontier_builder_and_msbfs_step_named():
    build = backend.make_frontier_builder(64, 256, weighted=False)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    lowered = build.lower(i32((64,)), i32((64,)), i32((256,)), i32((256,)),
                          jax.ShapeDtypeStruct((64,), jnp.bool_),
                          jax.ShapeDtypeStruct((1,), jnp.float32),
                          pad_v=1024, pad_e=1024)
    assert _module_name(lowered) == "jit_gt_frontier_build"
    u32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.uint32)
    lowered = msbfs_step.lower(u32((64, 1)), u32((64, 1)), i32((3, 64)),
                               i32(()), i32((256,)), i32((256,)), k=3)
    assert _module_name(lowered) == "jit_gt_msbfs_step"


def test_untraced_counts_without_annotations(graph, monkeypatch):
    # tracing off: no profiler annotation is opened, and the counters count
    from repro.telemetry import tracer as tracer_mod

    def refuse(name):
        raise AssertionError(f"annotation {name!r} opened while untraced")

    monkeypatch.setattr(tracer_mod, "_annotation", refuse)
    assert not telemetry.enabled()
    res = repro.compile(sources.BFS_ECP).bind(graph).run(root=3)
    assert res.trace is None
    assert res.stats.host_syncs == 2 * res.stats.host_iterations + BFS_PROPS
    assert res.stats.host_sync_bytes > 0
    np.testing.assert_array_equal(res.properties["old_level"][3], 1)
