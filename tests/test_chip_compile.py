"""Compile the main path's kernels for a described TPU v5e at R19 size.

Nothing runs: each test lowers and compiles one kernel for a v5e chip that
is described, not attached, at the paper's Table II R19 shape (2^24 edges,
2^19 vertices), and checks that the compiler accepts it and that its
memory fits one chip's 16 GB of HBM. This catches what the Pallas
interpreter cannot (tiling, VMEM limits, programs too large for the chip).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro
from repro.algorithms import sources
from repro.core import backend, mir
from repro.core.accelerator import GraphShape, _scalar_specs, _state_specs
from repro.core.target import Target
from repro.kernels import ops

E = 1 << 24  # R19: rmat-19-32
V = 1 << 19
HBM_BYTES = 16 * 10**9  # one TPU v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler library on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_fits(compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert 0 < total <= HBM_BYTES, total


@pytest.mark.parametrize("op,dtype", [("+", jnp.float32), ("min", jnp.int32)])
def test_shuffle_reduce_compiles_for_v5e(one_chip, op, dtype):
    compiled = jax.jit(
        lambda v, i: ops.shuffle_reduce(v, i, V, op, interpret=False)
    ).lower(
        _spec((E,), dtype, one_chip), _spec((E,), jnp.int32, one_chip)
    ).compile()
    _assert_fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()


def test_edge_stream_compiles_for_v5e(one_chip):
    compiled = jax.jit(
        lambda sv, w, d, a: ops.edge_stream(
            sv, w, d, a, V, "add", "min", interpret=False)
    ).lower(
        _spec((E,), jnp.float32, one_chip), _spec((E,), jnp.float32, one_chip),
        _spec((E,), jnp.int32, one_chip), _spec((E,), jnp.bool_, one_chip),
    ).compile()
    _assert_fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()


def test_bfs_edge_kernel_compiles_for_v5e(one_chip):
    """The BFS ECP edge kernel as the Accelerator lowers it (graph bindings,
    state and scalars as arguments), on the described chip."""
    module = repro.compile(sources.BFS_ECP).module
    kern = module.kernels["EdgeTraversal"]
    assert kern.kind is mir.KernelKind.EDGE
    lowered = backend.lower_kernel_generic(module, kern, V, E, Target())
    shape = GraphShape(n_vertices=V, n_edges=E)
    on_chip = lambda s: _spec(s.shape, s.dtype, one_chip)
    specs = [
        jax.tree.map(on_chip, tree) for tree in (
            backend.gb_array_specs(V, E), _state_specs(module, shape),
            _scalar_specs(module, kern),
        )
    ]
    compiled = lowered.jit_full.lower(*specs).compile()
    _assert_fits(compiled)


def test_msbfs_step_compiles_for_v5e(one_chip):
    """One level of the batched multi-source BFS for an 8-root batch."""
    from repro.batch.msbfs import msbfs_step

    k = 8
    compiled = msbfs_step.lower(
        _spec((V, 1), jnp.uint32, one_chip), _spec((V, 1), jnp.uint32, one_chip),
        _spec((k, V), jnp.int32, one_chip), _spec((), jnp.int32, one_chip),
        _spec((E,), jnp.int32, one_chip), _spec((E,), jnp.int32, one_chip),
        k=k,
    ).compile()
    _assert_fits(compiled)
