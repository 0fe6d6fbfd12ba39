"""Multi-chip graph processing: the shuffle network generalized across
devices (ForeGraph-style multi-accelerator scaling, expressed in JAX).

Vertices are range-partitioned across D devices; each edge lives on its
**source owner**. One edge-centric superstep is:

1. local gather+apply: every device computes (dst, value) update tuples
   for its edge shard from its local source-property slice;
2. **all_to_all**: tuples are routed to their destination owner — this is
   exactly the paper's shuffle module, with ICI links playing the role of
   the on-chip crossbar (updates were pre-bucketed by dst owner at
   partition time, so the routing is a static all_to_all, not dynamic);
3. local conflict-free reduce (sorted segment reduction) into the local
   destination-property slice — the URAM bank analogue.

:func:`partition_graph` buckets the edges and places each device's slice
on it; :func:`make_expr_push_step` builds one superstep under ``shard_map``
that takes the placed buckets as arguments.

:class:`DistEngine` (bottom of this module) is the full execution backend
built on top of it: it interprets the same host program as the local
:class:`~repro.core.engine.Engine`, but launches every edge kernel whose
body fits the ``src-gather -> dst-scatter-reduce`` shape as a distributed
superstep across the device mesh. Kernels outside that shape (multi-write
bodies, edge-weight mutation, neighbor loops) transparently fall back to
the local lowering, so any program that runs locally runs distributed
with identical results.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import backend, fir, mir
from .engine import Engine
from .options import CompileOptions
from .target import dist_mesh
from .. import telemetry as tel
from ..graph.storage import GraphData


@dataclass
class DistGraph:
    """Edge buckets [D, D, Emax]: axis0 = src owner (sharded), axis1 = dst
    owner (all_to_all routing axis)."""

    n_devices: int
    n_vertices_padded: int  # multiple of D
    src_local: np.ndarray  # [D, D, Emax] source id local to src owner
    dst_local: np.ndarray  # [D, D, Emax] dest id local to dst owner
    weight: np.ndarray  # [D, D, Emax]
    valid: np.ndarray  # [D, D, Emax]
    mesh: Mesh
    axis: str
    _placed: Optional[Tuple[jax.Array, ...]] = field(default=None, repr=False)

    @property
    def slice_len(self) -> int:
        return self.n_vertices_padded // self.n_devices

    def placed(self) -> Tuple[jax.Array, ...]:
        """(src_local, dst_local, weight, valid) on the mesh, sharded on
        the src-owner axis: device i holds the [1, D, Emax] slice of the
        edges it owns. Placed once; every superstep takes them as arguments."""
        if self._placed is None:
            sharding = NamedSharding(self.mesh, P(self.axis))
            self._placed = tuple(
                jax.device_put(a, sharding)
                for a in (self.src_local, self.dst_local, self.weight, self.valid)
            )
        return self._placed


def partition_graph(g: GraphData, mesh: Mesh, axis: str = "data") -> DistGraph:
    d = mesh.shape[axis]
    vpad = ((g.n_vertices + d - 1) // d) * d
    sl = vpad // d
    src_owner = g.src // sl
    dst_owner = g.dst // sl
    emax = 0
    buckets = {}
    for i in range(d):
        for j in range(d):
            sel = np.flatnonzero((src_owner == i) & (dst_owner == j))
            buckets[(i, j)] = sel
            emax = max(emax, len(sel))
    emax = max(1, emax)
    shape = (d, d, emax)
    src_l = np.zeros(shape, np.int32)
    dst_l = np.zeros(shape, np.int32)
    w = np.zeros(shape, np.float32)
    valid = np.zeros(shape, bool)
    for (i, j), sel in buckets.items():
        n = len(sel)
        src_l[i, j, :n] = g.src[sel] - i * sl
        dst_l[i, j, :n] = g.dst[sel] - j * sl
        if g.weights is not None:
            w[i, j, :n] = g.weights[sel]
        valid[i, j, :n] = True
    return DistGraph(d, vpad, src_l, dst_l, w, valid, mesh, axis)


def _identity(op: str, dtype):
    if op == "+":
        return jnp.zeros((), dtype)
    if op == "min":
        return jnp.asarray(
            jnp.iinfo(dtype).max if jnp.issubdtype(dtype, jnp.integer) else jnp.inf, dtype
        )
    return jnp.asarray(
        jnp.iinfo(dtype).min if jnp.issubdtype(dtype, jnp.integer) else -jnp.inf, dtype
    )


def make_push_step(
    dg: DistGraph,
    value_fn: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray],
    reduce_op: str = "+",
):
    """Build a superstep over one property.

    value_fn(src_prop_vals, weights) -> update values (elementwise).
    Returns fn(prop [Vpad]) -> reduced updates [Vpad] (combined with the
    old property by the caller's vertex kernel).
    """
    steps: Dict = {}

    def step(prop: jnp.ndarray) -> jnp.ndarray:
        dtype = jnp.dtype(prop.dtype)
        if dtype not in steps:
            steps[dtype] = make_expr_push_step(
                dg, ["prop"], lambda env, w, s: value_fn(env["prop"], w),
                None, reduce_op, dtype,
            )
        return steps[dtype]({"prop": prop}, {}, dg.placed())

    return step


# ---------------------------------------------------------------------------
# Generalized distributed edge-kernel superstep
# ---------------------------------------------------------------------------


class _NotDistributable(Exception):
    """Kernel body falls outside the src-gather -> dst-reduce shape."""


def _lower_dist_expr(
    module: mir.Module,
    kern: mir.Kernel,
    e: fir.Expr,
    src_props: Set[str],
    weight_ok: bool,
) -> Callable:
    """Lower a per-edge expression to ``fn(env, w, scalars) -> array``.

    ``env`` maps property name -> values gathered at the edge's source,
    ``w`` is the per-edge weight, ``scalars`` the host scalar environment.
    Anything needing dst-side gathers, accumulator cells, or id
    translation raises :class:`_NotDistributable` (local fallback).
    """
    if isinstance(e, fir.IntLit):
        v = jnp.int32(e.value)
        return lambda env, w, s: v
    if isinstance(e, fir.FloatLit):
        v = jnp.float32(e.value)
        return lambda env, w, s: v
    if isinstance(e, fir.BoolLit):
        v = jnp.bool_(e.value)
        return lambda env, w, s: v
    if isinstance(e, fir.Ident):
        name = e.name
        if name == kern.weight_param:
            if not weight_ok:
                raise _NotDistributable("edge weights are mutated elsewhere")
            return lambda env, w, s: w
        if name in module.scalars:
            return lambda env, w, s: s[name]
        raise _NotDistributable(f"identifier {name!r}")
    if isinstance(e, fir.Index):
        base, idx = e.base, e.index
        if (
            isinstance(base, fir.Ident)
            and base.name in module.properties
            and isinstance(idx, fir.Ident)
            and idx.name == kern.src_param
            and not module.properties[base.name].is_edge
        ):
            prop = base.name
            src_props.add(prop)
            return lambda env, w, s: env[prop]
        raise _NotDistributable("non-src-indexed property read")
    if isinstance(e, fir.BinOp):
        fa = _lower_dist_expr(module, kern, e.lhs, src_props, weight_ok)
        fb = _lower_dist_expr(module, kern, e.rhs, src_props, weight_ok)
        op = e.op
        return lambda env, w, s: backend._binop(op, fa(env, w, s), fb(env, w, s))
    if isinstance(e, fir.UnaryOp):
        fv = _lower_dist_expr(module, kern, e.operand, src_props, weight_ok)
        if e.op == "!":
            return lambda env, w, s: jnp.logical_not(fv(env, w, s))
        return lambda env, w, s: -fv(env, w, s)
    if isinstance(e, fir.Call):
        if e.func == "original_id":
            raise _NotDistributable("original_id needs the relabel table")
        fargs = [
            _lower_dist_expr(module, kern, a, src_props, weight_ok) for a in e.args
        ]
        func = e.func
        return lambda env, w, s: backend._builtin(func, [f(env, w, s) for f in fargs])
    raise _NotDistributable(type(e).__name__)


def _match_dist_kernel(kern: mir.Kernel) -> Tuple[Optional[fir.Expr], str, str, fir.Expr]:
    """Match ``[if cond] prop[dst] op= value`` and return its pieces."""
    body = list(kern.func.body)
    cond: Optional[fir.Expr] = None
    if (
        len(body) == 1
        and isinstance(body[0], fir.If)
        and not body[0].else_body
        and len(body[0].then_body) == 1
    ):
        cond = body[0].cond
        st = body[0].then_body[0]
    elif len(body) == 1:
        st = body[0]
    else:
        raise _NotDistributable("multi-statement body")
    if not isinstance(st, fir.ReduceAssign) or st.op not in ("+", "min", "max"):
        raise _NotDistributable("not a +/min/max reduction")
    tgt = st.target
    if not (
        isinstance(tgt, fir.Index)
        and isinstance(tgt.base, fir.Ident)
        and isinstance(tgt.index, fir.Ident)
        and tgt.index.name == kern.dst_param
    ):
        raise _NotDistributable("write is not prop[dst]")
    return cond, tgt.base.name, st.op, st.value


def make_expr_push_step(
    dg: DistGraph,
    src_props: List[str],
    val_fn: Callable,
    cond_fn: Optional[Callable],
    reduce_op: str,
    out_dtype,
    name: str = "push_step",
):
    """Build a jitted distributed superstep for one lowered edge kernel
    (named ``jit_gt_<name>`` on the device).

    The per-edge value/condition read a set of src-gathered properties
    plus host scalars; the edge buckets are arguments (``dg.placed()``),
    so each device reads the slice it holds:

        step(props: {name: [V]}, scalars: {name: 0-d}, buckets) -> reduced [Vpad]

    The returned array combines with the destination property via the
    kernel's reduce op (identity-filled where no edge contributed).
    """
    mesh, axis, sl = dg.mesh, dg.axis, dg.slice_len
    d = dg.n_devices
    vpad = dg.n_vertices_padded
    pspec = P(axis)
    seg = {
        "+": jax.ops.segment_sum,
        "min": jax.ops.segment_min,
        "max": jax.ops.segment_max,
    }[reduce_op]
    ident = _identity(reduce_op, out_dtype)

    def local_step(prop_slices, scalars, src_b, dst_b, w_b, valid_b):
        # [1, D, Emax] shards (leading src-owner axis sharded away)
        src_b, dst_b, w_b, valid_b = src_b[0], dst_b[0], w_b[0], valid_b[0]
        env = {n: ps.reshape(-1)[src_b] for n, ps in prop_slices.items()}
        vals = val_fn(env, w_b, scalars).astype(out_dtype)
        ok = valid_b
        if cond_fn is not None:
            ok = jnp.logical_and(ok, cond_fn(env, w_b, scalars).astype(jnp.bool_))
        vals = jnp.where(ok, vals, ident)
        # shuffle across chips: route each dst-owner bucket to its device
        vals_r = jax.lax.all_to_all(vals[None], axis, 1, 0, tiled=False)[:, 0]
        dst_r = jax.lax.all_to_all(dst_b[None], axis, 1, 0, tiled=False)[:, 0]
        ok_r = jax.lax.all_to_all(ok[None], axis, 1, 0, tiled=False)[:, 0]
        # local conflict-free reduce (sorted segment reduction)
        flat_v = jnp.where(ok_r, vals_r, ident).reshape(-1)
        flat_d = jnp.where(ok_r, dst_r, sl).reshape(-1)
        order = jnp.argsort(flat_d)
        red = seg(flat_v[order], flat_d[order], sl + 1, indices_are_sorted=True)[:sl]
        return red[None]

    smapped = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(pspec, P(), pspec, pspec, pspec, pspec),
        out_specs=pspec,
        check_vma=False,
    )

    def step(props: Dict[str, jnp.ndarray], scalars: Dict[str, jnp.ndarray],
             buckets: Tuple[jax.Array, ...]):
        grids = {}
        for n in src_props:
            arr = props[n]
            padded = jnp.zeros((vpad,), arr.dtype).at[: arr.shape[0]].set(arr)
            grids[n] = padded.reshape(d, sl)
        red = smapped(grids, scalars, *buckets)
        return red.reshape(-1)

    return backend.gt_jit(step, name)


class DistEngine(Engine):
    """Multi-device engine: the shared host interpreter of :class:`Engine`
    plus distributed supersteps for scatter-reduce edge kernels.

    Construction partitions the graph across ``mesh`` lazily (on the first
    distributable edge-kernel launch). Kernels that read edge weights are
    only distributed when no kernel in the module mutates weights (the
    partitioned weight buckets are built once at partition time).
    """

    def __init__(
        self,
        module: mir.Module,
        graph: GraphData,
        options: Optional[CompileOptions] = None,
        argv: Optional[List[str]] = None,
        mesh: Optional[Mesh] = None,
        axis: str = "data",
        *,
        target=None,
        library=None,
    ):
        super().__init__(module, graph, options, argv=argv, target=target,
                         library=library)
        if mesh is None:
            mesh = dist_mesh(jax.device_count(), axis)
        self.mesh = mesh
        self.axis = axis
        self._dist_graph: Optional[DistGraph] = None
        self._dist_lowered: Dict[str, Optional[tuple]] = {}
        self._weights_static = not any(
            k.writes_weight for k in module.kernels.values()
        )

    def refresh_graph(self, graph: Optional[GraphData] = None):
        super().refresh_graph(graph)
        # superstep closures captured the partitioned (sharded) graph:
        # re-partition lazily on the next distributable launch
        self._dist_graph = None
        self._dist_lowered.clear()

    # -- lazy partition -----------------------------------------------------
    def _partitioned(self) -> DistGraph:
        if self._dist_graph is None:
            self._dist_graph = partition_graph(self.graph, self.mesh, self.axis)
        return self._dist_graph

    def bucket_placement(self) -> Dict[int, List[int]]:
        """Device id -> the src-owner slices of the edge buckets it holds
        (empty until the first distributed superstep partitions the graph)."""
        if self._dist_graph is None:
            return {}
        out: Dict[int, List[int]] = {}
        for shard in self._dist_graph.placed()[0].addressable_shards:
            out.setdefault(shard.device.id, []).append(shard.index[0].start or 0)
        return out

    # -- per-kernel distributed lowering ------------------------------------
    def _dist_kernel(self, name: str) -> Optional[tuple]:
        if name in self._dist_lowered:
            return self._dist_lowered[name]
        kern = self.module.kernels[name]
        entry = None
        try:
            cond, out_prop, op, value = _match_dist_kernel(kern)
            src_props: Set[str] = set()
            val_fn = _lower_dist_expr(
                self.module, kern, value, src_props, self._weights_static
            )
            cond_fn = (
                _lower_dist_expr(self.module, kern, cond, src_props,
                                 self._weights_static)
                if cond is not None
                else None
            )
            out_dtype = self.state[out_prop].dtype
            step = make_expr_push_step(
                self._partitioned(), sorted(src_props), val_fn, cond_fn, op,
                out_dtype, name=name + "_superstep",
            )
            entry = (step, out_prop, op, sorted(src_props))
        except _NotDistributable:
            entry = None
        self._dist_lowered[name] = entry
        return entry

    # -- superstep execution -------------------------------------------------
    def _dist_exec(self, name: str, entry: tuple):
        """Run one distributed superstep for an already-lowered edge kernel."""
        step, out_prop, op, src_props = entry
        scalars = self._kernel_scalars(name)
        props = {p: self.state[p] for p in src_props}
        tr = tel.get()
        sp = tel.NULL_SPAN
        if tr.enabled:
            # shuffle volume: D x D dst-owner buckets of Emax slots each —
            # the all_to_all element count this superstep routes over ICI
            d0, d1, emax = self._partitioned().src_local.shape
            sp = tr.span(
                "superstep", kernel=name, devices=int(d0),
                shuffle_elements=int(d0 * d1 * emax),
                edges=self.graph.n_edges,
            )
        with sp:
            red = self._timed_call(("dist", name), step, props, scalars,
                                   self._partitioned().placed())[
                : self.graph.n_vertices
            ]
        cur = self.state[out_prop]
        self.state[out_prop] = backend.combine(op, cur, red.astype(cur.dtype))
        self.stats.dist_supersteps += 1
        self.stats.edges_traversed += self.graph.n_edges

    # -- per-launch batching hook (repro.batch) ------------------------------
    def batched_runner(self, name: str):
        """Batch-axis lowering of the distributed launch strategy.

        Edge kernels that run as shuffle supersteps sequentially keep doing
        so batched: the jitted shard_map step is vmapped over the query
        axis, so one all_to_all round serves all K queries (the batch axis
        rides along unsharded; per-lane reduction order is unchanged, hence
        results stay bit-identical to sequential distributed runs). Fused
        pipelines are consumed stage-wise exactly like the sequential
        ``launch`` override; everything else falls back to the local
        vmapped lowering via ``super()``.
        """
        from .engine import BatchedLaunch

        bl = self._batched.get(name)
        if bl is not None:
            return bl
        kern = self.module.kernels.get(name)
        if isinstance(kern, mir.PipelineKernel):
            entries = {s.name: self._dist_kernel(s.name) for s in kern.edge_stages}
            if any(e is not None for e in entries.values()):
                bl = self._batched[name] = self._batched_pipeline(kern, entries)
                return bl
        elif kern is not None and kern.kind is mir.KernelKind.EDGE:
            entry = self._dist_kernel(name)
            if entry is not None:
                n_edges = self.graph.n_edges

                def bump(stats):
                    stats.dist_supersteps += 1
                    stats.edges_traversed += n_edges

                bl = self._batched[name] = BatchedLaunch(
                    fn=self._with_buckets(backend.gt_jit(
                        self._batched_superstep(entry), name + "_batched")),
                    bump_stats=bump,
                )
                return bl
        return super().batched_runner(name)

    def _with_buckets(self, fn: Callable) -> Callable:
        """Adapt ``fn(state, scalars, buckets)`` to the BatchedLaunch
        signature, passing the placed edge buckets as arguments."""
        buckets = self._partitioned().placed()
        return lambda state, scalars: fn(state, scalars, buckets)

    def _batched_superstep(self, entry: tuple):
        """fn(state, scalars, buckets) -> {out_prop: combined} over a
        leading K axis (the buckets are shared by every query)."""
        step, out_prop, op, src_props = entry
        vstep = jax.vmap(step, in_axes=(0, 0, None))
        n_v = self.graph.n_vertices

        def run(state, scalars, buckets):
            red = vstep({p: state[p] for p in src_props}, scalars, buckets)[:, :n_v]
            cur = state[out_prop]
            return {out_prop: backend.combine(op, cur, red.astype(cur.dtype))}

        return run

    def _batched_pipeline(self, kern: mir.PipelineKernel, entries: Dict[str, Optional[tuple]]):
        """Stage-wise batched pipeline: dist-able edge stages run as vmapped
        supersteps, the rest as vmapped local traces, all inside ONE jit
        with stage-boundary commits (mirrors the sequential stage-wise
        consumption, so results and superstep accounting line up)."""
        from .engine import BatchedLaunch

        stage_fns = []
        n_dist = 0
        n_local_edges = 0
        n_presorted = 0
        for stage in kern.stages:
            entry = entries.get(stage.name)
            if entry is not None:
                stage_fns.append(self._batched_superstep(entry))
                n_dist += 1
            else:
                module, options, gb = self.module, self.options, self.gb
                vstage = jax.vmap(
                    lambda s, sc, stage=stage: backend._exec_kernel_full(
                        module, stage, options, gb, s, sc)
                )
                stage_fns.append(lambda s, sc, buckets, f=vstage: f(s, sc))
                if stage.kind is mir.KernelKind.EDGE:
                    n_local_edges += 1
                    n_presorted += (gb["dst_sorted"]
                                    and backend.commits_presorted(stage, options))

        def run(state, scalars, buckets):
            cur = dict(state)
            out = {}
            for fn in stage_fns:
                upd = fn(cur, scalars, buckets)
                cur.update(upd)
                out.update(upd)
            return out

        n_edges = self.graph.n_edges

        def bump(stats):
            stats.dist_supersteps += n_dist
            stats.full_launches += len(stage_fns) - n_dist
            stats.presorted_launches += n_presorted
            stats.edges_traversed += n_edges * (n_dist + n_local_edges)

        return BatchedLaunch(
            fn=self._with_buckets(backend.gt_jit(run, kern.name + "_batched")),
            bump_stats=bump,
        )

    # -- launch override -----------------------------------------------------
    def launch(self, name: str):
        kern = self.module.kernels.get(name)
        if isinstance(kern, mir.PipelineKernel):
            # consume a fused pipeline stage-by-stage whenever an edge stage
            # can run as a distributed superstep (stage kernels keep their
            # own entries in module.kernels, so per-stage lowering caches
            # under the original names); otherwise fall through to the
            # single-jit local pipeline lowering
            entries = {s.name: self._dist_kernel(s.name) for s in kern.edge_stages}
            if any(e is not None for e in entries.values()):
                self._count_launch(name, kern)
                tr = tel.get()
                sp = tr.span("launch:" + name, kernel=name, kind="pipeline",
                             mode="dist") if tr.enabled else tel.NULL_SPAN
                with sp:
                    for stage in kern.stages:
                        entry = entries.get(stage.name)
                        if entry is not None:
                            self._dist_exec(stage.name, entry)
                        else:
                            self._execute_kernel(stage.name, stage)
                return
        elif kern is not None and kern.kind is mir.KernelKind.EDGE:
            entry = self._dist_kernel(name)
            if entry is not None:
                self._count_launch(name, kern)
                tr = tel.get()
                sp = tr.span("launch:" + name, kernel=name, kind="edge",
                             mode="dist") if tr.enabled else tel.NULL_SPAN
                with sp:
                    self._dist_exec(name, entry)
                return
        super().launch(name)
