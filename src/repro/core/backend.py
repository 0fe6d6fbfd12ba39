"""Back-end: lowers MIR kernels to JAX executables (paper §III-B3).

The FPGA back-end emits Xilinx OpenCL modules (Burst Read, Cache, Edge/Vertex
Operation, Shuffle, RAW-resolve, Reduce, Burst Write — Fig. 4). Here each
module becomes a composable JAX/Pallas stage:

    Burst Read    -> static processing order: dst-partitioned, ascending-src
                     edge streaming (tiled HBM->VMEM DMA on TPU)
    Cache         -> hub-vertex relabeling so hot properties live in a dense
                     prefix block (VMEM-resident on TPU)
    Edge/Vertex Op-> the user function body, evaluated lane-parallel by the
                     expression evaluator below (VPU/MXU code on TPU)
    Shuffle+Reduce-> precomputed dst-sort permutation + sorted segment
                     reduction (conflict-free by construction); optionally
                     routed through the Pallas ``shuffle_reduce`` kernel
    Burst Write   -> sequential lane-aligned writes (plain vector ops)

Semantics notes (mirror the paper's pipeline transforms):
* RAW decoupling (Fig. 5->6): within one kernel, property reads observe the
  kernel's *input* state; scattered reduce-writes commit at kernel exit.
* RMW normalization (§III-C2) happens in the middle-end, so every scattered
  write reaching this layer is either a reduction or a declared plain store.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import fir, mir
from .options import CompileOptions
from ..graph.storage import GraphData

DTYPES = {"int": jnp.int32, "float": jnp.float32, "bool": jnp.bool_}

WEIGHT_KEY = "__weight__"


def dtype_of(scalar: str):
    return DTYPES[scalar]


def identity_for(op: str, dtype) -> Any:
    dtype = jnp.dtype(dtype)
    if op == "+":
        return dtype.type(0)
    if op == "*":
        return dtype.type(1)
    if op == "min":
        return jnp.iinfo(dtype).max if jnp.issubdtype(dtype, jnp.integer) else dtype.type(jnp.inf)
    if op == "max":
        return jnp.iinfo(dtype).min if jnp.issubdtype(dtype, jnp.integer) else dtype.type(-jnp.inf)
    raise ValueError(f"no identity for reduce op {op!r}")


def combine(op: str, a, b):
    if op == "+":
        return a + b
    if op == "-":
        return a - b
    if op == "*":
        return a * b
    if op == "min":
        return jnp.minimum(a, b)
    if op == "max":
        return jnp.maximum(a, b)
    raise ValueError(f"unknown reduce op {op!r}")


def segment_reduce(op: str, vals, ids, num_segments: int, indices_are_sorted: bool):
    if op in ("+", "-"):
        return jax.ops.segment_sum(vals, ids, num_segments, indices_are_sorted=indices_are_sorted)
    if op == "*":
        return jax.ops.segment_prod(vals, ids, num_segments, indices_are_sorted=indices_are_sorted)
    if op == "min":
        return jax.ops.segment_min(vals, ids, num_segments, indices_are_sorted=indices_are_sorted)
    if op == "max":
        return jax.ops.segment_max(vals, ids, num_segments, indices_are_sorted=indices_are_sorted)
    raise ValueError(op)


def apply_scatter(
    prop_arr: jnp.ndarray,
    idx: jnp.ndarray,
    vals: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    op: Optional[str],
    *,
    sort_perm: Optional[jnp.ndarray] = None,
    options: CompileOptions,
) -> jnp.ndarray:
    """Commit one scattered write group — the Shuffle/RAW/Reduce stage."""
    n = prop_arr.shape[0]
    vals = vals.astype(prop_arr.dtype) if vals.dtype != prop_arr.dtype else vals
    if op is None:
        if options.shuffle:
            # Deterministic last-write-wins: XLA leaves duplicate-index
            # .set() order unspecified, so under the shuffle substrate we
            # resolve each slot to the LAST writing edge in stream order —
            # the answer a sequential interpretation of the kernel gives.
            # (This is the commit path the GT101 race analysis forces on.)
            n_lanes = idx.shape[0]
            pos = jnp.arange(n_lanes, dtype=jnp.int32)
            if mask is not None:
                pos = jnp.where(mask, pos, -1)
            last = jax.ops.segment_max(pos, idx, n)
            written = last >= 0
            chosen = vals[jnp.clip(last, 0, max(n_lanes - 1, 0))]
            return jnp.where(written, chosen, prop_arr)
        # plain scatter store: mask by re-storing the original value
        if mask is not None:
            old = prop_arr[idx]
            vals = jnp.where(mask, vals, old)
        return prop_arr.at[idx].set(vals)
    if op == "-":
        vals, op = -vals, "+"
    ident = identity_for(op, prop_arr.dtype)
    if mask is not None:
        vals = jnp.where(mask, vals, ident)
    if options.pallas:
        from ..kernels import ops as kops

        reduced = kops.shuffle_reduce(
            vals, idx, n, op, interpret=options.interpret_effective
        )
        return combine(op, prop_arr, reduced)
    if options.shuffle and sort_perm is not None:
        # conflict-free path: precomputed routing (sort) + segment reduce
        reduced = segment_reduce(op, vals[sort_perm], idx[sort_perm], n, True)
        # segment_min/max fill empty segments with identity of that reduce,
        # segment_sum fills 0 — all are the correct identities.
        return combine(op, prop_arr, reduced)
    # unoptimized random scatter (the "baseline" path)
    if op == "+":
        return prop_arr.at[idx].add(vals)
    if op == "*":
        return prop_arr.at[idx].mul(vals)
    if op == "min":
        return prop_arr.at[idx].min(vals)
    if op == "max":
        return prop_arr.at[idx].max(vals)
    raise ValueError(op)


# ---------------------------------------------------------------------------
# Expression / statement evaluation contexts
# ---------------------------------------------------------------------------


@dataclass
class LaneCtx:
    """One vectorized execution scope (vertex lanes or edge lanes)."""

    n_lanes: int
    bindings: Dict[str, jnp.ndarray]  # param/loop-var name -> lane index array
    valid: Optional[jnp.ndarray]  # lane validity (padded subsets)
    # expanded-lane support: position into the parent lane array
    parent: Optional["LaneCtx"] = None
    parent_pos: Optional[jnp.ndarray] = None
    env: Dict[str, jnp.ndarray] = field(default_factory=dict)


@dataclass
class KernelExec:
    """Mutable state while lowering/executing one kernel invocation."""

    module: mir.Module
    kernel: mir.Kernel
    options: CompileOptions
    state: Dict[str, jnp.ndarray]
    scalars: Dict[str, jnp.ndarray]
    graph_bind: Dict[str, Any]  # csr/csc arrays for neighbor loops
    scatter_updates: List[Tuple[str, Optional[str], jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray], Optional[jnp.ndarray]]] = field(default_factory=list)
    seq_writes: Dict[str, jnp.ndarray] = field(default_factory=dict)

    # -- property views -------------------------------------------------
    def prop_current(self, name: str) -> jnp.ndarray:
        return self.seq_writes.get(name, self.state[name])

    # -- expression evaluation -------------------------------------------
    def eval(self, e: fir.Expr, lane: LaneCtx):
        m = self.module
        if isinstance(e, fir.IntLit):
            return jnp.int32(e.value)
        if isinstance(e, fir.FloatLit):
            return jnp.float32(e.value)
        if isinstance(e, fir.BoolLit):
            return jnp.bool_(e.value)
        if isinstance(e, fir.Ident):
            name = e.name
            if name in lane.bindings:
                return lane.bindings[name]
            if name in lane.env:
                return lane.env[name]
            if lane.parent is not None:
                # gather vertex-lane values into the expanded lane
                if name in lane.parent.bindings:
                    return lane.parent.bindings[name][lane.parent_pos]
                if name in lane.parent.env:
                    v = lane.parent.env[name]
                    return v[lane.parent_pos] if getattr(v, "ndim", 0) > 0 else v
            if name in self.scalars:
                return self.scalars[name]
            if name in m.properties:
                raise BackendError(
                    f"property {name!r} used without an index in kernel "
                    f"{self.kernel.name!r}"
                )
            raise BackendError(f"unknown identifier {name!r} in kernel {self.kernel.name!r}")
        if isinstance(e, fir.Index):
            if isinstance(e.base, fir.Ident) and e.base.name in m.properties:
                idx = self.eval(e.index, lane)
                return self.prop_current(e.base.name)[idx]
            raise BackendError("only property indexing is supported in kernels")
        if isinstance(e, fir.BinOp):
            a = self.eval(e.lhs, lane)
            b = self.eval(e.rhs, lane)
            return _binop(e.op, a, b)
        if isinstance(e, fir.UnaryOp):
            v = self.eval(e.operand, lane)
            return jnp.logical_not(v) if e.op == "!" else -v
        if isinstance(e, fir.Call):
            if e.func == "original_id":
                idx = self.eval(e.args[0], lane)
                return self.graph_bind["orig_id"][idx]
            args = [self.eval(a, lane) for a in e.args]
            return _builtin(e.func, args)
        if isinstance(e, fir.MethodCall):
            if e.method == "size":
                name = _obj_name(e.obj)
                # logical (unpadded) counts, traced so one AOT executable
                # serves every graph of the bucket; globally-normalized
                # algorithms (PageRank 1/|V|) thus agree padded vs unpadded
                lc = self.graph_bind.get("logical_counts")
                if name == self.module.graph.edgeset_name:
                    if lc is not None:
                        return lc[1]
                    return jnp.int32(self.graph_bind["n_edges"])
                if lc is not None:
                    return lc[0]
                return jnp.int32(self.graph_bind["n_vertices"])
            raise BackendError(f"method {e.method!r} not allowed inside kernels")
        raise BackendError(f"cannot evaluate {type(e).__name__} in kernel")

    # -- statement execution -----------------------------------------------
    def exec_block(self, stmts: Sequence[fir.Stmt], lane: LaneCtx, mask):
        for st in stmts:
            self.exec_stmt(st, lane, mask)

    def exec_stmt(self, st: fir.Stmt, lane: LaneCtx, mask):
        m = self.module
        if isinstance(st, fir.VarDecl):
            val = self.eval(st.init, lane) if st.init is not None else jnp.zeros((), DTYPES[st.type.kind])
            if isinstance(st.type, fir.ScalarType):
                val = _cast(val, DTYPES[st.type.kind])
            lane.env[st.name] = _broadcast(val, lane.n_lanes)
            return
        if isinstance(st, fir.Assign):
            self._write(st.target, None, self.eval(st.value, lane), lane, mask, st.line)
            return
        if isinstance(st, fir.ReduceAssign):
            self._write(st.target, st.op, self.eval(st.value, lane), lane, mask, st.line)
            return
        if isinstance(st, fir.If):
            cond = _broadcast(self.eval(st.cond, lane), lane.n_lanes)
            cond = cond.astype(jnp.bool_)
            tmask = cond if mask is None else jnp.logical_and(mask, cond)
            self.exec_block(st.then_body, lane, tmask)
            if st.else_body:
                fmask = jnp.logical_not(cond) if mask is None else jnp.logical_and(mask, jnp.logical_not(cond))
                self.exec_block(st.else_body, lane, fmask)
            return
        if isinstance(st, fir.For):
            self._exec_neighbor_loop(st, lane, mask)
            return
        if isinstance(st, fir.ExprStmt):
            self.eval(st.expr, lane)
            return
        raise BackendError(f"unsupported device statement {type(st).__name__}")

    # -- neighbor loop: vertex lane -> expanded CSR lane ---------------------
    def _exec_neighbor_loop(self, st: fir.For, lane: LaneCtx, mask):
        it = st.iter
        assert isinstance(it, fir.MethodCall)
        direction = "out" if it.method == "getNeighbors" else "in"
        gb = self.graph_bind
        if direction == "out":
            row_pos, ngh, eids = gb["csr_row_pos"], gb["csr_indices"], gb["csr_eids"]
        else:
            row_pos, ngh, eids = gb["csc_row_pos"], gb["csc_indices"], gb["csc_eids"]
        ex = LaneCtx(
            n_lanes=int(ngh.shape[0]),
            bindings={st.var: ngh, "edge": eids},
            valid=gb.get(f"{direction}_valid"),
            parent=lane,
            parent_pos=row_pos,
        )
        exp_mask = None
        if mask is not None:
            exp_mask = mask[row_pos]
        if ex.valid is not None:
            exp_mask = ex.valid if exp_mask is None else jnp.logical_and(exp_mask, ex.valid)
        # execute body in the expanded lane; local reduce-assigns to parent
        # vars become segment reductions (the unroll+reduce transform)
        self._expanded_parent_reduce(st.body, ex, exp_mask, lane, row_pos)

    def _expanded_parent_reduce(self, body, ex: LaneCtx, exp_mask, lane: LaneCtx, row_pos):
        for st in body:
            if isinstance(st, fir.ReduceAssign) and isinstance(st.target, fir.Ident) \
                    and st.target.name in lane.env:
                vals = _broadcast(self.eval(st.value, ex), ex.n_lanes)
                op = st.op
                if op == "-":
                    vals, op = -vals, "+"
                ident = identity_for(op, vals.dtype)
                if exp_mask is not None:
                    vals = jnp.where(exp_mask, vals, ident)
                red = segment_reduce(op, vals, row_pos, lane.n_lanes, True)
                old = lane.env[st.target.name]
                lane.env[st.target.name] = combine(op, old, red.astype(old.dtype))
            elif isinstance(st, fir.If):
                cond = _broadcast(self.eval(st.cond, ex), ex.n_lanes).astype(jnp.bool_)
                tmask = cond if exp_mask is None else jnp.logical_and(exp_mask, cond)
                self._expanded_parent_reduce(st.then_body, ex, tmask, lane, row_pos)
                if st.else_body:
                    fm = jnp.logical_not(cond)
                    fm = fm if exp_mask is None else jnp.logical_and(exp_mask, fm)
                    self._expanded_parent_reduce(st.else_body, ex, fm, lane, row_pos)
            else:
                self.exec_stmt(st, ex, exp_mask)

    # -- writes -------------------------------------------------------------
    def _write(self, target: fir.Expr, op: Optional[str], val, lane: LaneCtx, mask, line: int):
        m = self.module
        # local variable
        if isinstance(target, fir.Ident):
            name = target.name
            if name == self.kernel.weight_param:
                # edge-weight write (CGAW-style): lane-aligned store, visible
                # to subsequent reads of the weight param in this kernel
                cur = self.seq_writes.get(WEIGHT_KEY, lane.bindings[name])
                val = _broadcast(val, lane.n_lanes).astype(cur.dtype)
                new = val if op is None else combine(op, cur, val)
                wmask = mask
                if lane.valid is not None:
                    wmask = lane.valid if wmask is None else jnp.logical_and(wmask, lane.valid)
                if wmask is not None:
                    new = jnp.where(wmask, new, cur)
                self.seq_writes[WEIGHT_KEY] = new
                lane.bindings[name] = new
                return
            if name in lane.env:
                old = lane.env[name]
                new = _broadcast(val, lane.n_lanes).astype(old.dtype) if hasattr(old, "dtype") else val
                if op is not None:
                    new = combine(op, old, new)
                if mask is not None:
                    new = jnp.where(mask, new, old)
                lane.env[name] = new
                return
            if lane.parent is not None and name in lane.parent.env:
                raise BackendError(
                    f"line {line}: plain assignment to outer var {name!r} inside a "
                    "neighbor loop is ambiguous; use a reduction (+=, min=, ...)"
                )
            raise BackendError(f"line {line}: assignment to undeclared variable {name!r}")
        # property write
        assert isinstance(target, fir.Index) and isinstance(target.base, fir.Ident)
        prop = target.base.name
        if prop not in m.properties:
            raise BackendError(f"line {line}: write to unknown property {prop!r}")
        idx_expr = target.index
        # sequential (burst write) path: P[v] at the kernel's own vertex lane
        if (
            self.kernel.kind is mir.KernelKind.VERTEX
            and isinstance(idx_expr, fir.Ident)
            and idx_expr.name == self.kernel.vertex_param
            and lane.parent is None
        ):
            cur = self.prop_current(prop)
            vids = lane.bindings[idx_expr.name]
            val = _broadcast(val, lane.n_lanes).astype(cur.dtype)
            if lane.valid is None and lane.n_lanes == cur.shape[0]:
                old = cur
                new = val if op is None else combine(op, old, val)
                if mask is not None:
                    new = jnp.where(mask, new, old)
                self.seq_writes[prop] = new
            else:
                wmask = mask
                if lane.valid is not None:
                    wmask = lane.valid if wmask is None else jnp.logical_and(wmask, lane.valid)
                old = cur[vids]
                new = val if op is None else combine(op, old, val)
                if wmask is not None:
                    new = jnp.where(wmask, new, old)
                self.seq_writes[prop] = cur.at[vids].set(new)
            return
        # scattered / accumulator path
        idx = self.eval(idx_expr, lane)
        # the precomputed shuffle routing is only valid when scattering
        # along the edge kernel's destination lane in full-stream order
        dst_sorted = (
            self.kernel.kind is mir.KernelKind.EDGE
            and isinstance(idx_expr, fir.Ident)
            and idx_expr.name == self.kernel.dst_param
            and lane.parent is None
        )
        self._scatter(prop, op, idx, val, lane, mask, dst_sorted=dst_sorted)

    def _scatter(self, prop: str, op: Optional[str], idx, val, lane: LaneCtx, mask,
                 dst_sorted: bool = False):
        val = _broadcast(val, lane.n_lanes)
        idx = _broadcast(idx, lane.n_lanes)
        wmask = mask
        if lane.valid is not None:
            wmask = lane.valid if wmask is None else jnp.logical_and(wmask, lane.valid)
        sort_perm = self.graph_bind.get("dst_sort_perm") if dst_sorted else None
        self.scatter_updates.append((prop, op, idx, val, wmask, sort_perm))

    # -- commit ---------------------------------------------------------------
    def commit(self) -> Dict[str, jnp.ndarray]:
        out: Dict[str, jnp.ndarray] = {}
        out.update(self.seq_writes)
        for prop, op, idx, val, wmask, sort_perm in self.scatter_updates:
            cur = out.get(prop, self.state[prop])
            out[prop] = apply_scatter(
                cur, idx, val, wmask, op, sort_perm=sort_perm, options=self.options
            )
        return out


class BackendError(Exception):
    pass


def _binop(op: str, a, b):
    if op == "+":
        return a + b
    if op == "-":
        return a - b
    if op == "*":
        return a * b
    if op == "/":
        # '/' follows numpy true-division; integer contexts should use
        # to_int() explicitly (the paper's algorithms only divide floats)
        return a / b
    if op == "==":
        return a == b
    if op == "!=":
        return a != b
    if op == "<":
        return a < b
    if op == "<=":
        return a <= b
    if op == ">":
        return a > b
    if op == ">=":
        return a >= b
    if op == "&":
        return jnp.logical_and(a, b)
    if op == "|":
        return jnp.logical_or(a, b)
    raise BackendError(f"unknown operator {op!r}")


def _builtin(name: str, args):
    if name == "exp":
        return jnp.exp(args[0])
    if name == "log":
        return jnp.log(args[0])
    if name == "abs":
        return jnp.abs(args[0])
    if name == "sqrt":
        return jnp.sqrt(args[0])
    if name == "sigmoid":
        return jax.nn.sigmoid(args[0])
    if name == "leakyrelu":
        return jnp.where(args[0] > 0, args[0], args[0] * args[1])
    if name == "min":
        return jnp.minimum(args[0], args[1])
    if name == "max":
        return jnp.maximum(args[0], args[1])
    if name == "floor":
        return jnp.floor(args[0])
    if name == "pow":
        return jnp.power(args[0], args[1])
    if name == "to_float":
        return args[0].astype(jnp.float32)
    if name == "to_int":
        return args[0].astype(jnp.int32)
    raise BackendError(f"unknown builtin {name!r}")


def _broadcast(v, n: int):
    v = jnp.asarray(v)
    if v.ndim == 0:
        return jnp.broadcast_to(v, (n,))
    return v


def _cast(v, dt):
    v = jnp.asarray(v)
    return v.astype(dt) if v.dtype != dt else v


def _obj_name(e: fir.Expr) -> str:
    if isinstance(e, fir.Ident):
        return e.name
    raise BackendError("expected a plain identifier")


# ---------------------------------------------------------------------------
# Kernel lowering
# ---------------------------------------------------------------------------


def gt_jit(fn: Callable, name: str, **jit_kwargs) -> Callable:
    """``jax.jit`` of ``fn`` under the name ``gt_<name>``, so its HLO module
    and every device op of it in a profiler trace read ``jit_gt_<name>``
    (``jit_gt_EdgeTraversal_full``, ``jit_gt_frontier_build``, ...)."""
    fn.__name__ = fn.__qualname__ = "gt_" + name
    return jax.jit(fn, **jit_kwargs)


@dataclass
class LoweredKernel:
    """A device kernel lowered against a concrete graph + target."""

    name: str
    kind: mir.KernelKind
    run_full: Callable  # jit'd (or AOT-compiled): (state, scalars) -> prop updates
    run_subset: Optional[Callable] = None  # jit'd: (state, scalars, batch) -> updates
    frontier: Optional[mir.FrontierInfo] = None
    # traceable twin of run_full (raw Python, un-jitted): what vmap-based
    # batch lowering traces through. AOT-compiled executables cannot be
    # traced, so library-backed kernels MUST provide this.
    trace_full: Optional[Callable] = None


# graph-binding entries that are device arrays (as opposed to the static
# n_vertices/n_edges ints). Shape-generic (AOT) lowering passes exactly
# these as traced arguments so one executable serves every graph of a
# shape bucket; all are int32, [E]-shaped except orig_id ([V]) and
# logical_counts ([2]: unpadded |V|, |E| — what size() reports).
GB_ARRAY_KEYS: Tuple[str, ...] = (
    "order", "src", "dst", "dst_sort_perm",
    "csr_row_pos", "csr_indices", "csr_eids",
    "csc_row_pos", "csc_indices", "csc_eids",
    "orig_id", "logical_counts",
)


def make_frontier_builder(n_vertices: int, n_edges: int, weighted: bool):
    """Jitted device-side frontier expansion, shape-generic.

    Maps active-vertex masks to padded CSR edge ranges in O(V + pad_e)
    work (never O(E)). Per-graph arrays (degrees, row starts, CSR
    indices/eids) are traced arguments, so one builder serves every graph
    of a shape bucket; only (|V|, |E|, weighted) are baked in. This is the
    single copy of the expansion math — the engine binds its own graph's
    arrays over it, the accelerator's KernelLibrary shares one across
    binds.
    """

    def build(deg, starts, csr_indices, csr_eids, mask, weights, pad_v, pad_e):
        (act,) = jnp.nonzero(mask, size=pad_v, fill_value=n_vertices)  # O(V)
        vok = act < n_vertices
        act_c = jnp.minimum(act, n_vertices - 1)
        deg_a = jnp.where(vok, deg[act_c], 0)
        starts_a = starts[act_c]
        cum = jnp.cumsum(deg_a) - deg_a
        # ragged CSR-range expansion, O(pad_e)
        src = jnp.repeat(act_c, deg_a, total_repeat_length=pad_e)
        offs = jnp.repeat(cum, deg_a, total_repeat_length=pad_e)
        base = jnp.repeat(starts_a, deg_a, total_repeat_length=pad_e)
        pos = jnp.arange(pad_e, dtype=jnp.int32)
        valid = pos < jnp.sum(deg_a)
        slots = jnp.minimum(base + (pos - offs), n_edges - 1)
        dst = csr_indices[slots]
        eid = csr_eids[slots]
        w = weights[eid] if weighted else jnp.zeros((pad_e,), jnp.float32)
        return src, dst, w, eid, valid

    return gt_jit(build, "frontier_build", static_argnames=("pad_v", "pad_e"))


def gb_array_specs(n_vertices: int, n_edges: int) -> Dict[str, Any]:
    """jax.ShapeDtypeStruct tree of the graph-binding arrays for a shape."""
    specs = {}
    for key in GB_ARRAY_KEYS:
        if key == "logical_counts":
            n = 2
        elif key == "orig_id":
            n = n_vertices
        else:
            n = n_edges
        specs[key] = jax.ShapeDtypeStruct((n,), jnp.int32)
    return specs


def split_gb_arrays(gb: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
    """Project a concrete graph-binding dict onto its array entries."""
    return {k: gb[k] for k in GB_ARRAY_KEYS}


def _graph_bindings(
    g: GraphData,
    module: mir.Module,
    options,
    new2old: Optional[np.ndarray] = None,
):
    """Precompute static processing-order arrays (the Burst Read plan).

    ``options`` is a :class:`~repro.core.target.Target` (or a legacy
    CompileOptions through the compat shim — both expose the substrate
    attributes read here).
    """
    if options.burst:
        auto = getattr(options, "auto_partitions", None)
        if auto is not None:
            n_parts = auto(g.n_vertices)
        else:
            n_parts = options.n_partitions or max(1, g.n_vertices // 4096)
        pe = g.partition_by_dst(n_parts)
        order = pe.edge_order
    else:
        order = np.arange(g.n_edges, dtype=np.int32)
    src_o = g.src[order]
    dst_o = g.dst[order]
    dst_sort = np.argsort(dst_o, kind="stable").astype(np.int32)

    indptr, csr_idx, csr_eids = g.csr
    in_indptr, csc_idx, csc_eids = g.csc
    row_ids = np.repeat(np.arange(g.n_vertices, dtype=np.int32), np.diff(indptr).astype(np.int64))
    in_row_ids = np.repeat(np.arange(g.n_vertices, dtype=np.int32), np.diff(in_indptr).astype(np.int64))
    gb = {
        "n_vertices": g.n_vertices,
        "n_edges": g.n_edges,
        "order": jnp.asarray(order),
        "src": jnp.asarray(src_o),
        "dst": jnp.asarray(dst_o),
        "dst_sort_perm": jnp.asarray(dst_sort),
        "csr_row_pos": jnp.asarray(row_ids),
        "csr_indices": jnp.asarray(csr_idx),
        "csr_eids": jnp.asarray(csr_eids),
        "csc_row_pos": jnp.asarray(in_row_ids),
        "csc_indices": jnp.asarray(csc_idx),
        "csc_eids": jnp.asarray(csc_eids),
        # lane-id -> original vertex id (identity unless hub-relabeled)
        "orig_id": jnp.asarray(
            new2old if new2old is not None else np.arange(g.n_vertices, dtype=np.int32)
        ),
        # unpadded counts behind size(): traced so in-bucket graph updates
        # (and padding itself) never change the executable
        "logical_counts": jnp.asarray(
            [g.n_vertices_logical, g.n_edges_logical], dtype=np.int32
        ),
    }
    return gb


def _exec_kernel_full(
    module: mir.Module,
    kernel: mir.Kernel,
    options: CompileOptions,
    gb: Dict[str, Any],
    state: Dict[str, jnp.ndarray],
    scalars: Dict[str, jnp.ndarray],
) -> Dict[str, jnp.ndarray]:
    """Trace one full-stream kernel execution: lanes -> body -> commit.

    Shared between the per-kernel ``run_full`` lowering and the fused
    pipeline lowering (which chains several of these inside ONE jit, each
    stage seeing the previous stage's committed updates)."""
    ex = KernelExec(module, kernel, options, state, scalars, gb)
    if kernel.kind is mir.KernelKind.EDGE:
        n = gb["src"].shape[0]
        bindings = {kernel.src_param: gb["src"], kernel.dst_param: gb["dst"],
                    "edge": gb["order"]}
        if kernel.weight_param is not None:
            bindings[kernel.weight_param] = state[WEIGHT_KEY][gb["order"]]
        lane = LaneCtx(n_lanes=n, bindings=bindings, valid=None)
        ex.exec_block(kernel.func.body, lane, None)
        out = ex.commit()
        if WEIGHT_KEY in out:
            # processing-order weights -> original edge order
            out[WEIGHT_KEY] = state[WEIGHT_KEY].at[gb["order"]].set(out[WEIGHT_KEY])
        return out
    n = gb["n_vertices"]
    lane = LaneCtx(
        n_lanes=n,
        bindings={kernel.vertex_param: jnp.arange(n, dtype=jnp.int32)},
        valid=None,
    )
    ex.exec_block(kernel.func.body, lane, None)
    return ex.commit()


def lower_pipeline(
    module: mir.Module,
    pipeline: mir.PipelineKernel,
    gb: Dict[str, Any],
    options: CompileOptions,
) -> LoweredKernel:
    """Lower a fused multi-stage launch (paper Fig. 4 single pipeline).

    All stages trace into ONE jitted executable. Stage boundaries keep
    launch semantics: each stage's updates (including scattered reduces)
    are committed into the running state before the next stage traces, so
    results are identical to launching the stages separately — minus the
    per-launch dispatch/transfer overhead."""
    stages = list(pipeline.stages)

    def run_full(state, scalars):
        cur = dict(state)
        out: Dict[str, jnp.ndarray] = {}
        for stage in stages:
            upd = _exec_kernel_full(module, stage, options, gb, cur, scalars)
            cur.update(upd)
            out.update(upd)
        return out

    return LoweredKernel(
        pipeline.name, mir.KernelKind.PIPELINE,
        run_full=gt_jit(run_full, pipeline.name + "_full"),
        trace_full=run_full,
    )


def lower_kernel_batched(lowered: LoweredKernel) -> Callable:
    """Batch-axis lowering: vectorize a lowered kernel over a query axis.

    The full-stream executable already maps ``(state, scalars) -> updates``
    for one query; ``vmap`` lifts every state array to ``[K, n]`` and every
    scalar to ``[K]``, sharing the graph bindings (CSR/CSC/order arrays are
    closed over, so the graph is traversed ONCE per launch for all K lanes).
    vmap semantics guarantee per-lane results bit-identical to K sequential
    launches, which is what makes Session.run_many's batched rerouting a
    pure optimization.

    Library-backed (AOT) kernels supply ``trace_full`` — an un-jitted twin
    of ``run_full`` — because a compiled executable cannot be traced.
    """
    fn = lowered.trace_full if lowered.trace_full is not None else lowered.run_full
    return gt_jit(jax.vmap(fn), lowered.name + "_batched")


def lower_kernel(
    module: mir.Module,
    kernel: mir.Kernel,
    gb: Dict[str, Any],
    options: CompileOptions,
) -> LoweredKernel:
    if isinstance(kernel, mir.PipelineKernel):
        return lower_pipeline(module, kernel, gb, options)

    if kernel.kind is mir.KernelKind.EDGE:

        def run_full(state, scalars):
            return _exec_kernel_full(module, kernel, options, gb, state, scalars)

        def run_subset(state, scalars, batch):
            src, dst, w, eid, valid = batch
            # subsets are unsorted: disable the static shuffle permutation
            sub_gb = dict(gb, dst_sort_perm=None)
            ex = KernelExec(module, kernel, options, state, scalars, sub_gb)
            bindings = {kernel.src_param: src, kernel.dst_param: dst, "edge": eid}
            if kernel.weight_param is not None:
                bindings[kernel.weight_param] = w
            lane = LaneCtx(n_lanes=src.shape[0], bindings=bindings, valid=valid)
            ex.exec_block(kernel.func.body, lane, None)
            out = ex.commit()
            if WEIGHT_KEY in out:
                prev = state[WEIGHT_KEY]
                vals = jnp.where(valid, out[WEIGHT_KEY], prev[eid])
                out[WEIGHT_KEY] = prev.at[eid].set(vals)
            return out

        return LoweredKernel(
            kernel.name, kernel.kind,
            run_full=gt_jit(run_full, kernel.name + "_full"),
            run_subset=gt_jit(run_subset, kernel.name + "_subset"),
            frontier=kernel.frontier,
            trace_full=run_full,
        )

    # vertex kernel
    def run_full(state, scalars):
        return _exec_kernel_full(module, kernel, options, gb, state, scalars)

    def run_subset(state, scalars, batch):
        vids, valid = batch
        ex = KernelExec(module, kernel, options, state, scalars, gb)
        lane = LaneCtx(n_lanes=vids.shape[0], bindings={kernel.vertex_param: vids}, valid=valid)
        ex.exec_block(kernel.func.body, lane, None)
        return ex.commit()

    return LoweredKernel(
        kernel.name, kernel.kind,
        run_full=gt_jit(run_full, kernel.name + "_full"),
        run_subset=(gt_jit(run_subset, kernel.name + "_subset")
                    if not kernel.has_neighbor_loop else None),
        frontier=kernel.frontier,
        trace_full=run_full,
    )


# ---------------------------------------------------------------------------
# Shape-generic (AOT) kernel lowering — the Accelerator artifact's back-end
# ---------------------------------------------------------------------------


@dataclass
class GenericLoweredKernel:
    """A kernel lowered against a (target, shape bucket), graph-independent.

    Unlike :class:`LoweredKernel`, the graph-binding arrays are traced
    *arguments* rather than closed-over constants: every array the Burst
    Read plan produces has a shape fully determined by (|V|, |E|), so one
    executable serves every graph of the bucket — the software analogue of
    rebinding a synthesized bitstream to a new graph. ``compiled_full`` is
    the AOT executable (``jax.jit(...).lower(specs).compile()``) when the
    accelerator has been lowered; ``jit_full`` is the shared lazily-traced
    fallback (also what compacted-subset and batched paths reuse across
    binds, so shape-bucket rebinds never recompile).
    """

    name: str
    kind: mir.KernelKind
    raw_full: Callable  # traceable: (gb_arrays, state, scalars) -> updates
    jit_full: Callable  # jax.jit(raw_full)
    jit_subset: Optional[Callable] = None  # (gb_arrays, state, scalars, batch)
    frontier: Optional[mir.FrontierInfo] = None
    compiled_full: Optional[Any] = None  # AOT executable or None
    # shared batch-axis lowering (built lazily by KernelLibrary.batched_for):
    # jit(vmap(raw_full, in_axes=(None, 0, 0))) — graph bindings unbatched,
    # state/scalars over the query axis. Living here (not per engine) is
    # what lets same-bucket rebinds reuse the batched XLA traces too.
    jit_batched: Optional[Callable] = None


def lower_kernel_generic(
    module: mir.Module,
    kernel,
    n_vertices: int,
    n_edges: int,
    target,
) -> GenericLoweredKernel:
    """Lower one kernel with graph bindings as arguments (shape-generic)."""
    statics = {"n_vertices": n_vertices, "n_edges": n_edges}

    if isinstance(kernel, mir.PipelineKernel):
        stages = list(kernel.stages)

        def raw_full(gba, state, scalars):
            gb = dict(gba, **statics)
            cur = dict(state)
            out: Dict[str, jnp.ndarray] = {}
            for stage in stages:
                upd = _exec_kernel_full(module, stage, target, gb, cur, scalars)
                cur.update(upd)
                out.update(upd)
            return out

        return GenericLoweredKernel(
            kernel.name, mir.KernelKind.PIPELINE, raw_full,
            gt_jit(raw_full, kernel.name + "_full"),
        )

    if kernel.kind is mir.KernelKind.EDGE:

        def raw_full(gba, state, scalars):
            return _exec_kernel_full(
                module, kernel, target, dict(gba, **statics), state, scalars
            )

        def raw_subset(gba, state, scalars, batch):
            src, dst, w, eid, valid = batch
            # subsets are unsorted: disable the static shuffle permutation
            sub_gb = dict(gba, **statics, dst_sort_perm=None)
            ex = KernelExec(module, kernel, target, state, scalars, sub_gb)
            bindings = {kernel.src_param: src, kernel.dst_param: dst, "edge": eid}
            if kernel.weight_param is not None:
                bindings[kernel.weight_param] = w
            lane = LaneCtx(n_lanes=src.shape[0], bindings=bindings, valid=valid)
            ex.exec_block(kernel.func.body, lane, None)
            out = ex.commit()
            if WEIGHT_KEY in out:
                prev = state[WEIGHT_KEY]
                vals = jnp.where(valid, out[WEIGHT_KEY], prev[eid])
                out[WEIGHT_KEY] = prev.at[eid].set(vals)
            return out

        return GenericLoweredKernel(
            kernel.name, kernel.kind, raw_full,
            gt_jit(raw_full, kernel.name + "_full"),
            jit_subset=gt_jit(raw_subset, kernel.name + "_subset"),
            frontier=kernel.frontier,
        )

    # vertex kernel
    def raw_full(gba, state, scalars):
        return _exec_kernel_full(
            module, kernel, target, dict(gba, **statics), state, scalars
        )

    def raw_subset(gba, state, scalars, batch):
        vids, valid = batch
        ex = KernelExec(module, kernel, target, state, scalars, dict(gba, **statics))
        lane = LaneCtx(n_lanes=vids.shape[0], bindings={kernel.vertex_param: vids},
                       valid=valid)
        ex.exec_block(kernel.func.body, lane, None)
        return ex.commit()

    return GenericLoweredKernel(
        kernel.name, kernel.kind, raw_full,
        gt_jit(raw_full, kernel.name + "_full"),
        jit_subset=(gt_jit(raw_subset, kernel.name + "_subset")
                    if not kernel.has_neighbor_loop else None),
        frontier=kernel.frontier,
    )
