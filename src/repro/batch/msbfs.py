"""Bit-packed multi-source BFS: the classic MS-BFS layout for batched roots.

Level-synchronous BFS programs dominate multi-query graph serving (every
query is "the same traversal from a different root"), and their per-query
state is ONE bit: "is v in the frontier". Packing up to ``word_bits``
queries into each lane word turns K frontier expansions into one:

* ``frontier[v]`` / ``seen[v]`` are ``[V, W]`` word arrays (W = ceil(K/32)
  uint32 words — 64 sources ride one int64 lane word on x64-enabled
  builds, 32 per uint32 word otherwise);
* one traversal step ORs every in-neighbor's frontier word into each
  vertex — a segmented bitwise-OR over the dst-sorted CSC edge stream,
  computed as a sorted segment max over the words unpacked to one byte
  per query (the shuffle network reduced to 1-bit lanes);
* newly reached bits record their BFS level, and the loop runs until every
  packed query has an empty frontier — one launch per level serves the
  whole batch, so the launch total is independent of K.

Selection is automatic and conservative: :func:`match_msbfs` re-derives
the BFS template from the MIR — the Property Detector results, the
frontier/direction verdicts assigned by the PR-2 pass pipeline (the edge
kernel must carry a dynamic frontier check on the level property), and the
exact host-loop shape — and anything that doesn't match falls back to the
general vmapped batch path. The reconstruction below is exact: for a
matched program, every output property and host scalar is provably equal
to what the sequential interpreter computes (levels are unique per vertex,
``tuple[v]`` collapses to the vertex's own level for every reached vertex
except the root, which takes the min over its reached in-neighbors), so
the fast path preserves the bit-identical batching contract.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import fir, mir
from ..core.backend import gt_jit
from ..core.engine import count_launch, host_read, timed_first_call


@dataclass(frozen=True)
class MSBFSPlan:
    """The pieces of a matched level-synchronous BFS program."""

    level_prop: str  # e.g. old_level: the frontier/level property
    next_prop: str  # e.g. new_level: double-buffered level copy
    tuple_prop: str  # e.g. tuple: the min-reduce scratch
    counter_prop: str  # e.g. activeVertex: frontier-size accumulator
    level_scalar: str  # e.g. level
    root_scalar: str  # e.g. root
    loop_var: str  # e.g. frontier_size (local declared in main)
    inf: int  # the "unreached" fill of tuple_prop
    init_kernel: str
    loop_launches: Tuple[str, ...]  # launch names per host iteration

    def accepts(self, param_keys, n_vertices: int) -> bool:
        """Fast path applies when queries only vary the root and the
        unreached sentinel cannot be confused with a real level."""
        return set(param_keys) <= {self.root_scalar} and self.inf > n_vertices + 1


# ---------------------------------------------------------------------------
# template matching on the MIR
# ---------------------------------------------------------------------------


def _int_value(e: fir.Expr) -> Optional[int]:
    if isinstance(e, fir.IntLit):
        return e.value
    if isinstance(e, fir.UnaryOp) and e.op == "-" and isinstance(e.operand, fir.IntLit):
        return -e.operand.value
    return None


def _is_prop_at(e: fir.Expr, prop: str, var: str) -> bool:
    return (
        isinstance(e, fir.Index)
        and isinstance(e.base, fir.Ident)
        and e.base.name == prop
        and isinstance(e.index, fir.Ident)
        and e.index.name == var
    )


def _match_eq(e: fir.Expr) -> Optional[Tuple[fir.Expr, fir.Expr]]:
    if isinstance(e, fir.BinOp) and e.op == "==":
        return e.lhs, e.rhs
    return None


def _match_prop_eq(e: fir.Expr, var: str):
    """Match ``P[var] == rhs`` (either operand order) -> (prop, rhs)."""
    sides = _match_eq(e)
    if sides is None:
        return None
    for a, b in (sides, sides[::-1]):
        if (
            isinstance(a, fir.Index)
            and isinstance(a.base, fir.Ident)
            and isinstance(a.index, fir.Ident)
            and a.index.name == var
        ):
            return a.base.name, b
    return None

def _is_scalar_plus_one(e: fir.Expr, scalar: str) -> bool:
    if not (isinstance(e, fir.BinOp) and e.op == "+"):
        return False
    for a, b in ((e.lhs, e.rhs), (e.rhs, e.lhs)):
        if isinstance(a, fir.Ident) and a.name == scalar and _int_value(b) == 1:
            return True
    return False


def _launch_name(st: fir.Stmt) -> Optional[str]:
    if (
        isinstance(st, fir.ExprStmt)
        and isinstance(st.expr, fir.MethodCall)
        and st.expr.method in ("init", "process")
        and len(st.expr.args) == 1
        and isinstance(st.expr.args[0], fir.Ident)
    ):
        return st.expr.args[0].name
    return None


def _expand_launch(module: mir.Module, name: str) -> List[str]:
    """Resolve a fused/pipelined launch back to the original kernel names."""
    parts = module.fusion_groups.get(name)
    if parts:
        return list(parts)
    return [name]


def match_msbfs(module: mir.Module) -> Optional[MSBFSPlan]:
    """Re-derive the BFS template from an analyzed module, or None.

    Matches the paper-Fig.-1 edge-centric BFS shape regardless of which
    passes ran: fused vertex kernels and pipelines are expanded back to
    their original stages via ``module.fusion_groups`` before matching.
    """
    if module.host is None or module.graph.weighted:
        return None
    body = module.host.main.body
    if len(body) != 5:
        return None
    st_init, st_l, st_n, st_var, st_loop = body

    # vertices.init(reset)
    init_names = (
        _expand_launch(module, _launch_name(st_init))
        if _launch_name(st_init)
        else []
    )
    if len(init_names) != 1:
        return None
    init_kernel = init_names[0]

    # L[root] = 1; N[root] = 1
    def _root_assign(st: fir.Stmt) -> Optional[Tuple[str, str]]:
        if (
            isinstance(st, fir.Assign)
            and isinstance(st.target, fir.Index)
            and isinstance(st.target.base, fir.Ident)
            and isinstance(st.target.index, fir.Ident)
            and _int_value(st.value) == 1
        ):
            return st.target.base.name, st.target.index.name
        return None

    la, na = _root_assign(st_l), _root_assign(st_n)
    if la is None or na is None or la[1] != na[1]:
        return None
    level_prop, root_scalar = la
    next_prop = na[0]
    if root_scalar not in module.scalars or level_prop == next_prop:
        return None

    # var fs: int = 1
    if not (isinstance(st_var, fir.VarDecl) and _int_value(st_var.init) == 1):
        return None
    loop_var = st_var.name

    # while (fs) { launches...; fs = C[0]; C[0] = 0; lvl += 1; }
    if not (
        isinstance(st_loop, fir.While)
        and isinstance(st_loop.cond, fir.Ident)
        and st_loop.cond.name == loop_var
    ):
        return None
    loop_body = list(st_loop.body)
    launches: List[str] = []
    while loop_body and _launch_name(loop_body[0]) is not None:
        launches.append(_launch_name(loop_body[0]))
        loop_body.pop(0)
    if len(loop_body) != 3 or not launches:
        return None
    st_fs, st_c0, st_lvl = loop_body
    if not (
        isinstance(st_fs, fir.Assign)
        and isinstance(st_fs.target, fir.Ident)
        and st_fs.target.name == loop_var
        and isinstance(st_fs.value, fir.Index)
        and isinstance(st_fs.value.base, fir.Ident)
        and _int_value(st_fs.value.index) == 0
    ):
        return None
    counter_prop = st_fs.value.base.name
    if not (
        isinstance(st_c0, fir.Assign)
        and isinstance(st_c0.target, fir.Index)
        and isinstance(st_c0.target.base, fir.Ident)
        and st_c0.target.base.name == counter_prop
        and _int_value(st_c0.target.index) == 0
        and _int_value(st_c0.value) == 0
    ):
        return None
    if not (
        isinstance(st_lvl, fir.ReduceAssign)
        and st_lvl.op == "+"
        and isinstance(st_lvl.target, fir.Ident)
        and _int_value(st_lvl.value) == 1
    ):
        return None
    level_scalar = st_lvl.target.name
    if level_scalar not in module.scalars:
        return None
    if _int_value(module.scalars[level_scalar].init or fir.IntLit(value=-1)) != 1:
        return None

    # expand fused launches back to [edge, update, apply] originals
    expanded: List[str] = []
    for nm in launches:
        expanded.extend(_expand_launch(module, nm))
    if len(expanded) != 3:
        return None
    e_name, u_name, a_name = expanded
    ek = module.kernels.get(e_name)
    uk = module.kernels.get(u_name)
    ak = module.kernels.get(a_name)
    ik = module.kernels.get(init_kernel)
    if not all(
        k is not None and isinstance(k, mir.Kernel) for k in (ek, uk, ak, ik)
    ):
        return None
    if ek.kind is not mir.KernelKind.EDGE:
        return None
    if uk.kind is not mir.KernelKind.VERTEX or ak.kind is not mir.KernelKind.VERTEX:
        return None
    if ik.kind is not mir.KernelKind.VERTEX:
        return None

    # the PR-2 verdicts must agree this is a dynamic frontier on L:
    # DENSE would mean the guard is loop-invariant — not a real BFS frontier
    if ek.frontier is None or ek.frontier.props != {level_prop}:
        return None
    if ek.direction is mir.Direction.DENSE:
        return None

    # edge kernel: if (L[src] == lvl) T[dst] min= lvl + 1
    eb = ek.func.body
    if not (
        len(eb) == 1
        and isinstance(eb[0], fir.If)
        and not eb[0].else_body
        and len(eb[0].then_body) == 1
    ):
        return None
    g = _match_prop_eq(eb[0].cond, ek.src_param)
    if g is None or g[0] != level_prop:
        return None
    if not (isinstance(g[1], fir.Ident) and g[1].name == level_scalar):
        return None
    red = eb[0].then_body[0]
    if not (
        isinstance(red, fir.ReduceAssign)
        and red.op == "min"
        and isinstance(red.target, fir.Index)
        and isinstance(red.target.base, fir.Ident)
        and _is_prop_at(red.target, red.target.base.name, ek.dst_param)
        and _is_scalar_plus_one(red.value, level_scalar)
    ):
        return None
    tuple_prop = red.target.base.name
    if tuple_prop in (level_prop, next_prop, counter_prop):
        return None

    # update kernel: if ((T[v] == lvl+1) & (L[v] == -1)) { N[v] = T[v]; C[0] += 1 }
    ub = uk.func.body
    if not (
        len(ub) == 1
        and isinstance(ub[0], fir.If)
        and not ub[0].else_body
        and len(ub[0].then_body) == 2
    ):
        return None
    cond = ub[0].cond
    if not (isinstance(cond, fir.BinOp) and cond.op == "&"):
        return None
    matched_t = matched_l = False
    for side in (cond.lhs, cond.rhs):
        m = _match_prop_eq(side, uk.vertex_param)
        if m is None:
            return None
        prop, rhs = m
        if prop == tuple_prop and _is_scalar_plus_one(rhs, level_scalar):
            matched_t = True
        elif prop == level_prop and _int_value(rhs) == -1:
            matched_l = True
    if not (matched_t and matched_l):
        return None
    set_n, bump_c = ub[0].then_body
    if not (
        isinstance(set_n, fir.Assign)
        and _is_prop_at(set_n.target, next_prop, uk.vertex_param)
        and _is_prop_at(set_n.value, tuple_prop, uk.vertex_param)
    ):
        return None
    if not (
        isinstance(bump_c, fir.ReduceAssign)
        and bump_c.op == "+"
        and isinstance(bump_c.target, fir.Index)
        and isinstance(bump_c.target.base, fir.Ident)
        and bump_c.target.base.name == counter_prop
        and _int_value(bump_c.target.index) == 0
        and _int_value(bump_c.value) == 1
    ):
        return None

    # apply kernel: L[v] = N[v]
    ab = ak.func.body
    if not (
        len(ab) == 1
        and isinstance(ab[0], fir.Assign)
        and _is_prop_at(ab[0].target, level_prop, ak.vertex_param)
        and _is_prop_at(ab[0].value, next_prop, ak.vertex_param)
    ):
        return None

    # init kernel: L[v] = -1; N[v] = -1; T[v] = INF (any order)
    inits: Dict[str, int] = {}
    for st in ik.func.body:
        if not (
            isinstance(st, fir.Assign)
            and isinstance(st.target, fir.Index)
            and isinstance(st.target.base, fir.Ident)
            and isinstance(st.target.index, fir.Ident)
            and st.target.index.name == ik.vertex_param
            and _int_value(st.value) is not None
        ):
            return None
        inits[st.target.base.name] = _int_value(st.value)
    if set(inits) != {level_prop, next_prop, tuple_prop}:
        return None
    if inits[level_prop] != -1 or inits[next_prop] != -1:
        return None
    inf = inits[tuple_prop]
    if inf <= 1:
        return None

    # level / tuple / next must be ints for levels to transfer exactly
    for prop in (level_prop, next_prop, tuple_prop, counter_prop):
        if module.properties[prop].scalar != "int":
            return None
    if module.scalars[root_scalar].scalar != "int":
        return None

    return MSBFSPlan(
        level_prop=level_prop,
        next_prop=next_prop,
        tuple_prop=tuple_prop,
        counter_prop=counter_prop,
        level_scalar=level_scalar,
        root_scalar=root_scalar,
        loop_var=loop_var,
        inf=inf,
        init_kernel=init_kernel,
        loop_launches=tuple(launches),
    )


# ---------------------------------------------------------------------------
# packed traversal
# ---------------------------------------------------------------------------


def _word_dtype():
    """64 sources per lane word when x64 is enabled, else 32 per uint32."""
    if jax.config.jax_enable_x64:
        return jnp.uint64, 64
    return jnp.uint32, 32


def _lanes(words: jnp.ndarray, k: int) -> jnp.ndarray:
    """[N, W] packed words -> [k, N] int32 0/1, one row per query lane.

    Lane-major int32 keeps the TPU layout dense (a trailing axis of k
    bytes would be padded to a full tile)."""
    word_bits = jnp.iinfo(words.dtype).bits
    lane = np.arange(k)
    shift = jnp.asarray(lane % word_bits, words.dtype)[:, None]
    return ((words.T[lane // word_bits] >> shift) & 1).astype(jnp.int32)


def _pack(lanes: jnp.ndarray, n_words: int, wdt) -> jnp.ndarray:
    """[k, N] 0/1 lanes -> [N, W] packed words (inverse of :func:`_lanes`)."""
    k, n = lanes.shape
    word_bits = jnp.iinfo(wdt).bits
    lanes = jnp.pad(lanes.astype(wdt), ((0, n_words * word_bits - k), (0, 0)))
    shifts = jnp.arange(word_bits, dtype=wdt)[None, :, None]
    # distinct bits: the sum is their OR
    words = (lanes.reshape(n_words, word_bits, n) << shifts).sum(axis=1, dtype=wdt)
    return words.T


def _msbfs_step(frontier, seen, levels, depth, src, dst, *, k: int):
    """One level of the packed traversal for ``k`` queries.

    ``frontier``/``seen`` are [V, W] packed words, ``levels`` [K, V];
    ``src``/``dst`` the CSC edge stream, ``dst`` ascending. Each vertex ORs
    its in-neighbors' frontier words: unpacked to one lane per query, that
    OR is a sorted segment max (the shuffle/reduce network on 1-bit
    lanes), packed again to words.
    """
    n_v, n_words = frontier.shape
    reach = jax.vmap(lambda lane: jax.ops.segment_max(
        lane, dst, num_segments=n_v, indices_are_sorted=True,
    ))(_lanes(frontier[src], k))  # [k, V]; int32 min where no in-edge
    new = _pack(reach > 0, n_words, frontier.dtype) & ~seen
    seen = seen | new
    newly = _lanes(new, k) != 0  # [K, V]
    levels = jnp.where(jnp.logical_and(newly, levels < 0), depth + 1, levels)
    return new, seen, levels, jnp.any(new)


msbfs_step = gt_jit(_msbfs_step, "msbfs_step", static_argnames=("k",))


def run_msbfs(be, plan: MSBFSPlan) -> None:
    """Execute the packed traversal on a BatchEngine and fill its state.

    Operates entirely in the engine's (possibly hub-relabeled) vertex id
    space; the BatchEngine's shared result-splitting path translates back.
    """
    eng = be.engine
    g = be.graph
    k = be.batch_size
    n_v, n_e = g.n_vertices, g.n_edges
    wdt, word_bits = _word_dtype()
    n_words = (k + word_bits - 1) // word_bits

    roots_orig = np.asarray(be.host_env[plan.root_scalar], np.int64)
    roots_orig = np.broadcast_to(roots_orig, (k,))
    o2n = eng.old2new
    roots = np.asarray(o2n)[roots_orig] if o2n is not None else roots_orig

    lanes = np.arange(k)
    np_wdt = np.dtype(str(jnp.dtype(wdt)))
    frontier0 = np.zeros((n_v, n_words), np_wdt)
    np.bitwise_or.at(
        frontier0,
        (roots, lanes // word_bits),
        (np_wdt.type(1) << (lanes % word_bits).astype(np_wdt)),
    )
    levels0 = np.full((k, n_v), -1, np.int32)
    levels0[lanes, roots] = 1

    indptr, csc_idx, _ = g.csc
    frontier = jnp.asarray(frontier0)
    seen = jnp.asarray(frontier0)
    levels = jnp.asarray(levels0)

    if n_e > 0:
        # the CSC edge stream as arguments (a closed-over array would be
        # baked into the executable as a constant): source and destination
        # of every edge, destinations ascending
        src_dev = jnp.asarray(np.asarray(csc_idx, np.int32))
        dst_dev = jnp.asarray(
            np.repeat(np.arange(n_v, dtype=np.int32), np.diff(indptr)))

    its = 0
    while True:
        its += 1
        be.stats.host_iterations += 1
        count_launch(be.stats, be.module, be.MSBFS_NAME)
        be.stats.full_launches += 1
        be.stats.edges_traversed += n_e
        if n_e == 0:
            break
        # first touch of each (k, graph shape) compiles: timed into
        # compile_time_s through the engine's shared warm-key registry
        frontier, seen, levels, any_new = timed_first_call(
            be.stats, eng._warm_keys, ("msbfs", k, n_v, n_e),
            functools.partial(msbfs_step, k=k),
            frontier, seen, levels, jnp.int32(its), src_dev, dst_dev,
        )
        if not host_read(be.stats, any_new, "cond"):
            break

    # ---- exact reconstruction of the sequential interpreter's state ----
    levels_np = host_read(be.stats, levels, "result")  # [K, V], -1 = unreached
    depth = levels_np.max(axis=1)  # >= 1 (the root)
    inf = np.int32(plan.inf)
    tup = np.where(levels_np >= 1, levels_np, inf).astype(np.int32)
    # tuple[v] = min over reached in-neighbors u of (level[u] + 1): for any
    # reached v != root that is exactly level[v]; for the root it needs the
    # explicit in-neighbor minimum (the root's level 1 was host-assigned,
    # never min-reduced); unreached vertices keep INF
    for q in range(k):
        r = int(roots[q])
        nbrs = csc_idx[indptr[r]: indptr[r + 1]]
        lv = levels_np[q, nbrs]
        lv = lv[lv >= 1]
        tup[q, r] = lv.min() + 1 if lv.size else inf

    be.state[plan.level_prop] = jnp.asarray(levels_np)
    be.state[plan.next_prop] = jnp.asarray(levels_np)
    be.state[plan.tuple_prop] = jnp.asarray(tup)
    # counter prop stays all-zero (host clears it after the last iteration),
    # as do any other never-written properties — _reset zeroed them all.
    be.host_env[plan.level_scalar] = (depth + 1).astype(np.int64)
    be.host_env[plan.loop_var] = np.zeros(k, np.int64)
