"""BatchEngine: one set of launches answering K parameterized queries.

The sequential :class:`~repro.core.engine.Engine` interprets the host
program per query and launches each device kernel once per query. On an
immutable graph, the kernels are query-independent executables — only the
*state* they transform differs per query — so K parameter bindings can ride
one launch set:

* every property / scalar gains a **leading batch axis**: state arrays are
  ``[K, n]``, host scalars are ``[K]`` numpy arrays, and device kernels run
  through the backend's batch-axis lowering
  (:func:`repro.core.backend.lower_kernel_batched` — vmap over the shared
  graph bindings, or a vmapped shuffle superstep on the distributed
  engine);
* the host program runs ONCE with **per-query active masks**: an ``if``
  executes both branches under refined masks, a ``while`` iterates until
  every lane's condition is false, and converged queries stop contributing
  state changes (their lanes are masked out of every merge) without
  stopping the batch;
* BFS-like frontier programs additionally get the **bit-packed
  multi-source fast path** (:mod:`repro.batch.msbfs`), selected
  automatically from the MIR frontier/direction verdicts.

Per-lane results are bit-identical to K sequential ``Engine`` runs: vmap
evaluates the same operations per lane, masked merges only suppress writes
a sequential run would not have performed, and the full-stream launches the
batch path always uses agree exactly with the engine's compacted-frontier
launches for every reduction the DSL admits on the frontier path (integer
min/max/add).

The engine is driven through :class:`repro.core.session.BatchSession`; it
wraps (never subclasses) a sequential engine so every registered execution
backend that exposes an ``engine`` attribute serves batches through its own
launch strategy via :meth:`Engine.batched_runner`.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry as tel
from ..core import fir
from ..core.backend import DTYPES, WEIGHT_KEY, combine
from ..core.engine import (
    Engine,
    EngineError,
    EngineResult,
    EngineStats,
    count_launch,
    host_read,
    timed_first_call,
)


class BatchError(Exception):
    pass


# host builtins vectorized over [K] lanes (the numpy analogues of the
# scalar `math`-module table in Engine._host_call)
_VEC_FNS = {
    "exp": np.exp,
    "log": np.log,
    "abs": np.abs,
    "sqrt": np.sqrt,
    "min": np.minimum,
    "max": np.maximum,
    "floor": lambda x: np.floor(x).astype(np.int64),
    "pow": np.power,
    "to_float": lambda x: np.asarray(x, np.float64),
    "to_int": lambda x: np.asarray(x, np.int64),
    "sigmoid": lambda x: 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64))),
    "leakyrelu": lambda x, a: np.where(np.asarray(x) > 0, x, a * np.asarray(x)),
}


def _vec_binop(op: str, a, b):
    if op == "&":
        return np.logical_and(a, b)
    if op == "|":
        return np.logical_or(a, b)
    return {
        "+": lambda: a + b, "-": lambda: a - b, "*": lambda: a * b,
        "/": lambda: a / b, "==": lambda: a == b, "!=": lambda: a != b,
        "<": lambda: a < b, "<=": lambda: a <= b, ">": lambda: a > b,
        ">=": lambda: a >= b,
    }[op]()


class BatchEngine:
    """Executes one compiled module over K parameter bindings at once.

    Wraps any sequential :class:`~repro.core.engine.Engine` (or subclass):
    the inner engine provides the graph, the lowered kernels, and the
    per-launch batching hooks; this class owns the batched state and the
    masked host interpretation.
    """

    MSBFS_NAME = "__msbfs__"  # kernel_launches key of the bit-packed path

    def __init__(self, engine: Engine, enable_msbfs: bool = True):
        self.engine = engine
        self.module = engine.module
        self.options = engine.options
        self.graph = engine.graph  # already hub-relabeled by the engine
        self.argv = engine.argv
        self.enable_msbfs = enable_msbfs
        self.stats = EngineStats()
        self.state: Dict[str, jnp.ndarray] = {}
        self.host_env: Dict[str, Any] = {}
        self.batch_size = 0
        self._msbfs_plan: Any = False  # False = not yet matched

    def refresh_graph(self):
        """Re-point at the inner engine's graph after engine.refresh_graph().

        The inner engine rebuilds its relabeled graph object on refresh;
        this wrapper only snapshots the reference (the msbfs plan is
        module-derived and the batched launch closures live on the inner
        engine, which already dropped them).
        """
        self.graph = self.engine.graph

    # ------------------------------------------------------------------
    # entry point
    # ------------------------------------------------------------------
    def run_batch(self, param_sets: Sequence[Dict[str, Any]]) -> List[EngineResult]:
        """Answer every parameter binding; results in input order.

        All sets must share one key set (the batch-eligibility contract —
        checked again here because this is the last line of defense).
        """
        k = len(param_sets)
        if k == 0:
            return []
        keys = set(param_sets[0])
        for p in param_sets[1:]:
            if set(p) != keys:
                raise BatchError(
                    "batched execution needs one shared parameter key set; got "
                    f"{sorted(keys)} vs {sorted(p)}"
                )
        t0 = time.perf_counter()
        self.batch_size = k
        self.stats = EngineStats(batch_size=k)
        self._reset(param_sets)
        tr = tel.get()
        root_ctx = None
        # as in Engine.run: the run ends when its answers are on the host
        if tr.enabled:
            with tr.span("run", engine=type(self).__name__,
                         batch_size=k) as sp:
                self._run_host(keys, k)
                props = self._read_back()
                sp.set(launches=self.stats.total_launches,
                       msbfs=self.MSBFS_NAME in self.stats.kernel_launches,
                       host_syncs=self.stats.host_syncs)
            root_ctx = sp.context()
        else:
            self._run_host(keys, k)
            props = self._read_back()
        self.stats.wall_time_s = time.perf_counter() - t0
        self.stats.run_time_s = max(
            0.0, self.stats.wall_time_s - self.stats.compile_time_s
        )
        results = self._finalize(props)
        if root_ctx is not None:
            trace = tr.summarize(root=root_ctx)
            for r in results:
                r.trace = trace  # shared, like stats
        return results

    def _run_host(self, keys, k: int) -> None:
        plan = self._msbfs()
        if plan is not None and plan.accepts(keys, self.graph.n_vertices):
            from .msbfs import run_msbfs

            run_msbfs(self, plan)
        else:
            host = self.module.host
            assert host is not None
            self._exec_block(host.main.body, np.ones(k, dtype=bool))

    def _msbfs(self):
        if not self.enable_msbfs:
            return None
        if self._msbfs_plan is False:
            from .msbfs import match_msbfs

            self._msbfs_plan = match_msbfs(self.module)
        return self._msbfs_plan

    # ------------------------------------------------------------------
    # batched state
    # ------------------------------------------------------------------
    def _reset(self, param_sets: Sequence[Dict[str, Any]]) -> None:
        k = len(param_sets)
        module, graph = self.module, self.graph
        self.state = {}
        for p in module.properties.values():
            n = graph.n_edges if p.is_edge else graph.n_vertices
            self.state[p.name] = jnp.zeros((k, n), DTYPES[p.scalar])
        for name, direction in module.degree_props.items():
            deg = graph.out_degree if direction == "out" else graph.in_degree
            row = jnp.asarray(deg).astype(DTYPES[module.properties[name].scalar])
            self.state[name] = jnp.broadcast_to(row, (k,) + row.shape)
        if module.graph.weighted:
            wdt = DTYPES[module.graph.weight_scalar or "float"]
            row = jnp.asarray(graph.weights).astype(wdt)
            self.state[WEIGHT_KEY] = jnp.broadcast_to(row, (k,) + row.shape)
        # scalar initial values: let the inner engine re-derive them (same
        # _eval_host semantics as a sequential run), then broadcast per lane
        self.engine.reset()
        self.host_env = {
            name: np.full(k, v) if isinstance(v, (int, float, bool, np.number)) else v
            for name, v in self.engine.host_env.items()
        }
        if param_sets:
            for name in param_sets[0]:
                self.host_env[name] = np.asarray([ps[name] for ps in param_sets])

    # ------------------------------------------------------------------
    # kernel launching (batched)
    # ------------------------------------------------------------------
    def _launch(self, name: str, mask: np.ndarray) -> None:
        kern = self.module.kernels.get(name)
        if kern is None:
            raise EngineError(f"{name!r} is not a device kernel")
        count_launch(self.stats, self.module, name)
        tr = tel.get()
        if tr.enabled:
            with tr.span("launch:" + name, kernel=name, mode="batched",
                         batch_size=self.batch_size,
                         active_lanes=int(mask.sum())):
                self._launch_inner(name, kern, mask)
        else:
            self._launch_inner(name, kern, mask)

    def _launch_inner(self, name: str, kern, mask: np.ndarray) -> None:
        bl = self.engine.batched_runner(name)
        scalars = self._kernel_scalars(name, kern)
        # first-touch (cold) timing: every distinct batch size K is its own
        # XLA trace; share the inner engine's warm-key registry so the
        # compile/run split stays consistent across run modes
        updates = timed_first_call(
            self.stats, self.engine._warm_keys,
            ("batched", name, self.batch_size), bl.fn, self.state, scalars,
        )
        bl.bump_stats(self.stats)
        self._merge(updates, mask)

    def _kernel_scalars(self, name: str, kern) -> Dict[str, jnp.ndarray]:
        out = {}
        for s in sorted(kern.scalar_reads):
            info = self.module.scalars[s]
            out[s] = jnp.asarray(np.asarray(self.host_env[s]), DTYPES[info.scalar])
        return out

    def _merge(self, updates: Dict[str, jnp.ndarray], mask: np.ndarray) -> None:
        """Commit per-lane updates: inactive (converged) lanes keep state."""
        if mask.all():
            self.state.update(updates)
            return
        m = jnp.asarray(mask)[:, None]
        for prop, arr in updates.items():
            self.state[prop] = jnp.where(m, arr, self.state[prop])

    # ------------------------------------------------------------------
    # vertex id translation (vectorized host/device boundary)
    # ------------------------------------------------------------------
    def _xlate(self, prop: str, idx) -> np.ndarray:
        info = self.module.properties[prop]
        eng = self.engine
        idx = np.broadcast_to(np.asarray(idx, np.int64), (self.batch_size,))
        if (
            eng.old2new is not None
            and not info.is_edge
            and prop not in eng.accumulator_props
            and prop not in self.module.degree_props
        ):
            return np.asarray(eng.old2new)[idx]
        return idx

    # ------------------------------------------------------------------
    # masked host interpretation
    # ------------------------------------------------------------------
    def _truthy(self, v) -> np.ndarray:
        if isinstance(v, jax.Array):
            v = host_read(self.stats, v, "cond")
        return np.broadcast_to(np.asarray(v) != 0, (self.batch_size,))

    def _exec_block(self, body: List[fir.Stmt], mask: np.ndarray) -> None:
        for st in body:
            self._exec_stmt(st, mask)

    def _exec_stmt(self, st: fir.Stmt, mask: np.ndarray) -> None:
        if isinstance(st, fir.VarDecl):
            val = self._eval(st.init, mask) if st.init is not None else 0
            val = np.broadcast_to(np.asarray(val), (self.batch_size,))
            old = self.host_env.get(st.name)
            # first declaration seeds every lane; re-declarations (loop
            # bodies) only overwrite the active lanes
            self.host_env[st.name] = (
                np.array(val) if old is None else np.where(mask, val, old)
            )
            return
        if isinstance(st, fir.Assign):
            tgt = st.target
            val = self._eval(st.value, mask)
            if isinstance(tgt, fir.Ident):
                old = self.host_env[tgt.name]
                self.host_env[tgt.name] = np.where(mask, val, old)
                return
            if isinstance(tgt, fir.Index) and isinstance(tgt.base, fir.Ident):
                self._write_prop(tgt.base.name, tgt.index, None, val, mask)
                return
            raise EngineError("unsupported host assignment")
        if isinstance(st, fir.ReduceAssign):
            tgt = st.target
            val = self._eval(st.value, mask)
            if isinstance(tgt, fir.Ident):
                cur = self.host_env[tgt.name]
                new = {
                    "+": lambda: cur + val, "-": lambda: cur - val,
                    "*": lambda: cur * val,
                    "min": lambda: np.minimum(cur, val),
                    "max": lambda: np.maximum(cur, val),
                }[st.op]()
                self.host_env[tgt.name] = np.where(mask, new, cur)
                return
            if isinstance(tgt, fir.Index) and isinstance(tgt.base, fir.Ident):
                self._write_prop(tgt.base.name, tgt.index, st.op, val, mask)
                return
            raise EngineError("unsupported host reduce target")
        if isinstance(st, fir.If):
            cond = self._truthy(self._eval(st.cond, mask))
            tmask = np.logical_and(mask, cond)
            if tmask.any():
                self._exec_block(st.then_body, tmask)
            if st.else_body:
                fmask = np.logical_and(mask, np.logical_not(cond))
                if fmask.any():
                    self._exec_block(st.else_body, fmask)
            return
        if isinstance(st, fir.While):
            guard = 0
            m = np.logical_and(mask, self._truthy(self._eval(st.cond, mask)))
            while m.any():
                self.stats.host_iterations += 1
                self._exec_block(st.body, m)
                m = np.logical_and(m, self._truthy(self._eval(st.cond, m)))
                guard += 1
                if guard > 1_000_000:
                    raise EngineError("host while loop exceeded 1e6 iterations")
            return
        if isinstance(st, fir.ExprStmt):
            self._eval(st.expr, mask)
            return
        if isinstance(st, fir.For):
            raise EngineError("host for loops are not part of the grammar")
        raise EngineError(f"unsupported host statement {type(st).__name__}")

    def _write_prop(self, prop: str, idx_expr: fir.Expr, op: Optional[str],
                    val, mask: np.ndarray) -> None:
        if prop not in self.module.properties:
            raise EngineError(f"host write to unknown property {prop!r}")
        cols = self._xlate(prop, self._eval(idx_expr, mask))
        rows = np.arange(self.batch_size)
        arr = self.state[prop]
        cur = arr[rows, cols]
        val = jnp.asarray(np.broadcast_to(np.asarray(val), (self.batch_size,)),
                          arr.dtype)
        if op is None:
            new = val
        elif op in ("+", "*", "min", "max"):
            new = combine(op, cur, val)
        else:
            raise EngineError(f"host reduce {op!r}")
        new = jnp.where(jnp.asarray(mask), new, cur)
        self.state[prop] = arr.at[rows, cols].set(new)

    # ------------------------------------------------------------------
    # vectorized host expression evaluation
    # ------------------------------------------------------------------
    def _eval(self, e: Optional[fir.Expr], mask: np.ndarray):
        if e is None:
            return None
        if isinstance(e, (fir.IntLit, fir.FloatLit, fir.BoolLit, fir.StrLit)):
            return e.value
        if isinstance(e, fir.Ident):
            if e.name in self.host_env:
                return self.host_env[e.name]
            if e.name == "argv":
                return self.argv
            raise EngineError(f"unknown host identifier {e.name!r}")
        if isinstance(e, fir.Index):
            base = e.base
            if isinstance(base, fir.Ident) and base.name in self.module.properties:
                cols = self._xlate(base.name, self._eval(e.index, mask))
                rows = np.arange(self.batch_size)
                return host_read(self.stats, self.state[base.name][rows, cols], "scalar")
            idx = self._eval(e.index, mask)
            if isinstance(idx, np.ndarray):
                uniq = np.unique(idx)
                if uniq.size != 1:
                    raise EngineError("host sequence index must be lane-uniform")
                idx = uniq[0]
            seq = self._eval(base, mask)
            return seq[int(idx)]
        if isinstance(e, fir.BinOp):
            return _vec_binop(e.op, self._eval(e.lhs, mask), self._eval(e.rhs, mask))
        if isinstance(e, fir.UnaryOp):
            v = self._eval(e.operand, mask)
            return np.logical_not(v) if e.op == "!" else -np.asarray(v)
        if isinstance(e, fir.Call):
            return self._host_call(e, mask)
        if isinstance(e, fir.MethodCall):
            return self._host_method(e, mask)
        raise EngineError(f"cannot evaluate host expression {type(e).__name__}")

    def _host_call(self, e: fir.Call, mask: np.ndarray):
        if e.func == "load":
            return None  # graph loading happened at engine construction
        if e.func == "swap":
            a, b = e.args
            an, bn = a.name, b.name  # type: ignore[attr-defined]
            va, vb = self.state[an], self.state[bn]
            if mask.all():
                self.state[an], self.state[bn] = vb, va
            else:  # per-lane swap: converged lanes keep their buffers
                m = jnp.asarray(mask)[:, None]
                self.state[an] = jnp.where(m, vb, va)
                self.state[bn] = jnp.where(m, va, vb)
            return None
        if e.func == "print":
            print(*[self._eval(a, mask) for a in e.args])
            return None
        host = self.module.host
        if host is not None and e.func in host.host_funcs:
            self._exec_block(host.host_funcs[e.func].body, mask)
            return None
        if e.func in _VEC_FNS:
            args = [self._eval(a, mask) for a in e.args]
            return _VEC_FNS[e.func](*args)
        raise EngineError(f"unknown host function {e.func!r}")

    def _host_method(self, e: fir.MethodCall, mask: np.ndarray):
        obj = e.obj
        name = obj.name if isinstance(obj, fir.Ident) else None
        g = self.module.graph
        if e.method == "size":
            # logical counts, mirroring Engine._host_method: padding is
            # invisible to size()-normalized math
            if name == g.edgeset_name:
                return self.graph.n_edges_logical
            return self.graph.n_vertices_logical
        if e.method in ("init", "process"):
            fn = e.args[0]
            if not isinstance(fn, fir.Ident):
                raise EngineError("init/process expects a function name")
            self._launch(fn.name, mask)
            return None
        if e.method == "getVertices":
            return None
        if e.method in ("getOutDegrees", "getInDegrees"):
            return None
        raise EngineError(f"unknown host method {e.method!r}")

    # ------------------------------------------------------------------
    # result splitting
    # ------------------------------------------------------------------
    def _read_back(self) -> Dict[str, np.ndarray]:
        """Every property on the host, ``[K, n]``, in original vertex ids."""
        eng = self.engine
        props: Dict[str, np.ndarray] = {}
        for p in self.module.properties.values():
            arr = host_read(self.stats, self.state[p.name], "result")
            if (
                eng.old2new is not None
                and not p.is_edge
                and p.name not in eng.accumulator_props
            ):
                arr = arr[:, eng.old2new]
            props[p.name] = arr
        if WEIGHT_KEY in self.state:
            props["weight"] = host_read(self.stats, self.state[WEIGHT_KEY],
                                         "result")
        return props

    def _finalize(self, props: Dict[str, np.ndarray]) -> List[EngineResult]:
        results = []
        for k in range(self.batch_size):
            henv: Dict[str, Any] = {}
            for name, v in self.host_env.items():
                if isinstance(v, np.ndarray):
                    x = v[k] if v.ndim else v
                    henv[name] = x.item() if hasattr(x, "item") else x
                else:
                    henv[name] = v
            results.append(
                EngineResult(
                    properties={n: a[k] for n, a in props.items()},
                    host_env=henv,
                    stats=self.stats,  # shared: batch_size says how many
                )
            )
        return results
