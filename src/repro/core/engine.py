"""Host driver: executes ``main()`` and launches device kernels.

This is the system-integration layer of the paper (§III-D): the FPGA build
manages accelerators through OpenCL/XRT (clSetKernelArg / clEnqueueTask /
clEnqueueMigrateMemObjects). Here the host program is interpreted in
Python, device kernels are jitted JAX executables, and host<->device data
movement is JAX array transfer. Graph loading / partitioning / property
allocation are implicit interfaces hidden from the algorithm author,
exactly as in the paper.

Engine-level optimizations:
* **hub-vertex cache** (options.cache): the graph is degree-relabeled once
  at load so hub properties occupy a dense prefix; host-side vertex ids are
  transparently translated at the host/device boundary.
* **frontier compaction** (options.compact_frontier): edge kernels guarded
  by a Frontier Check only traverse edges whose source is active, with
  power-of-two padding to keep jit cache hits high. When the frontier is
  large the engine automatically falls back to the full-edge streaming
  kernel — the direction-switching insight of paper Fig. 2 applied
  automatically.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import backend, fir, mir, semantic
from .backend import WEIGHT_KEY, DTYPES
from .options import CompileOptions
from .. import telemetry as tel
from ..graph.storage import GraphData


class EngineError(Exception):
    pass


@dataclass
class EngineStats:
    """Per-run execution counters.

    A stats object describes ONE engine run, which may answer more than one
    query: batched execution (:mod:`repro.batch`) runs K parameter bindings
    through a single set of launches and attaches the same stats object to
    all K results with ``batch_size == K``. Launch/edge counters are
    per-*batch*, never silently per-query — divide by ``batch_size`` (or use
    :meth:`per_query_launches`) when aggregating across results that may mix
    batched and sequential runs.
    """

    kernel_launches: Dict[str, int] = field(default_factory=dict)
    compacted_launches: int = 0
    full_launches: int = 0
    # full-stream edge launches whose every scattered write committed over
    # the dst-ordered stream with no runtime permutation
    # (backend.commits_presorted); never a compacted launch
    presorted_launches: int = 0
    dist_supersteps: int = 0
    edges_traversed: int = 0
    host_iterations: int = 0
    wall_time_s: float = 0.0
    # cold-vs-warm split of wall_time_s: compile_time_s is the first-touch
    # cost of every executable this run hit for the first time in-process
    # (trace + XLA compile + its one execution); run_time_s is the warm
    # remainder. An Accelerator-backed session starts pre-warmed (AOT), so
    # warm-start wins show up directly as compile_time_s ~ 0.
    compile_time_s: float = 0.0
    run_time_s: float = 0.0
    # kernel-fusion accounting (the `fuse` MIR pass): how many launches hit
    # a fused kernel, and how many separate launches fusion saved overall
    fused_launches: int = 0
    launches_saved: int = 0
    # how many queries this run answered (1 = plain sequential run; K > 1 =
    # one batched run whose launches served K parameter bindings at once)
    batch_size: int = 1
    # device->host reads the interpreter waited on (Engine.host_read), and
    # the bytes they brought back; per batch, like the launch counters
    host_syncs: int = 0
    host_sync_bytes: int = 0

    @property
    def total_launches(self) -> int:
        return sum(self.kernel_launches.values())

    @property
    def per_query_launches(self) -> float:
        """Launches amortized over the queries this run answered."""
        return self.total_launches / max(self.batch_size, 1)


def count_launch(stats: EngineStats, module: mir.Module, name: str) -> None:
    """Record one logical kernel launch (a fused kernel counts once, not per
    stage). Shared by the sequential engines and the batch engine so fusion
    accounting stays consistent across both run modes."""
    stats.kernel_launches[name] = stats.kernel_launches.get(name, 0) + 1
    parts = module.fusion_groups.get(name)
    if parts:
        stats.fused_launches += 1
        stats.launches_saved += len(parts) - 1


def host_read(stats: EngineStats, value, reason: str) -> np.ndarray:
    """Read a device value back to the host, counted in ``stats``.

    The one place the interpreters wait on the device: every read bumps
    ``host_syncs`` / ``host_sync_bytes``, and under a recording tracer it
    is a ``host_sync`` span with ``reason`` (``scalar``, ``cond``,
    ``frontier_mask``, ``result``) and ``bytes``."""
    nbytes = int(value.nbytes)
    stats.host_syncs += 1
    stats.host_sync_bytes += nbytes
    tr = tel.get()
    if not tr.enabled:
        return np.asarray(value)
    with tr.span("host_sync", reason=reason, bytes=nbytes):
        return np.asarray(value)


def timed_first_call(stats: EngineStats, warm: set, key, fn, *args):
    """Call ``fn``; attribute a first-touch (cold) call's wall time to
    ``stats.compile_time_s`` and mark ``key`` warm. Shared by the
    sequential, batched and MS-BFS launch paths, so the compile/run split
    reads the same in every run mode."""
    if key in warm:
        return fn(*args)
    t0 = time.perf_counter()
    try:
        return fn(*args)
    finally:
        stats.compile_time_s += time.perf_counter() - t0
        warm.add(key)


@dataclass
class EngineResult:
    properties: Dict[str, np.ndarray]
    host_env: Dict[str, Any]
    stats: EngineStats
    # graph version the query was answered against (streaming sessions pin
    # every admitted query to one version; 0 = static/unversioned binding)
    version: int = 0
    # per-run telemetry summary (repro.telemetry): aggregated span tree of
    # this run when tracing was enabled, None otherwise. Batched runs share
    # one summary object across the K results, mirroring `stats`.
    trace: Optional[Dict[str, Any]] = None


@dataclass
class BatchedLaunch:
    """One kernel launch lowered over a leading batch (query) axis.

    ``fn(state, scalars) -> updates`` where every state array carries a
    leading ``K`` axis and every scalar is a ``[K]`` array; ``bump_stats``
    applies the same counter increments the sequential engine would record
    for ONE launch (the batch engine counts a batched launch once — the
    per-query amortization lives in ``EngineStats.batch_size``).
    """

    fn: Callable[[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]], Dict[str, jnp.ndarray]]
    bump_stats: Callable[[EngineStats], None]


def _next_pow2(n: int) -> int:
    return 1 << max(10, (max(1, n) - 1).bit_length())


class Engine:
    """Executes one compiled Graphitron module against one graph."""

    def __init__(
        self,
        module: mir.Module,
        graph: GraphData,
        options: Optional[CompileOptions] = None,
        argv: Optional[List[str]] = None,
        *,
        target=None,
        library=None,
    ):
        from .target import Target

        options = options if options is not None else CompileOptions()
        self.module = module
        self.options = options
        # the execution substrate: an explicit Target (Accelerator path) or
        # one resolved from the legacy CompileOptions substrate fields
        self.target = target if target is not None else Target.from_options(options)
        # Race-safety override: a program whose static analysis found a true
        # scatter race (GT101) is only sequentially-correct under the sorted
        # shuffle substrate — disabling shuffle on it is an ablation of
        # correctness, not of performance, so the analysis verdict wins.
        self.shuffle_forced = False
        if not self.target.shuffle:
            from ..analysis.analyses import needs_shuffle

            if needs_shuffle(module):
                import dataclasses as _dc

                self.target = _dc.replace(self.target, shuffle=True)
                self.shuffle_forced = True
        self.argv = argv or []
        self.stats = EngineStats()
        # AOT kernel library (repro.core.accelerator): shape-generic lowered
        # kernels shared by every bind of one Accelerator
        self.library = library
        if library is not None:
            library.check_graph(graph)
        # executables already compiled in-process: first-touch timing keys.
        # Library-backed engines share the library's registry, so a rebind
        # of the same accelerator starts warm.
        self._warm_keys = library.warm_keys if library is not None else set()

        # the graph as handed in (original vertex ids) — refresh_graph
        # re-derives every binding from it after an in-place mutation
        self.source_graph = graph

        # ---- hub cache: degree relabeling (paper Fig. 7(b)) ----
        if self.target.cache:
            self.graph, self.old2new = graph.relabel_by_degree()
            new2old = graph.degree_rank
        else:
            self.graph, self.old2new = graph, None
            new2old = None

        self.gb = backend._graph_bindings(self.graph, module, self.target,
                                          new2old=new2old)
        self._lowered: Dict[str, backend.LoweredKernel] = {}
        self._subset_cache: Dict[Tuple[str, int], Callable] = {}
        # per-launch batching hooks: kernel name -> BatchedLaunch (built on
        # demand by batched_runner(); driven by repro.batch.BatchEngine)
        self._batched: Dict[str, "BatchedLaunch"] = {}

        # accumulator properties are NOT vertex-indexed (no id translation)
        self.accumulator_props = set()
        for k in module.kernels.values():
            self.accumulator_props |= k.accumulators

        # ---- memory allocation (implicit interface) ----
        self.state: Dict[str, jnp.ndarray] = {}
        for p in module.properties.values():
            n = self.graph.n_edges if p.is_edge else self.graph.n_vertices
            self.state[p.name] = jnp.zeros((n,), DTYPES[p.scalar])
        for name, direction in module.degree_props.items():
            deg = self.graph.out_degree if direction == "out" else self.graph.in_degree
            dt = DTYPES[module.properties[name].scalar]
            self.state[name] = jnp.asarray(deg).astype(dt)
        if module.graph.weighted:
            w = self.graph.weights
            if w is None:
                raise EngineError("weighted edgeset but the loaded graph has no weights")
            wdt = DTYPES[module.graph.weight_scalar or "float"]
            self.state[WEIGHT_KEY] = jnp.asarray(w).astype(wdt)

        # ---- host scalar environment ----
        self.host_env: Dict[str, Any] = {}
        for s in module.scalars.values():
            self.host_env[s.name] = self._eval_host(s.init) if s.init is not None else 0

    def reset(self):
        """Reinitialize device/host state, keeping lowered (compiled)
        kernels — the repeat-run path for benchmarking and reuse."""
        module, graph = self.module, self.graph
        self.stats = EngineStats()
        for p in module.properties.values():
            n = graph.n_edges if p.is_edge else graph.n_vertices
            self.state[p.name] = jnp.zeros((n,), DTYPES[p.scalar])
        for name, direction in module.degree_props.items():
            deg = graph.out_degree if direction == "out" else graph.in_degree
            self.state[name] = jnp.asarray(deg).astype(DTYPES[module.properties[name].scalar])
        if module.graph.weighted:
            wdt = DTYPES[module.graph.weight_scalar or "float"]
            self.state[WEIGHT_KEY] = jnp.asarray(graph.weights).astype(wdt)
        self.host_env = {}
        for s in module.scalars.values():
            self.host_env[s.name] = self._eval_host(s.init) if s.init is not None else 0

    def refresh_graph(self, graph: Optional[GraphData] = None):
        """Re-derive every graph-dependent binding after an in-place update.

        The streaming path mutates ``GraphData`` arrays in place
        (:meth:`GraphData.apply_updates`), which invalidates the hub
        relabeling, the burst processing order and every CSR/CSC binding
        this engine captured at construction. Because the physical shape is
        unchanged (same bucket), library-backed engines keep their AOT
        executables — graph arrays are traced arguments there, so the
        refresh costs no recompilation (``compile_time_s`` stays 0). Plain
        engines close graph constants into their jits and must re-lower;
        their first-touch timing keys are reset so the recompile is
        reported honestly.
        """
        graph = graph if graph is not None else self.source_graph
        self.source_graph = graph
        if self.library is not None:
            self.library.check_graph(graph)
        if self.target.cache:
            self.graph, self.old2new = graph.relabel_by_degree()
            new2old = graph.degree_rank
        else:
            self.graph, self.old2new = graph, None
            new2old = None
        self.gb = backend._graph_bindings(self.graph, self.module, self.target,
                                          new2old=new2old)
        # closures over the old gb arrays; rebuilt on demand (cheap binds
        # over the shared library, fresh jits otherwise)
        self._lowered.clear()
        self._subset_cache.clear()
        self._batched.clear()
        for attr in ("_build_batch", "_deg_np"):
            if hasattr(self, attr):
                delattr(self, attr)
        if self.library is None:
            # non-library jits captured graph constants: the rebuilt ones
            # recompile, so nothing is warm anymore
            self._warm_keys.clear()
        self.reset()

    # ------------------------------------------------------------------
    # vertex id translation at the host/device boundary
    # ------------------------------------------------------------------
    def _xlate(self, prop: str, idx: int) -> int:
        info = self.module.properties[prop]
        if (
            self.old2new is not None
            and not info.is_edge
            and prop not in self.accumulator_props
            and prop not in self.module.degree_props
        ):
            return int(self.old2new[idx])
        return int(idx)

    # ------------------------------------------------------------------
    # kernel launching
    # ------------------------------------------------------------------
    def _kernel(self, name: str) -> backend.LoweredKernel:
        if name not in self._lowered:
            k = self.module.kernels.get(name)
            if k is None:
                raise EngineError(f"{name!r} is not a device kernel")
            if self.library is not None:
                self._lowered[name] = self.library.kernel_for(name, self.gb)
            else:
                self._lowered[name] = backend.lower_kernel(
                    self.module, k, self.gb, self.target
                )
        return self._lowered[name]

    def _timed_call(self, key, fn, *args):
        """First-touch timing (:func:`timed_first_call`). The warm-key
        registry survives reset() (kernels stay compiled) and is shared
        across binds when a kernel library backs this engine."""
        return timed_first_call(self.stats, self._warm_keys, key, fn, *args)

    def host_read(self, value, reason: str) -> np.ndarray:
        """Read a device value back to the host (:func:`host_read`)."""
        return host_read(self.stats, value, reason)

    def _kernel_scalars(self, name: str) -> Dict[str, jnp.ndarray]:
        k = self.module.kernels[name]
        out = {}
        for s in sorted(k.scalar_reads):
            info = self.module.scalars[s]
            out[s] = jnp.asarray(self.host_env[s], DTYPES[info.scalar])
        return out

    def launch(self, name: str):
        kern = self.module.kernels.get(name)
        if kern is None:
            raise EngineError(f"{name!r} is not a device kernel")
        self._count_launch(name, kern)
        tr = tel.get()
        if not tr.enabled:  # hot path: one attribute check when untraced
            self._execute_kernel(name, kern)
            return
        direction = getattr(kern, "direction", None)
        with tr.span(
            "launch:" + name,
            kernel=name,
            kind=kern.kind.name.lower(),
            direction=direction.name.lower() if direction is not None else None,
        ) as sp:
            self._execute_kernel(name, kern, sp)

    # -- per-launch batching hook (repro.batch) -------------------------------
    def batched_runner(self, name: str) -> "BatchedLaunch":
        """Return the batch-axis executable for kernel ``name``.

        The returned :class:`BatchedLaunch` runs one logical launch over a
        leading query axis: state arrays are ``[K, n]``, scalar arrays are
        ``[K]``, and the per-lane results are bit-identical to ``K``
        independent sequential launches (vmap semantics). Subclasses
        (e.g. :class:`~repro.core.dist_engine.DistEngine`) override this to
        batch their own launch strategy — the shared contract is only
        ``fn(state, scalars) -> updates`` plus honest stats accounting.
        """
        bl = self._batched.get(name)
        if bl is None:
            kern = self.module.kernels.get(name)
            if kern is None:
                raise EngineError(f"{name!r} is not a device kernel")
            if self.library is not None:
                # library-shared vmap trace: rebinds of one accelerator
                # reuse every batch-size compilation (and the shared
                # warm-key registry stays honest about it)
                fn = self.library.batched_for(name, self.gb)
            else:
                fn = backend.lower_kernel_batched(self._kernel(name))
            bl = self._batched[name] = BatchedLaunch(
                fn=fn,
                bump_stats=self._full_stats_bump(kern, self._kernel(name).presorted),
            )
        return bl

    def _full_stats_bump(self, kern, presorted: bool) -> Callable[[EngineStats], None]:
        """Stats increment matching one full-stream launch of ``kern``."""
        n_edges = self.graph.n_edges
        if kern.kind is mir.KernelKind.EDGE:
            edges = n_edges
        elif isinstance(kern, mir.PipelineKernel):
            edges = n_edges * len(kern.edge_stages)
        else:
            edges = 0

        def bump(stats: EngineStats) -> None:
            stats.full_launches += 1
            stats.presorted_launches += presorted
            stats.edges_traversed += edges

        return bump

    def _count_launch(self, name: str, kern):
        """One logical launch (a fused kernel counts once, not per stage)."""
        count_launch(self.stats, self.module, name)

    def _execute_kernel(self, name: str, kern, sp=tel.NULL_SPAN):
        lk = self._kernel(name)
        scalars = self._kernel_scalars(name)
        if (
            self.target.compact_frontier
            and kern.kind is mir.KernelKind.EDGE
            # DENSE = compile-time verdict that the guard is loop-invariant:
            # skip host-side frontier mask evaluation entirely
            and kern.direction is not mir.Direction.DENSE
            and lk.frontier is not None
            and lk.run_subset is not None
        ):
            launched = self._launch_compacted_edge(lk, kern, scalars, sp)
            if launched:
                return
        self.stats.full_launches += 1
        self.stats.presorted_launches += lk.presorted
        edges = 0
        if kern.kind is mir.KernelKind.EDGE:
            edges = self.graph.n_edges
        elif isinstance(kern, mir.PipelineKernel):
            edges = self.graph.n_edges * len(kern.edge_stages)
        self.stats.edges_traversed += edges
        sp.set(mode="full", edges=edges)
        updates = self._timed_call(("full", name), lk.run_full, self.state, scalars)
        self.state.update(updates)

    # -- frontier compaction (direction optimization, engine-automatic) ----
    def _batch_builder(self):
        """Frontier expansion bound to this graph's arrays.

        The expansion math lives once, shape-generic, in
        :func:`backend.make_frontier_builder`; library-backed engines share
        the accelerator's builder (so same-bucket rebinds reuse every
        compiled (pad_v, pad_e) bucket), plain engines build their own.
        """
        if hasattr(self, "_build_batch"):
            return self._build_batch
        gb = self.gb
        indptr, _, _ = self.graph.csr
        deg_dev = jnp.asarray(np.diff(indptr).astype(np.int32))
        starts_dev = jnp.asarray(indptr[:-1].astype(np.int32))
        if self.library is not None:
            generic = self.library.frontier_builder()
        else:
            generic = backend.make_frontier_builder(
                self.graph.n_vertices, self.graph.n_edges,
                self.module.graph.weighted,
            )

        def build(mask, weights, pad_v, pad_e):
            return generic(
                deg_dev, starts_dev, gb["csr_indices"], gb["csr_eids"],
                mask, weights, pad_v=pad_v, pad_e=pad_e,
            )

        self._build_batch = build
        return build

    def _launch_compacted_edge(self, lk, kern: mir.Kernel, scalars,
                               sp=tel.NULL_SPAN) -> bool:
        mask = self._vertex_mask_host(kern, lk.frontier.cond)
        if mask is None:
            return False
        if not hasattr(self, "_deg_np"):
            indptr, _, _ = self.graph.csr
            self._deg_np = np.diff(indptr)
        n_active = int(mask.sum())
        n_active_edges = int(self._deg_np[mask].sum())
        # heuristic switch: large frontiers stream the whole edge list
        if n_active_edges > self.graph.n_edges // 4:
            return False
        pad_v = _next_pow2(n_active)
        pad_e = _next_pow2(n_active_edges)
        if pad_e > self.graph.n_edges:
            return False
        sp.set(
            mode="compacted", edges=n_active_edges, frontier_size=n_active,
            frontier_occupancy=round(n_active / max(1, self.graph.n_vertices), 6),
            pad_v=pad_v, pad_e=pad_e,
        )
        weights = self.state.get(WEIGHT_KEY, jnp.zeros((1,), jnp.float32))
        batch = self._timed_call(
            ("fbuild", pad_v, pad_e),
            self._batch_builder(), jnp.asarray(mask), weights, pad_v, pad_e,
        )
        updates = self._timed_call(
            ("subset", kern.name, pad_v, pad_e),
            lk.run_subset, self.state, scalars, batch,
        )
        self.state.update(updates)
        self.stats.compacted_launches += 1
        self.stats.edges_traversed += n_active_edges
        return True

    def _vertex_mask_host(self, kern: mir.Kernel, cond: fir.Expr) -> Optional[np.ndarray]:
        """Evaluate a frontier condition per-vertex on the host (numpy)."""

        def ev(e: fir.Expr):
            if isinstance(e, fir.IntLit):
                return e.value
            if isinstance(e, fir.FloatLit):
                return e.value
            if isinstance(e, fir.BoolLit):
                return e.value
            if isinstance(e, fir.Ident):
                if e.name in self.host_env:
                    return self.host_env[e.name]
                raise EngineError(f"frontier cond references {e.name!r}")
            if isinstance(e, fir.Index) and isinstance(e.base, fir.Ident):
                prop = e.base.name
                idx = e.index
                if isinstance(idx, fir.Ident) and idx.name in (
                    kern.src_param,
                    kern.vertex_param,
                ):
                    return self.host_read(self.state[prop], "frontier_mask")
                raise EngineError("frontier cond must index by src/v")
            if isinstance(e, fir.BinOp):
                a, b = ev(e.lhs), ev(e.rhs)
                return {
                    "+": lambda: a + b, "-": lambda: a - b, "*": lambda: a * b,
                    "/": lambda: a / b, "==": lambda: a == b, "!=": lambda: a != b,
                    "<": lambda: a < b, "<=": lambda: a <= b, ">": lambda: a > b,
                    ">=": lambda: a >= b,
                    "&": lambda: np.logical_and(a, b),
                    "|": lambda: np.logical_or(a, b),
                }[e.op]()
            if isinstance(e, fir.UnaryOp):
                v = ev(e.operand)
                return np.logical_not(v) if e.op == "!" else -v
            raise EngineError("unsupported frontier expression")

        try:
            mask = ev(cond)
        except EngineError:
            return None
        mask = np.asarray(mask)
        if mask.ndim != 1:
            return None
        return mask

    # ------------------------------------------------------------------
    # host program interpretation
    # ------------------------------------------------------------------
    def run(self) -> EngineResult:
        t0 = time.perf_counter()
        host = self.module.host
        assert host is not None
        tr = tel.get()
        root_ctx = None
        # the run ends when its answer is on the host: the readback is part
        # of it, so wall_time_s (and the run span) cover the device's work
        if tr.enabled:
            with tr.span("run", engine=type(self).__name__,
                         target=self.target.kind, batch_size=1) as sp:
                self._exec_host_block(host.main.body)
                props = self._read_back()
                sp.set(launches=self.stats.total_launches,
                       compacted=self.stats.compacted_launches,
                       full=self.stats.full_launches,
                       supersteps=self.stats.dist_supersteps,
                       host_syncs=self.stats.host_syncs)
            root_ctx = sp.context()
        else:
            self._exec_host_block(host.main.body)
            props = self._read_back()
        self.stats.wall_time_s = time.perf_counter() - t0
        self.stats.run_time_s = max(
            0.0, self.stats.wall_time_s - self.stats.compile_time_s
        )
        result = EngineResult(
            properties=props, host_env=dict(self.host_env), stats=self.stats
        )
        if root_ctx is not None:
            result.trace = tr.summarize(root=root_ctx)
        return result

    def _read_back(self) -> Dict[str, np.ndarray]:
        """Every property on the host, in original vertex ids."""
        props = {}
        for p in self.module.properties.values():
            arr = self.host_read(self.state[p.name], "result")
            if (
                self.old2new is not None
                and not p.is_edge
                and p.name not in self.accumulator_props
            ):
                arr = arr[self.old2new]
            props[p.name] = arr
        if WEIGHT_KEY in self.state:
            props["weight"] = self.host_read(self.state[WEIGHT_KEY], "result")
        return props

    def _exec_host_block(self, body: List[fir.Stmt]):
        for st in body:
            self._exec_host_stmt(st)

    def _exec_host_stmt(self, st: fir.Stmt):
        if isinstance(st, fir.VarDecl):
            self.host_env[st.name] = (
                self._eval_host(st.init) if st.init is not None else 0
            )
            return
        if isinstance(st, fir.Assign):
            tgt = st.target
            val = self._eval_host(st.value)
            if isinstance(tgt, fir.Ident):
                self.host_env[tgt.name] = val
                return
            if isinstance(tgt, fir.Index) and isinstance(tgt.base, fir.Ident):
                prop = tgt.base.name
                if prop not in self.module.properties:
                    raise EngineError(f"host write to unknown property {prop!r}")
                i = self._xlate(prop, int(self._eval_host(tgt.index)))
                dt = self.state[prop].dtype
                self.state[prop] = self.state[prop].at[i].set(jnp.asarray(val, dt))
                return
            raise EngineError("unsupported host assignment")
        if isinstance(st, fir.ReduceAssign):
            # host scalar reduce: level += 1
            tgt = st.target
            if isinstance(tgt, fir.Ident):
                cur = self.host_env[tgt.name]
                val = self._eval_host(st.value)
                self.host_env[tgt.name] = {
                    "+": cur + val, "-": cur - val, "*": cur * val,
                    "min": min(cur, val), "max": max(cur, val),
                }[st.op]
                return
            if isinstance(tgt, fir.Index) and isinstance(tgt.base, fir.Ident):
                prop = tgt.base.name
                i = self._xlate(prop, int(self._eval_host(tgt.index)))
                cur = self.state[prop]
                val = jnp.asarray(self._eval_host(st.value), cur.dtype)
                if st.op == "+":
                    self.state[prop] = cur.at[i].add(val)
                elif st.op == "min":
                    self.state[prop] = cur.at[i].min(val)
                elif st.op == "max":
                    self.state[prop] = cur.at[i].max(val)
                elif st.op == "*":
                    self.state[prop] = cur.at[i].mul(val)
                else:
                    raise EngineError(f"host reduce {st.op!r}")
                return
            raise EngineError("unsupported host reduce target")
        if isinstance(st, fir.If):
            if self._truthy(self._eval_host(st.cond)):
                self._exec_host_block(st.then_body)
            else:
                self._exec_host_block(st.else_body)
            return
        if isinstance(st, fir.While):
            guard = 0
            while self._truthy(self._eval_host(st.cond)):
                self.stats.host_iterations += 1
                self._exec_host_block(st.body)
                guard += 1
                if guard > 1_000_000:
                    raise EngineError("host while loop exceeded 1e6 iterations")
            return
        if isinstance(st, fir.ExprStmt):
            self._eval_host(st.expr)
            return
        if isinstance(st, fir.For):
            raise EngineError("host for loops are not part of the grammar")
        raise EngineError(f"unsupported host statement {type(st).__name__}")

    def _truthy(self, v) -> bool:
        if isinstance(v, jax.Array):
            v = self.host_read(v, "cond")
        return bool(np.asarray(v).item() if hasattr(v, "item") else v)

    def _eval_host(self, e: Optional[fir.Expr]):
        if e is None:
            return None
        if isinstance(e, fir.IntLit):
            return e.value
        if isinstance(e, fir.FloatLit):
            return e.value
        if isinstance(e, fir.BoolLit):
            return e.value
        if isinstance(e, fir.StrLit):
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
                i = self._xlate(base.name, int(self._eval_host(e.index)))
                return self.host_read(self.state[base.name][i], "scalar").item()
            if isinstance(base, fir.Ident) and base.name == "argv":
                return self.argv[int(self._eval_host(e.index))]
            seq = self._eval_host(base)
            return seq[int(self._eval_host(e.index))]
        if isinstance(e, fir.BinOp):
            a = self._eval_host(e.lhs)
            b = self._eval_host(e.rhs)
            return {
                "+": lambda: a + b, "-": lambda: a - b, "*": lambda: a * b,
                "/": lambda: a / b, "==": lambda: a == b, "!=": lambda: a != b,
                "<": lambda: a < b, "<=": lambda: a <= b, ">": lambda: a > b,
                ">=": lambda: a >= b, "&": lambda: bool(a) and bool(b),
                "|": lambda: bool(a) or bool(b),
            }[e.op]()
        if isinstance(e, fir.UnaryOp):
            v = self._eval_host(e.operand)
            return (not v) if e.op == "!" else -v
        if isinstance(e, fir.Call):
            return self._host_call(e)
        if isinstance(e, fir.MethodCall):
            return self._host_method(e)
        raise EngineError(f"cannot evaluate host expression {type(e).__name__}")

    def _host_call(self, e: fir.Call):
        if e.func == "load":
            return None  # graph loading happened at engine construction
        if e.func == "swap":
            a, b = e.args
            an, bn = a.name, b.name  # type: ignore[attr-defined]
            self.state[an], self.state[bn] = self.state[bn], self.state[an]
            return None
        if e.func == "print":
            print(*[self._eval_host(a) for a in e.args])
            return None
        if e.func in self.module.host.host_funcs:
            self._exec_host_block(self.module.host.host_funcs[e.func].body)
            return None
        if e.func in semantic.DEVICE_BUILTINS:
            import math

            args = [self._eval_host(a) for a in e.args]
            fns = {
                "exp": math.exp, "log": math.log, "abs": abs, "sqrt": math.sqrt,
                "min": min, "max": max, "floor": math.floor, "pow": pow,
                "to_float": float, "to_int": int,
                "sigmoid": lambda x: 1.0 / (1.0 + math.exp(-x)),
                "leakyrelu": lambda x, a: x if x > 0 else a * x,
            }
            return fns[e.func](*args)
        raise EngineError(f"unknown host function {e.func!r}")

    def _host_method(self, e: fir.MethodCall):
        obj = e.obj
        name = obj.name if isinstance(obj, fir.Ident) else None
        g = self.module.graph
        if e.method == "size":
            # logical counts: padding (isolated vertices + self-loops) and
            # free update slots are invisible to size()-normalized math
            if name == g.edgeset_name:
                return self.graph.n_edges_logical
            return self.graph.n_vertices_logical
        if e.method in ("init", "process"):
            fn = e.args[0]
            if not isinstance(fn, fir.Ident):
                raise EngineError("init/process expects a function name")
            self.launch(fn.name)
            return None
        if e.method == "getVertices":
            return None  # vertexset binding is implicit
        if e.method in ("getOutDegrees", "getInDegrees"):
            return None  # handled at allocation time
        raise EngineError(f"unknown host method {e.method!r}")


# ---------------------------------------------------------------------------
# deprecated one-call shims (use repro.compile(...).bind(...).run(...))
# ---------------------------------------------------------------------------


def compile_source(src: str) -> mir.Module:
    """Deprecated: use ``repro.compile(src)`` which returns a cached
    :class:`~repro.core.program.Program` (this shim shares its cache)."""
    from .program import compile_program

    return compile_program(src).module


def run_source(
    src: str,
    graph: GraphData,
    options: Optional[CompileOptions] = None,
    argv: Optional[List[str]] = None,
) -> EngineResult:
    """Deprecated: use ``repro.compile(src, options).bind(graph).run(...)``."""
    from .program import compile_program

    module = compile_program(src, options).module
    return Engine(module, graph, options, argv=argv).run()
