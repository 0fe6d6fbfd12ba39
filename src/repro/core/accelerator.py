"""Accelerator artifacts: AOT-lowered, serializable compile products.

Graphitron's output is not an in-process interpreter but a *generated
accelerator*: the back-end lowers the algorithm against a hardware
description once, and the resulting artifact is deployed and rebound to
new graphs (paper §IV; the ThunderGP-style template flow ships
precompiled bitstreams rebound per graph). This module is that stage
split for the JAX substrate — the pipeline becomes

    program     = repro.compile(src, options)        # front-end + passes
    accelerator = program.lower(target, shape)       # AOT back-end, offline
    session     = accelerator.bind(graph)            # shape check only

* :class:`GraphShape` is the **shape bucket** an accelerator is lowered
  against: ``(n_vertices, n_edges, weighted)``. Every device buffer and
  graph-binding array has a shape fully determined by the bucket, so one
  lowering serves every graph in it — use :meth:`GraphShape.bucketed` and
  :meth:`repro.graph.storage.GraphData.pad_to` to coarsen buckets.
* :class:`KernelLibrary` holds the shape-generic lowered kernels (graph
  bindings are traced *arguments*, see
  :func:`repro.core.backend.lower_kernel_generic`) plus their AOT-compiled
  executables (``jax.jit(...).lower(specs).compile()``). The library is
  shared by every Session bound from one Accelerator: rebinds and process
  warm-starts never pay jit compilation again.
* :class:`Accelerator` is the deployable artifact: ``report()`` is the
  moral equivalent of an HLS resource report (per-kernel launch plan,
  FLOPs/bytes estimates, live-buffer peak), ``save(path)`` /
  :func:`load_accelerator` persist it (canonical MIR + target + pass
  report always; compiled executables where the backend supports
  serialization, transparent re-lower fallback otherwise).

Distributed targets lower lazily at bind (shard_map supersteps close over
the device mesh), but carry the same artifact metadata, report, and
persistence — ``load_accelerator`` still skips the front-end and pass
pipeline.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pickle
import time
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple, TYPE_CHECKING

import jax
import jax.numpy as jnp

from . import backend, mir
from .backend import DTYPES, WEIGHT_KEY
from .options import CompileOptions
from .target import Target
from .. import telemetry as tel

if TYPE_CHECKING:  # pragma: no cover - import cycle guards
    from ..graph.storage import GraphData
    from .program import Program
    from .session import BatchSession, Session, SessionPool

# format 2: logical_counts joined GB_ARRAY_KEYS (size() reads unpadded
# counts), changing the AOT executable signature — format-1 artifacts are
# rejected and re-lowered
# format 3: dst_sort_perm left GB_ARRAY_KEYS (shuffle targets stream edges
# in dst order), changing the signature again
ARTIFACT_FORMAT = 3
MANIFEST_NAME = "manifest.json"


class AcceleratorError(Exception):
    """Raised for shape/target mismatches and stale/corrupt artifacts."""


def accelerator_fingerprint(program_fingerprint: str, target: Target,
                            shape: "GraphShape") -> str:
    """Content identity of a lowered accelerator (program x target x shape).

    Computable without lowering — artifact stores key their directories on
    it, so a stale or foreign artifact simply lives at a different path.
    """
    h = hashlib.sha256()
    h.update(program_fingerprint.encode("ascii"))
    h.update(repr(target).encode("utf-8"))
    h.update(repr(shape).encode("utf-8"))
    return h.hexdigest()


@dataclass(frozen=True)
class GraphShape:
    """The shape bucket an Accelerator is lowered against.

    Two graphs with the same ``(n_vertices, n_edges, weighted)`` triple
    produce identically-shaped device buffers and graph-binding arrays, so
    they share one AOT lowering. Pad graphs up to a common bucket with
    :meth:`GraphData.pad_to` when their raw shapes differ.
    """

    n_vertices: int
    n_edges: int
    weighted: bool = False

    def __post_init__(self):
        if self.n_vertices < 1 or self.n_edges < 1:
            raise ValueError("GraphShape needs n_vertices >= 1 and n_edges >= 1")

    @staticmethod
    def of(graph: "GraphData") -> "GraphShape":
        return GraphShape(int(graph.n_vertices), int(graph.n_edges),
                          bool(graph.weighted))

    def bucketed(self, v_round: int = 1024, e_round: int = 4096) -> "GraphShape":
        """Round the shape up to multiples — a coarser bucket so more
        graphs alias one lowering (pad graphs with ``GraphData.pad_to``).

        Padding changes |V|/|E|, which globally-normalized algorithms
        (PageRank-class) observe — see the ``GraphData.pad_to`` docstring
        for the exact transparency contract before bucketing those.
        """

        def up(n, m):
            return ((n + m - 1) // m) * m

        return GraphShape(up(self.n_vertices, v_round),
                          up(self.n_edges, e_round), self.weighted)

    @classmethod
    def bucket_for(cls, n_vertices: int, n_edges: int, weighted: bool = False,
                   *, headroom: float = 0.125, ratio: float = 1.25,
                   v_base: int = 1024, e_base: int = 4096) -> "GraphShape":
        """Geometric shape bucket for a (possibly growing) logical graph.

        Linear rounding (:meth:`bucketed`) re-buckets every ``e_round``
        added edges — a stream of small deltas would churn lowerings.
        Geometric rounding grows buckets by ``ratio`` steps above a base,
        after adding ``headroom`` slack, so the number of distinct buckets
        (= lowerings) over any growth trajectory is logarithmic, and every
        fresh bucket arrives with free padding slots for
        :meth:`GraphData.apply_updates` to consume. Deterministic integer
        iteration — no float-log boundary jitter.
        """
        if n_vertices < 1 or n_edges < 1:
            raise ValueError("bucket_for needs n_vertices >= 1 and n_edges >= 1")

        def up(n: int, base: int) -> int:
            n = n + (n * int(headroom * 1024)) // 1024  # integer headroom
            b = base
            while b < n:
                b = max(b + 1, int(b * ratio))
            return b

        bv, be = up(n_vertices, v_base), up(n_edges, e_base)
        if be > n_edges and bv <= n_vertices:
            bv = max(bv + 1, int(bv * ratio))  # padded edges need a pad vertex
        return cls(bv, be, weighted)

    def accepts(self, graph: "GraphData") -> bool:
        return GraphShape.of(graph) == self

    def check_bucket(self, graph: "GraphData") -> None:
        """Raise unless ``graph`` can bind an accelerator of this bucket.

        Exact |V|/|E| match; a weighted graph may bind an unweighted bucket
        (the program never reads weights), but a weighted bucket promises
        weights the graph must have. The single source of truth for every
        bind-time check (Accelerator and KernelLibrary both delegate here).
        """
        got = GraphShape.of(graph)
        ok = (got.n_vertices == self.n_vertices
              and got.n_edges == self.n_edges
              and (got.weighted or not self.weighted))
        if not ok:
            raise AcceleratorError(
                f"graph shape ({got.describe()}) does not match the "
                f"accelerator's bucket ({self.describe()}); pad the graph "
                f"with GraphData.pad_to(...) or lower a new bucket"
            )

    def to_dict(self) -> dict:
        return {"n_vertices": self.n_vertices, "n_edges": self.n_edges,
                "weighted": self.weighted}

    def describe(self) -> str:
        return (f"|V|={self.n_vertices} |E|={self.n_edges} "
                f"{'weighted' if self.weighted else 'unweighted'}")


# ---------------------------------------------------------------------------
# AOT input signatures
# ---------------------------------------------------------------------------


def _state_specs(module: mir.Module, shape: GraphShape) -> Dict[str, Any]:
    """ShapeDtypeStruct tree of the full device state for a shape bucket."""
    specs: Dict[str, Any] = {}
    for p in module.properties.values():
        n = shape.n_edges if p.is_edge else shape.n_vertices
        specs[p.name] = jax.ShapeDtypeStruct((n,), DTYPES[p.scalar])
    if module.graph.weighted:
        wdt = DTYPES[module.graph.weight_scalar or "float"]
        specs[WEIGHT_KEY] = jax.ShapeDtypeStruct((shape.n_edges,), wdt)
    return specs


def _scalar_specs(module: mir.Module, kern) -> Dict[str, Any]:
    return {
        s: jax.ShapeDtypeStruct((), DTYPES[module.scalars[s].scalar])
        for s in sorted(kern.scalar_reads)
    }


# ---------------------------------------------------------------------------
# kernel library: shape-generic lowered kernels shared across binds
# ---------------------------------------------------------------------------


class KernelLibrary:
    """Shape-generic lowered kernels + AOT executables for one bucket.

    One library backs every Session bound from one Accelerator. All jit
    caches (full stream, compacted subsets per pad bucket, the frontier
    builder) live on shared function objects with graph bindings as traced
    arguments — so N same-bucket graphs, and every rebind after the first,
    share one compilation. ``warm_keys`` is the first-touch registry the
    engines consult for the compile/run time split: AOT-compiled kernels
    are born warm.
    """

    def __init__(self, module: mir.Module, target: Target, shape: GraphShape):
        self.module = module
        self.target = target
        self.shape = shape
        self.warm_keys: set = set()
        # what happened to the artifact's saved executables: loaded, or
        # lowered again because the saved bytes could not be used
        self.deserialized = 0
        self.relowered = 0
        self._frontier_build = None
        self._generic: Dict[str, backend.GenericLoweredKernel] = {}
        for name, kern in module.kernels.items():
            self._generic[name] = backend.lower_kernel_generic(
                module, kern, shape.n_vertices, shape.n_edges, target
            )

    # -- validation ----------------------------------------------------------
    def check_graph(self, graph: "GraphData") -> None:
        self.shape.check_bucket(graph)

    # -- AOT compilation -----------------------------------------------------
    def compile_all(self, blobs: Optional[Dict[str, Any]] = None) -> Tuple["KernelPlan", ...]:
        """AOT-compile every kernel's full-stream executable.

        ``blobs`` maps kernel name -> a serialized executable payload from
        a saved artifact (None where the artifact lists one that cannot be
        used here); entries that deserialize are loaded instead of
        recompiled, anything else re-lowers and counts in ``relowered``.
        """
        gb_specs = backend.gb_array_specs(self.shape.n_vertices, self.shape.n_edges)
        state_specs = _state_specs(self.module, self.shape)
        plans = []
        for name, g in self._generic.items():
            kern = self.module.kernels[name]
            scal_specs = _scalar_specs(self.module, kern)
            t0 = time.perf_counter()
            mode = "aot"
            compiled = None
            blob = (blobs or {}).get(name)
            if blob is not None:
                compiled = _deserialize_executable(blob)
                if compiled is not None:
                    mode = "aot-loaded"
                    self.deserialized += 1
            if compiled is None and blobs is not None and name in blobs:
                self.relowered += 1
            if compiled is None:
                compiled = g.jit_full.lower(
                    gb_specs, state_specs, scal_specs
                ).compile()
            g.compiled_full = compiled
            self.warm_keys.add(("full", name))
            plans.append(_kernel_plan(
                self.module, kern, compiled, mode,
                compile_time_s=time.perf_counter() - t0,
                shape=self.shape,
            ))
        return tuple(plans)

    # -- engine adapters -----------------------------------------------------
    def kernel_for(self, name: str, gb: Dict[str, Any]) -> backend.LoweredKernel:
        """Adapt the shape-generic kernel to one graph's binding arrays."""
        g = self._generic.get(name)
        if g is None:
            raise AcceleratorError(f"{name!r} is not a device kernel")
        gba = backend.split_gb_arrays(gb)
        compiled, jit_full = g.compiled_full, g.jit_full

        def run_full(state, scalars):
            if compiled is not None:
                return compiled(gba, state, scalars)
            return jit_full(gba, state, scalars)

        def trace_full(state, scalars):
            return g.raw_full(gba, state, scalars)

        run_subset = None
        if g.jit_subset is not None:
            def run_subset(state, scalars, batch):
                return g.jit_subset(gba, state, scalars, batch)

        return backend.LoweredKernel(
            name, g.kind, run_full=run_full, run_subset=run_subset,
            frontier=g.frontier, trace_full=trace_full, presorted=g.presorted,
        )

    def batched_for(self, name: str, gb: Dict[str, Any]):
        """Shared batch-axis executable for one graph's binding arrays.

        The vmapped trace lives on the generic kernel (one jit per library,
        graph bindings as an unbatched argument), so a rebind of the same
        accelerator reuses every batch-size trace already compiled — which
        keeps the engines' shared warm-key accounting truthful.
        """
        g = self._generic.get(name)
        if g is None:
            raise AcceleratorError(f"{name!r} is not a device kernel")
        if g.jit_batched is None:
            g.jit_batched = backend.gt_jit(
                jax.vmap(g.raw_full, in_axes=(None, 0, 0)), name + "_batched"
            )
        gba = backend.split_gb_arrays(gb)
        jit_batched = g.jit_batched

        def run(state, scalars):
            return jit_batched(gba, state, scalars)

        return run

    def frontier_builder(self):
        """Shared jitted frontier expansion (graph arrays as arguments).

        One builder per library: every bind of the accelerator reuses the
        (pad_v, pad_e) buckets any previous bind compiled.
        """
        if self._frontier_build is None:
            self._frontier_build = backend.make_frontier_builder(
                self.shape.n_vertices, self.shape.n_edges,
                self.module.graph.weighted,
            )
        return self._frontier_build


# ---------------------------------------------------------------------------
# resource report (the HLS report analogue)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class KernelPlan:
    """Per-kernel launch plan + cost estimates of one lowered accelerator."""

    name: str
    kind: str  # 'vertex' | 'edge' | 'pipeline'
    stages: Tuple[str, ...]  # fused stage names (pipelines), else ()
    direction: str  # compile-time push/pull verdict ('auto' pre-pass)
    mode: str  # 'aot' | 'aot-loaded' | 'lazy'
    flops: Optional[float] = None  # per full-stream launch (XLA estimate)
    bytes_accessed: Optional[float] = None
    arg_bytes: Optional[int] = None
    out_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    compile_time_s: float = 0.0


def _xla_estimates(compiled) -> Dict[str, Optional[float]]:
    """Best-effort XLA cost/memory estimates for one AOT executable.

    Interpreted/CPU backends (and deserialized executables on some JAX
    versions) may not implement ``cost_analysis``/``memory_analysis``,
    may return empty results, or may raise — every failure mode degrades
    to explicit ``None`` estimates here. Callers (``report()``, the
    :mod:`repro.autotune` cost model) treat ``None`` as "unknown"; an
    unavailable estimate must never crash a report or a tuning trial.
    """
    est: Dict[str, Optional[float]] = {
        "flops": None, "bytes_accessed": None,
        "arg_bytes": None, "out_bytes": None, "temp_bytes": None,
    }
    if compiled is None:
        return est
    with contextlib.suppress(Exception):
        cost = compiled.cost_analysis()
        entry = cost[0] if isinstance(cost, (list, tuple)) else cost
        if entry:
            est["flops"] = float(entry.get("flops", 0.0)) or None
            est["bytes_accessed"] = (
                float(entry.get("bytes accessed", 0.0)) or None
            )
    with contextlib.suppress(Exception):
        m = compiled.memory_analysis()
        est["arg_bytes"] = int(m.argument_size_in_bytes)
        est["out_bytes"] = int(m.output_size_in_bytes)
        est["temp_bytes"] = int(m.temp_size_in_bytes)
    return est


def _kernel_plan(module, kern, compiled, mode, compile_time_s, shape) -> KernelPlan:
    est = _xla_estimates(compiled)
    flops, bytes_accessed = est["flops"], est["bytes_accessed"]
    arg_bytes, out_bytes, temp_bytes = (
        est["arg_bytes"], est["out_bytes"], est["temp_bytes"]
    )
    if flops is None:
        # static fallback: one op-estimate per streamed lane per access
        lanes = shape.n_edges if kern.kind is mir.KernelKind.EDGE else shape.n_vertices
        if isinstance(kern, mir.PipelineKernel):
            lanes = sum(
                shape.n_edges if s.kind is mir.KernelKind.EDGE else shape.n_vertices
                for s in kern.stages
            )
            accesses = sum(len(s.reads) + len(s.writes) for s in kern.stages)
        else:
            accesses = len(kern.reads) + len(kern.writes)
        flops = float(lanes * max(1, accesses))
    stages = tuple(s.name for s in kern.stages) if isinstance(kern, mir.PipelineKernel) else ()
    direction = getattr(getattr(kern, "direction", None), "value", "auto")
    return KernelPlan(
        name=kern.name, kind=kern.kind.value, stages=stages,
        direction=direction, mode=mode, flops=flops,
        bytes_accessed=bytes_accessed, arg_bytes=arg_bytes,
        out_bytes=out_bytes, temp_bytes=temp_bytes,
        compile_time_s=compile_time_s,
    )


@dataclass(frozen=True)
class AcceleratorReport:
    """Queryable resource report of one lowered accelerator."""

    target: Target
    shape: GraphShape
    kernels: Tuple[KernelPlan, ...]
    state_bytes: int  # device property buffers (+ weights)
    gb_bytes: int  # graph-binding arrays (the Burst Read plan)
    live_buffer_peak_bytes: int  # resident state+plan+worst kernel temps
    lower_time_s: float
    pass_report: Tuple[str, ...] = ()
    #: determinism certificate from repro.analysis (deterministic /
    #: reduction-deterministic / racy) — also stored in artifact manifests
    determinism: str = "unknown"

    @property
    def total_flops_per_launch_set(self) -> float:
        return sum(k.flops or 0.0 for k in self.kernels)

    def describe(self) -> str:
        lines = [
            f"accelerator [{self.target.describe()}] {self.shape.describe()}",
            f"  buffers: state {_fmt_bytes(self.state_bytes)}, "
            f"graph plan {_fmt_bytes(self.gb_bytes)}, "
            f"live peak {_fmt_bytes(self.live_buffer_peak_bytes)}",
            f"  lowered in {self.lower_time_s:.3f}s "
            f"({sum(1 for k in self.kernels if k.mode.startswith('aot'))}"
            f"/{len(self.kernels)} kernels AOT)",
            f"  determinism: {self.determinism}",
        ]
        for k in self.kernels:
            extra = f" = {' -> '.join(k.stages)}" if k.stages else ""
            cost = f"{k.flops:.3g} flops" if k.flops else "?"
            if k.bytes_accessed:
                cost += f", {_fmt_bytes(int(k.bytes_accessed))} accessed"
            lines.append(
                f"  kernel {k.name} [{k.kind}{extra}] {k.mode} "
                f"dir={k.direction} ~{cost} "
                f"(compile {k.compile_time_s * 1e3:.0f}ms)"
            )
        for entry in self.pass_report:
            lines.append(f"  pass {entry}")
        return "\n".join(lines)


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024
    return f"{n}B"  # pragma: no cover


def _module_state_bytes(module: mir.Module, shape: GraphShape) -> int:
    total = 0
    for p in module.properties.values():
        n = shape.n_edges if p.is_edge else shape.n_vertices
        total += n * jnp.dtype(DTYPES[p.scalar]).itemsize
    if module.graph.weighted:
        wdt = DTYPES[module.graph.weight_scalar or "float"]
        total += shape.n_edges * jnp.dtype(wdt).itemsize
    return total


# ---------------------------------------------------------------------------
# executable serialization (best-effort; re-lower is always a valid fallback)
# ---------------------------------------------------------------------------


def _serialize_executable(compiled) -> Optional[bytes]:
    try:
        from jax.experimental import serialize_executable

        return pickle.dumps(serialize_executable.serialize(compiled))
    except Exception:
        return None


def _deserialize_executable(payload: bytes):
    try:
        from jax.experimental import serialize_executable

        return serialize_executable.deserialize_and_load(*pickle.loads(payload))
    except Exception:
        return None


# ---------------------------------------------------------------------------
# the artifact
# ---------------------------------------------------------------------------


class Accelerator:
    """An AOT-lowered Graphitron accelerator for one (target, shape bucket).

    Produced by ``program.lower(target, shape)``. Bind it to any graph of
    the bucket — ``bind`` performs a shape/padding check only and returns a
    ready-warm :class:`~repro.core.session.Session`. ``save``/:func:
    `load_accelerator` persist it across processes.
    """

    def __init__(self, program: "Program", target: Target, shape: GraphShape,
                 *, _blobs: Optional[Dict[str, Optional[bytes]]] = None,
                 _tuned: Optional[Dict[str, Any]] = None):
        module = program.module
        if module.graph.weighted and not shape.weighted:
            raise AcceleratorError(
                "program declares a weighted edgeset but the shape bucket is "
                "unweighted; lower with GraphShape(..., weighted=True)"
            )
        self.program = program
        self.target = target
        self.shape = shape
        self.fingerprint = accelerator_fingerprint(
            program.fingerprint, target, shape
        )
        # provenance of an autotuned Target (a TunedConfig dict from
        # repro.autotune, stamped by the tuner / tuned lowering paths);
        # persisted in the artifact manifest so a warm-started process
        # knows it runs a tuned substrate without re-searching
        self.tuned: Optional[Dict[str, Any]] = (
            dict(_tuned) if _tuned else None
        )
        tr = tel.get()
        sp = tr.span(
            "lower", fingerprint=self.fingerprint[:16], target=target.kind,
            bucket=f"{shape.n_vertices}v/{shape.n_edges}e",
            from_artifact=_blobs is not None,
        ) if tr.enabled else tel.NULL_SPAN
        t0 = time.perf_counter()
        with sp:
            if target.kind == "local":
                self.library: Optional[KernelLibrary] = KernelLibrary(
                    module, target, shape
                )
                self._plans = self.library.compile_all(blobs=_blobs)
            else:
                # distributed supersteps close over the device mesh: lowered
                # lazily at bind, but the artifact metadata/report still holds
                self.library = None
                self._plans = tuple(
                    _kernel_plan(module, k, None, "lazy", 0.0, shape)
                    for k in module.kernels.values()
                )
        self.lower_time_s = time.perf_counter() - t0
        self.binds = 0
        self.serialized = 0  # executables written by the last save()

    # -- introspection -------------------------------------------------------
    def executable_counts(self) -> Dict[str, int]:
        """Kernels whose executables the last ``save`` serialized, that were
        deserialized from an artifact, and that were re-lowered because an
        artifact's executable could not be loaded."""
        lib = self.library
        return {
            "serialized": self.serialized,
            "deserialized": lib.deserialized if lib is not None else 0,
            "relowered": lib.relowered if lib is not None else 0,
        }

    def report(self) -> AcceleratorReport:
        """The HLS-resource-report analogue for this lowering."""
        module = self.program.module
        state_bytes = _module_state_bytes(module, self.shape)
        gb_bytes = 4 * (
            (len(backend.GB_ARRAY_KEYS) - 2) * self.shape.n_edges
            + self.shape.n_vertices  # orig_id is [V]
            + 2  # logical_counts is [2]
        )
        temps = [k.temp_bytes or 0 for k in self._plans]
        outs = [k.out_bytes or 0 for k in self._plans]
        peak = state_bytes + gb_bytes + max(
            (t + o for t, o in zip(temps, outs)), default=0
        )
        return AcceleratorReport(
            target=self.target, shape=self.shape, kernels=self._plans,
            state_bytes=state_bytes, gb_bytes=gb_bytes,
            live_buffer_peak_bytes=peak, lower_time_s=self.lower_time_s,
            pass_report=tuple(module.pass_report),
            determinism=self._determinism(),
        )

    def _determinism(self) -> str:
        from ..analysis import determinism_certificate

        return determinism_certificate(self.program.module)

    def __repr__(self) -> str:
        return (
            f"Accelerator({self.fingerprint[:12]}, {self.target.describe()}, "
            f"{self.shape.describe()}, kernels={len(self._plans)})"
        )

    # -- binding -------------------------------------------------------------
    def _check(self, graph: "GraphData") -> None:
        self.shape.check_bucket(graph)

    def _backend_opts(self, extra: Dict[str, Any]) -> Dict[str, Any]:
        opts = dict(extra)
        opts["target"] = self.target
        if self.target.kind == "local":
            opts["library"] = self.library
        else:
            opts.setdefault("mesh", self.target.mesh())
            opts.setdefault("axis", self.target.axis)
        return opts

    def bind(self, graph: "GraphData", *, argv: Optional[list] = None,
             **backend_opts) -> "Session":
        """Place this accelerator onto a graph of the bucket shape.

        A shape/padding check is the only per-graph work: the returned
        Session reuses the artifact's AOT executables, so N graphs of one
        bucket — and every process restart via :func:`load_accelerator` —
        share a single lowering.
        """
        from .session import Session

        self._check(graph)
        self.binds += 1
        tr = tel.get()
        sp = tr.span(
            "bind", fingerprint=self.fingerprint[:16],
            n_vertices=graph.n_vertices, n_edges=graph.n_edges,
        ) if tr.enabled else tel.NULL_SPAN
        with sp:
            session = Session(self.program, graph, backend=self.target.kind,
                              argv=argv, **self._backend_opts(backend_opts))
        session.accelerator = self
        return session

    def pool(self, graph: "GraphData", size: int = 2, *,
             argv: Optional[list] = None, **backend_opts) -> "SessionPool":
        """A SessionPool over one bucket graph; every worker shares the
        artifact's kernel library (no per-worker compile cost)."""
        from .session import SessionPool

        self._check(graph)
        self.binds += 1
        return SessionPool(self.program, graph, backend=self.target.kind,
                           size=size, argv=argv,
                           **self._backend_opts(backend_opts))

    def bind_batch(self, graph: "GraphData", *, argv: Optional[list] = None,
                   max_batch: Optional[int] = None, msbfs: bool = True,
                   **backend_opts) -> "BatchSession":
        """Batched multi-query twin of :meth:`bind` (see Program.bind_batch)."""
        from .session import BatchSession

        self._check(graph)
        self.binds += 1
        session = BatchSession(self.program, graph, backend=self.target.kind,
                               argv=argv, max_batch=max_batch, msbfs=msbfs,
                               **self._backend_opts(backend_opts))
        session.accelerator = self
        return session

    # -- persistence ---------------------------------------------------------
    def save(self, path: str, include_executables: bool = True) -> str:
        """Persist this accelerator to a directory artifact.

        Always written: the manifest (format/fingerprints/target/shape/
        options/pass report), the ``.gt`` source, and the canonical
        serialized MIR. When the JAX backend supports executable
        serialization (and ``include_executables``), the AOT executables
        are stored too; otherwise :func:`load_accelerator` transparently
        re-lowers from the MIR.
        """
        os.makedirs(path, exist_ok=True)
        opts = self.program.options
        kernels_manifest: Dict[str, Dict[str, Any]] = {}
        exe_dir = os.path.join(path, "executables")
        self.serialized = 0
        for plan in self._plans:
            entry: Dict[str, Any] = {"mode": plan.mode, "executable": None}
            if include_executables and self.library is not None:
                g = self.library._generic.get(plan.name)
                payload = (
                    _serialize_executable(g.compiled_full)
                    if g is not None and g.compiled_full is not None else None
                )
                if payload is not None:
                    os.makedirs(exe_dir, exist_ok=True)
                    rel = os.path.join("executables", f"{plan.name}.bin")
                    with open(os.path.join(path, rel), "wb") as f:
                        f.write(payload)
                    entry["executable"] = rel
                    self.serialized += 1
            kernels_manifest[plan.name] = entry
        manifest = {
            "format": ARTIFACT_FORMAT,
            "jax_version": jax.__version__,
            "jax_backend": jax.default_backend(),
            "fingerprint": self.fingerprint,
            "program_fingerprint": self.program.fingerprint,
            "mir_fingerprint": mir.fingerprint(self.program.module),
            "target": self.target.to_dict(),
            "shape": self.shape.to_dict(),
            "options": {
                "passes": opts.passes,
                "scalar_bindings": [list(b) for b in opts.scalar_bindings],
                "target_overrides": [list(o) for o in opts.target_overrides],
            },
            "pass_report": list(self.program.module.pass_report),
            "determinism": self._determinism(),
            "kernels": kernels_manifest,
            "tuned": self.tuned,
        }
        with open(os.path.join(path, "program.gt"), "w") as f:
            f.write(self.program.source)
        with open(os.path.join(path, "mir.txt"), "w") as f:
            f.write(mir.canonical_serialize(self.program.module))
        with open(os.path.join(path, MANIFEST_NAME), "w") as f:
            json.dump(manifest, f, indent=2, sort_keys=True)
            f.write("\n")
        return path


def quarantine_artifact(path: str) -> Optional[str]:
    """Move a failed artifact directory aside so it is never re-probed.

    Re-lowering after a load failure overwrites the directory in place
    (``save`` is the normal heal path), but serving registries want the
    failed content *out of the key's path* atomically — otherwise every
    request between the failure and the heal retries the same corrupt
    load (a stale-artifact retry storm). A rename keeps the bytes around
    for postmortem under ``<path>.quarantined[.N]``. Best-effort: returns
    the new path, or None when the store does not permit the rename.
    """
    for i in range(1000):
        dst = f"{path}.quarantined" + ("" if i == 0 else f".{i}")
        if os.path.exists(dst):
            continue
        try:
            os.rename(path, dst)
            return dst
        except OSError:
            return None
    return None  # pragma: no cover - 1000 quarantines of one key


def load_or_lower(program: "Program", target: Target, shape: GraphShape,
                  artifact_dir: str) -> Tuple[Accelerator, bool, float]:
    """Resolve an accelerator from an artifact store, lowering on a miss.

    Artifact directories are keyed by :func:`accelerator_fingerprint`, so a
    stale or foreign artifact is simply not found (and a corrupt one fails
    its load check and is re-lowered). On a miss the fresh lowering is
    saved back best-effort — an unwritable store degrades to cold lowering,
    never to a failure. Returns ``(accelerator, loaded, seconds)`` where
    ``seconds`` is the load or lower wall time. This is the one shared
    resolution path (serve warm-start, ci_bench warm-bind gate).
    """
    key = accelerator_fingerprint(program.fingerprint, target, shape)
    path = os.path.join(artifact_dir, key[:24])
    if os.path.isdir(path):
        # corrupt/stale content at a matching path: a tampered manifest
        # or truncated source raises anything from AcceleratorError to
        # ProgramError/ValueError — every load failure means re-lower
        with contextlib.suppress(Exception):
            t0 = time.perf_counter()
            acc = load_accelerator(path)
            return acc, True, time.perf_counter() - t0
    t0 = time.perf_counter()
    acc = Accelerator(program, target, shape)
    dt = time.perf_counter() - t0
    # artifact store not writable: cold result is still valid
    with contextlib.suppress(OSError):
        acc.save(path)
    return acc, False, dt


def load_accelerator(path: str) -> Accelerator:
    """Load a saved accelerator artifact (see :meth:`Accelerator.save`).

    The source is recompiled through the (front-end) Program cache and the
    result is verified against the stored program fingerprint — a drifted
    toolchain or edited artifact fails loudly instead of running a program
    that no longer matches its executables. Stored executables are loaded
    where the current JAX backend can deserialize them; anything else
    re-lowers transparently.
    """
    from .program import compile_program

    manifest_path = os.path.join(path, MANIFEST_NAME)
    try:
        with open(manifest_path) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise AcceleratorError(f"cannot read accelerator manifest: {e}") from e
    if manifest.get("format") != ARTIFACT_FORMAT:
        raise AcceleratorError(
            f"unsupported artifact format {manifest.get('format')!r} "
            f"(this build reads format {ARTIFACT_FORMAT})"
        )
    try:
        with open(os.path.join(path, "program.gt")) as f:
            source = f.read()
    except OSError as e:
        raise AcceleratorError(f"artifact is missing program.gt: {e}") from e
    o = manifest.get("options", {})
    options = CompileOptions(
        passes=o.get("passes", "default"),
        scalar_bindings=tuple(tuple(b) for b in o.get("scalar_bindings", [])),
        target_overrides=tuple(tuple(t) for t in o.get("target_overrides", [])),
    )
    program = compile_program(source, options)
    if program.fingerprint != manifest.get("program_fingerprint"):
        raise AcceleratorError(
            "stale accelerator artifact: recompiling its source yields a "
            "different program fingerprint (source/options/toolchain drift); "
            "re-lower with program.lower(target, shape) and save again"
        )
    # kernel -> saved executable bytes; None marks one the artifact lists
    # but this process cannot use (other JAX or backend, unreadable file),
    # so compile_all counts its re-lowering
    blobs: Dict[str, Optional[bytes]] = {}
    same_runtime = (manifest.get("jax_version") == jax.__version__
                    and manifest.get("jax_backend") == jax.default_backend())
    for name, entry in manifest.get("kernels", {}).items():
        rel = entry.get("executable")
        if rel:
            blobs[name] = None
            if same_runtime:
                with contextlib.suppress(OSError), \
                        open(os.path.join(path, rel), "rb") as f:
                    blobs[name] = f.read()
    target = Target.from_dict(manifest["target"])
    shape = GraphShape(**manifest["shape"])
    tuned = manifest.get("tuned")
    return Accelerator(program, target, shape, _blobs=blobs or None,
                       _tuned=tuned if isinstance(tuned, dict) else None)
