"""Sessions: a bound (program, graph, backend) triple you run many times.

A :class:`Session` owns lowered kernels and device state for one graph on
one execution backend, and exposes exactly one way to execute: explicit,
validated keyword parameters —

    session = program.bind(graph, backend="local")
    result = session.run(root=3)

replacing the old pattern of constructing an ``Engine`` by hand and
mutating ``engine.host_env`` between runs. Backends implement the
:class:`ExecutionBackend` protocol; "local" wraps the single-device
:class:`~repro.core.engine.Engine` and "distributed" wraps the
multi-device :class:`~repro.core.dist_engine.DistEngine` (shard_map +
all_to_all shuffle supersteps). New backends register via
:func:`register_backend`.

:class:`SessionPool` holds N sessions over the same bound graph and
serves batch/async query streams — the serving path used by
``repro.launch.serve --graph``.

:class:`BatchSession` (``program.bind_batch(graph)``) answers K parameter
bindings per launch set through :class:`repro.batch.BatchEngine`; both
``Session.run_many`` and ``SessionPool.run_batch`` reroute batch-eligible
query lists through it automatically, falling back to the sequential path
otherwise. Any backend registered via :func:`register_backend` whose
:class:`ExecutionBackend` exposes an ``engine`` attribute (an
:class:`~repro.core.engine.Engine` subclass) serves batches through its
own launch strategy — the local and distributed engines both do.
"""
from __future__ import annotations

import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Protocol, Sequence, runtime_checkable

from .engine import EngineResult
from .program import Program, ProgramError

try:  # pragma: no cover - trivially importable in-repo
    from ..graph.storage import GraphData
except ImportError:  # pragma: no cover
    GraphData = Any  # type: ignore


class SessionError(Exception):
    pass


class ServiceClosed(SessionError):
    """Submission to a closed serving surface (pool, batcher, scheduler,
    service). Typed so clients can distinguish "shut down, stop sending"
    from a genuine execution failure — previously a closed pool could
    surface a raw executor/queue RuntimeError instead."""


@runtime_checkable
class ExecutionBackend(Protocol):
    """What a backend must provide to host a Session.

    The lifecycle per ``run()`` is reset -> apply_params -> execute; the
    backend keeps compiled/lowered kernels warm across the reset.
    """

    name: str

    def reset(self) -> None:  # pragma: no cover - protocol
        ...

    def apply_params(self, params: Dict[str, Any]) -> None:  # pragma: no cover
        ...

    def execute(self) -> EngineResult:  # pragma: no cover
        ...


class EngineBackend:
    """Backend over any :class:`~repro.core.engine.Engine` (sub)class: the
    run lifecycle (reset -> apply_params -> execute) is engine-independent,
    so every engine flavor shares this one implementation."""

    def __init__(self, name: str, engine):
        self.name = name
        self.engine = engine

    def reset(self) -> None:
        self.engine.reset()

    def apply_params(self, params: Dict[str, Any]) -> None:
        self.engine.host_env.update(params)

    def execute(self) -> EngineResult:
        return self.engine.run()


def LocalBackend(program: Program, graph: GraphData,
                 argv: Optional[list] = None, target=None,
                 library=None) -> EngineBackend:
    """Single-device execution: the paper's one-accelerator system.

    ``target`` pins the execution substrate explicitly (otherwise resolved
    from the program's CompileOptions); ``library`` is an AOT kernel
    library from :meth:`repro.core.accelerator.Accelerator` — when given,
    the engine starts warm (no per-bind jit compilation).
    """
    from .engine import Engine

    return EngineBackend(
        "local",
        Engine(program.module, graph, program.options, argv=argv,
               target=target, library=library),
    )


def DistributedBackend(program: Program, graph: GraphData,
                       argv: Optional[list] = None, mesh=None,
                       axis: str = "data", target=None) -> EngineBackend:
    """Multi-device execution: edge kernels become shuffle supersteps
    across the device mesh (ForeGraph-style multi-accelerator scaling)."""
    from .dist_engine import DistEngine

    return EngineBackend(
        "distributed",
        DistEngine(program.module, graph, program.options, argv=argv,
                   mesh=mesh, axis=axis, target=target),
    )


_BACKENDS: Dict[str, Callable[..., ExecutionBackend]] = {}


def register_backend(name: str, factory: Callable[..., ExecutionBackend]) -> None:
    """Register an execution backend under ``name`` for Program.bind()."""
    _BACKENDS[name] = factory


def backend_names() -> List[str]:
    return sorted(_BACKENDS)


register_backend("local", LocalBackend)
register_backend("distributed", DistributedBackend)


# chunk size for the implicit BatchSessions behind Session.run_many /
# SessionPool.run_batch: every distinct batch size K is a fresh XLA trace
# of all kernels at state shape [K, n], so the automatic reroute caps K —
# the possible trace shapes are then bounded (at most AUTO_MAX_BATCH of
# them) no matter how query-list lengths vary across calls. Explicit
# bind_batch() callers pick their own max_batch.
AUTO_MAX_BATCH = 64


def batch_eligible(coerced_sets: Sequence[Dict[str, Any]]) -> bool:
    """True when a list of validated parameter sets can share one batch.

    Eligibility is purely structural: every set binds the SAME parameter
    names (values are scalars by construction — ``validate_params`` already
    coerced them), so one vectorized state layout fits all of them. Mixed
    key sets (e.g. some queries overriding ``iters`` and some not) fall
    back to the sequential path.
    """
    if not coerced_sets:
        return False
    keys = set(coerced_sets[0])
    return all(set(p) == keys for p in coerced_sets[1:])


class Session:
    """One program bound to one graph on one backend; run it many times.

    ``run(**params)`` validates the keyword parameters against the
    program's declared host scalars, resets device/host state (keeping
    lowered kernels warm), applies the parameters, and executes.
    """

    def __init__(self, program: Program, graph: GraphData, backend: str = "local",
                 *, argv: Optional[list] = None, **backend_opts):
        if backend not in _BACKENDS:
            raise SessionError(
                f"unknown backend {backend!r}; available: {backend_names()}"
            )
        self.program = program
        self.graph = graph
        self.backend_name = backend
        argv = list(argv) if argv is not None else ["prog", "<graph>"]
        self._argv = argv
        self._backend_opts = dict(backend_opts)
        self.backend: ExecutionBackend = _BACKENDS[backend](
            program, graph, argv=argv, **backend_opts
        )
        self.runs = 0
        # set by Accelerator.bind: the artifact this session was bound from
        self.accelerator = None
        self._batch_session: Optional["BatchSession"] = None
        self._batch_unsupported = False
        self._batch_init_lock = threading.Lock()
        self._lock = threading.Lock()

    def run(self, **params) -> EngineResult:
        """Execute the bound program with explicit run-time parameters."""
        coerced = self.program.validate_params(params)
        with self._lock:  # a Session is a stateful device context
            self.backend.reset()
            self.backend.apply_params(coerced)
            result = self.backend.execute()
            self.runs += 1
        return result

    def run_many(self, param_sets: Sequence[Dict[str, Any]],
                 batched: Optional[bool] = None) -> List[EngineResult]:
        """Run a sequence of parameter sets; results in submission order.

        Results are **element-wise identical** to calling :meth:`run` once
        per set, in order: ``run_many(ps)[i]`` carries bit-identical
        properties and host scalars to ``run(**ps[i])``. When the sets are
        batch-eligible (two or more sets sharing one parameter key set —
        see :func:`batch_eligible`) and the backend exposes an engine, the
        queries are answered by ONE batched execution
        (:class:`BatchSession`) whose launches serve all K lanes at once;
        otherwise the sequential loop runs. ``batched=True`` forces the
        batched path (raising if ineligible), ``batched=False`` forces the
        sequential loop; the default picks automatically. Only the
        ``stats`` objects differ between the two paths: batched results
        share one :class:`~repro.core.engine.EngineStats` with
        ``batch_size == K`` and per-batch launch counters.
        """
        sets = [dict(p) for p in param_sets]
        if batched is None:
            coerced = [self.program.validate_params(p) for p in sets]
            batched = len(sets) > 1 and batch_eligible(coerced)
            if batched and self._ensure_batch_session() is None:
                batched = False
        if batched:
            bs = self._ensure_batch_session()
            if bs is None:
                raise SessionError(
                    f"backend {self.backend_name!r} does not expose an engine "
                    "for batched execution"
                )
            return bs.run_many(sets)
        return [self.run(**p) for p in sets]

    def refresh_graph(self, graph: Optional[GraphData] = None) -> None:
        """Rebind after an in-place graph mutation (streaming update path).

        Re-derives the backend engine's graph-dependent bindings (hub
        relabeling, processing order, CSR/CSC device arrays) against the
        updated — same-shape — graph. Only callable on backends exposing an
        ``engine``. The caller must guarantee no query is in flight (the
        :class:`repro.streaming.StreamingSession` write gate does);
        this method still takes the session lock as a second line of
        defense against torn reads.
        """
        graph = graph if graph is not None else self.graph
        engine = getattr(self.backend, "engine", None)
        if engine is None:
            raise SessionError(
                f"backend {self.backend_name!r} does not expose an engine; "
                "cannot refresh its graph binding in place"
            )
        with self._lock:
            self.graph = graph
            engine.refresh_graph(graph)
        if self._batch_session is not None:
            self._batch_session.refresh_graph(graph)

    def _ensure_batch_session(self) -> Optional["BatchSession"]:
        """Lazily build the batched twin of this session (None if the
        backend cannot host one; the failure is memoized so engine-less
        backends don't rebuild-and-discard a backend per call)."""
        with self._batch_init_lock:
            if self._batch_session is None and not self._batch_unsupported:
                try:
                    self._batch_session = BatchSession(
                        self.program, self.graph, backend=self.backend_name,
                        argv=self._argv, max_batch=AUTO_MAX_BATCH,
                        **self._backend_opts,
                    )
                except SessionError:
                    self._batch_unsupported = True
            return self._batch_session

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        """Release the session (hook for future device-owning backends)."""
        if self._batch_session is not None:
            self._batch_session.close()

    def __repr__(self) -> str:
        return (
            f"Session({self.program.fingerprint[:12]} on {self.backend_name}, "
            f"|V|={getattr(self.graph, 'n_vertices', '?')}, runs={self.runs})"
        )


class BatchSession:
    """One program bound to one graph, answering K queries per launch set.

    Created by ``program.bind_batch(graph, backend=...)``. ``run_many``
    takes a list of parameter sets that share one key set and executes
    them as a single batched run: state gains a leading batch axis, host
    control flow runs with per-query active masks, and BFS-like frontier
    programs automatically take the bit-packed multi-source path
    (:mod:`repro.batch.msbfs`). Results are element-wise **bit-identical**
    to sequential :meth:`Session.run` calls, in submission order.

    Works on any registered backend whose :class:`ExecutionBackend`
    exposes an ``engine`` attribute (the local and distributed engines
    both do): the batch engine drives that engine's own per-launch
    batching hooks, so e.g. distributed edge kernels still run as shuffle
    supersteps — one vmapped all_to_all round for the whole batch.

    ``max_batch`` chunks oversized query lists (a new batch size means a
    new XLA trace, so serving paths pick one size and stick to it);
    ``msbfs=False`` disables the multi-source BFS fast path (the generic
    vmapped path then serves BFS too).
    """

    def __init__(self, program: Program, graph: GraphData, backend: str = "local",
                 *, argv: Optional[list] = None, max_batch: Optional[int] = None,
                 msbfs: bool = True, **backend_opts):
        if backend not in _BACKENDS:
            raise SessionError(
                f"unknown backend {backend!r}; available: {backend_names()}"
            )
        if max_batch is not None and max_batch < 1:
            raise SessionError("max_batch must be >= 1")
        self.program = program
        self.graph = graph
        self.backend_name = backend
        argv = list(argv) if argv is not None else ["prog", "<graph>"]
        self.backend: ExecutionBackend = _BACKENDS[backend](
            program, graph, argv=argv, **backend_opts
        )
        inner = getattr(self.backend, "engine", None)
        if inner is None:
            raise SessionError(
                f"backend {backend!r} does not expose an engine attribute; "
                "batched execution needs one (see ExecutionBackend)"
            )
        from ..batch.engine import BatchEngine

        self.engine = BatchEngine(inner, enable_msbfs=msbfs)
        self.max_batch = max_batch
        self.runs = 0
        self.queries = 0
        # set by Accelerator.bind_batch: the artifact this session was bound from
        self.accelerator = None
        self._lock = threading.Lock()

    def run_many(self, param_sets: Sequence[Dict[str, Any]]) -> List[EngineResult]:
        """Answer every parameter set in one (or few) batched executions.

        All sets must share one parameter key set; raises
        :class:`SessionError` otherwise (use :meth:`Session.run_many` for
        mixed streams — it falls back to the sequential path).
        """
        coerced = [self.program.validate_params(dict(p)) for p in param_sets]
        if not coerced:
            return []
        if not batch_eligible(coerced):
            raise SessionError(
                "param sets are not batch-eligible: every set must bind the "
                "same parameter names (Session.run_many handles mixed streams)"
            )
        step = self.max_batch or len(coerced)
        out: List[EngineResult] = []
        with self._lock:  # one batched device context
            for i in range(0, len(coerced), step):
                chunk = coerced[i:i + step]
                out.extend(self.engine.run_batch(chunk))
                self.runs += 1
                self.queries += len(chunk)
        return out

    def refresh_graph(self, graph: Optional[GraphData] = None) -> None:
        """Rebind after an in-place graph mutation (see Session.refresh_graph)."""
        graph = graph if graph is not None else self.graph
        with self._lock:
            self.graph = graph
            self.engine.engine.refresh_graph(graph)  # inner Engine
            self.engine.refresh_graph()  # BatchEngine re-points its snapshot

    def __enter__(self) -> "BatchSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        """Release the session (hook for future device-owning backends)."""

    def __repr__(self) -> str:
        return (
            f"BatchSession({self.program.fingerprint[:12]} on "
            f"{self.backend_name}, |V|={getattr(self.graph, 'n_vertices', '?')}, "
            f"runs={self.runs}, queries={self.queries})"
        )


class SessionPool:
    """N worker sessions over one (program, graph, backend): batch serving.

    Each worker owns an independent session (its own device state and its
    own jitted kernels), so queries execute concurrently — but each worker
    also pays its own one-time kernel-compilation cost on its first run;
    call :meth:`warmup` before latency-sensitive serving. ``submit``
    returns a Future; ``run_batch`` preserves submission order in its
    result list.

    ``batch=N`` (N > 1) turns on **dynamic batching**: submitted queries
    are collected by a :class:`repro.batch.DynamicBatcher` into groups of
    up to N (waiting ``batch_wait_s`` for stragglers) and answered by one
    shared :class:`BatchSession` instead of N worker runs — same results,
    same Future surface, far fewer launches. ``pool.batch_stats`` then
    reports batch occupancy.
    """

    def __init__(self, program: Program, graph: GraphData, backend: str = "local",
                 size: int = 2, *, argv: Optional[list] = None, batch: int = 0,
                 batch_wait_s: float = 0.002, **backend_opts):
        if size < 1:
            raise SessionError("SessionPool size must be >= 1")
        self.program = program
        self.graph = graph
        self.size = size
        self.backend_name = backend
        self._argv = argv
        self._backend_opts = dict(backend_opts)
        self._sessions = [
            Session(program, graph, backend=backend, argv=argv, **backend_opts)
            for _ in range(size)
        ]
        self._idle: "list[Session]" = list(self._sessions)
        self._idle_lock = threading.Lock()
        self._idle_ready = threading.Condition(self._idle_lock)
        self._executor = ThreadPoolExecutor(
            max_workers=size, thread_name_prefix="repro-session"
        )
        self._closed = False
        self._batch_session: Optional[BatchSession] = None
        self._batch_unsupported = False
        self._batch_lock = threading.Lock()
        self._batcher = None
        if batch > 1:
            from ..batch.dynamic import DynamicBatcher

            bs = self._ensure_batch_session(max_batch=batch)
            if bs is None:
                raise SessionError(
                    f"backend {backend!r} cannot host the dynamic batcher "
                    "(no engine attribute on its ExecutionBackend)"
                )
            self._batcher = DynamicBatcher(
                bs.run_many, max_batch=batch, max_wait_s=batch_wait_s
            )

    @property
    def batch_stats(self):
        """Dynamic-batching occupancy stats (None unless ``batch > 1``)."""
        return self._batcher.stats if self._batcher is not None else None

    def _ensure_batch_session(self, max_batch: Optional[int] = None):
        """Lazily build the pool-shared BatchSession (None if unsupported;
        the failure is memoized)."""
        with self._batch_lock:
            if self._batch_session is None and not self._batch_unsupported:
                try:
                    self._batch_session = BatchSession(
                        self.program, self.graph, backend=self.backend_name,
                        argv=self._argv, max_batch=max_batch or AUTO_MAX_BATCH,
                        **self._backend_opts,
                    )
                except SessionError:
                    self._batch_unsupported = True
            return self._batch_session

    # -- scheduling ---------------------------------------------------------
    def _acquire(self) -> Session:
        with self._idle_ready:
            while not self._idle:
                self._idle_ready.wait()
            return self._idle.pop()

    def _release(self, sess: Session) -> None:
        with self._idle_ready:
            self._idle.append(sess)
            self._idle_ready.notify()

    def _run_one(self, params: Dict[str, Any]) -> EngineResult:
        sess = self._acquire()
        try:
            return sess.run(**params)
        finally:
            self._release(sess)

    # -- public API ---------------------------------------------------------
    def warmup(self, **params) -> None:
        """Run one query on EVERY worker session so each jit-compiles its
        kernel launch paths before real traffic arrives. Warmups run
        concurrently (XLA compilation releases the GIL). With dynamic
        batching enabled, the shared BatchSession is warmed too — at a full
        ``batch``-sized query list, since that is the trace shape real
        traffic hits (partial trailing batches still compile on first
        sight)."""
        if self._closed:
            raise ServiceClosed("SessionPool is closed")
        self.program.validate_params(params)
        futures = [self._executor.submit(s.run, **params) for s in self._sessions]
        for f in futures:
            f.result()
        if self._batcher is not None and self._batch_session is not None:
            self._batch_session.run_many([dict(params)] * self._batcher.max_batch)

    def submit(self, **params) -> "Future[EngineResult]":
        """Async: enqueue one parameterized query, get a Future.

        With dynamic batching enabled (``batch > 1``), the query joins the
        collector queue and is answered as part of a batch; otherwise it is
        dispatched to the next idle worker session. Either way the Future
        resolves to the same result a dedicated :meth:`Session.run` would
        produce.
        """
        if self._closed:
            raise ServiceClosed("SessionPool is closed")
        self.program.validate_params(params)  # fail fast on the caller thread
        if self._batcher is not None:
            return self._batcher.submit(params)
        try:
            return self._executor.submit(self._run_one, params)
        except RuntimeError as e:
            # close() raced this submit: the executor rejects with a raw
            # RuntimeError("cannot schedule new futures after shutdown")
            raise ServiceClosed("SessionPool is closed") from e

    def refresh_graph(self, graph: Optional[GraphData] = None) -> None:
        """Rebind every worker (and the shared BatchSession) after an
        in-place graph mutation. The pool must be quiescent — no query in
        flight and the dynamic batcher drained; the streaming layer's
        write gate guarantees this, and callers driving the pool directly
        must arrange the same.
        """
        if self._closed:
            raise ServiceClosed("SessionPool is closed")
        graph = graph if graph is not None else self.graph
        self.graph = graph
        if self._batcher is not None:
            self._batcher.drain()
        for s in self._sessions:
            s.refresh_graph(graph)
        if self._batch_session is not None:
            self._batch_session.refresh_graph(graph)

    def run_batch(self, param_sets: Sequence[Dict[str, Any]],
                  batched: Optional[bool] = None) -> List[EngineResult]:
        """Run every parameter set; results in submission order.

        Results are element-wise identical to one :meth:`Session.run` per
        set — whichever path answers them. Batch-eligible lists (same
        parameter key set everywhere, two or more sets) are rerouted
        through the pool's shared :class:`BatchSession` so one launch set
        serves the whole list; anything else fans out to the worker
        sessions. ``batched=True``/``False`` forces the choice (True raises
        on ineligible lists).
        """
        if self._closed:
            raise ServiceClosed("SessionPool is closed")
        sets = [dict(p) for p in param_sets]
        if batched is None:
            coerced = [self.program.validate_params(p) for p in sets]
            batched = len(sets) > 1 and batch_eligible(coerced)
            if batched and self._ensure_batch_session() is None:
                batched = False
        if batched:
            bs = self._ensure_batch_session()
            if bs is None:
                raise SessionError(
                    f"backend {self.backend_name!r} does not expose an engine "
                    "for batched execution"
                )
            return bs.run_many(sets)
        futures = [self.submit(**p) for p in sets]
        return [f.result() for f in futures]

    def close(self, wait: bool = True) -> None:
        self._closed = True
        if self._batcher is not None:
            self._batcher.close(wait=wait)
        self._executor.shutdown(wait=wait)
        for s in self._sessions:
            s.close()
        if self._batch_session is not None:
            self._batch_session.close()

    def __enter__(self) -> "SessionPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __repr__(self) -> str:
        return f"SessionPool(size={self.size}, program={self.program.fingerprint[:12]})"


__all__ = [
    "ExecutionBackend",
    "EngineBackend",
    "LocalBackend",
    "DistributedBackend",
    "BatchSession",
    "Session",
    "SessionError",
    "ServiceClosed",
    "SessionPool",
    "ProgramError",
    "batch_eligible",
    "register_backend",
    "backend_names",
]
