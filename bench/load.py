"""The one traffic generator: reads a mix from ``bench/traffic/<mix>.json``
and drives queries into a submit function from one closed-loop client,
which sends each query as soon as the last one has returned.

A mix names the query (``algorithm``, fixed ``params``, and the rule that
draws each query's ``root``):

- ``roots.rule`` ``out_degree_positive``: ``roots.count`` distinct roots
  drawn from the seed, without replacement, among vertices with at least
  one out-arc, as Graph500 and GAP draw BFS roots. The window sends them
  in order, each once, and starts over only if it outlasts them.
  ``roots.strata`` fixes the mix of traversal shapes: a root's stratum is
  how many of its BFS levels are dense, a dense level being one whose
  frontier's out-arcs exceed ``share`` of all arcs, and the window's
  sequence is made of blocks that each hold ``block[k]`` roots of stratum
  ``k``, in an order drawn from the seed. Every seed then sends the same
  mix of shapes, in another order.
- The warm-up of a mix with roots sends every window root, mapped by a
  permutation of the vertex ids drawn from the seed, to a relabeled copy
  of the graph. Each frontier the window will meet has its twin there, of
  the same size and the same out-arcs, so every shape the window uses is
  compiled before it opens; yet no window query is asked of the served
  graph before the window, so nothing a query leaves behind can answer
  it. One more root, never sent in the window, goes to the graph itself
  so that it is bound.
- Without ``roots`` every query has the same parameters, and the warm-up
  sends one of them.
"""
from __future__ import annotations

import functools
import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional

import numpy as np

#: numpy stream ids under the run's seed (the graph uses jax.random)
ROOT_STREAM, RELABEL_STREAM = 1, 2
#: roots traversed together by ``dense_levels`` (one bit of a word each)
LANES = 64


@dataclass
class Query:
    index: int
    params: Dict[str, Any]
    t_submit: float = 0.0
    t_done: float = 0.0
    result: Any = None
    error: Optional[BaseException] = None
    settled: threading.Event = field(default_factory=threading.Event,
                                     repr=False)

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit


@dataclass
class Plan:
    """The queries of one run, drawn from the seed.

    ``warmup`` goes to the served graph before the window; with
    ``relabel`` (a permutation of the vertex ids) ``warmup_relabeled``
    goes to the copy of the graph whose vertex ``v`` is ``relabel[v]``."""

    warmup: List[Dict[str, Any]]
    window: Iterator[Dict[str, Any]] = field(repr=False)
    relabel: Optional[np.ndarray] = None
    warmup_relabeled: List[Dict[str, Any]] = field(default_factory=list)


def dense_levels(arcs, roots, share: float) -> np.ndarray:
    """For each root (at most ``LANES``), how many levels of a BFS from it
    have a frontier whose out-arcs exceed ``share`` of all arcs.

    One bit-parallel traversal serves all roots: bit ``j`` of a vertex's
    word says it is in root ``j``'s frontier."""
    roots = np.asarray(roots, np.int64)
    k = roots.size
    if not 0 < k <= LANES or np.unique(roots).size != k:
        raise ValueError("dense_levels takes 1 to 64 distinct roots")
    indptr, sources = arcs.in_adjacency
    has_in = np.diff(indptr) > 0
    starts = indptr[:-1][has_in]
    deg = arcs.out_degree.astype(np.float64)
    frontier = np.zeros(arcs.n, np.uint64)
    frontier[roots] = np.uint64(1) << np.arange(k, dtype=np.uint64)
    seen = frontier.copy()
    counts = np.zeros(k, np.int64)
    while True:
        active = np.flatnonzero(frontier)
        if active.size == 0:
            return counts
        bits = np.unpackbits(frontier[active].view(np.uint8).reshape(-1, 8),
                             axis=1, bitorder="little")[:, :k]
        counts += deg[active] @ bits > share * arcs.m
        nxt = np.zeros_like(frontier)
        nxt[has_in] = np.bitwise_or.reduceat(frontier[sources], starts)
        frontier = nxt & ~seen
        seen |= frontier


def _stratified(order, arcs, strata: dict, count: int,
                rng: np.random.Generator) -> List[int]:
    """``count`` roots taken in seed order while their stratum has room,
    laid out in blocks of the strata's mix."""
    block = {int(k): int(v) for k, v in strata["block"].items()}
    size = sum(block.values())
    if count % size:
        raise ValueError(f"roots.count {count} is not a multiple of the "
                         f"block size {size}")
    want = {k: v * count // size for k, v in block.items()}
    found: Dict[int, List[int]] = {k: [] for k in block}
    for lo in range(0, order.size, LANES):
        chunk = order[lo:lo + LANES]
        for r, k in zip(chunk, dense_levels(arcs, chunk,
                                            float(strata["share"]))):
            if len(found.get(int(k), ())) < want.get(int(k), 0):
                found[int(k)].append(int(r))
        if all(len(found[k]) == want[k] for k in block):
            break
    else:
        raise ValueError(f"too few roots for strata {block}: found "
                         f"{ {k: len(v) for k, v in found.items()} }")
    out: List[int] = []
    for b in range(count // size):
        members = [found[k][b * block[k] + i] for k in block
                   for i in range(block[k])]
        out += [members[i] for i in rng.permutation(len(members))]
    return out


def plan(traffic: dict, arcs, seed: int) -> Plan:
    base = dict(traffic.get("params", {}))
    roots = traffic.get("roots")
    if roots is None:
        return Plan([dict(base)], itertools.repeat(base))
    if roots["rule"] != "out_degree_positive":
        raise ValueError(f"unknown root rule {roots['rule']!r}")
    rng = np.random.default_rng([seed, ROOT_STREAM])
    order = rng.permutation(np.flatnonzero(arcs.out_degree > 0))
    count = int(roots["count"])
    if "strata" in roots:
        chosen = _stratified(order, arcs, roots["strata"], count, rng)
    else:
        chosen = [int(r) for r in order[:count]]
    if len(chosen) < count or order.size <= count:
        raise ValueError(f"the graph has too few roots for {count}")
    taken = set(chosen)
    spare = next(int(r) for r in order if int(r) not in taken)
    relabel = np.random.default_rng([seed, RELABEL_STREAM]).permutation(
        arcs.n).astype(np.int32)
    return Plan([{**base, "root": spare}],
                itertools.cycle([{**base, "root": r} for r in chosen]),
                relabel=relabel,
                warmup_relabeled=[{**base, "root": int(relabel[r])}
                                  for r in chosen])


def drive(submit: Callable[[Dict[str, Any]], Any], queries: Iterable[dict],
          until: Optional[float] = None,
          keep: Callable[[Any], Any] = lambda result: result,
          annotate: Callable[[str], Any] = None,
          late_s: Optional[float] = None) -> List[Query]:
    """Send queries from one closed-loop client, each as soon as the last
    one has returned, until the ``time.perf_counter`` reading ``until``
    (or, with ``until`` None, until ``queries`` runs out).

    Returns every query sent, in order. ``submit(params)`` returns a
    future; ``keep`` maps its result to what the query record holds;
    ``annotate(name)`` gives a context manager around each submit and each
    wait (host spans in the profiler's trace). With ``late_s``, an answer
    is awaited until that long past ``until``, and a query still
    unanswered then is left unsettled, which ends the sending."""
    annotate = annotate or (lambda name: _NULL)
    sent: List[Query] = []
    for params in queries:
        if until is not None and time.perf_counter() >= until:
            break
        qy = Query(len(sent), params)
        sent.append(qy)
        with annotate("bench.submit"):
            qy.t_submit = time.perf_counter()
            try:
                fut = submit(params)
            except Exception as exc:  # refused at admission: counted as failed
                qy.t_done, qy.error = time.perf_counter(), exc
                qy.settled.set()
                continue
            fut.add_done_callback(functools.partial(_settle, qy, keep))
        with annotate("bench.wait"):
            limit = (None if until is None or late_s is None
                     else max(0.0, until + late_s - time.perf_counter()))
            if not qy.settled.wait(limit):
                break
    return sent


def _settle(qy: Query, keep, fut) -> None:
    """Record the query's result, and the time its future resolved."""
    qy.t_done = time.perf_counter()
    try:
        qy.result = keep(fut.result())
    except Exception as exc:  # a failed query is counted, not raised
        qy.error = exc
    qy.settled.set()


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()
