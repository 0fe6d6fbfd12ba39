"""Reduce a profiler trace (``*.xplane.pb``) of one window to the device
numbers of a run.

- busy: the union of the intervals in which an operation ran on a chip,
  inside the window, averaged over the chips in the trace;
- window: the host span named ``bench.window`` (the benchmark's own
  ``jax.profiler.TraceAnnotation`` around its measured window);
- device_ops: device time by operation, largest first; an operation is
  named by its jitted program (the "XLA Modules" event it runs in), its
  HLO instruction and its result shape;
- idle_gaps: idle device time inside the window by what the host was
  doing then: the innermost host event that spans the middle of each gap
  (a thread blocked on a lock, an event or a sleep was doing nothing, so
  such events name a gap only where nothing else does), largest total
  first.

Run as a script on a trace file or directory to print the reduction."""
from __future__ import annotations

import glob
import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Tuple

import numpy as np

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
TOP = 10
#: host events of a thread that is blocked, not working
BLOCKED = re.compile(r"acquire|wait|sleep|select|poll|(^|\s)get$", re.I)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def _events(line) -> Tuple[List[str], np.ndarray, np.ndarray]:
    names, starts, ends = [], [], []
    for e in line.events:
        names.append(e.name)
        starts.append(e.start_ns)
        ends.append(e.start_ns + e.duration_ns)
    return names, np.asarray(starts, np.float64), np.asarray(ends, np.float64)


def _short_op(hlo: str) -> str:
    """'%fusion.1 = s32[16]{0:T(1024)} fusion(...)' -> 'fusion.1 s32[16]'."""
    lhs, _, rhs = hlo.partition(" = ")
    return f"{lhs.lstrip('%')} {rhs.split('{')[0].split(' ')[0]}".strip()


def _qualify(names, starts, mod_names, mod_s, mod_e):
    """Prefix each operation with the program (module) it ran in."""
    order = np.argsort(mod_s, kind="stable")
    mod_s, mod_e = mod_s[order], mod_e[order]
    mod_names = [re.sub(r"\(\d+\)$", "", mod_names[i]) for i in order]
    j = np.searchsorted(mod_s, starts, side="right") - 1
    out = []
    for name, k, t in zip(names, j, starts):
        prog = mod_names[k] if k >= 0 and t < mod_e[k] else "?"
        out.append(f"{prog}: {_short_op(name)}")
    return out


def union_intervals(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Merged [start, end] rows of possibly overlapping intervals."""
    if starts.size == 0:
        return np.zeros((0, 2))
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    # a new merged interval starts where a start lies past every end before it
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > e[:-1]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, s.size - 1)
    return np.stack([s[idx], e[last]], axis=1)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def reduce_trace(path: str) -> Dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_names, host_s, host_e = [], [], []
    window = None
    devices = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if OP_LINE in lines:
                names, s, e = _events(lines[OP_LINE])
                if MODULE_LINE in lines:
                    names = _qualify(names, s, *_events(lines[MODULE_LINE]))
                devices.append((names, s, e))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                names, s, e = _events(ln)
                for name, a, b in zip(names, s, e):
                    if name == WINDOW and window is None:
                        window = (a, b)
                    elif b > a:
                        host_names.append(name)
                        host_s.append(a)
                        host_e.append(b)
    if not devices:
        raise ValueError(f"no TPU device operations in {path}")
    if window is None:
        raise ValueError(f"no {WINDOW!r} host span in {path}")
    lo, hi = window
    host_s, host_e = np.asarray(host_s), np.asarray(host_e)
    host_len = host_e - host_s
    blocked = np.array([bool(BLOCKED.search(n)) for n in host_names], bool)
    busy, ops, gaps = [], defaultdict(float), defaultdict(float)
    for names, s, e in devices:
        iv = _clip(union_intervals(s, e), lo, hi)
        busy.append(float((iv[:, 1] - iv[:, 0]).sum()) * 1e-9)
        inside = (e > lo) & (s < hi)
        for name, a, b in zip(np.asarray(names)[inside], s[inside], e[inside]):
            ops[name] += (min(b, hi) - max(a, lo)) * 1e-9
        edges = np.concatenate([[lo], iv.ravel(), [hi]]).reshape(-1, 2)
        for a, b in edges[edges[:, 1] > edges[:, 0]]:
            mid = 0.5 * (a + b)
            cover = np.flatnonzero((host_s <= mid) & (host_e >= mid))
            working = cover[~blocked[cover]]
            pick = working if working.size else cover
            name = (host_names[pick[np.argmin(host_len[pick])]]
                    if pick.size else "none")
            gaps[name] += (b - a) * 1e-9
    n = len(devices)

    def top(d):
        return [[k, v / n] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "busy_s": sum(busy) / n,
        "window_s": (hi - lo) * 1e-9,
        "chips": n,
        "device_ops": top(ops),
        "idle_gaps": top(gaps),
    }


if __name__ == "__main__":
    target = sys.argv[1]
    path = find_xplane(target) if os.path.isdir(target) else target
    print(json.dumps(reduce_trace(path), indent=1))
