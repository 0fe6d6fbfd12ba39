"""How a run starts and how its set-up ends: the service starts on the
cell's chips, the first failed warm-up query ends the run, and set-up
that outlasts ``SETUP_LIMIT_S`` ends the process, naming its phase."""
import concurrent.futures
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

import repro
from bench import run

SEED = 2**33 + 7
SMALL = """
import copy
import sys

sys.path[:0] = [{root!r}, {src!r}]
from bench import run

real_resolve = run.resolve_cell


def small_cell(manifest, workload):
    cell, config, traffic, e2e, per_layer = real_resolve(manifest, workload)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config["graph"].update(scale=9, edge_factor=8)
    if "roots" in traffic:
        traffic["roots"]["count"] = 8
    return cell, config, traffic, e2e, per_layer


run.resolve_cell = small_cell
"""


def _subprocess(body: str, devices: int, tmp_path, timeout: float):
    code = SMALL.format(root=str(run.ROOT), src=str(run.ROOT / "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-c", code + textwrap.dedent(body)], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=timeout)


ROUTE = """
import json

import jax
import repro

real_serve = repro.serve
calls, supersteps = [], []


class Stop(Exception):
    pass


def recording(go_on):
    def serve(*args, **kw):
        target = kw.get("target") or repro.Target(
            kind=kw.get("backend", "local"))
        calls.append({{
            "args": list(args), "keys": sorted(kw),
            "backend": kw.get("backend"),
            "n_devices": kw["target"].n_devices if "target" in kw else None,
            "mesh": (target.mesh().devices.size
                     if target.kind == "distributed" else None)}})
        if not go_on:
            raise Stop
        svc = real_serve(*args, **kw)
        submit = svc.submit

        def counted(*a, **k):
            fut = submit(*a, **k)
            fut.add_done_callback(
                lambda f: supersteps.append(f.result().stats.dist_supersteps))
            return fut

        svc.submit = counted
        return svc
    return serve


cell, config, traffic, e2e, per_layer = run.resolve_cell(
    run.load_manifest(), "r19.bfs.serial")
outs = {{}}
for n, go_on in ((4, True), (2, False), (1, False)):
    repro.serve = recording(go_on)
    try:
        outs[n] = run.run_cell("r19.bfs.serial", config, traffic, e2e,
                               per_layer, {seed}, 0.6, False,
                               jax.devices()[:n], store="store")
    except Stop:
        outs[n] = None
print(json.dumps({{"calls": calls, "supersteps": supersteps,
                  "out": outs[4]}}))
"""


def test_chips_reach_the_service(tmp_path):
    """Four chips of four serve on the distributed backend over all four,
    two of four on a mesh pinned to two, and one chip as before."""
    p = _subprocess(ROUTE.format(seed=SEED), 4, tmp_path, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    four, two, one = got["calls"]
    assert four == {"args": ["store"], "keys": ["backend"],
                    "backend": "distributed", "n_devices": None, "mesh": 4}
    assert two == {"args": ["store"], "keys": ["backend", "target"],
                   "backend": "distributed", "n_devices": 2, "mesh": 2}
    assert one == {"args": ["store"], "keys": [], "backend": None,
                   "n_devices": None, "mesh": None}
    out = got["out"]
    assert out["correct"] is True, out["checks"]
    assert out["device"]["count"] == 4 and out["attempted"] > 0
    assert got["supersteps"] and min(got["supersteps"]) > 0


class _FailingService:
    """A service whose every query fails, as a bind that runs out of
    device memory would."""

    def __init__(self):
        self.submits = []
        self.closed = False

    def submit(self, name, graph, **params):
        self.submits.append(params)
        fut = concurrent.futures.Future()
        fut.set_exception(MemoryError("RESOURCE_EXHAUSTED: bind"))
        return fut

    def close(self):
        self.closed = True


@pytest.mark.parametrize("workload",
                         ["r19.bfs.serial", "g500s18.pagerank.serial"])
def test_first_failed_warm_up_query_ends_the_run(workload, tmp_path,
                                                 monkeypatch, capsys):
    svc = _FailingService()
    monkeypatch.setattr(repro, "serve", lambda *a, **kw: svc)
    cell, config, traffic, e2e, per_layer = run.resolve_cell(
        run.load_manifest(), workload)
    config = dict(config, graph=dict(config["graph"], scale=9,
                                     edge_factor=8))
    if "roots" in traffic:
        traffic = dict(traffic, roots=dict(traffic["roots"], count=8))
    import jax

    with pytest.raises(run.SetupFailed, match="warm-up query 0 .*"
                       "RESOURCE_EXHAUSTED"):
        run.run_cell(workload, config, traffic, e2e, per_layer, SEED, 0.6,
                     False, jax.devices()[:1], store=tmp_path / "store")
    assert len(svc.submits) == 1 and svc.closed
    assert "MemoryError" in capsys.readouterr().err


MAIN = """
import time

import jax
import repro

run.cell_devices = lambda workload, chips: jax.devices()[:chips]
{serve}
# the generator's program is compiled before the limit is set, so that
# set-up reaches the warm-up well inside it
from bench.graphs import kronecker
kronecker.generate(dict(scale=9, edge_factor=8, a=0.57, b=0.19, c=0.19,
                        permute=False, undirected=False), 1)
run.SETUP_LIMIT_S = time.perf_counter() - run.T_START + {grace}
raise SystemExit(run.main(["--workload", "r19.bfs.serial", "--seed",
                           "{seed}", "--seconds", "0.6", "--trace", "0"]))
"""

SLEEPY = """
def sleepy_serve(*args, **kw):
    svc = real_serve(*args, **kw)

    def submit(name, graph, **params):
        time.sleep(120)

    svc.submit = submit
    return svc


real_serve = repro.serve
repro.serve = sleepy_serve
"""

FAILING = """
import concurrent.futures


class Failing:
    def submit(self, name, graph, **params):
        fut = concurrent.futures.Future()
        fut.set_exception(MemoryError("RESOURCE_EXHAUSTED: bind"))
        return fut

    def close(self):
        pass


repro.serve = lambda *a, **kw: Failing()
"""


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        assert not (isinstance(obj, dict) and "correct" in obj), line


def test_set_up_past_the_limit_ends_the_process(tmp_path):
    t = time.perf_counter()
    p = _subprocess(MAIN.format(serve=SLEEPY, grace=6, seed=SEED), 1,
                    tmp_path, timeout=110)
    elapsed = time.perf_counter() - t
    assert p.returncode == 1, p.stderr[-4000:]
    assert elapsed < 60
    last = p.stderr.strip().splitlines()[-1]
    assert last.startswith("bench: set-up passed ") and last.endswith(
        " s in warm-up"), last
    _no_result(p.stdout)


def test_failed_warm_up_exits_1_without_a_result(tmp_path):
    p = _subprocess(MAIN.format(serve=FAILING, grace=300, seed=SEED), 1,
                    tmp_path, timeout=300)
    assert p.returncode == 1, p.stderr[-4000:]
    last = p.stderr.strip().splitlines()[-1]
    assert last.startswith("bench: warm-up query 0 {'root': ")
    assert "RESOURCE_EXHAUSTED" in last
    _no_result(p.stdout)
