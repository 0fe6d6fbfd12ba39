"""A whole run of each cell at a tiny size on the CPU (the look for a chip
skipped), with the served path broken underneath: every fault the cell
can have makes ``correct`` false, and the unbroken path makes it true."""
import concurrent.futures
import copy

import numpy as np
import pytest

import repro
from bench import run

SECONDS = 0.6
SEED = 2**33 + 5


def _result_fault(kind):
    """A ``repro.serve`` whose futures carry results broken as ``kind``."""
    real_serve = repro.serve

    def break_result(res, params):
        props = res.properties
        if kind == "unchanged":
            if "rank" in props:
                n = props["rank"].shape[0]
                props["rank"] = np.full(n, 1.0 / n, np.float32)
            else:
                lv = np.full_like(props["old_level"], -1)
                lv[params["root"]] = 1
                props["old_level"] = lv
        elif kind == "altered":
            key = "rank" if "rank" in props else "old_level"
            a = np.array(props[key])
            a[np.argmax(a)] += 1 if key == "old_level" else a.max() * 1e-2
            props[key] = a

    def serve(*args, **kw):
        svc = real_serve(*args, **kw)
        submit = svc.submit

        def broken_submit(name, graph, **params):
            broken = concurrent.futures.Future()

            def on_done(f):
                try:
                    res = f.result()
                except Exception as exc:
                    broken.set_exception(exc)
                    return
                break_result(res, params)
                broken.set_result(res)

            submit(name, graph, **params).add_done_callback(on_done)
            return broken

        svc.submit = broken_submit
        return svc

    return serve


def _run(workload, tmp_path, monkeypatch, fault=None):
    manifest = run.load_manifest()
    cell, config, traffic, e2e, per_layer = run.resolve_cell(manifest,
                                                             workload)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config["graph"].update(scale=9, edge_factor=8)
    if "roots" in traffic:
        traffic["roots"]["count"] = 8
    if fault:
        monkeypatch.setattr(repro, "serve", _result_fault(fault))
    import jax

    return run.run_cell(workload, config, traffic, e2e, per_layer, SEED,
                        SECONDS, False, jax.devices()[:1],
                        store=tmp_path / "store")


CASES = [
    ("r19.bfs.serial", None, True),
    ("r19.bfs.serial", "unchanged", False),
    ("r19.bfs.serial", "altered", False),
    ("g500s18.pagerank.serial", None, True),
    ("g500s18.pagerank.serial", "unchanged", False),
    ("g500s18.pagerank.serial", "altered", False),
]


@pytest.mark.parametrize("workload,fault,correct", CASES)
def test_fault_makes_correct_false(workload, fault, correct, tmp_path,
                                   monkeypatch, capsys):
    out = _run(workload, tmp_path, monkeypatch, fault)
    assert out["correct"] is correct, out["checks"]
    assert out["attempted"] > 0
    assert list(out)[-1] == "checks"
    if fault is None:  # the warm-up compiled every shape the window used
        lines = [ln for ln in capsys.readouterr().out.splitlines()
                 if ln.startswith("query ")]
        assert lines and all(ln.endswith(" compile 0.000s") for ln in lines)
