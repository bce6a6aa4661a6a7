"""The comparison that decides ``correct``, driven through a whole run of
the harness on the CPU at a small size (the look for a chip skipped): a
sound run passes; the bfloat16 control and each fault planted in the
timed path's output fail."""
import dataclasses
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, control, run
from repro.core import workflow

CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]]
# traffic that no cell runs yet, driven through the same window
TRAFFIC = {
    "values": {},
    "a_at": {"operands": "a_at"},
    "open": {"arrival": {"kind": "open", "rate_per_s": 20.0}},
}


def _small(name, traffic=None, **over):
    """The cell at its configuration's ``small`` size, on other traffic or
    with traffic keys changed where asked."""
    cell = cells.resolve(name)
    cell.config = dict(cell.config, params=cell.config["small"])
    if traffic is not None:
        with open(cells.BENCH_DIR / "traffic" / f"{traffic}.json") as f:
            cell.traffic = json.load(f)
    cell.traffic = dict(cell.traffic, **over)
    return cell


def _run(cell, multiply=None, seed=2**31 + 17, traced=False):
    return run.run_cell(cell, seed, 0.3, traced, t_start=time.perf_counter(),
                        multiply=multiply, device_kind="TPU v5 lite")


def _altered_value(a, b, **kw):
    c, rep = workflow.ocean_spgemm(a, b, **kw)
    v = c.values.at[c.nnz // 2].multiply(1.0 + 2.0 ** -12)
    return dataclasses.replace(c, values=v), rep


def _dropped_entry(a, b, **kw):
    c, rep = workflow.ocean_spgemm(a, b, **kw)
    k = c.nnz // 2
    keep = np.ones(c.capacity, bool)
    keep[k] = False
    row = int(np.searchsorted(np.asarray(c.indptr), k, side="right")) - 1
    ip = np.asarray(c.indptr).copy()
    ip[row + 1:] -= 1
    return dataclasses.replace(
        c, indptr=jnp.asarray(ip), indices=c.indices[keep],
        values=c.values[keep], nnz=c.nnz - 1), rep


def _half_rows_left_out(a, b, **kw):
    c, rep = workflow.ocean_spgemm(a, b, **kw)
    ip = np.asarray(c.indptr).copy()
    half = c.m // 2
    ip[half + 1:] = ip[half]
    return dataclasses.replace(c, indptr=jnp.asarray(ip),
                               nnz=int(ip[-1])), rep


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    r = _run(_small(name))
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["checks"]["rows_wrong"]["value"] == 0
    assert set(r["metrics"]) == {m["name"] for m in cells.load_benchmark()[
        "end_to_end"] if name in m.get("workloads", [name])}


@pytest.mark.parametrize("kind", sorted(TRAFFIC))
def test_other_traffic_is_correct_and_its_control_is_not(kind):
    traffic = "values" if kind == "values" else "fresh"
    cell = _small("kron_g500_s13.fresh", traffic, **TRAFFIC[kind])
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["metrics"]["gflops"]["value"] > 0
    lat = r["_info"]["latency_seconds"]
    assert all(x >= y for x, y in zip(lat, r["_info"]["call_seconds"]))
    r = _run(cell, control.control_multiply(workflow.ocean_spgemm))
    assert not r["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_bfloat16_control_is_not_correct(name):
    r = _run(_small(name), control.control_multiply(workflow.ocean_spgemm))
    assert not r["correct"]
    assert r["checks"]["rows_wrong"]["value"] == 0
    assert r["checks"]["value_err_over_f32_bound"]["value"] > \
        r["checks"]["value_err_over_f32_bound"]["limit"]


@pytest.mark.parametrize("name,fault", [
    pytest.param(n, f, id=f.__name__ if n == CELLS[0]
                 else f"{n}-{f.__name__}")
    for n in CELLS for f in (_altered_value, _dropped_entry,
                             _half_rows_left_out)])
def test_planted_fault_is_not_correct(name, fault):
    r = _run(_small(name), fault)
    assert not r["correct"]
    assert r["failed"] == r["attempted"]


def test_traced_run_reads_its_per_layer_metrics():
    r = _run(_small(CELLS[0]), traced=True)
    assert r["correct"]
    # no device in the CPU trace: only the host-side readings appear
    assert {"plan_s", "merge_s", "slab_fill"} <= set(r["metrics"])
    assert r["_info"]["hash_bins"] is not None
    assert "idle_share" not in r["metrics"]
    assert r["device"]["window_s"] > 0


def test_run_refuses_the_cpu(capsys):
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                     "1", "--trace", "0"]) != 0
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err


def test_run_fails_without_the_program(tmp_path):
    import shutil
    import subprocess
    import sys
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(cells.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(tmp_path)})
    assert p.returncode != 0
    assert p.stdout == ""
    assert "cannot import the system under test" in p.stderr
