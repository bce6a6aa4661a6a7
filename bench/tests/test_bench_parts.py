"""The benchmark's yardstick on the CPU: operations and bytes, peaks,
the trace reduction, the generators and the resolution of every cell."""
import dataclasses
import json

import numpy as np
import pytest
import scipy.sparse as sp

from bench import cells, peaks, run, trace_reduce, work
from bench.generators import hpcg, kronecker


def test_work_counts_match_a_hand_count():
    # A = [[1, 1, 0], [0, 0, 1], [1, 0, 0]]; C = A @ A
    # row 0: B rows 0 (2 entries) + 1 (1 entry) -> 3 products, C row 0
    #        has columns {0, 1, 2} -> 3 entries
    # row 1: B row 2 (1 entry) -> 1 product, C row 1 = {0}
    # row 2: B row 0 (2 entries) -> 2 products, C row 2 = {0, 1}
    indptr, indices = np.array([0, 2, 3, 4]), np.array([0, 1, 2, 0])
    prods = work.row_products(indptr, indices, indptr)
    assert prods.tolist() == [3, 1, 2]
    c_indptr = np.array([0, 3, 4, 6])
    w = work.rows_work([0, 2], prods, indptr, c_indptr)
    assert w.products == 5
    assert w.ops == 10
    # A entries 2 + 1, products 5, C entries 3 + 2, 8 bytes each
    assert w.bytes == 8 * (3 + 5 + 5)
    p = peaks.peaks_for("TPU v5 lite")
    t, bound = w.least_seconds(p)
    assert bound == "bytes" and t == pytest.approx(w.bytes / 819e9)


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks.peaks_for("TPU v5 lite").bytes_per_s == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks_for("TPU v9 imaginary")


_TRACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000
             stats { metadata_id: 1 str_value: "jit_spgemm_hash_bin" } }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 1000000
             stats { metadata_id: 1 str_value: "jit_spgemm_hash_bin" } }
    events { metadata_id: 3 offset_ps: 12000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 7000000 } }
  event_metadata { key: 1 value { id: 1 name: "%spgemm_hash_bin.1 = (s32[8,512]) custom-call(...)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8] fusion(...)" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.3 = f32[8] copy(...)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_spgemm_hash_bin" } }
  stat_metadata { key: 1 value { id: 1 name: "hlo_module" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "exec.merge" } }
}
'''


def test_trace_reduction_on_a_small_recorded_trace():
    import jax
    tr = trace_reduce.from_profile(jax.profiler.ProfileData.from_text_proto(
        _TRACE))
    assert list(tr.device_ops) == ["/device:TPU:0"]
    # the window is [1000, 11000] ns; ops cover [1000, 4000] and
    # [7000, 8000] inside it (the copy at 13000 ns lies outside)
    assert trace_reduce.window_bounds(tr) == (1000.0, 11000.0)
    assert trace_reduce.busy_seconds(tr) == pytest.approx(4e-6)
    assert trace_reduce.kernel_seconds(tr, "spgemm_hash_bin") == \
        pytest.approx(3e-6)
    assert trace_reduce.kernel_seconds(tr, "spgemm_dense_bin") == 0.0
    top = trace_reduce.top_ops(tr)
    assert top[0][0] == "jit_spgemm_hash_bin/%spgemm_hash_bin.1"
    # the fusion has no module stat: it takes the module it ran inside
    assert top[1][0] == "jit_spgemm_hash_bin/%fusion.2"
    assert top[0][1] == pytest.approx(3e-6)
    gaps = trace_reduce.idle_gaps(tr, tr.host_spans)
    # idle: [4000, 7000] under exec.merge, [8000, 11000] under the window
    assert [g[0] for g in gaps] == ["exec.merge", "bench.window"]
    assert [g[1] for g in gaps] == pytest.approx([3e-6, 3e-6])
    # 1 - busy / window, as idle_share reads it
    assert 1 - trace_reduce.busy_seconds(tr) / 10e-6 == pytest.approx(0.6)


def test_trace_reduction_on_a_recorded_chip_trace():
    # one fresh A @ A of a 6^3-node FEM stiffness on a TPU v5e, recorded
    # under a bench.window annotation; its dense bins ran the dense kernel
    # for 30.460 + 9.408 ms (the profiler's own per-op durations)
    tr = trace_reduce.load(str(cells.BENCH_DIR / "tests" / "data"
                               / "fem_q1_g6_fresh.xplane.pb"))
    assert list(tr.device_ops) == ["/device:TPU:0"]
    lo, hi = trace_reduce.window_bounds(tr)
    assert trace_reduce.kernel_seconds(tr, "spgemm_dense_bin") == \
        pytest.approx(0.039868, abs=2e-6)
    assert trace_reduce.kernel_seconds(tr, "spgemm_hash_bin") == 0.0
    busy = trace_reduce.busy_seconds(tr)
    assert 0.039868 < busy < (hi - lo) * 1e-9
    assert trace_reduce.top_ops(tr)[0][0].startswith(
        "jit_spgemm_dense_bin/%spgemm_dense_bin.1")


def test_union_and_gaps():
    iv = [(0, 2), (1, 3), (5, 6)]
    assert trace_reduce.union_ns(iv) == 4
    assert trace_reduce.gaps(iv, 0, 8) == [(3, 5), (6, 8)]


def _pattern_product(m):
    p = sp.csr_matrix((np.ones(m.nnz), m.indices, m.indptr), shape=m.shape)
    return p @ p


def test_hpcg_stencil_rows_and_values():
    g = hpcg.build({"nx": 5, "ny": 4, "nz": 3})
    m = g.matrix(np.random.default_rng(0))
    lens = np.diff(m.indptr)
    # the interior point (ix, iy, iz) = (2, 1, 1) couples to 27 points,
    # a corner to 8
    assert lens[1 * 20 + 1 * 5 + 2] == 27
    assert lens[0] == 8 and lens.max() == 27
    # per dimension of n points: 3n - 2 neighbours in all
    assert m.nnz == (3 * 5 - 2) * (3 * 4 - 2) * (3 * 3 - 2)
    a = sp.csr_matrix((m.values, m.indices, m.indptr), shape=m.shape)
    assert (a != a.T).nnz == 0
    assert np.all(a.diagonal() == 26.0)
    assert sorted(set(m.values.tolist())) == [-1.0, 26.0]
    for r in range(m.shape[0]):
        assert np.all(np.diff(m.indices[m.indptr[r]:m.indptr[r + 1]]) > 0)
    # HPCG's values do not depend on the seed; re-assembled ones do, on
    # the same pattern, and stay diagonally dominant as HPCG's (equal to
    # the off-diagonal sum in the interior, above it on the boundary)
    assert np.array_equal(g.matrix(np.random.default_rng(1)).values,
                          m.values)
    v = g.values(np.random.default_rng(1))
    b = sp.csr_matrix((v, m.indices, m.indptr), shape=m.shape)
    off = np.asarray(abs(b).sum(axis=1)).ravel() - b.diagonal()
    assert np.all(b.diagonal() >= off * (1 - 1e-6))
    assert np.any(b.diagonal() > off * 1.1)


def test_transpose_gathers_the_values():
    g = kronecker.build({"scale": 6, "edge_factor": 4, "a": 0.57,
                         "b": 0.19, "c": 0.19, "graph_seed": 2})
    m = g.matrix(np.random.default_rng(0))
    m = dataclasses.replace(m, indptr=m.indptr[:33],
                            indices=m.indices[: m.indptr[32]],
                            values=m.values[: m.indptr[32]],
                            shape=(32, m.shape[1]))
    t, take = m.transposed()
    a = sp.csr_matrix((m.values, m.indices, m.indptr), shape=m.shape)
    at = sp.csr_matrix((t.values, t.indices, t.indptr), shape=t.shape)
    assert t.shape == (m.shape[1], 32)
    assert (at != a.T).nnz == 0
    assert np.array_equal(m.values[take], t.values)


@pytest.mark.parametrize("entry", cells.load_benchmark()["configs"],
                         ids=lambda c: c["name"])
def test_generators_give_the_reckoned_sizes(entry):
    with open(cells.ROOT / entry["file"]) as f:
        conf = json.load(f)
    g = cells.load_generator(conf)
    m = g.matrix(np.random.default_rng(7))
    c = _pattern_product(m)
    want = conf["reckoned"]
    assert (m.shape[0], m.nnz) == (want["rows"], want["nnz"])
    assert np.diff(m.indptr).max() == want["max_row"]
    assert int(c.data.sum()) == want["products"]
    assert c.nnz == want["nnz_c"]
    assert c.data.sum() / c.nnz == pytest.approx(
        want["compression_ratio"], abs=0.01)


def test_kronecker_graph_is_symmetric_without_loops():
    g = kronecker.build({"scale": 8, "edge_factor": 16, "a": 0.57,
                         "b": 0.19, "c": 0.19, "graph_seed": 3})
    m = g.matrix(np.random.default_rng(0))
    a = sp.csr_matrix((np.ones(m.nnz), m.indices, m.indptr), shape=m.shape)
    assert (a != a.T).nnz == 0
    assert a.diagonal().sum() == 0
    assert np.all((m.values >= 0) & (m.values < 1))


def test_kronecker_ids_are_permuted():
    # unpermuted, R-MAT puts its hubs at the lowest ids (a = 0.57 is the
    # top-left quadrant); Graph500 and GAP permute the ids at random
    g = kronecker.build({"scale": 10, "edge_factor": 16, "a": 0.57,
                         "b": 0.19, "c": 0.19, "graph_seed": 3})
    lens = np.diff(g.indptr)
    hubs = np.argsort(lens)[-16:]
    assert hubs.min() > 0 and np.median(hubs) > 64


@pytest.mark.parametrize("name", [w["name"] for w in
                                  cells.load_benchmark()["workloads"]])
def test_every_workload_resolves_its_parts_by_name(name):
    cell = cells.resolve(name)
    assert cell.config_name == name.split(".")[0]
    assert cells.load_generator(cell.config) is not None
    assert "small" in cell.config
    assert cells.load_generator(dict(cell.config,
                                     params=cell.config["small"])) is not None
    assert cell.traffic["plan_cache"] in ("off", "private")
    assert cell.traffic["operands"] in ("a_a", "a_at")
    assert set(cell.config["limits"]) == {"rows_wrong",
                                          "value_err_over_f32_bound"}
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end:
        assert callable(cells.end_to_end_reader(m["name"]))
    for m in cell.per_layer:
        assert callable(cells.metric_reader(m["name"]))


def test_end_to_end_readers_over_a_window():
    calls = [run.Call(0.0, 0.0, 1.0), run.Call(1.0, 1.0, 2.5)]
    win = run.Window(calls=calls, window_s=2.5, setup_s=7.0,
                     products_per_call=10 ** 9)
    assert cells.end_to_end_reader("gflops")(win) == pytest.approx(1.6)
    assert cells.end_to_end_reader("setup_s")(win) == 7.0
    assert cells.end_to_end_reader("gflops")(
        dataclasses.replace(win, calls=[])) is None
    with pytest.raises(FileNotFoundError):
        cells.end_to_end_reader("no_such_metric")
