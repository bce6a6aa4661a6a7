"""The readers of the ESC accumulator's metrics (``esc_s``,
``esc_slot_fill``, ``esc_roofline``) and of the plan-cache lookup
(``lookup_s``), on hand-made traces and reports, and in traced runs of
the cells that list them, on the CPU."""
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import cells, peaks, run, trace_reduce, work
from repro.core import workflow

# the window [1000, 11000] ns on the host; exec.esc [2000, 3000] and
# [10000, 12000] (1000 ns inside); plan.lookup [4000, 4500] and
# [7000, 7500].
# On the device: ops of jit_esc_spgemm [2000, 3000] and [2500, 3500]
# (overlapping: 1500 ns), [10500, 12500] (500 ns inside); one op of
# another module [4000, 6000]
_TRACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 9500000 duration_ps: 2000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 1500000 }
    events { metadata_id: 5 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 9500000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "%sort.3 = (s32[64]) sort(...)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.7 = f32[64] fusion(...)" } }
  event_metadata { key: 3 value { id: 3 name: "%spgemm_hash_bin.1 = (s32[8,512]) custom-call(...)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_esc_spgemm(9047009911558461880)" } }
  event_metadata { key: 5 value { id: 5 name: "jit_spgemm_hash_bin(17)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 9000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "exec.esc" } }
  event_metadata { key: 3 value { id: 3 name: "plan.lookup" } }
}
'''
# the same window with none of the program's spans and no device
_BARE = '''
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.call" } }
}
'''
# A = [[1, 1, 0], [0, 0, 1], [1, 0, 0]], C = A @ A (bench/work's hand
# count): rows 0 and 2 hold 3 A entries, 5 products and 5 C entries
A_INDPTR = np.array([0, 2, 3, 4])
A_INDICES = np.array([0, 1, 2, 0])
C_INDPTR = np.array([0, 3, 4, 6])
ESC_ROWS = np.array([0, 2])


def _ctx(text, reports=(), plan=None, calls=2):
    import jax
    tr = trace_reduce.from_profile(
        jax.profiler.ProfileData.from_text_proto(text))
    return run.Context(calls=calls, window_s=1e-5, reports=list(reports),
                       plan=plan, products=work.row_products(
                           A_INDPTR, A_INDICES, A_INDPTR),
                       a_indptr=A_INDPTR, c_indptr=C_INDPTR, trace=tr,
                       busy_s=0.0, peaks=peaks.peaks_for("TPU v5 lite"),
                       memory_peak_bytes=0)


def _report(products, slots):
    return SimpleNamespace(esc_products=products, esc_slots=slots)


_PLAN = SimpleNamespace(esc=SimpleNamespace(rows=ESC_ROWS))


@pytest.mark.parametrize("name,want", [
    ("esc_s", 1e-6),           # (1000 + 1000) ns over 2 calls
    ("lookup_s", 0.5e-6),      # (500 + 500) ns over 2 calls
])
def test_span_metrics_read_a_hand_made_trace(name, want):
    assert cells.metric_reader(name)(_ctx(_TRACE)) == \
        pytest.approx(want, rel=1e-9)


def test_esc_slot_fill_sums_the_calls():
    read = cells.metric_reader("esc_slot_fill")
    ctx = _ctx(_BARE, [_report(3, 4), _report(5, 12)])
    assert read(ctx) == pytest.approx(100.0 * 8 / 16)


def test_esc_module_time_is_the_union_of_its_ops_in_the_window():
    from bench.metrics import esc_roofline
    tr = _ctx(_TRACE).trace
    assert esc_roofline.module_seconds(tr, "jit_esc_spgemm") == \
        pytest.approx(2e-6, rel=1e-9)
    assert esc_roofline.module_seconds(tr, "jit_spgemm_hash_bin") == \
        pytest.approx(2e-6, rel=1e-9)
    assert esc_roofline.module_seconds(tr, "jit_no_such") == 0.0


def test_esc_roofline_reads_a_hand_count():
    read = cells.metric_reader("esc_roofline")
    ctx = _ctx(_TRACE, plan=_PLAN)
    # 8 bytes for each of 3 A entries, 5 products and 5 C entries: 104
    # bytes and 10 operations, bound by bytes; 2000 ns over 2 calls
    want = 100.0 * (104 / 819e9) * 2 / 2e-6
    assert read(ctx) == pytest.approx(want, rel=1e-9)
    # a chip that moves those bytes in the module's time per call: 100%
    ctx.peaks = peaks.Peaks(flops_per_s=1e30, bytes_per_s=104 / 1e-6,
                            hbm_bytes=1.0, source="hand count")
    assert read(ctx) == pytest.approx(100.0, rel=1e-9)


@pytest.mark.parametrize("name", ["esc_s", "lookup_s", "esc_slot_fill",
                                  "esc_roofline"])
def test_esc_metrics_find_nothing_where_nothing_matches(name):
    # a program whose spans reach no trace, whose reports carry no ESC
    # counters and whose plan has no ESC bin reads nothing, and raises
    # nothing
    read = cells.metric_reader(name)
    bare = [SimpleNamespace(stage_seconds={}) for _ in range(2)]
    assert read(_ctx(_BARE, bare, plan=_PLAN)) is None
    assert read(_ctx(_BARE, [], plan=SimpleNamespace(esc=None))) is None
    no_trace = _ctx(_BARE, bare)
    no_trace.trace = None
    assert read(no_trace) is None


def test_esc_slot_fill_reads_nothing_without_an_esc_pass():
    read = cells.metric_reader("esc_slot_fill")
    assert read(_ctx(_BARE, [_report(0, 0), _report(0, 0)])) is None
    assert read(_ctx(_TRACE, [_report(3, 4), SimpleNamespace()])) is None


def test_esc_roofline_reads_nothing_without_the_esc_bin():
    read = cells.metric_reader("esc_roofline")
    assert read(_ctx(_TRACE, plan=SimpleNamespace(esc=None))) is None
    assert read(_ctx(_TRACE, plan=None)) is None


def _listing(metric):
    return next(m for m in cells.load_benchmark()["per_layer"]
                if m["name"] == metric)["workloads"]


@pytest.mark.parametrize("name", _listing("esc_s"))
def test_traced_run_reads_the_esc_metrics(name):
    # a whole traced run at the configuration's small size, on the CPU:
    # the ESC spans and counters are read where an ESC pass ran (at its
    # small size Kronecker's plan overflows no row); no device reaches
    # the trace, so the roofline has nothing to read
    cell = cells.resolve(name)
    cell.config = dict(cell.config, params=cell.config["small"])
    hits = []

    def multiply(a, b, **kw):
        c, rep = workflow.ocean_spgemm(a, b, **kw)
        hits.append(rep.plan_cache_hit)
        return c, rep

    r = run.run_cell(cell, 2 ** 31 + 37, 0.3, True,
                     t_start=time.perf_counter(), multiply=multiply,
                     device_kind="TPU v5 lite")
    assert r["correct"]
    # the window's calls follow the traffic's warm-up calls; a private
    # plan cache makes every one of them a hit, no cache none of them
    window = hits[cell.traffic["warmup_calls"]:]
    assert r["attempted"] == len(window) > 0
    assert all(window) == (cell.traffic["plan_cache"] == "private")
    assert any(window) == (cell.traffic["plan_cache"] == "private")
    got = r["metrics"]
    ran_esc = (r["_info"]["rows_per_rung"]["esc"] > 0
               or max(r["_info"]["overflow_rows"]) > 0)
    assert ("esc_s" in got) == ("esc_slot_fill" in got) == ran_esc
    if ran_esc:
        assert got["esc_s"]["value"] > 0.0
        assert 0.0 < got["esc_slot_fill"]["value"] <= 100.0
    assert "esc_roofline" not in got
    assert ("lookup_s" in got) == (name in _listing("lookup_s"))
    if name.startswith("hypre_lap7"):
        assert r["_info"]["workflow"] == ["upper_bound"]
        small = cell.config["small"]
        assert r["_info"]["rows_per_rung"]["esc"] == \
            small["nx"] * small["ny"] * small["nz"]
        # 41,440 products in 2^16 slots
        assert got["esc_slot_fill"]["value"] == pytest.approx(
            100.0 * 41440 / 65536)
