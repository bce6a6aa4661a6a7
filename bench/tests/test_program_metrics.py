"""The per-layer metrics read from the program's own spans and counters
(``predict_s``, ``fallback_s``, ``collect_s``, ``host_copy_gib``), on a
hand-made trace and hand-made reports, and in a traced run of a cell on
the CPU."""
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import cells, run, trace_reduce

# host events on one line from 1000 ns: the window [1000, 11000] ns;
# plan.prediction [2000, 4000] and [10000, 12000] (1000 ns inside);
# exec.collect [5000, 5500] and [6000, 6500]; exec.overflow_fallback
# [7000, 10000]; exec.compact [13000, 14000], outside the window
_TRACE = '''
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 9000000 duration_ps: 2000000 }
    events { metadata_id: 5 offset_ps: 12000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "plan.prediction" } }
  event_metadata { key: 3 value { id: 3 name: "exec.collect" } }
  event_metadata { key: 4 value { id: 4 name: "exec.overflow_fallback" } }
  event_metadata { key: 5 value { id: 5 name: "exec.compact" } }
}
'''
# the same window with none of the program's spans in it
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
GIB = 2 ** 30
NEW = ["predict_s", "fallback_s", "collect_s", "host_copy_gib"]


def _ctx(text, reports):
    import jax
    tr = trace_reduce.from_profile(
        jax.profiler.ProfileData.from_text_proto(text))
    return run.Context(calls=2, window_s=1e-5, reports=reports, plan=None,
                       products=None, a_indptr=None, c_indptr=None,
                       trace=tr, busy_s=0.0, peaks=None,
                       memory_peak_bytes=0)


_REPORTS = [SimpleNamespace(copy_bytes={"d2h": GIB, "h2d": GIB // 2}),
            SimpleNamespace(copy_bytes={"d2h": GIB // 2, "h2d": 0})]


@pytest.mark.parametrize("name,want", [
    ("predict_s", 1.5e-6),     # (2000 + 1000) ns over 2 calls
    ("collect_s", 0.5e-6),     # (500 + 500) ns over 2 calls
    ("fallback_s", 1.5e-6),    # 3000 ns over 2 calls
    ("host_copy_gib", 1.0),    # (1.5 + 0.5) GiB over 2 calls
])
def test_program_metrics_read_a_hand_made_trace(name, want):
    assert cells.metric_reader(name)(_ctx(_TRACE, _REPORTS)) == \
        pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_program_metrics_find_nothing_where_nothing_matches(name):
    # a program whose spans reach no profiler trace and whose reports
    # count no copies reads nothing, and raises nothing
    bare = [SimpleNamespace(stage_seconds={}) for _ in range(2)]
    read = cells.metric_reader(name)
    assert read(_ctx(_BARE, bare)) is None
    assert read(_ctx(_BARE, [])) is None
    no_trace = _ctx(_BARE, bare)
    no_trace.trace = None
    assert read(no_trace) is None


def test_traced_run_reads_the_program_metrics():
    # a whole traced run of a cell at a small size, on the CPU: the
    # prediction's spans on the profiler's clock agree with the stage
    # seconds the reports hold
    cell = cells.resolve("hpcg_27pt_n36.fresh")
    cell.config = dict(cell.config, params=cell.config["small"])
    r = run.run_cell(cell, 2 ** 31 + 29, 0.3, True,
                     t_start=time.perf_counter(), device_kind="TPU v5 lite")
    assert r["correct"]
    got = r["metrics"]
    assert {"predict_s", "collect_s", "host_copy_gib"} <= set(got)
    stages = r["_info"]["stage_seconds"]
    mean = np.mean([s["prediction"] for s in stages])
    assert got["predict_s"]["value"] == pytest.approx(mean, abs=1e-3)
    assert got["predict_s"]["value"] <= got["plan_s"]["value"]
    assert got["host_copy_gib"]["value"] > 0.0


# one device plane: hll_merge launches [2000, 3000] and [6000, 7000] ns
# and a fusion beside them, inside the window [1000, 11000] ns
_DEVICE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "%hll_merge.1 = (s32[1024,128]) custom-call(...)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = s32[8] fusion(...)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
'''


def _estimated(workflow="estimation", err=0.25):
    acc = None if err is None else SimpleNamespace(est_err_p95=err)
    return SimpleNamespace(workflow=workflow, m_regs=64,
                           estimation_accuracy=acc)


def test_hll_merge_roofline_reads_a_hand_made_trace():
    from bench import peaks
    ctx = _ctx(_DEVICE, [_estimated(), _estimated()])
    ctx.a_indptr = np.array([0, 2, 5])
    ctx.peaks = peaks.peaks_for("TPU v5 lite")
    # 2 rows, 5 entries, 64 registers: 4*5 + 4*64*5 + (4*64 + 4)*2 bytes
    # and 64*5 operations, bound by bytes; 2000 ns of hll_merge, 2 calls
    want = 100.0 * (1820 / 819e9) * 2 / 2e-6
    read = cells.metric_reader("hll_merge_roofline")
    assert read(ctx) == pytest.approx(want, rel=1e-9)
    # a window whose calls did not all estimate has no full-row merge
    ctx.reports = [_estimated(), _estimated("symbolic")]
    assert read(ctx) is None
    # nor does a trace without the kernel
    assert read(_ctx(_BARE, [_estimated(), _estimated()])) is None


def test_est_err_p95_averages_the_calls_that_estimated():
    read = cells.metric_reader("est_err_p95")
    assert read(_ctx(_BARE, [_estimated(err=0.2), _estimated(err=0.4),
                             _estimated("symbolic", 0.0)])) == \
        pytest.approx(0.3)
    assert read(_ctx(_BARE, [_estimated("symbolic", 0.0)])) is None
    assert read(_ctx(_BARE, [_estimated(err=None)])) is None
    assert read(_ctx(_BARE, [])) is None


# device ops [1000, 3000] and [7000, 8000] ns in the window [1000, 11000];
# the program's exec.compact [3000, 7000] covers the first idle gap, and
# a runtime event of the profiler [4000, 6000] lies inside it
_GAPS = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion(...)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 4000000 } }
  lines { id: 2 name: "runtime" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "exec.compact" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(f)" } }
}
'''


def test_idle_gaps_are_named_by_the_program_spans_on_the_trace():
    import jax
    from repro.obs.trace import Tracer
    tr = trace_reduce.from_profile(
        jax.profiler.ProfileData.from_text_proto(_GAPS))
    tracer = Tracer()
    tracer.add_span("exec.compact", 0.0, 1.0)
    gaps = trace_reduce.idle_gaps(tr, run._named_spans(tr, tracer))
    assert [g[0] for g in gaps] == ["exec.compact", "bench.window"]
    assert [g[1] for g in gaps] == pytest.approx([4e-6, 3e-6])
    # every host event would name the first gap by the runtime's event
    assert trace_reduce.idle_gaps(tr, tr.host_spans)[0][0] == \
        "PjitFunction(f)"


@pytest.mark.parametrize("name", [
    w["name"] for w in cells.load_benchmark()["workloads"]
    if w["name"] in next(m for m in cells.load_benchmark()["per_layer"]
                         if m["name"] == "est_err_p95")["workloads"]])
def test_traced_estimation_run_reads_its_estimate_error(name):
    # a cell that lists est_err_p95 takes the estimation workflow at its
    # small size too; on the CPU no kernel reaches the trace, so the merge
    # kernel's roofline has nothing to read
    cell = cells.resolve(name)
    cell.config = dict(cell.config, params=cell.config["small"])
    r = run.run_cell(cell, 2 ** 31 + 31, 0.3, True,
                     t_start=time.perf_counter(), device_kind="TPU v5 lite")
    assert r["correct"]
    assert r["_info"]["workflow"] == ["estimation"]
    assert 0.0 < r["metrics"]["est_err_p95"]["value"] < 1.0
    assert "hll_merge_roofline" not in r["metrics"]
