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
    from bench.tests.test_bench_check import SMALL
    cell = cells.resolve("hpcg_27pt_n36.fresh")
    cell.config = dict(cell.config, params=SMALL[cell.config_name])
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
