"""Observability layer: tracer fast path, spans on the profiler's host
plane, host<->device copy counts, estimation-accuracy telemetry, the
metrics registry, and ServiceStats aggregation."""
import glob
import json
import threading
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.core import dispatch, formats
from repro.core.planner import OceanReport, PlanCache
from repro.core.workflow import ocean_spgemm
from repro.obs import accuracy, metrics, trace
from repro.serving.spgemm_service import ServiceStats


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_parents():
    tr = trace.Tracer()
    with trace.tracing(tr):
        with trace.span("outer", k=1):
            with trace.span("inner") as sp:
                sp.set(found=True)
    evs = tr.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # close order
    inner, outer = evs
    assert inner["parent"] == "outer" and outer["parent"] is None
    assert inner["attrs"] == {"found": True}
    assert outer["attrs"] == {"k": 1}
    assert inner["t0"] >= outer["t0"]
    assert inner["dur"] <= outer["dur"]


def test_add_span_retroactive_nests_under_open_span():
    tr = trace.Tracer()
    with trace.tracing(tr):
        with trace.span("stage"):
            trace.add_span("sub", tr.epoch, 0.001, rows=3)
    sub = tr.events()[0]
    assert sub["name"] == "sub" and sub["parent"] == "stage"
    assert sub["attrs"] == {"rows": 3}


def test_add_span_cross_thread_is_parentless():
    tr = trace.Tracer()
    with trace.tracing(tr):
        with trace.span("stage"):
            tr.add_span("worker", tr.epoch, 0.001, tid=999,
                        thread="merge-worker")
    w = tr.events()[0]
    assert w["tid"] == 999 and w["thread"] == "merge-worker"
    assert w["parent"] is None  # other thread's nesting is unknown


def test_tracing_restores_previous_tracer():
    assert trace.current() is None
    tr1, tr2 = trace.Tracer(), trace.Tracer()
    with trace.tracing(tr1):
        assert trace.current() is tr1
        with trace.tracing(tr2):
            assert trace.current() is tr2
        assert trace.current() is tr1
    assert trace.current() is None and not trace.enabled()


def _count_annotations(monkeypatch, calls):
    """Replace ``jax.profiler.TraceAnnotation`` with a counting twin."""
    real = jax.profiler.TraceAnnotation

    class Counting:
        def __init__(self, name, **kw):
            calls["ann"] += 1
            self._ann = real(name, **kw)

        def __enter__(self):
            self._ann.__enter__()
            return self

        def __exit__(self, *exc):
            return self._ann.__exit__(*exc)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)


def test_disabled_path_constructs_no_span(monkeypatch):
    """The no-op fast path: with tracing off, span() must return the
    NULL_SPAN singleton without ever constructing a Span or a profiler
    annotation."""
    calls = {"n": 0, "ann": 0}
    orig_init = trace.Span.__init__

    def counting_init(self, *a, **kw):
        calls["n"] += 1
        orig_init(self, *a, **kw)

    monkeypatch.setattr(trace.Span, "__init__", counting_init)
    _count_annotations(monkeypatch, calls)
    assert trace.current() is None
    for _ in range(100):
        with trace.span("hot", attr=1) as sp:
            sp.measured(0.0, 1.0).set(more=2)
        trace.add_span("hot2", 0.0, 1.0, rows=5)
    assert calls == {"n": 0, "ann": 0}
    assert trace.span("x") is trace.NULL_SPAN
    # and the same shims prove the enabled path constructs both, once
    tr = trace.Tracer()
    with trace.tracing(tr):
        with trace.span("on"):
            pass
        trace.add_span("retro", 0.0, 1.0)
    assert calls == {"n": 1, "ann": 1} and len(tr) == 2


def test_untraced_spgemm_constructs_no_annotation(monkeypatch):
    calls = {"ann": 0}
    _count_annotations(monkeypatch, calls)
    a = formats.random_uniform_csr(11, 48, 40, 4.0)
    b = formats.random_uniform_csr(12, 40, 52, 4.0)
    for executor in ("pipelined", "threaded", "serial"):
        ocean_spgemm(a, b, cache=False, executor=executor)
    assert calls["ann"] == 0


def test_measured_span_records_the_callers_measurement():
    tr = trace.Tracer()
    with trace.tracing(tr):
        with trace.span("stage") as sp:
            sp.measured(tr.epoch + 1.0, 0.25)
    ev = tr.events()[0]
    assert (ev["t0"], ev["dur"]) == (tr.epoch + 1.0, 0.25)


def test_threaded_spans_keep_independent_stacks():
    tr = trace.Tracer()
    errs = []

    def worker(i):
        try:
            with trace.span(f"w{i}"):
                with trace.span(f"w{i}.inner"):
                    pass
        except Exception as e:  # pragma: no cover
            errs.append(e)

    with trace.tracing(tr):
        ts = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    assert not errs and len(tr) == 16
    for e in tr.events():
        if e["name"].endswith(".inner"):
            assert e["parent"] == e["name"][:-len(".inner")]


# ---------------------------------------------------------------------------
# spans on the profiler's clock
# ---------------------------------------------------------------------------

def _profiled(tmp_path, fn):
    """Run ``fn`` with a Tracer installed under ``jax.profiler``; returns
    (fn's result, the tracer, the host plane's lines)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tr = trace.Tracer()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with trace.tracing(tr):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path[0])
    lines = [ln for plane in pd.planes if plane.name.startswith("/host:")
             for ln in plane.lines]
    return out, tr, lines


def _seconds(lines, name):
    return sum(e.duration_ns for ln in lines for e in ln.events
               if e.name == name) * 1e-9


def test_traced_spgemm_lands_on_the_profilers_host_plane(tmp_path):
    """Each stage's annotation on the profiler's host plane brackets the
    measurement its stage_seconds holds, within 1 ms; the tracer records
    that very measurement."""
    a = formats.powerlaw_csr(45, 256, 256, 8.0)
    c_ref, _ = ocean_spgemm(a, a, cache=False)
    (c, rep), tr, lines = _profiled(
        tmp_path, lambda: ocean_spgemm(a, a, cache=False))
    assert np.array_equal(np.asarray(c.indices), np.asarray(c_ref.indices))
    for name, stage in [("plan.prediction", "prediction"),
                        ("exec.dispatch", "dispatch"),
                        ("exec.collect", "collect")]:
        on_plane = _seconds(lines, name)
        assert on_plane > 0.0, name
        assert on_plane == pytest.approx(rep.stage_seconds[stage],
                                         abs=1e-3), name
        recorded = sum(e["dur"] for e in tr.events() if e["name"] == name)
        assert recorded == pytest.approx(rep.stage_seconds[stage],
                                         rel=1e-9, abs=1e-12), name
    compact = [e["dur"] for e in tr.events() if e["name"] == "exec.compact"]
    assert len(compact) == 1
    assert _seconds(lines, "exec.compact") == pytest.approx(compact[0],
                                                            abs=1e-3)
    for name in ("plan.analysis", "plan.binning", "analysis.wave1",
                 "analysis.wave2", "exec.merge", "exec.report"):
        assert _seconds(lines, name) > 0.0, name


def test_merge_worker_spans_sit_on_the_workers_thread_line(tmp_path):
    a = formats.skewed_rows_csr(44, 400, 400, 5.0)
    _, tr, lines = _profiled(
        tmp_path, lambda: ocean_spgemm(a, a, cache=False,
                                       executor="threaded"))
    names = [{e.name for e in ln.events} for ln in lines]
    worker = [n for n in names if "exec.merge_worker" in n]
    assert len(worker) == 1
    assert not worker[0] & {"exec.collect", "exec.dispatch",
                            "plan.analysis"}
    main = [n for n in names if "exec.collect" in n]
    assert len(main) == 1 and "exec.merge_worker" not in main[0]
    # the tracer's own records: the worker's thread, no parent there
    evs = [e for e in tr.events() if e["name"] == "exec.merge_worker"]
    assert evs and {e["thread"] for e in evs} == {"ocean-merge-worker"}
    assert all(e["parent"] is None for e in evs)


# ---------------------------------------------------------------------------
# host<->device copy counts
# ---------------------------------------------------------------------------

def test_copy_helpers_count_what_crosses():
    copies = dispatch.new_copy_bytes()
    dev = dispatch.to_device(np.arange(6, dtype=np.int32), copies)
    assert copies == {"d2h": 0, "h2d": 24}
    # a device array moves between devices, if at all: nothing crosses
    dispatch.to_device(dev, copies)
    # int64 lands as int32 while x64 is off
    dispatch.to_device(np.arange(3, dtype=np.int64), copies)
    assert copies == {"d2h": 0, "h2d": 36}
    host = dispatch.to_host(dev, copies)
    assert host.tolist() == list(range(6)) and copies["d2h"] == 24
    dispatch.to_host(host, copies)
    assert copies["d2h"] == 24


def test_copy_bytes_of_a_small_product_match_a_hand_count():
    # A = B = the 8 x 8 identity: one product a row, so the selector takes
    # the upper bound and every row goes to the ESC bin
    a = formats.csr_from_arrays(np.arange(9), np.arange(8),
                                np.ones(8, np.float32), (8, 8))
    c, rep = ocean_spgemm(a, a, cache=False)
    assert rep.workflow == "upper_bound" and rep.bins.get("esc") == 8
    # planning: wave 1 uploads A and B as blocks padded to 64 rows and
    # 256 entries ((65 + 256) int32 each); wave 2 reads back products and
    # output column bounds, 64 int32 each
    plan_h2d, plan_d2h = 2 * (65 + 256) * 4, 3 * 64 * 4
    # execution: A's values down (8 f32); the ESC bin's sub-CSR up (9 + 8
    # int32, 8 f32); its count (1 int32), row pointers (9 int32) and one
    # ELL column of indices and values (8 + 8) down; C up (9 + 8 int32,
    # 8 f32); C's row pointers down for the accuracy telemetry (9 int32)
    exec_h2d = (9 + 8 + 8) * 4 + (9 + 8 + 8) * 4
    exec_d2h = (8 + 1 + 9 + 16 + 9) * 4
    assert rep.copy_bytes == {"d2h": plan_d2h + exec_d2h,
                              "h2d": plan_h2d + exec_h2d}
    # a plan-cache hit plans nothing: only the execution's copies
    cache = PlanCache()
    ocean_spgemm(a, a, cache=cache)
    _, hit = ocean_spgemm(a, a, cache=cache)
    assert hit.plan_cache_hit
    assert hit.copy_bytes == {"d2h": exec_d2h, "h2d": exec_h2d}


# ---------------------------------------------------------------------------
# estimation-accuracy telemetry
# ---------------------------------------------------------------------------

def _fake_plan(pred, products, *, dense=(), hash_=(), esc_rows=None,
               workflow="estimation", feed_forward=False):
    return SimpleNamespace(
        workflow=workflow, feed_forward=feed_forward,
        pred_row_nnz=np.asarray(pred, np.float64),
        products=np.asarray(products, np.int64),
        dense=list(dense), hash=list(hash_),
        esc=None if esc_rows is None else SimpleNamespace(
            rows=np.asarray(esc_rows, np.int64)))


def test_measure_accuracy_math():
    # rows: exact [10, 20, 0(dead), 8]; pred [10, 30, 5, 4]
    pred = [10.0, 30.0, 5.0, 4.0]
    exact = [10, 20, 0, 8]
    dense = [SimpleNamespace(is_longrow=False, window=256, cap=32,
                             rows=np.array([0, 1]))]
    hash_ = [SimpleNamespace(table=64, spill=16, rows=np.array([3]))]
    plan = _fake_plan(pred, [5, 5, 0, 5], dense=dense, hash_=hash_)
    acc = accuracy.measure_accuracy(plan, np.asarray(exact))
    assert acc.n_rows == 3  # dead row 2 excluded
    # signed errors over live rows: 0.0, 0.5, -0.5 -> |err| sorted 0, .5, .5
    assert acc.est_err_p50 == pytest.approx(0.5)
    assert acc.est_err_p95 == pytest.approx(0.5)
    assert sum(acc.signed_err_hist.values()) == 3
    assert acc.signed_err_hist["[0.5,1)"] == 1      # +0.5 overprediction
    assert acc.signed_err_hist["[-0.5,-0.2)"] == 1  # -0.5 underprediction
    # dense cap 32 >= 4x max(exact,1) for rows 0 (10) and 1 (20)? 32<40,80
    d = acc.per_rung["dense_w256"]
    assert d == {"rows": 2, "capacity": 32, "underpredicted": 0,
                 "overpredicted": 0}
    # hash capacity table+spill = 80 >= 4*8 -> row 3 overpredicted
    h = acc.per_rung["hash_t64"]
    assert h["rows"] == 1 and h["overpredicted"] == 1
    assert acc.rung_mispredict_rate == pytest.approx(1 / 3)
    s = acc.summary()
    assert set(s) == {"workflow", "n_rows", "est_err_p50", "est_err_p95",
                      "rung_mispredict_rate", "overflow_fallback_causes"}


def test_measure_accuracy_underprediction_and_esc_exempt():
    dense = [SimpleNamespace(is_longrow=False, window=256, cap=8,
                             rows=np.array([0]))]
    plan = _fake_plan([4.0, 100.0], [3, 3], dense=dense, esc_rows=[1])
    acc = accuracy.measure_accuracy(plan, np.asarray([16, 1]),
                                    {"dense_window": 1})
    assert acc.per_rung["dense_w256"]["underpredicted"] == 1
    # ESC rows never mispredict: the pass is exact
    assert acc.per_rung["esc"] == {"rows": 1, "capacity": 0,
                                   "underpredicted": 0, "overpredicted": 0}
    assert acc.overflow_causes == {"dense_window": 1}


def test_measure_accuracy_none_without_prediction():
    plan = _fake_plan([1.0], [1])
    plan.pred_row_nnz = None  # plans frozen before this telemetry
    assert accuracy.measure_accuracy(plan, np.asarray([1])) is None


def test_accuracy_feeds_installed_registry():
    reg = metrics.MetricsRegistry()
    plan = _fake_plan([10.0], [5], dense=[SimpleNamespace(
        is_longrow=False, window=256, cap=32, rows=np.array([0]))])
    prev = metrics.install_registry(reg)
    try:
        accuracy.measure_accuracy(plan, np.asarray([10]),
                                  {"hash_spill": 2})
    finally:
        metrics.install_registry(prev)
    snap = reg.snapshot()
    assert snap["counters"]["ocean.executions{workflow=estimation}"] == 1
    assert snap["counters"][
        "ocean.overflow_fallback_rows{cause=hash_spill}"] == 2
    assert snap["counters"]["ocean.rung_rows{rung=dense_w256}"] == 1


def test_record_decision_contents():
    cfg = SimpleNamespace(er_threshold=2.0, cr_threshold=0.5,
                          upper_bound_avg_products=16.0)
    rec = accuracy.record_decision(
        workflow="upper_bound", forced=None, feed_forward=False, er=1.5,
        sampled_cr=0.4, nproducts_avg=7.0, cfg=cfg)
    assert rec["workflow"] == "upper_bound" and rec["forced"] is None
    assert rec["er"] == 1.5 and rec["sampled_cr"] == 0.4
    assert rec["er_threshold"] == 2.0 and rec["cr_threshold"] == 0.5


def test_report_carries_accuracy_and_decision():
    a = formats.random_uniform_csr(21, 64, 48, 4.0)
    b = formats.random_uniform_csr(22, 48, 56, 4.0)
    _, rep = ocean_spgemm(a, b, cache=False)
    acc = rep.estimation_accuracy
    assert acc is not None and acc.n_rows > 0
    assert acc.est_err_p95 >= acc.est_err_p50 >= 0.0
    assert 0.0 <= acc.rung_mispredict_rate <= 1.0
    assert sum(r["rows"] for r in acc.per_rung.values()) > 0
    assert rep.decision is not None
    assert rep.decision["workflow"] == rep.workflow
    assert rep.audit() == []


# ---------------------------------------------------------------------------
# OceanReport.audit
# ---------------------------------------------------------------------------

def _report(**kw):
    base = dict(workflow="estimation", er=1.0, sampled_cr=None,
                nproducts_avg=1.0, total_products=10, m_regs=64,
                stage_seconds={"analysis": 0.1, "merge": 0.2},
                bins={}, overflow_rows=0, nnz_out=5)
    base.update(kw)
    return OceanReport(**base)


def test_audit_flags_violations():
    assert _report().audit() == []
    assert any("negative" in v for v in _report(
        stage_seconds={"analysis": -0.1}).audit())
    bad = _report(overlap_seconds=0.5)  # > merge stage 0.2
    assert any("exceeds parent merge" in v for v in bad.audit())
    assert bad.merge_overlap_frac == 1.0  # the view clamps
    assert any("negative" in v
               for v in _report(wave2_overlap_seconds=-1.0).audit())
    assert any("analysis_shard_seconds" in v for v in _report(
        analysis_shard_seconds=[0.1, -0.2]).audit())


def test_merge_overlap_frac_is_a_view():
    rep = _report(overlap_seconds=0.1)
    assert rep.merge_overlap_frac == pytest.approx(0.5)
    rep.stage_seconds["merge"] = 0.0
    assert rep.merge_overlap_frac == 0.0  # no merge work -> no fraction


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_labeled_series_and_snapshot():
    reg = metrics.MetricsRegistry()
    reg.counter("req").inc()
    reg.counter("req", tenant="acme").inc(2)
    reg.counter("req", tenant="globex").inc(3)
    assert reg.counter("req").value == 1  # get-or-create returns same obj
    assert reg.labeled_values("req", "tenant") == {"acme": 2, "globex": 3}
    reg.gauge("depth").set(4)
    reg.gauge("peak", agg="max").set_max(7)
    reg.histogram("lat").record(1.0)
    snap = reg.snapshot()
    assert snap["counters"] == {"req": 1, "req{tenant=acme}": 2,
                                "req{tenant=globex}": 3}
    assert snap["gauges"] == {"depth": 4, "peak": 7}
    assert snap["histograms"]["lat"]["count"] == 1
    json.dumps(snap)  # export form must be JSON-ready


def test_registry_merge_policies_and_reset():
    a, b = metrics.MetricsRegistry(), metrics.MetricsRegistry()
    a.counter("n").inc(2)
    b.counter("n").inc(5)
    a.gauge("depth").set(1)
    b.gauge("depth").set(2)
    a.gauge("peak", agg="max").set(9)
    b.gauge("peak", agg="max").set(4)
    a.gauge("mode", agg="last").set(1)
    b.gauge("mode", agg="last").set(2)
    a.histogram("lat").record(1.0)
    b.histogram("lat").record(3.0)
    a.merge(b)
    assert a.counter("n").value == 7
    assert a.gauge("depth").value == 3          # sum
    assert a.gauge("peak", agg="max").value == 9  # max keeps larger
    assert a.gauge("mode", agg="last").value == 2  # merged-in wins
    h = a.histogram("lat")
    assert h.count == 2 and sorted(h.sample()) == [1.0, 3.0]
    a.reset()
    assert a.counter("n").value == 0 and a.gauge("peak").value == 0
    assert a.histogram("lat").count == 0 and not a.histogram("lat").sample()


def test_histogram_reservoir_keeps_newest_and_percentiles_exact():
    h = metrics.Histogram(cap=8)
    for v in range(20):
        h.record(float(v))
    assert h.count == 20 and h.total == sum(range(20))
    assert h.sample() == [float(v) for v in range(12, 20)]  # newest cap
    xs = h.sample()
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(xs, q)))
    assert metrics.Histogram().percentile(50) == 0.0


# ---------------------------------------------------------------------------
# ServiceStats aggregation (registry-backed views)
# ---------------------------------------------------------------------------

def test_service_stats_merge_under_threaded_burst():
    """Per-worker ServiceStats merged concurrently into one aggregate:
    counters sum exactly, peaks take the max, reservoirs concatenate."""
    total = ServiceStats()
    n_workers, per = 8, 50
    errs = []

    def worker(i):
        try:
            st = ServiceStats()
            for j in range(per):
                st.requests += 1
                st.note_queue_depth(i + 1)
                st.note_plan_warm_hit("acme" if j % 2 else "globex")
                st.record_latency(0.001 * (i + 1))
            total.merge(st)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(n_workers)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    assert total.requests == n_workers * per
    assert total.plan_warm_hits == n_workers * per
    assert total.plan_warm_hits_by_tenant == {
        "acme": n_workers * (per // 2), "globex": n_workers * (per // 2)}
    assert total.queue_depth_peak == n_workers  # max across workers
    assert len(total.latency_sample()) == n_workers * per
    snap = total.snapshot()
    assert snap["counters"]["requests"] == total.requests
    assert snap["histograms"]["latency_seconds"]["count"] == \
        n_workers * per
    total.reset()
    assert total.requests == 0 and total.queue_depth_peak == 0
    assert total.latency_sample() == []
    assert total.plan_warm_hits_by_tenant == {"acme": 0, "globex": 0}


def test_service_stats_fields_are_registry_views():
    st = ServiceStats()
    st.requests += 3
    st.batches = 2
    assert st.registry.counter("requests").value == 3
    st.registry.counter("batches").inc(5)
    assert st.batches == 7  # reads come from the same series
    assert st.snapshot()["counters"]["requests"] == 3
