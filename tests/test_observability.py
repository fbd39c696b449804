"""Unified observability plane (repro/obs/): histogram bucket math and
quantile bounds (property-tested), span nesting and ring eviction,
cross-process trace stitching through a real spawned ``ProcessBackend``
worker, exporter formats, and the ``Castor.stats()`` schema-stability
contract ISSUE 10 makes ``snapshot()`` a superset of."""
import functools
import json
import math

import pytest
from _hypothesis_compat import given, settings, st

from repro.core import Castor
from repro.forecast import LinearForecaster
from repro.obs.export import chrome_trace, prometheus_text, write_chrome_trace
from repro.obs.metrics import (_EMIN, _NBUCKETS, Histogram, MetricsRegistry,
                               bucket_bounds, bucket_index)
from repro.obs.trace import Tracer, get_tracer, set_tracer
from repro.serverless import ProcessBackend, ServerlessExecutor
from repro.testing import FLEET_NOW as NOW, build_steady_castor

#: positive range safely inside the unclamped buckets: lower edge of
#: bucket 1 is 2**_EMIN, upper edge of the second-to-last 2**(_EMIN+62)
_LO = 2.0 ** _EMIN
_HI = 2.0 ** (_EMIN + 40)


class _FakeClock:
    """Injectable monotonic clock: each ``advance`` is explicit, so span
    durations and orderings are exact, not wall-time dependent."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt=1.0):
        self.t += dt
        return self.t


@pytest.fixture
def tracer():
    """Fresh deterministic tracer installed as the process default (the
    components look the default up at call time), restored afterwards."""
    clock = _FakeClock()
    tr = Tracer(capacity=4096, clock=clock, epoch=(0.0, 0.0))
    tr.clock_fake = clock
    prev = set_tracer(tr)
    try:
        yield tr
    finally:
        set_tracer(prev)


# ------------------------------------------------------- histogram math
@settings(max_examples=50)
@given(st.floats(min_value=_LO, max_value=_HI))
def test_bucket_index_brackets_value(v):
    i = bucket_index(v)
    lo, hi = bucket_bounds(i)
    assert lo <= v < hi or v == _LO == hi  # frexp: [2**(e-1), 2**e)
    assert 0 <= i < _NBUCKETS
    assert hi == (2.0 * lo if i else 2.0 ** _EMIN)


def test_bucket_index_edges():
    assert bucket_index(0.0) == 0
    assert bucket_index(-1.0) == 0
    assert bucket_index(5e-300) == 0          # underflow clamps
    assert bucket_index(1e300) == _NBUCKETS - 1


@settings(max_examples=30)
@given(st.lists(st.floats(min_value=_LO, max_value=_HI),
                min_size=1, max_size=200),
       st.floats(min_value=0.05, max_value=0.99))
def test_quantile_within_bucket_factor_of_order_statistic(vals, q):
    """The estimate is the upper edge of the crossing bucket, clamped to
    the observed range: always in [min, max], and within a factor of 2
    above the true order statistic (log2 buckets)."""
    h = Histogram("t")
    for v in vals:
        h.observe(v)
    est = h.quantile(q)
    true = sorted(vals)[max(0, math.ceil(q * len(vals)) - 1)]
    assert min(vals) <= est <= max(vals)
    assert true <= est <= 2.0 * true


@settings(max_examples=20)
@given(st.lists(st.floats(min_value=_LO, max_value=_HI),
                min_size=1, max_size=100))
def test_quantile_monotone_in_q(vals):
    h = Histogram("t")
    for v in vals:
        h.observe(v)
    qs = [h.quantile(q) for q in (0.1, 0.5, 0.9, 0.95, 0.99, 1.0)]
    assert qs == sorted(qs)


def test_histogram_summary_and_empty():
    h = Histogram("t")
    assert h.quantile(0.5) == 0.0
    assert h.summary()["count"] == 0 and h.summary()["p99"] == 0.0
    for v in (1.0, 2.0, 4.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 3 and s["sum"] == 7.0
    assert s["min"] == 1.0 and s["max"] == 4.0
    assert s["mean"] == pytest.approx(7.0 / 3.0)


def test_registry_get_or_create_and_type_collision():
    r = MetricsRegistry()
    c = r.counter("a.b")
    c.inc()
    c.inc(3)
    assert r.counter("a.b") is c and c.value == 4
    r.gauge("g").set(2.5)
    r.histogram("h").observe(1.0)
    with pytest.raises(TypeError):
        r.gauge("a.b")                 # registered as a Counter
    snap = r.snapshot()
    assert snap["a.b"] == 4 and snap["g"] == 2.5
    assert snap["h"]["count"] == 1
    assert list(snap) == sorted(snap)


# ------------------------------------------------------------- tracer
def test_span_nesting_parents_and_trace_ids(tracer):
    with tracer.span("root", k=1):
        tracer.clock_fake.advance()
        with tracer.span("child"):
            tracer.clock_fake.advance()
            with tracer.span("grandchild"):
                tracer.clock_fake.advance()
    with tracer.span("root2"):
        pass
    by_name = {s.name: s for s in tracer.spans()}
    root, child, grand = (by_name["root"], by_name["child"],
                          by_name["grandchild"])
    assert root.parent_id == 0
    assert child.parent_id == root.span_id
    assert grand.parent_id == child.span_id
    assert root.trace_id == child.trace_id == grand.trace_id
    assert by_name["root2"].trace_id != root.trace_id   # new root trace
    # children finish inside the parent interval (deterministic clock)
    assert root.t0 <= child.t0 <= grand.t0
    assert grand.t1 <= child.t1 <= root.t1
    assert root.duration == 3.0 and grand.duration == 1.0
    assert root.args == {"k": 1}


def test_span_late_args_and_disabled_noop(tracer):
    with tracer.span("s") as sp:
        sp.set(jobs=7)
    assert tracer.spans()[-1].args == {"jobs": 7}
    tracer.enabled = False
    before = tracer.finished
    with tracer.span("off") as sp:
        sp.set(ignored=True)           # the shared no-op accepts set()
    assert tracer.finished == before
    assert tracer.current() is None


def test_ring_eviction_bounds_buffer(tracer):
    small = Tracer(capacity=4, clock=tracer.clock_fake, epoch=(0.0, 0.0))
    for i in range(10):
        with small.span(f"s{i}"):
            pass
    assert len(small.spans()) == 4
    assert small.finished == 10 and small.evicted == 6
    assert [s.name for s in small.spans()] == ["s6", "s7", "s8", "s9"]
    st_ = small.stats()
    assert st_["buffered"] == 4 and st_["evicted"] == 6


def test_export_since_and_absorb_remap(tracer):
    """The stitching primitives, single-process: a 'worker' tracer adopts
    the invoker's context, its shipped spans re-id onto the invoker's
    counter with internal parentage remapped, the remote parent link
    preserved, and timestamps rebased to ``t_base``."""
    worker = Tracer(capacity=64, clock=tracer.clock_fake, epoch=(0.0, 0.0))
    invoke_id = tracer.allocate_id()
    trace_id = tracer.new_trace_id()
    mark = worker.mark()
    with worker.adopt({"trace_id": trace_id, "parent_id": invoke_id}):
        with worker.span("worker.execute"):
            tracer.clock_fake.advance()
            with worker.span("exec.bin"):
                tracer.clock_fake.advance()
    shipped = worker.export_since(mark)
    assert [d["name"] for d in shipped] == ["exec.bin", "worker.execute"]
    assert all(d["trace_id"] == trace_id for d in shipped)
    n = tracer.absorb(shipped, t_base=100.0)
    assert n == 2
    by_name = {s.name: s for s in tracer.spans()}
    we, eb = by_name["worker.execute"], by_name["exec.bin"]
    assert we.parent_id == invoke_id          # remote parent preserved
    assert eb.parent_id == we.span_id         # internal link remapped
    assert we.span_id != shipped[1]["span_id"]  # re-id'd locally
    assert we.trace_id == eb.trace_id == trace_id
    assert min(we.t0, eb.t0) == 100.0         # rebased onto t_base


def test_record_with_preallocated_id(tracer):
    sid = tracer.allocate_id()
    tid = tracer.new_trace_id()
    got = tracer.record("serverless.invoke", 1.0, 2.0, span_id=sid,
                        trace_id=tid, args={"ok": True})
    (sp,) = tracer.spans()
    assert got == sid and sp.span_id == sid and sp.trace_id == tid
    assert sp.duration == 1.0 and sp.args == {"ok": True}


# ----------------------------------------------------------- exporters
def test_chrome_trace_export(tracer, tmp_path):
    with tracer.span("castor.tick", now=1.0):
        tracer.clock_fake.advance(0.5)
        with tracer.span("scheduler.poll"):
            tracer.clock_fake.advance(0.25)
    doc = chrome_trace(tracer)
    evs = doc["traceEvents"]
    assert len(evs) == 2 and all(e["ph"] == "X" for e in evs)
    tick = next(e for e in evs if e["name"] == "castor.tick")
    assert tick["cat"] == "castor"
    assert tick["dur"] == pytest.approx(0.75e6)      # µs
    assert tick["args"]["now"] == 1.0
    assert "span_id" in tick["args"] and "parent_id" in tick["args"]
    path = tmp_path / "t.perfetto-trace.json"
    write_chrome_trace(path, tracer)
    assert json.loads(path.read_text())["traceEvents"]


def test_prometheus_text_exposition():
    r = MetricsRegistry()
    r.counter("serverless.invocations").inc(3)
    r.gauge("store.points").set(12.0)
    h = r.histogram("exec.bin_seconds")
    h.observe(0.5)
    h.observe(1.5)
    text = prometheus_text(r)
    assert "repro_serverless_invocations 3" in text
    assert "repro_store_points 12.0" in text
    assert 'repro_exec_bin_seconds_bucket{le="+Inf"} 2' in text
    assert "repro_exec_bin_seconds_count 2" in text
    assert "repro_exec_bin_seconds_sum 2.0" in text
    # cumulative: every bucket count is non-decreasing
    counts = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
              if line.startswith("repro_exec_bin_seconds_bucket")]
    assert counts == sorted(counts)


# ------------------------------------------ stitched cross-process trace
def test_process_backend_produces_one_stitched_trace(tracer):
    """ISSUE 10 acceptance: a serverless run through a REAL spawned
    ``ProcessBackend`` worker yields ONE trace in the invoker's tracer —
    worker spans parent under the pre-allocated invoke-span ids, and the
    span counts agree with ``InvocationMonitor``'s invocation counts."""
    tracer.clock = __import__("time").perf_counter   # real latencies
    factory = functools.partial(build_steady_castor, "lr",
                                LinearForecaster, {}, n=2)
    c = factory()
    ex = ServerlessExecutor(c, backend=ProcessBackend(factory, n_workers=1),
                            speculative=False)
    c._serverless_ex = ex
    try:
        res = c.tick(NOW, executor="serverless")
        assert res and all(r.ok for r in res)
    finally:
        ex.close()
    spans = tracer.spans()
    ticks = [s for s in spans if s.name == "castor.tick"]
    invokes = [s for s in spans if s.name == "serverless.invoke"]
    workers = [s for s in spans if s.name == "worker.execute"]
    assert len(ticks) == 1
    # ONE stitched trace: every span shares the tick's trace id
    assert {s.trace_id for s in spans} == {ticks[0].trace_id}
    # span counts == monitor counts (the 1:1 record/span contract)
    assert len(invokes) == len(ex.monitor.records) >= 2  # train + score
    assert len(workers) == sum(1 for r in ex.monitor.records if r["ok"])
    # stitched parentage: each worker span under exactly one invoke span
    invoke_ids = {s.span_id for s in invokes}
    assert all(w.parent_id in invoke_ids for w in workers)
    # invoke spans hang off the serverless.phase spans under the tick
    phases = {s.span_id for s in spans if s.name == "serverless.phase"}
    assert all(s.parent_id in phases for s in invokes)
    # worker-side children (exec phases) parent under worker.execute
    worker_ids = {w.span_id for w in workers}
    inner = [s for s in spans if s.name.startswith("exec.phase.")
             and s.parent_id in worker_ids]
    assert inner, "worker executor spans did not ship back"


def test_invoke_spans_match_monitor_with_retries(tracer):
    """Failed copies get spans too: one 'serverless.invoke' span per
    monitor record even when deliveries fail and retry."""
    import threading

    from repro.serverless import InlineBackend
    from repro.serverless.backend import InvocationError

    class _Flaky(InlineBackend):
        def __init__(self, system):
            super().__init__(system, n_workers=2)
            self.seen = {}
            self._l = threading.Lock()

        def invoke(self, payload, worker_id):
            with self._l:
                n = self.seen.get(payload.invocation_id, 0)
                self.seen[payload.invocation_id] = n + 1
            if n < 1:
                raise InvocationError("transient")
            return super().invoke(payload, worker_id)

    tracer.clock = __import__("time").perf_counter
    c = build_steady_castor("lr", LinearForecaster, {}, n=3)
    ex = ServerlessExecutor(c, backend=_Flaky(c), max_retries=2,
                            backoff_base_s=0.01, speculative=False)
    res = ex.run(c.scheduler.poll(NOW))
    assert res and all(r.ok for r in res)
    invokes = [s for s in tracer.spans() if s.name == "serverless.invoke"]
    assert len(invokes) == len(ex.monitor.records)
    failed = [s for s in invokes if not s.args["ok"]]
    assert len(failed) == sum(1 for r in ex.monitor.records if not r["ok"])
    assert all(s.args.get("error") for s in failed)


# ------------------------------------------------- monitor ring bound
def test_invocation_monitor_ring_is_bounded():
    from repro.serverless.monitor import InvocationMonitor
    from repro.serverless.payload import InvocationPayload, InvocationResult

    mon = InvocationMonitor(max_records=8)
    for i in range(20):
        p = InvocationPayload(invocation_id=f"i{i}", jobs=(),
                              created_at=0.0)
        r = InvocationResult(invocation_id=f"i{i}", worker_id="w0",
                             cold_start=(i == 0), started_at=float(i),
                             finished_at=float(i) + 0.5, outcomes=())
        mon.record(payload=p, result=r, worker_id="w0")
    assert len(mon.records) == 8                   # ring, not a list
    assert mon.dropped == 12
    assert mon.invocations == 20                   # totals keep counting
    assert [r["queue_s"] for r in mon.records] == [float(i)
                                                   for i in range(12, 20)]
    # p95 over the tail window still works on the deque
    assert mon.recent_queue_p95(window=4) >= 18.0
    s = mon.summary()
    assert s["invocations"] == 20 and s["records_dropped"] == 12


# ------------------------------------------------ rolling error gauges
def test_detection_rolling_error_gauges():
    from repro.flows.detection import DetectionRecord, DetectionStore
    from repro.obs.metrics import get_metrics

    ds = DetectionStore(rolling_window=4)
    for i, score in enumerate([0.0, 1.0, 2.0, 3.0, 4.0, 5.0]):
        ds.save(DetectionRecord(
            deployment_name="det-a", signal="S", entity="E",
            scheduled_at=float(i), score=score, n_readings=1,
            n_anomalies=0, band_misses=0, model_version=1,
            derived_signal="S.anomaly"))
    # window 4 over [2,3,4,5] -> mean 3.5; duplicates must not move it
    ds.save(DetectionRecord(
        deployment_name="det-a", signal="S", entity="E",
        scheduled_at=5.0, score=99.0, n_readings=1, n_anomalies=0,
        band_misses=0, model_version=1, derived_signal="S.anomaly"))
    assert ds.rolling_errors() == {"det-a": pytest.approx(3.5)}
    g = get_metrics().gauge("detection.rolling_error.det-a")
    assert g.value == pytest.approx(3.5)


# ------------------------------------------------- schema stability
def test_castor_stats_schema_is_stable():
    """``stats()`` is the backward-compatible view ``snapshot()`` wraps:
    the pre-ISSUE-10 key set must survive verbatim."""
    c = build_steady_castor("lr", LinearForecaster, {}, n=2)
    res = c.tick(NOW)
    assert res and all(r.ok for r in res)
    s = c.stats()
    for key in ("points", "segments", "store_reads", "store_read_many",
                "deployments", "deployments_by_flow",
                "deployment_revision", "model_versions", "forecasts",
                "detection", "scheduler"):
        assert key in s, key
    for key in ("records", "scored_readings", "anomalies_flagged",
                "band_misses", "band_miss_rate"):
        assert key in s["detection"], key
    snap = c.snapshot()
    assert snap["stats"] == c.stats()
    assert snap["trace"]["capacity"] > 0
    assert any(k.startswith("store.") for k in snap["metrics"])
    assert any(k.startswith("scheduler.") for k in snap["metrics"])


def test_castor_dump_trace_writes_chrome_json(tmp_path):
    tr = Tracer(capacity=1024)
    prev = set_tracer(tr)
    try:
        c = build_steady_castor("lr", LinearForecaster, {}, n=2)
        res = c.tick(NOW)
        assert res and all(r.ok for r in res)
        path = c.dump_trace(tmp_path / "tick.perfetto-trace.json")
        doc = json.loads(open(path).read())
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"castor.tick", "scheduler.poll"} <= names
        assert any(n.startswith("exec.") for n in names)
    finally:
        set_tracer(prev)


def test_retrace_counters_named_per_program():
    """Satellite 2: the shared helper breaks the legacy retrace total
    down per jitted program family without changing its deltas."""
    from repro.forecast.features import note_trace, trace_count
    from repro.obs.metrics import get_metrics, retrace_counts

    before_total = trace_count()
    before = retrace_counts().get("test_prog", 0)
    note_trace("test_prog")
    note_trace("test_prog")
    assert trace_count() - before_total == 2       # legacy delta intact
    assert retrace_counts()["test_prog"] - before == 2
    assert get_metrics().counter("jit.retrace.test_prog").value >= 2


# ------------------------------------------------ the profiler's clock
def _host_events(trace_dir):
    """``{name: (start_ns, end_ns, line)}`` of the obs-style events in the
    host planes of the one xplane under ``trace_dir``."""
    import pathlib

    from jax.profiler import ProfileData
    (path,) = pathlib.Path(trace_dir).rglob("*.xplane.pb")
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith("obs_test."):
                        out[e.name] = (e.start_ns, e.end_ns,
                                       (plane.name, k))
    return out


def test_spans_land_in_a_recording_profiler_trace(tmp_path):
    import jax

    tr = Tracer(capacity=64)
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("obs_test.tick", now=1.0):
            with tr.span("obs_test.bin", jobs=3):
                with tr.span("obs_test.write"):
                    jax.numpy.ones(4).block_until_ready()
            with tr.span("obs_test.commit"):
                pass
    ev = _host_events(tmp_path)
    assert set(ev) == {"obs_test.tick", "obs_test.bin", "obs_test.write",
                       "obs_test.commit"}      # by name alone, no args
    assert len({line for _, _, line in ev.values()}) == 1   # one thread
    ring = {s.name: s for s in tr.spans()}
    by_id = {s.span_id: s.name for s in tr.spans()}
    for name, s in ring.items():
        if s.parent_id:                        # nested as in the ring
            a, b, _ = ev[name]
            pa, pb, _ = ev[by_id[s.parent_id]]
            assert pa <= a <= b <= pb, name


def test_no_annotation_is_made_while_no_trace_records(monkeypatch,
                                                      tmp_path):
    import jax
    import repro.obs.trace as obs_trace
    from jax.profiler import TraceAnnotation

    made = []

    class Counting(TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(obs_trace, "_ANNOTATION", Counting)
    tr = Tracer(capacity=64)
    for _ in range(3):
        with tr.span("obs_test.off"):
            pass
    assert made == [] and tr.finished == 3
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("obs_test.on"):
            pass
    assert made == ["obs_test.on"]


def test_read_many_counts_the_tail_points_it_sorts(tracer):
    """``tail_points`` on ``store.read_many`` counts the tail points
    sorted since the previous traced read: out-of-order chunks are merged
    into the sorted tail when they land, and a read sorts nothing, so the
    count is 0 when no such chunk landed in between."""
    import numpy as np

    from repro.timeseries.store import TimeSeriesStore
    st_ = TimeSeriesStore(tail_max=1024)
    st_.append("a", np.arange(100.0), np.ones(100))
    st_.compact()                              # 100 points out of the tail
    for lo in (300.0, 100.0, 200.0):           # three unsorted chunks
        st_.append("a", lo + np.arange(10.0), np.ones(10))
    st_.append("b", np.arange(7.0)[::-1], np.ones(7))
    for _ in range(2):
        st_.read_many(["a", "b", "missing"], since=50.0, prior_counts=True)
        st_.read_many_flat(["a", "b"], since=50.0)
    reads = [s for s in tracer.spans() if s.name == "store.read_many"]
    # two chunks behind a's tail (20 + 30 points moved), b's unsorted one (7)
    assert [s.args["tail_points"] for s in reads] == [57, 0, 0, 0]
    assert st_.tail_sort_points == 57 and st_.tail_merges == 3
    st_.append("b", [9.0], [1.0])              # in order: no merge
    st_.read_many_flat(["a", "b"], since=50.0)
    assert tracer.spans()[-1].args["tail_points"] == 0
    st_.append("b", [3.0], [1.0])              # behind b's tail of 8
    st_.read_many_flat(["a", "b"], since=50.0)
    assert tracer.spans()[-1].args["tail_points"] == 9
    assert st_.tail_sort_points == 66 and st_.tail_merges == 4
