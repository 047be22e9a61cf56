"""End-to-end serve observability (repro.obs): the unified metrics
registry (counters / gauges / fixed-bucket histograms + the frozen
stats() schema shapes), request span tracing, the bounded flight
recorder with exactly-once incident dumps, and the JSONL / Prometheus
exporters — plus the integration contracts: obs-enabled serving is
bit-identical to the default path, a multi-producer chaos run leaves
the registry arithmetically consistent with every span tree closed,
and a router worker-kill produces one trace spanning original dispatch
-> failover -> replay -> retire with exactly one recorder dump."""

import json
import threading
import time

import numpy as np
import pytest
import jax

from repro.core import mapping as M
from repro.data.synthetic import lidar_scene
from repro.obs import (FlightRecorder, Histogram, MetricsRegistry,
                       Observability, SpanTracer, TraceSchemaError,
                       iter_trace_records, metrics as MX, prometheus_text,
                       validate_trace_jsonl, write_trace_jsonl)
from repro.serve import faults as FLT
from repro.serve.buckets import geometric_ladder
from repro.serve.engine import PointCloudEngine
from repro.serve.faults import FaultPlan
from repro.serve.router import LivenessPolicy, ServeRouter
from repro.serve.scheduler import ServeScheduler
from tests.test_serve_faults import _mini_params


def _scene(seed, n):
    c, m, f = lidar_scene(seed=340 + seed, n_points=n, grid=16)
    return c, f, m


@pytest.fixture(scope="module")
def served():
    """(params, engine) shared across the module, jit paid once."""
    jax.clear_caches()
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(64, 128))
    return params, engine


# ---------------------------------------------------------------------------
# registry units (no engine)
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.inc()
    c.inc(3)
    assert c.value == 4
    g = reg.gauge("depth")
    assert g.value is None                    # unset gauge reads None
    g.set(2)
    g.inc()
    g.dec(3)
    assert g.value == 0
    lazy = reg.gauge("lazy_depth")
    backing = [7]
    lazy.labels().set_function(lambda: backing[0])
    assert lazy.value == 7
    backing[0] = 9
    assert lazy.value == 9
    lazy.labels().set_function(lambda: 1 / 0)  # broken fn reads None
    assert lazy.value is None


def test_registry_idempotent_and_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "x", labelnames=("instance",))
    b = reg.counter("x_total", "different help", labelnames=("instance",))
    assert a is b                             # get-or-create, help ignored
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")                  # kind mismatch
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("x_total", labelnames=("code",))  # label mismatch
    with pytest.raises(ValueError, match="takes labels"):
        a.labels()                            # arity enforced


def test_family_labels_and_items():
    reg = MetricsRegistry()
    fam = reg.counter("f_total", labelnames=("instance", "code"))
    fam.labels("w0", "shed").inc(2)
    fam.labels("w1", "shed").inc()
    fam.labels("w0", "timeout").inc()
    assert fam.labels("w0", "shed") is fam.labels("w0", "shed")
    only_w0 = fam.items(instance="w0")
    assert [k for k, _ in only_w0] == [("w0", "shed"), ("w0", "timeout")]
    assert sum(c.value for _, c in fam.items(code="shed")) == 3
    with pytest.raises(ValueError, match="no label"):
        fam.items(bucket="64")


def test_histogram_quantiles():
    h = Histogram(bounds=(1.0, 2.0, 4.0))
    assert h.quantile(0.5) == 0.0             # empty
    for v in (0.5, 1.5, 1.5, 3.0):
        h.observe(v)
    assert h.count == 4 and h.sum == pytest.approx(6.5)
    assert h.counts == [1, 2, 1, 0]
    # p50: rank 2 lands in the (1, 2] bucket, interpolated
    assert 1.0 <= h.quantile(0.5) <= 2.0
    h.observe(100.0)                          # +Inf bucket
    assert h.quantile(0.999) == 4.0           # clamped to the last bound
    with pytest.raises(ValueError, match="quantile"):
        h.quantile(1.5)
    q = h.quantiles()
    assert set(q) == {"p50", "p95", "p99"}
    with pytest.raises(ValueError, match="strictly"):
        Histogram(bounds=(2.0, 1.0))


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("serve_reqs_total", "requests",
                labelnames=("instance",)).labels("w0").inc(3)
    reg.gauge("serve_depth", "queue depth").set(2)
    h = reg.histogram("serve_lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = prometheus_text(reg)
    assert "# HELP serve_reqs_total requests" in text
    assert "# TYPE serve_reqs_total counter" in text
    assert 'serve_reqs_total{instance="w0"} 3' in text
    assert "serve_depth 2" in text
    # cumulative buckets + the implicit +Inf bucket + sum/count
    assert 'serve_lat_seconds_bucket{le="0.1"} 1' in text
    assert 'serve_lat_seconds_bucket{le="1"} 2' in text
    assert 'serve_lat_seconds_bucket{le="+Inf"} 2' in text
    assert "serve_lat_seconds_sum 0.55" in text
    assert "serve_lat_seconds_count 2" in text


# ---------------------------------------------------------------------------
# tracer + recorder units
# ---------------------------------------------------------------------------

def test_tracer_span_tree():
    tr = SpanTracer()
    tr.begin("t1", t=0.0, rid=1)
    tr.begin("t1", t=5.0)                     # idempotent: keeps root t=0
    a = tr.span("t1", "assembly", t_start=1.0, t_end=2.0, cache_hit=True)
    tr.span("t1", "arena_staging", parent=a, t_start=1.0, t_end=1.5)
    w = tr.span("t1", "device_wait", t_start=2.0)
    tr.end_span("t1", w, t_end=3.0, ok=True)
    tr.event("t1", "retire", t=3.0)
    trace = tr.get("t1")
    assert not trace.closed
    assert trace.names() == ["request", "assembly", "arena_staging",
                             "device_wait", "retire"]
    tree = trace.tree()
    assert tree["name"] == "request" and tree["attrs"] == {"rid": 1}
    asm = next(c for c in tree["children"] if c["name"] == "assembly")
    assert [c["name"] for c in asm["children"]] == ["arena_staging"]
    (dw,) = trace.find("device_wait")
    assert dw.t_end == 3.0 and dw.attrs == {"ok": True}
    (rt,) = trace.find("retire")
    assert rt.t_start == rt.t_end == 3.0      # events are instant
    tr.end("t1", t=4.0, outcome="ok")
    trace = tr.get("t1")
    assert trace.closed
    assert trace.spans[trace.root_id].attrs["outcome"] == "ok"
    assert tr.stats() == {"live": 0, "finished": 1, "spans_recorded": 5,
                          "dropped": 0}


def test_tracer_unknown_tid_drops_and_bound():
    tr = SpanTracer(max_finished=2)
    assert tr.span("ghost", "x") is None      # unknown tid no-ops
    tr.end_span("ghost", 0)
    tr.end("ghost")
    assert tr.stats()["dropped"] == 3
    for i in range(5):
        tr.begin(f"t{i}", t=0.0)
        tr.end(f"t{i}", t=1.0)
    assert tr.stats()["finished"] == 2        # bounded deque
    assert tr.get("t0") is None               # evicted
    assert tr.get("t4").closed


def test_flight_recorder_dump_once():
    shipped = []
    rec = FlightRecorder(capacity=3, max_dumps=2, sink=shipped.append)
    for i in range(5):
        rec.record("submit", t=float(i), rid=i)
    assert [e["rid"] for e in rec.events()] == [2, 3, 4]   # ring bound
    d = rec.dump("exec_failed", key=("exec_failed", "s", 4))
    assert d["reason"] == "exec_failed"
    assert [e["rid"] for e in d["events"]] == [2, 3, 4]
    assert rec.dump("exec_failed", key=("exec_failed", "s", 4)) is None
    assert shipped == [d]                     # sink got it exactly once
    st = rec.stats()
    assert st["events"] == 5 and st["ring"] == 3
    assert st["dumps"] == 1 and st["suppressed"] == 1
    bad = FlightRecorder(sink=lambda d: 1 / 0)
    bad.record("x")
    assert bad.dump("r", key="k") is not None  # broken sink swallowed
    with pytest.raises(ValueError, match="capacity"):
        FlightRecorder(capacity=0)


def test_trace_jsonl_roundtrip(tmp_path):
    tr = SpanTracer()
    tr.begin("rid:1", t=0.0, rid=1)
    tr.span("rid:1", "dispatch", t_start=1.0, t_end=2.0,
            n=np.int64(3))                     # numpy attrs must serialize
    tr.end("rid:1", t=3.0, outcome="ok")
    tr.begin("rid:2", t=0.0)                   # still live
    rec = FlightRecorder()
    rec.record("submit", t=0.5, rid=1)
    rec.dump("failover", key="w0")
    path = tmp_path / "trace.jsonl"
    n = write_trace_jsonl(path, tr, recorder=rec)
    kinds = [r["kind"] for r in iter_trace_records(tr, rec)]
    assert n == len(kinds) == 4                # 3 spans + 1 dump
    report = validate_trace_jsonl(path)
    assert report == {"lines": 4, "spans": 3, "dumps": 1, "traces": 2,
                      "closed_traces": 1}
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    disp = next(r for r in rows if r.get("name") == "dispatch")
    assert disp["attrs"]["n"] == 3             # np.int64 -> plain int

    # schema violations are loud
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"kind": "span"}) + "\n")
    with pytest.raises(TraceSchemaError, match="missing"):
        validate_trace_jsonl(bad)
    bad.write_text("not json\n")
    with pytest.raises(TraceSchemaError, match="not valid JSON"):
        validate_trace_jsonl(bad)
    bad.write_text(json.dumps(dict(rows[1], t_end=0.5)) + "\n")
    with pytest.raises(TraceSchemaError, match="t_end"):
        validate_trace_jsonl(bad)


def test_observability_bundle():
    default = Observability()
    assert default.tracer is None and default.recorder is None
    assert isinstance(default.registry, MetricsRegistry)
    on = Observability.enabled(max_finished=8, capacity=4)
    assert on.tracer is not None and on.recorder is not None
    assert on.recorder.capacity == 4


# ---------------------------------------------------------------------------
# stats() schema shapes (satellite: the drifted dicts, frozen)
# ---------------------------------------------------------------------------

def test_scheduler_stats_schema(served):
    _, engine = served
    sched = ServeScheduler(engine, max_batch=2)
    out = sched.serve([_scene(0, 40), _scene(1, 90)])
    assert all(r.ok for r in out.values())
    st = sched.stats()
    assert set(st) == MX.SCHEDULER_STATS_KEYS
    assert set(st["faults"]) == MX.SCHEDULER_FAULT_KEYS
    for b in st["buckets"].values():
        assert set(b) == MX.SCHEDULER_BUCKET_KEYS
    q = st["latency_quantiles_s"]
    assert set(q) == {"p50", "p95", "p99"}
    assert 0.0 < q["p50"] <= q["p95"] <= q["p99"]
    sched.close()


def test_router_stats_schema(served):
    _, engine = served
    router = ServeRouter(lambda: engine, 1, max_batch=2)
    out = router.serve([_scene(0, 40)])
    assert all(r.error is None for r in out.values())
    st = router.stats()
    assert set(st) == MX.ROUTER_STATS_KEYS
    assert set(st["faults"]) == MX.ROUTER_FAULT_KEYS
    assert set(st["latency_quantiles_s"]) == {"p50", "p95", "p99"}
    router.close()


# ---------------------------------------------------------------------------
# scheduler integration: parity, span trees, error-path latencies
# ---------------------------------------------------------------------------

def test_obs_enabled_bit_identical(served):
    _, engine = served
    scenes = [_scene(i, 40 + 10 * i) for i in range(4)]
    plain = ServeScheduler(engine, max_batch=2)
    traced = ServeScheduler(engine, max_batch=2,
                            obs=Observability.enabled())
    ref = plain.serve(scenes)
    got = traced.serve(scenes)
    for rid in ref:
        assert ref[rid].ok and got[rid].ok
        np.testing.assert_array_equal(ref[rid].preds, got[rid].preds)
    # the view over the registry matches the plain path count for count
    a, b = plain.stats(), traced.stats()
    for key in ("n_submitted", "n_completed", "n_ok", "faults",
                "padding_overhead"):
        assert a[key] == b[key]
    plain.close()
    traced.close()


def test_scheduler_request_span_tree(served):
    _, engine = served
    obs = Observability.enabled()
    sched = ServeScheduler(engine, max_batch=2, obs=obs,
                           instance="s0")
    out = sched.serve([_scene(0, 40), _scene(1, 90)])
    assert all(r.ok for r in out.values())
    assert obs.tracer.stats()["live"] == 0    # every tree closed
    for rid in out:
        trace = obs.tracer.get(f"s0:rid:{rid}")
        assert trace is not None and trace.closed
        names = trace.names()
        for stage in ("request", "admission", "queue_wait", "dispatch",
                      "assembly", "arena_staging", "assembly_lookup",
                      "device_wait", "retire", "lock_wait", "mapping",
                      "stack_levels", "launch", "result_wait"):
            assert stage in names, (rid, names)
        root = trace.spans[trace.root_id]
        assert root.attrs["outcome"] == "ok"
        (qw,) = trace.find("queue_wait")
        (dp,) = trace.find("dispatch")
        assert qw.t_end is not None and qw.t_end <= dp.t_start + 1e-9
        # nesting: the root's children tile submit -> hand-out; assembly
        # and launch sit under dispatch, the assembly phases under it
        (asm,) = trace.find("assembly")
        parent_of = {s.name: s.parent_id for s in trace.span_list()}
        for name in ("admission", "lock_wait", "queue_wait", "dispatch",
                     "device_wait", "retire", "result_wait"):
            assert parent_of[name] == trace.root_id, name
        assert parent_of["assembly"] == parent_of["launch"] == dp.span_id
        for name in ("arena_staging", "assembly_lookup", "mapping",
                     "stack_levels"):
            assert parent_of[name] == asm.span_id, name
        assert asm.attrs["cache_hit"] is False
        (mp,) = trace.find("mapping")
        assert mp.attrs["rows"] > 0 and mp.attrs["bucket"] in (64, 128)
        # a cache-miss build names the key lookup this backend lowers to
        assert mp.attrs.get("search") == (
            None if mp.attrs["cache_hit"]
            else M.lookup_kind(jax.default_backend()))
        assert dp.attrs["trigger"] == "flush" and dp.attrs["n_real"] == 1
        (adm,) = trace.find("admission")
        (lw,) = trace.find("lock_wait")
        assert adm.t_end == lw.t_start and lw.t_end == qw.t_start
        (rt,) = trace.find("retire")
        (rw,) = trace.find("result_wait")
        assert rw.t_start == rt.t_end and root.t_end == rw.t_end
    sched.close()


def _uncovered(trace) -> float:
    """Share of the root not covered by the union of its children."""
    root = trace.spans[trace.root_id]
    kids = sorted((s.t_start, s.t_end) for s in trace.span_list()
                  if s.parent_id == root.span_id)
    covered, end = 0.0, root.t_start
    for a, b in kids:
        a, b = max(a, end), min(b, root.t_end)
        if b > a:
            covered += b - a
            end = b
    return 1.0 - covered / (root.t_end - root.t_start)


def test_dispatch_trigger_deadline_vs_full(served):
    """A partial micro-batch flushed by max_wait_s on the watchdog and a
    full bucket dispatched in submit say so on their dispatch spans; on
    both paths the root's children cover it from submit to the poll that
    hands the result out."""
    _, engine = served
    obs = Observability.enabled()
    sched = ServeScheduler(engine, max_batch=2, max_wait_s=0.05, obs=obs,
                           instance="tg")
    lone = sched.submit(*_scene(20, 40))
    time.sleep(0.5)                     # nobody polls: the watchdog flushes
    pair = [sched.submit(*_scene(21, 40)), sched.submit(*_scene(22, 40))]
    want, got = {lone, *pair}, {}
    deadline = time.monotonic() + 60
    while want - set(got) and time.monotonic() < deadline:
        got.update((r.rid, r) for r in sched.poll())
        time.sleep(0.002)
    assert set(got) == want and all(r.ok for r in got.values())
    (d,) = obs.tracer.get(f"tg:rid:{lone}").find("dispatch")
    assert d.attrs["trigger"] == "deadline"
    assert d.attrs["thread"] == "serve-watchdog"
    assert d.attrs["n_real"] == 1
    for rid in pair:
        (d,) = obs.tracer.get(f"tg:rid:{rid}").find("dispatch")
        assert d.attrs["trigger"] == "full" and d.attrs["n_real"] == 2
        assert d.attrs["thread"] == threading.current_thread().name
    for rid in want:
        trace = obs.tracer.get(f"tg:rid:{rid}")
        assert trace.closed
        assert _uncovered(trace) <= 0.01, (rid, trace.names())
    sched.close()


def test_lock_wait_behind_another_threads_mapping(served, monkeypatch):
    """A submit made while another thread's dispatch maps a micro-batch
    under the scheduler lock records the wait as `lock_wait`, after its
    admission ended."""
    _, engine = served
    obs = Observability.enabled()
    sched = ServeScheduler(engine, max_batch=2, obs=obs, instance="lw")
    mapping = threading.Event()
    real = engine._build

    def slow_build(*args):
        mapping.set()
        time.sleep(0.3)
        return real(*args)

    monkeypatch.setattr(engine, "_build", slow_build)

    def fill_bucket():                  # the second submit maps both
        sched.submit(*_scene(30, 40))
        sched.submit(*_scene(31, 40))

    t = threading.Thread(target=fill_bucket, name="producer")
    t.start()
    assert mapping.wait(60)
    rid = sched.submit(*_scene(32, 40))
    t.join()
    sched.flush()
    out = sched.drain()
    assert len(out) == 3 and all(r.ok for r in out)
    trace = obs.tracer.get(f"lw:rid:{rid}")
    (adm,) = trace.find("admission")
    (lw,) = trace.find("lock_wait")
    assert adm.t_end <= lw.t_start
    assert lw.t_end - lw.t_start > 0.2
    other = obs.tracer.get(f"lw:rid:{rid - 1}")
    (d,) = other.find("dispatch")
    assert d.attrs["thread"] == "producer" and d.attrs["trigger"] == "full"
    maps = [s for s in other.find("mapping")]
    assert len(maps) == 1 and maps[0].attrs["cache_hit"] is False
    assert maps[0].attrs["search"] == M.lookup_kind(jax.default_backend())
    assert maps[0].t_end - maps[0].t_start >= 0.3
    sched.close()


def test_tracer_off_records_nothing(served, monkeypatch):
    """With no tracer the scheduler keeps no trace state, hands `_assemble`
    no marks, reads the clock only where the untraced path always did,
    and serves the same predictions as a traced scheduler."""
    from repro.serve import scheduler as SCH

    _, engine = served
    scenes = [_scene(40, 40), _scene(41, 90)]
    marks_seen = []
    real_assemble = ServeScheduler._assemble

    def assemble(self, reqs, cap, mb, marks=None):
        marks_seen.append(marks)
        return real_assemble(self, reqs, cap, mb, marks)

    monkeypatch.setattr(ServeScheduler, "_assemble", assemble)
    calls = [0]
    real_time = SCH.time

    class _Clock:
        @staticmethod
        def monotonic():
            calls[0] += 1
            return real_time.monotonic()

        def __getattr__(self, name):
            return getattr(real_time, name)

    plain = ServeScheduler(engine, max_batch=2, instance="off")
    traced = ServeScheduler(engine, max_batch=2, instance="on",
                            obs=Observability.enabled())
    monkeypatch.setattr(SCH, "time", _Clock())
    ref = plain.serve(scenes)
    # one read per submit, per dispatch and per retire: two scenes in
    # two buckets
    assert calls[0] == 6
    calls[0] = 0
    got = traced.serve(scenes)
    assert calls[0] > 6
    monkeypatch.undo()
    assert marks_seen[:2] == [None, None]
    assert all(m is not None for m in marks_seen[2:])
    for rid in ref:
        np.testing.assert_array_equal(ref[rid].preds, got[rid].preds)
    assert plain.obs.tracer is None
    for state in (plain._rid_trace, plain._qspans, plain._wspans,
                  plain._rspans):
        assert state == {}
    plain.close()
    traced.close()


def test_anchor_puts_spans_on_the_profiler_clock(tmp_path):
    """A span recorded around a `TraceAnnotation("probe")` lands on the
    probe's host event within 1 ms once mapped through the first clock
    anchor's offset."""
    import glob

    from jax.profiler import ProfileData, TraceAnnotation

    tr = SpanTracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr.anchor()
        tr.begin("p", t=time.monotonic())
        t0 = time.monotonic()
        with TraceAnnotation("probe"):
            time.sleep(0.02)
        tr.span("p", "probe", t_start=t0, t_end=time.monotonic())
        time.sleep(0.05)
        tr.anchor()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    host = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("obs.clock", "probe"):
                    host.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    assert len(host["obs.clock"]) == 2 and len(host["probe"]) == 1
    assert [a[0] for a in tr.anchors] == ["obs.clock"] * 2
    _, before, inside = tr.anchors[0]
    assert before <= inside
    offset = min(host["obs.clock"])[0] - (before + inside) / 2
    (span,) = tr.get("p").find("probe")
    (p0, p1) = host["probe"][0]
    assert abs(span.t_start * 1e9 + offset - p0) < 1e6
    assert abs(span.t_end * 1e9 + offset - p1) < 1e6


def test_error_latency_separate_histogram(served):
    """Satellite: error-path completions land in the labeled error
    histogram, never in the OK latency histogram the averages use."""
    _, engine = served
    obs = Observability.enabled()
    sched = ServeScheduler(engine, max_batch=2, obs=obs, instance="s1")
    # oversized -> rejected at admission
    big = _scene(7, 300)
    rid_rej = sched.submit(*big)
    # deadline_s=0 -> timeout converted at the next submit/flush tick
    rid_to = sched.submit(*_scene(8, 40), deadline_s=0.0)
    sched.flush()
    out = sched.take([rid_rej, rid_to])
    assert out[rid_rej].error.code == FLT.REJECTED
    assert out[rid_to].error.code == FLT.TIMEOUT
    st = sched.stats()
    assert st["faults"]["rejected"] == 1
    assert st["faults"]["timeout"] == 1
    assert st["latency_avg_s"] == 0.0         # OK histogram untouched
    errlat = obs.registry.histogram(
        "serve_error_latency_seconds",
        labelnames=("instance", "code"))
    assert errlat.labels("s1", FLT.REJECTED).count == 1
    assert errlat.labels("s1", FLT.TIMEOUT).count == 1
    # the error trace is closed with the error code as the outcome
    trace = obs.tracer.get(f"s1:rid:{rid_rej}")
    assert trace.closed
    assert trace.spans[trace.root_id].attrs["outcome"] == FLT.REJECTED
    sched.close()


def test_chaos_registry_reconciles(served):
    """Satellite: concurrent producers under a chaos plan (poisoned rid
    -> exec_failed, corrupted scene -> rejected) leave the registry
    arithmetically consistent and every completed rid's span tree
    closed, with the exec_failed flight-recorder dump emitted once."""
    _, engine = served
    n_producers, per_producer = 3, 4
    n_total = n_producers * per_producer
    plan = FaultPlan(poison_rids=frozenset({1}),
                     corrupt_scenes=frozenset({2}))
    obs = Observability.enabled()
    sched = ServeScheduler(engine, max_batch=2, fault_plan=plan,
                           obs=obs, instance="cx")
    rids, errs = [], []
    lock = threading.Lock()

    def producer(k):
        try:
            for j in range(per_producer):
                rid = sched.submit(*_scene(10 + k * per_producer + j,
                                           40 + 10 * j))
                with lock:
                    rids.append(rid)
        except Exception as e:                # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=producer, args=(k,))
               for k in range(n_producers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    sched.flush()
    out = sched.take(rids)
    st = sched.stats()
    ft = st["faults"]
    # conservation: every submit is accounted for, exactly once
    assert st["n_submitted"] == n_total
    assert st["n_completed"] == n_total
    assert st["n_submitted"] == (st["n_ok"] + ft["rejected"] + ft["shed"]
                                 + ft["timeout"] + ft["exec_failed"])
    assert ft["exec_failed"] == 1             # the poisoned rid
    assert ft["rejected"] == 1                # the corrupted scene
    n_ok = sum(1 for r in out.values() if r.ok)
    assert n_ok == st["n_ok"]
    # every completed rid's span tree is closed with a final outcome
    assert obs.tracer.stats()["live"] == 0
    for rid in rids:
        trace = obs.tracer.get(f"cx:rid:{rid}")
        assert trace is not None and trace.closed, rid
        assert "outcome" in trace.spans[trace.root_id].attrs
    # exec_failed triggered exactly one flight-recorder dump
    assert obs.recorder.stats()["dumps"] == 1
    (dump,) = obs.recorder.dumps
    assert dump["reason"] == "exec_failed"
    sched.close()


# ---------------------------------------------------------------------------
# router chaos: the acceptance trace (dispatch -> failover -> replay ->
# retire) + exactly-once dump
# ---------------------------------------------------------------------------

def test_router_failover_trace(served):
    # the kill must land while the first victim is genuinely in flight:
    # big scenes make the device execution (~tens of ms for a cap-65536
    # micro-batch) outlast failover detection (health tick every 2.5ms
    # spots the dead thread), so neither w0's end-of-iteration publish
    # nor the salvage harvest can retire it — it HAS to be replayed.
    # miss_beats is huge so the cold cap-65536 compile (seconds, inside
    # submit) is never misread as a hang.
    params, _ = served
    factory = PointCloudEngine.factory(params, 2, flow="fod",
                                       ladder=geometric_ladder(1024, 65536))
    liveness = LivenessPolicy(beat_s=0.005, miss_beats=1_000_000,
                              health_s=0.0025)

    def _big(seed):
        c, m, f = lidar_scene(seed=560 + seed, n_points=60_000, grid=64)
        return c, f, m

    # pick scenes the rendezvous digests route to worker w0, so the
    # kill (on w0's 2nd request) strands an in-flight victim
    probe = ServeRouter(factory, 2, max_batch=1)
    victims = []
    for s in range(24):
        c, f, m = _big(s)
        if probe.preview(c, m) == "w0":
            victims.append((c, f, m))
        if len(victims) == 2:
            break
    probe.close()
    assert len(victims) == 2, "seed sweep found no w0-routed scenes"

    obs = Observability.enabled()
    plan = FaultPlan(kill_workers={0: 1})
    router = ServeRouter(factory, 2, max_batch=1, fault_plan=plan,
                         liveness=liveness, obs=obs)
    out = router.serve(victims)
    st = router.stats()
    assert all(r.error is None for r in out.values())
    assert st["faults"]["failovers"] == 1
    assert st["faults"]["replayed"] >= 1
    router.close()

    # the victim's single trace spans both lives of the request; the
    # genuinely in-flight victim dispatched twice (w0 then the
    # survivor) — a victim killed before w0 touched it only once
    replayed = [t for t in obs.tracer.finished()
                if "failover" in t.names()]
    assert replayed, "no trace recorded the failover"
    inflight = [t for t in replayed if t.names().count("dispatch") == 2]
    assert inflight, [t.names() for t in replayed]
    trace = inflight[0]
    assert trace.closed
    assert trace.spans[trace.root_id].attrs["outcome"] == "ok"
    names = trace.names()
    i_disp = names.index("dispatch")
    i_fail = names.index("failover")
    i_replay = names.index("replay")
    i_retire = len(names) - 1 - names[::-1].index("retire")
    assert i_disp < i_fail < i_replay < i_retire, names
    # the replay re-ran admission/dispatch on the survivor
    assert names.count("admission") == 2
    assert names.count("dispatch") == 2
    # one failover incident -> exactly one flight-recorder dump
    assert obs.recorder.stats()["dumps"] == 1
    (dump,) = obs.recorder.dumps
    assert dump["reason"] == "failover"


# ---------------------------------------------------------------------------
# partition fan-out trace
# ---------------------------------------------------------------------------

def test_partition_chunk_trace(served):
    from repro.partition import PartitionPolicy

    params, _ = served
    obs = Observability.enabled()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(64, 128),
                              obs=obs)
    c, m, f = lidar_scene(seed=460, n_points=100, grid=16)
    preds, _ = engine.segment(
        c, m, f, partition=PartitionPolicy(chunk_budget=32, force=True))
    assert int((np.asarray(preds)[m] < 0).sum()) == 0
    part = [t for t in obs.tracer.finished() if t.tid.startswith("partition:")]
    assert len(part) == 1
    trace = part[0]
    assert trace.closed
    assert trace.spans[trace.root_id].attrs["outcome"] == "ok"
    (fan,) = trace.find("chunk_fanout")
    (stitch,) = trace.find("stitch")
    n_chunks = engine.last_partition_stats["n_chunks"]
    assert fan.attrs["n_chunks"] == n_chunks
    assert len(fan.attrs["rids"]) == n_chunks
    assert stitch.attrs["n_errors"] == 0
    # each chunk rid cross-references an ordinary closed request trace
    for rid in fan.attrs["rids"]:
        chunk = obs.tracer.get(f"scheduler:rid:{rid}")
        assert chunk is not None and chunk.closed
