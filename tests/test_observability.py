"""Unified observability layer: metrics registry, span tracer, step
timeline, and the trace_report analysis tool.

Also holds the registry<->stats() sync guard: every converted
component's legacy ``stats()`` keys must be backed by instruments in
its :class:`~repro.obs.metrics.InstrumentSet` (no orphaned ad-hoc dict
keys after the migration).
"""
import gc
import json
import threading
import time

import pytest

from repro.analysis.trace_report import (attribution, category_rollup,
                                         load_chrome_trace,
                                         load_metrics_jsonl,
                                         median_step_wall, overhead_pct,
                                         slowest_spans)
from repro.obs.metrics import (Counter, Gauge, Histogram, InstrumentSet,
                               MetricsRegistry, default_buckets)
from repro.obs.timeline import STALL_CATEGORIES, StepTimeline
from repro.obs.trace import TRACER, SpanTracer, trace_span, traced


# ---------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------
class TestInstruments:
    def test_counter(self):
        c = Counter("x")
        c.add()
        c.add(4)
        assert c.value == 5
        assert c.snapshot() == {"name": "x", "type": "counter", "value": 5}
        c.reset()
        assert c.value == 0

    def test_gauge(self):
        g = Gauge("depth")
        g.set(7)
        g.add(-3)
        assert g.value == 4
        assert g.snapshot()["type"] == "gauge"

    def test_default_buckets_monotonic(self):
        b = default_buckets()
        assert b == sorted(b)
        assert b[0] == pytest.approx(1e-5)
        assert b[-1] == pytest.approx(100.0)

    def test_histogram_basic(self):
        h = Histogram("t")
        for v in (0.001, 0.002, 0.003, 0.004):
            h.observe(v)
        assert h.count == 4
        assert h.sum == pytest.approx(0.01)
        assert h.value == h.sum
        assert h.mean() == pytest.approx(0.0025)
        snap = h.snapshot()
        assert snap["min"] == pytest.approx(0.001)
        assert snap["max"] == pytest.approx(0.004)
        assert snap["p50"] <= snap["p95"] <= snap["p99"] <= 0.004 + 1e-9

    def test_histogram_empty(self):
        h = Histogram("t")
        assert h.percentile(50) == 0.0
        assert h.snapshot()["min"] is None

    def test_histogram_percentile_bounded_by_extremes(self):
        h = Histogram("t")
        for _ in range(100):
            h.observe(0.5)
        # all mass in one bucket: interpolation stays inside [min, max]
        assert 0.5 - 1e-9 <= h.percentile(50) <= 0.5 + 1e-9
        assert h.percentile(99) <= 0.5 + 1e-9

    def test_registry_weakref_gc(self):
        reg = MetricsRegistry()
        c = reg.counter("ephemeral")
        c.add(3)
        assert [m["name"] for m in reg.collect()] == ["ephemeral"]
        del c
        gc.collect()
        assert reg.collect() == []

    def test_registry_aggregates_same_name(self):
        reg = MetricsRegistry()
        a, b = reg.counter("store.bytes"), reg.counter("store.bytes")
        a.add(10)
        b.add(5)
        (snap,) = reg.collect()
        assert snap["value"] == 15
        h1, h2 = reg.histogram("lat"), reg.histogram("lat")
        h1.observe(0.1)
        h2.observe(0.3)
        merged = [m for m in reg.collect() if m["name"] == "lat"][0]
        assert merged["count"] == 2
        assert merged["sum"] == pytest.approx(0.4)

    def test_instrument_set_memoizes(self):
        reg = MetricsRegistry()
        s = InstrumentSet("q", registry=reg)
        assert s.counter("n") is s.counter("n")
        s.counter("n").add(2)
        s.histogram("wait").observe(1.0)
        assert s.keys() == ["n", "wait"]
        assert s.view() == {"n": 2, "wait": 1.0}
        assert s.counter("n").name == "q.n"


# ---------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------
@pytest.fixture
def tracer():
    t = SpanTracer(buffer=1024, enabled=True)
    yield t


@pytest.fixture
def global_tracer():
    TRACER.clear()
    TRACER.enable(1024)
    yield TRACER
    TRACER.disable()
    TRACER.clear()


class TestTracer:
    def test_disabled_is_shared_noop(self):
        assert not TRACER.enabled
        s1 = trace_span("a", "cat", k=1)
        s2 = trace_span("b")
        assert s1 is s2  # module-level singleton: zero allocation
        with s1 as s:
            s.set(bytes=10)
        assert len(TRACER) == 0

    def test_disabled_overhead_guard(self):
        """The disabled path must stay cheap enough to sprinkle on the
        step path: 100k no-op spans well under a second even on a
        loaded CI box."""
        assert not TRACER.enabled
        t0 = time.perf_counter()
        for _ in range(100_000):
            with trace_span("hot", "pipeline"):
                pass
        assert time.perf_counter() - t0 < 1.0

    def test_ring_bound_and_drop_count(self):
        t = SpanTracer(buffer=16, enabled=True)
        for i in range(100):
            with t.span(f"s{i}"):
                pass
        assert len(t) == 16
        assert t.events_total == 100
        assert t.dropped == 84
        # ring keeps the newest spans
        assert t.events()[-1][0] == "s99"
        assert t.stats()["capacity"] == 16

    def test_span_nesting(self, tracer):
        with tracer.span("parent", "pipeline") as p:
            with tracer.span("child", "pipeline"):
                time.sleep(0.001)
        events = {e[0]: e for e in tracer.events()}
        # child commits first (exit order), interval nested in parent
        assert [e[0] for e in tracer.events()] == ["child", "parent"]
        child, parent = events["child"], events["parent"]
        assert parent[4] <= child[4] <= child[5] <= parent[5]

    def test_thread_identity(self, tracer):
        def work(n):
            with tracer.span("w", "pipeline", n=n):
                time.sleep(0.001)

        threads = [threading.Thread(target=work, args=(i,),
                                    name=f"worker-{i}") for i in range(3)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        tids = {e[2] for e in tracer.events()}
        names = {e[3] for e in tracer.events()}
        assert len(tids) == 3
        assert names == {"worker-0", "worker-1", "worker-2"}

    def test_attrs_set_mid_span(self, tracer):
        with tracer.span("persist.batch", "persist", n=4) as sp:
            sp.set(bytes=123)
        (_, _, _, _, _, _, attrs, _) = tracer.events()[0]
        assert attrs == {"n": 4, "bytes": 123}

    def test_traced_decorator(self, global_tracer):
        @traced("maint.gc", "maintenance")
        def gc_slice():
            return 7

        assert gc_slice() == 7
        assert global_tracer.events()[0][:2] == ("maint.gc", "maintenance")

    def test_chrome_export_round_trip(self, global_tracer, tmp_path):
        with trace_span("ckpt.offload", "persist", step=3) as sp:
            sp.set(bytes=456)
        with trace_span("backend.put", "backend", tier="local"):
            pass
        path = str(tmp_path / "trace.json")
        n = global_tracer.export_chrome(path)
        events = load_chrome_trace(path)  # validates schema, raises on bad
        assert n == len(events)
        xs = [e for e in events if e["ph"] == "X"]
        metas = [e for e in events if e["ph"] == "M"]
        assert {e["name"] for e in xs} == {"ckpt.offload", "backend.put"}
        off = [e for e in xs if e["name"] == "ckpt.offload"][0]
        assert off["cat"] == "persist"
        assert off["args"] == {"step": 3, "bytes": 456}
        assert off["dur"] >= 0
        assert metas and metas[0]["name"] == "thread_name"
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        assert doc["otherData"]["dropped_events"] == 0

    def test_load_chrome_trace_rejects_malformed(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"events": []}))
        with pytest.raises(ValueError):
            load_chrome_trace(str(bad))
        bad.write_text(json.dumps(
            {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "tid": 1}]}))
        with pytest.raises(ValueError):  # complete event missing ts/dur
            load_chrome_trace(str(bad))

    def test_ring_tuple_fields(self, tracer):
        """The first seven fields keep their meaning; the eighth is the
        parent span's name."""
        with tracer.span("outer", "engine", k=1):
            with tracer.span("inner", "recovery"):
                pass
        inner, outer = tracer.events()
        th = threading.current_thread()
        assert inner[:4] == ("inner", "recovery", th.ident, th.name)
        assert outer[:4] == ("outer", "engine", th.ident, th.name)
        assert outer[4] <= inner[4] <= inner[5] <= outer[5]
        assert (inner[6], outer[6]) == (None, {"k": 1})
        assert (inner[7], outer[7]) == ("outer", None)
        xs = [e for e in tracer.to_chrome()["traceEvents"] if e["ph"] == "X"]
        assert [(e["name"], e["cat"], e["tid"]) for e in xs] == [
            ("inner", "recovery", th.ident), ("outer", "engine", th.ident)]
        assert xs[1]["args"] == {"k": 1} and "args" not in xs[0]
        assert xs[0]["ts"] == round(inner[4] * 1e6, 3)

    def test_parent_is_per_thread(self, tracer):
        """A span's parent is the span open on its own thread: a worker
        started inside a main-thread span has none."""
        def work():
            with tracer.span("w.outer"):
                with tracer.span("w.inner"):
                    pass

        with tracer.span("main"):
            th = threading.Thread(target=work, name="worker")
            th.start()
            th.join(timeout=10)
            assert not th.is_alive()
            with tracer.span("main.child"):
                pass
        parents = {e[0]: e[7] for e in tracer.events()}
        assert parents == {"w.inner": "w.outer", "w.outer": None,
                           "main.child": "main", "main": None}

    def test_enable_resizes_ring(self):
        t = SpanTracer(buffer=8, enabled=True)
        for i in range(8):
            with t.span(f"s{i}"):
                pass
        t.enable(4)
        assert len(t) == 4  # keeps the newest 4
        assert t.events()[-1][0] == "s7"


# ---------------------------------------------------------------------
# spans in the jax.profiler trace
# ---------------------------------------------------------------------
def _profiled(tmp_path, body):
    """Run ``body`` under ``jax.profiler`` and return the host events
    as {name: [(line index, start_ns, end_ns)]}."""
    import glob

    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (i, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


class TestProfilerBridge:
    def test_spans_of_two_threads_nest_in_the_profiler_trace(
            self, global_tracer, tmp_path):
        def work():
            with trace_span("bridge.worker", "persist"):
                with trace_span("bridge.worker_inner", "persist"):
                    time.sleep(0.002)

        def body():
            with trace_span("bridge.main", "recovery"):
                with trace_span("bridge.main_inner", "recovery"):
                    th = threading.Thread(target=work, name="bridge-w")
                    th.start()
                    th.join(timeout=10)
                    assert not th.is_alive()

        evs = _profiled(tmp_path, body)
        (m,), (mi,) = evs["bridge.main"], evs["bridge.main_inner"]
        (w,), (wi,) = evs["bridge.worker"], evs["bridge.worker_inner"]
        assert m[0] == mi[0] and w[0] == wi[0] and m[0] != w[0]
        assert m[1] <= mi[1] <= mi[2] <= m[2]
        assert w[1] <= wi[1] <= wi[2] <= w[2]
        # the ring records the same nesting, per thread
        parents = {e[0]: e[7] for e in global_tracer.events()}
        assert parents["bridge.main_inner"] == "bridge.main"
        assert parents["bridge.worker_inner"] == "bridge.worker"
        assert parents["bridge.worker"] is None

    def test_disabled_tracer_puts_nothing_in_the_profiler_trace(
            self, tmp_path):
        assert not TRACER.enabled

        def body():
            with trace_span("bridge.disabled", "recovery"):
                time.sleep(0.001)

        evs = _profiled(tmp_path, body)
        assert "bridge.disabled" not in evs
        assert len(TRACER) == 0


# ---------------------------------------------------------------------
# step timeline / stall attribution
# ---------------------------------------------------------------------
class TestStepTimeline:
    def test_commit_sums_to_wall(self):
        tl = StepTimeline()
        tl.begin(1)
        tl.charge("queue_backpressure", 0.010)
        tl.charge("snapshot_stall", 0.005)
        rec = tl.commit(1, 0.100)
        assert rec["compute"] == pytest.approx(0.085)
        total = rec["compute"] + sum(rec.get(c, 0.0)
                                     for c in STALL_CATEGORIES)
        assert total == pytest.approx(rec["wall"])

    def test_overcharge_clamps_compute(self):
        tl = StepTimeline()
        tl.begin(1)
        tl.charge("flush_stall", 0.5)
        rec = tl.commit(1, 0.1)
        assert rec["compute"] == 0.0

    def test_charge_outside_window_dropped(self):
        tl = StepTimeline()
        tl.charge("queue_backpressure", 1.0)  # no open step
        tl.begin(1)
        rec = tl.commit(1, 0.1)
        assert "queue_backpressure" not in rec
        assert rec["compute"] == pytest.approx(0.1)

    def test_event_out_of_step(self):
        tl = StepTimeline()
        tl.event("recovery", 0.25, step=7)
        (rec,) = tl.records()
        assert rec["out_of_step"] and rec["recovery"] == 0.25
        assert rec["compute"] == 0.0

    def test_event_inside_window_redirects(self):
        tl = StepTimeline()
        tl.begin(2)
        tl.event("flush_stall", 0.02)
        rec = tl.commit(2, 0.1)
        assert rec["flush_stall"] == pytest.approx(0.02)
        assert not rec.get("out_of_step")
        assert len(tl.records()) == 1

    def test_stall_fraction_excludes_out_of_step(self):
        tl = StepTimeline()
        for s in range(4):
            tl.begin(s)
            tl.charge("queue_backpressure", 0.05)
            tl.commit(s, 0.1)
        tl.event("recovery", 100.0)  # must not pollute the signal
        assert tl.stall_fraction() == pytest.approx(0.5)

    def test_totals_and_stats(self):
        tl = StepTimeline()
        tl.begin(1)
        tl.charge("snapshot_stall", 0.03)
        tl.commit(1, 0.1)
        tl.event("flush_stall", 0.2)
        t = tl.totals()
        assert t["wall"] == pytest.approx(0.3)
        attributed = sum(t[c] for c in ("compute",) + STALL_CATEGORIES)
        assert attributed == pytest.approx(t["wall"])
        assert tl.stats()["steps"] == 1

    def test_write_jsonl_round_trip(self, tmp_path):
        tl = StepTimeline()
        tl.begin(1)
        tl.commit(1, 0.1)
        tl.event("recovery", 0.2)
        path = str(tmp_path / "m.jsonl")
        n = tl.write_jsonl(path, extra=[
            {"kind": "metric", "name": "store.writes", "type": "counter",
             "value": 3}])
        assert n == 3
        steps, metrics = load_metrics_jsonl(path)
        assert len(steps) == 2 and len(metrics) == 1
        assert metrics[0]["name"] == "store.writes"

    def test_bounded(self):
        tl = StepTimeline(maxlen=8)
        for s in range(50):
            tl.begin(s)
            tl.commit(s, 0.01)
        assert len(tl.records()) == 8
        assert tl.steps_total == 50


# ---------------------------------------------------------------------
# trace_report analyses
# ---------------------------------------------------------------------
class TestTraceReport:
    STEPS = [
        {"kind": "step", "step": 1, "wall": 0.10, "compute": 0.08,
         "queue_backpressure": 0.02},
        {"kind": "step", "step": 2, "wall": 0.12, "compute": 0.12},
        {"kind": "step", "step": None, "wall": 0.30, "compute": 0.0,
         "recovery": 0.30, "out_of_step": True},
    ]

    def test_attribution_fraction(self):
        tot = attribution(self.STEPS)
        assert tot["wall"] == pytest.approx(0.52)
        assert tot["attributed_fraction"] == pytest.approx(1.0)
        assert tot["recovery"] == pytest.approx(0.30)

    def test_median_excludes_out_of_step(self):
        assert median_step_wall(self.STEPS) == pytest.approx(0.11)

    def test_overhead_pct(self):
        base = [{"wall": 0.10, "compute": 0.10}]
        cur = [{"wall": 0.104, "compute": 0.104}]
        assert overhead_pct(cur, base) == pytest.approx(4.0)
        assert overhead_pct(cur, []) == 0.0

    def test_span_helpers(self):
        evs = [
            {"name": "a", "ph": "X", "cat": "persist", "pid": 1, "tid": 1,
             "ts": 0, "dur": 500.0},
            {"name": "b", "ph": "X", "cat": "persist", "pid": 1, "tid": 1,
             "ts": 0, "dur": 1500.0},
            {"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
             "args": {"name": "MainThread"}},
        ]
        assert [e["name"] for e in slowest_spans(evs, 1)] == ["b"]
        roll = category_rollup(evs)
        assert roll == {"persist": {"count": 2, "total_ms": 2.0}}


# ---------------------------------------------------------------------
# registry <-> stats() sync guard (no orphaned ad-hoc dict keys)
# ---------------------------------------------------------------------
class TestStatsSync:
    def _assert_backed(self, obj, derived=()):
        """Every legacy KEYS entry reads through an instrument, and the
        component's stats() numeric surface is KEYS + declared derived
        keys — nothing hand-rolled survives outside the registry."""
        inst_keys = set(obj.instruments().keys())
        for k in obj.KEYS:
            assert k in inst_keys, f"{k} not backed by an instrument"
            getattr(obj, k)  # legacy attribute surface still reads

    def test_copy_meter(self):
        from repro.checkpoint.io import CopyMeter
        m = CopyMeter()
        m.add(10)
        m.add_h2d(20)
        m.add_d2h(30, wait_s=0.01, span_s=0.02)
        self._assert_backed(m)
        s = m.stats()
        assert set(s) == set(m.KEYS) | {"d2h_overlap_ratio"}
        assert s["bytes"] == 10 and s["h2d_bytes"] == 20
        assert s["d2h_bytes"] == 30
        assert s["d2h_wait_s"] == pytest.approx(0.01)
        m.reset()
        assert m.stats()["bytes"] == 0

    def test_quant_meter(self):
        from repro.compression.quant_span import QuantMeter
        m = QuantMeter()
        m.add_encode(0.01, 4096, 1024)
        m.add_decode(0.002)
        self._assert_backed(m)
        s = m.stats()
        assert set(s) == set(m.KEYS) | {"ratio"}
        assert s["bytes_in"] == 4096 and s["bytes_out"] == 1024
        assert s["ratio"] == pytest.approx(4.0)
        assert s["encode_s"] == pytest.approx(0.01)
        assert s["decode_s"] == pytest.approx(0.002)
        m.reset()
        assert m.stats()["bytes_in"] == 0 and m.stats()["ratio"] is None

    def test_reusing_queue(self):
        from repro.core.reusing_queue import ReusingQueue
        q = ReusingQueue(maxsize=2)
        blocked = q.put(1, "a")
        assert isinstance(blocked, float) and blocked >= 0.0
        assert q.get(timeout=1.0) == (1, "a")
        q.close()
        self._assert_backed(q)
        s = q.stats()
        assert set(s) == set(q.KEYS) | {"consumer_error"}
        assert s["enqueued"] == 1

    def test_snapshot_arena(self):
        from repro.core.snapshot import SnapshotArena
        a = SnapshotArena(slots=2)
        self._assert_backed(a)
        assert set(a.stats()) == {"slots"} | set(a.KEYS)

    def test_store(self, tmp_path):
        from repro.checkpoint.store import CheckpointStore
        store = CheckpointStore(str(tmp_path))
        try:
            inst = set(store.instruments().keys())
            # every counter the old stats() dict hand-rolled
            assert {"bytes_written", "writes", "gc_deleted", "quarantined",
                    "folds", "fold_bytes", "folded_patches",
                    "max_amplification", "write_time_s"} <= inst
            assert store.bytes_written == 0 and store.writes == 0
        finally:
            store.close()

    def test_remote_backend(self):
        from repro.checkpoint.remote import (FakeObjectStore,
                                             RemoteObjectBackend)
        b = RemoteObjectBackend(FakeObjectStore())
        b.put("k0", {"a": 1})
        self._assert_backed(b)
        assert b.puts == 1
        assert b.stats()["puts"] == 1

    def test_global_instances_registered(self):
        """The process-global meter aggregates into the default
        registry under its prefix."""
        from repro.checkpoint.io import COPY_METER
        from repro.compression.quant_span import QUANT_METER
        from repro.obs.metrics import REGISTRY
        names = {m["name"] for m in REGISTRY.collect()}
        assert any(n.startswith("copy_meter.") for n in names)
        assert COPY_METER.instruments().get("bytes") is not None
        assert {"quant.encode_s", "quant.decode_s", "quant.bytes_in",
                "quant.bytes_out"} <= names
        assert QUANT_METER.instruments().get("encode_s") is not None
