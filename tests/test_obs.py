"""Observability plane tests (ISSUE 7): request-scoped tracing, unified
metrics, flight recorder.

The headline test drives ONE request through a 3-replica ServiceFabric
with an injected replica kill and batched serving, exports the Perfetto
JSON, and asserts the whole story is ONE trace: client/fabric root span
→ per-attempt child spans (failed + retried) → serving batch span
LINKED to the successful attempt → fused-segment span parented on it.
"""
import bisect
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from nnstreamer_tpu.obs import context as obs_ctx
from nnstreamer_tpu.obs import flight as obs_flight
from nnstreamer_tpu.obs import metrics as obs_metrics
from nnstreamer_tpu.utils import trace as nns_trace

CAPS = "other/tensors,format=static,dimensions=4,types=float32"


@pytest.fixture(autouse=True)
def _clean_obs():
    yield
    obs_ctx.disable_tracing()
    obs_ctx.reset()
    nns_trace.uninstall_tracers()


# ---------------------------------------------------------------------------
# trace context / span core
# ---------------------------------------------------------------------------

class TestTraceCore:
    def test_meta_roundtrip_and_garbage(self):
        span = obs_ctx.start_span("root")
        ctx = span.context()
        span.end()  # NNS_LEAKCHECK: a started span must be closed
        back = obs_ctx.TraceContext.from_meta(ctx.to_meta())
        assert (back.trace_id, back.span_id) == (ctx.trace_id, ctx.span_id)
        # meta is client-supplied wire data: garbage parses to None
        for bad in (None, 42, "x", {}, {"trace_id": 1, "span_id": 2},
                    {"trace_id": "t"}, []):
            assert obs_ctx.TraceContext.from_meta(bad) is None

    def test_parentage_links_and_status(self):
        root = obs_ctx.start_span("req", kind="fabric")
        child = obs_ctx.start_span("attempt", parent=root)
        assert child.trace_id == root.trace_id
        assert child.parent_id == root.span_id
        cctx = child.end("error:ConnectionError")
        root.end()
        linked = obs_ctx.record_span("batch", trace_id=root.trace_id,
                                     links=[cctx], dur_s=0.01)
        assert linked.trace_id == root.trace_id
        spans = obs_ctx.spans_for_trace(root.trace_id)
        assert {s.name for s in spans} == {"req", "attempt", "batch"}
        batch = next(s for s in spans if s.name == "batch")
        assert (cctx.trace_id, cctx.span_id) in batch.links
        assert next(s for s in spans if s.name == "attempt").status \
            == "error:ConnectionError"

    def test_end_is_idempotent(self):
        before = len(obs_ctx.finished_spans())
        s = obs_ctx.start_span("once")
        s.end()
        s.end("error:late")
        spans = obs_ctx.finished_spans()
        assert len(spans) == before + 1
        assert spans[-1].status == "ok"

    def test_parent_from_meta_dict(self):
        root = obs_ctx.start_span("root")
        child = obs_ctx.record_span("fused", parent=root.context().to_meta(),
                                    dur_s=0.001)
        root.end()  # NNS_LEAKCHECK: a started span must be closed
        assert child.trace_id == root.trace_id

    def test_export_chrome_trace(self, tmp_path):
        obs_ctx.reset()
        root = obs_ctx.start_span("req", attrs={"key": "k1"})
        obs_ctx.start_span("attempt", parent=root).end()
        root.end()
        path = tmp_path / "spans.json"
        doc = obs_ctx.export_chrome_trace(str(path))
        loaded = json.loads(path.read_text())
        assert loaded == doc
        evs = loaded["traceEvents"]
        assert len(evs) == 2
        by_name = {e["name"]: e for e in evs}
        assert by_name["attempt"]["args"]["parent_span_id"] \
            == by_name["req"]["args"]["span_id"]
        assert by_name["req"]["args"]["key"] == "k1"
        assert all(e["ph"] == "X" for e in evs)

    def test_span_recorded_into_flight(self):
        start = obs_flight.count()
        obs_ctx.start_span("flightcheck", kind="query").end()
        events = obs_flight.dump(last=8)
        assert any(e["kind"] == "span" and "flightcheck" in e["name"]
                   for e in events)
        assert obs_flight.count() > start


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_wraps_in_order(self):
        rec = obs_flight.FlightRecorder(capacity=8)
        for i in range(20):
            rec.record("test", f"e{i}", {"i": i})
        events = rec.dump()
        assert len(events) == 8
        assert [e["name"] for e in events] == [f"e{i}" for i in range(12, 20)]
        assert rec.count() == 20
        assert rec.dump(last=3)[-1]["name"] == "e19"

    def test_pipeline_filter(self):
        rec = obs_flight.FlightRecorder(capacity=16)
        rec.record("pipeline", "playing", pipeline="a")
        rec.record("pipeline", "playing", pipeline="b")
        assert [e["pipeline"] for e in rec.dump(pipeline="a")] == ["a"]

    def test_pipeline_lifecycle_recorded(self):
        from nnstreamer_tpu.runtime.parse import parse_launch

        pipe = parse_launch(
            "tensor_src num-buffers=2 dimensions=4 types=float32 "
            "! tensor_sink")
        pipe.run(timeout=20)
        events = obs_flight.dump(pipeline=pipe.name)
        kinds = [e["name"] for e in events if e["kind"] == "pipeline"]
        assert "playing" in kinds and "eos" in kinds and "stopped" in kinds

    def test_crash_report_embeds_flight_tail(self):
        from nnstreamer_tpu.service.supervisor import (RestartPolicy,
                                                       Supervisor)

        class _Svc:
            name = "dummy"
            pipeline = None

            def _supervised_give_up(self, why):
                pass

        obs_flight.record("test", "before-crash", {"mark": 1})
        sup = Supervisor(_Svc(), RestartPolicy(mode="never"))
        sup.notify_crash("error", "boom")
        sup.join_threads()
        report = sup.crash_reports[0]
        assert isinstance(report.flight, list) and report.flight
        names = [e["name"] for e in report.flight]
        assert "before-crash" in names
        # the crash itself is recorded before capture, so the tail
        # answers "what led up to this" including the verdict
        assert "crash" in names
        assert "flight" in report.to_dict()


# ---------------------------------------------------------------------------
# metrics registry + prometheus rendering
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counter_gauge_histogram_render(self):
        reg = obs_metrics.Registry()
        c = reg.counter("t_requests_total", "requests", ("pool",))
        c.inc(pool="a")
        c.inc(2, pool="a")
        c.inc(pool='evil"\n')
        g = reg.gauge("t_depth", "depth")
        g.set(7)
        h = reg.histogram("t_lat_seconds", "latency", ("p",),
                          buckets=(0.01, 0.1))
        h.observe(0.005, p="x")
        h.observe(0.05, p="x")
        text = reg.render()
        assert '# TYPE t_requests_total counter' in text
        assert 't_requests_total{pool="a"} 3' in text
        assert '\\n' in text and '\\"' in text  # label escaping
        assert "t_depth 7" in text
        assert 't_lat_seconds_bucket{p="x",le="0.01"} 1' in text
        assert 't_lat_seconds_bucket{p="x",le="+Inf"} 2' in text
        assert 't_lat_seconds_count{p="x"} 2' in text
        assert 't_lat_seconds_sum{p="x"} 0.055' in text

    def test_type_and_label_conflicts_raise(self):
        reg = obs_metrics.Registry()
        reg.counter("t_x_total", "x", ("a",))
        with pytest.raises(obs_metrics.MetricError):
            reg.gauge("t_x_total", "x", ("a",))
        with pytest.raises(obs_metrics.MetricError):
            reg.counter("t_x_total", "x", ("b",))
        with pytest.raises(obs_metrics.MetricError):
            reg.counter("bad name", "x")

    def test_clear_drops_samples(self):
        reg = obs_metrics.Registry()
        g = reg.gauge("t_state", "s", ("state",))
        g.set(1, state="ready")
        g.set(1, state="degraded")
        g.clear()
        g.set(1, state="degraded")
        text = reg.render()
        assert 't_state{state="degraded"} 1' in text
        assert 'state="ready"' not in text

    def test_stale_service_series_disappear(self):
        """Snapshot-mirror collectors repopulate from live sources each
        scrape: a deregistered service (and its state history) must not
        keep reporting."""
        from nnstreamer_tpu.service import ServiceManager

        mgr = ServiceManager()
        try:
            mgr.register("obs-stale-svc",
                         "tensor_src num-buffers=1 dimensions=4 "
                         "types=float32 ! tensor_sink")
            text = obs_metrics.render()
            assert ('nns_service_state{service="obs-stale-svc",'
                    'state="registered"} 1') in text
            mgr.unregister("obs-stale-svc")
            text = obs_metrics.render()
            assert 'service="obs-stale-svc"' not in text
        finally:
            mgr.shutdown()

    def test_collector_failure_does_not_kill_scrape(self):
        reg = obs_metrics.Registry()
        reg.counter("t_ok_total", "fine").inc()

        def bad(_reg):
            raise RuntimeError("source died")

        reg.register_collector("bad", bad)
        text = reg.render()
        assert "t_ok_total 1" in text

    def test_fabric_pool_joins_plane_and_snapshot_fold(self):
        from nnstreamer_tpu.serving import metrics_snapshot
        from nnstreamer_tpu.service.fabric import ReplicaPool

        pool = ReplicaPool("obs-snap-pool", CAPS)
        try:
            pool.add_endpoint("127.0.0.1", 9, replica_id="r0")
            # satellite: serving.metrics_snapshot() folds fabric pools in
            snap = metrics_snapshot()
            assert "fabric" in snap
            psnap = snap["fabric"]["obs-snap-pool"]
            rep = psnap["replicas"][0]
            assert {"id", "state", "score", "inflight"} <= set(rep)
            assert {"evictions", "readmissions", "hedges"} <= set(psnap)
            # and the Prometheus plane sees the same pool
            text = obs_metrics.render()
            assert 'nns_fabric_replica_score{pool="obs-snap-pool",' \
                   'replica="r0"}' in text
        finally:
            pool.close()

    def test_failover_counters_reach_the_scrape(self):
        """What an external monitor clocks a failover by: the pool's
        eviction and readmission counters and the replica's up gauge are
        on the plane as the pool counts them, read by the one parser."""
        from nnstreamer_tpu.obs import promtext
        from nnstreamer_tpu.service.fabric import ReplicaPool

        # no probe comes due inside the test: readmission is ours to make
        pool = ReplicaPool("obs-failover-pool", CAPS,
                           quarantine_base_s=3600.0)
        labels = {"pool": "obs-failover-pool"}

        def read(name, **more):
            return promtext.sample(obs_metrics.render(), name,
                                   dict(labels, **more))

        try:
            replica = pool.add_endpoint("127.0.0.1", 9, replica_id="r0")
            assert read("nns_fabric_evictions_total") == 0
            assert read("nns_fabric_replica_up", replica="r0") == 1
            pool.evict("r0", "test")
            assert read("nns_fabric_evictions_total") == 1
            assert read("nns_fabric_readmissions_total") == 0
            assert read("nns_fabric_replica_up", replica="r0") == 0
            pool._readmit(replica)
            assert read("nns_fabric_readmissions_total") == 1
            assert read("nns_fabric_replica_up", replica="r0") == 1
        finally:
            pool.close()


# ---------------------------------------------------------------------------
# chrometrace fixes (satellite)
# ---------------------------------------------------------------------------

class TestChromeTrace:
    def test_save_vs_concurrent_flow(self, tmp_path):
        path = tmp_path / "chrome.json"
        tracer = nns_trace.ChromeTraceTracer(path=str(path))
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                tracer.serving_event("batch", "s", time.monotonic(),
                                     0.001, {"i": 1})

        threads = [threading.Thread(target=hammer, daemon=True)
                   for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.05)
        saved = tracer.save()
        stop.set()
        for t in threads:
            t.join(timeout=5)
        assert saved == str(path)
        doc = json.loads(path.read_text())  # valid JSON despite the race
        assert doc["traceEvents"]
        # finalized: later events are dropped, a second save is a no-op
        tracer.serving_event("batch", "s", time.monotonic(), 0.001, {})
        assert tracer.save() is None

    def test_flush_keeps_recording(self, tmp_path):
        path = tmp_path / "chrome.json"
        tracer = nns_trace.ChromeTraceTracer(path=str(path))
        tracer.serving_event("batch", "a", time.monotonic(), 0.001, {})
        assert tracer.flush() == str(path)
        tracer.serving_event("batch", "b", time.monotonic(), 0.001, {})
        tracer.flush()
        names = [e["name"] for e in
                 json.loads(path.read_text())["traceEvents"]]
        assert names == ["batch:a", "batch:b"]

    def test_env_activated_flushes_on_pipeline_stop(self, tmp_path,
                                                    monkeypatch):
        from nnstreamer_tpu.runtime.parse import parse_launch

        path = tmp_path / "env_trace.json"
        monkeypatch.setenv("NNS_CHROME_TRACE", str(path))
        tracer = nns_trace.ChromeTraceTracer()  # env-activated form
        nns_trace.install_tracer(tracer)
        try:
            pipe = parse_launch(
                "tensor_src num-buffers=3 dimensions=4 types=float32 "
                "! tensor_sink")
            pipe.run(timeout=20)
            # satellite: the stop() flush wrote the file — no interpreter
            # exit needed
            assert path.exists()
            assert json.loads(path.read_text())["traceEvents"]
        finally:
            nns_trace.uninstall_tracers()
            tracer.save()  # unregister the atexit hook


# ---------------------------------------------------------------------------
# control-plane surfaces: /metrics, /flight, CLI
# ---------------------------------------------------------------------------

class TestEndpoints:
    def test_metrics_and_flight_routes(self):
        from nnstreamer_tpu.service import (ControlClient, ControlServer,
                                            ServiceManager)

        mgr = ServiceManager()
        srv = ControlServer(mgr).start()
        try:
            with urllib.request.urlopen(srv.endpoint + "/metrics",
                                        timeout=5) as resp:
                ctype = resp.headers.get("Content-Type", "")
                text = resp.read().decode()
            assert ctype.startswith("text/plain")
            assert "# TYPE nns_flight_events_total counter" in text
            assert "nns_tracing_enabled" in text
            client = ControlClient(srv.endpoint)
            assert "nns_flight_events_total" in client.metrics_text()
            obs_flight.record("test", "endpoint-probe")
            events = client.flight(last=500)["events"]
            assert any(e["name"] == "endpoint-probe" for e in events)
        finally:
            srv.stop()
            mgr.shutdown()

    def test_obs_cli_local(self, capsys, tmp_path):
        from nnstreamer_tpu.__main__ import main

        assert main(["obs", "metrics"]) == 0
        assert "nns_flight_events_total" in capsys.readouterr().out
        obs_flight.record("test", "cli-probe")
        assert main(["obs", "flight", "--last", "8"]) == 0
        assert "cli-probe" in capsys.readouterr().out
        obs_ctx.start_span("cli-span").end()
        out_path = tmp_path / "spans.json"
        assert main(["obs", "trace", "--out", str(out_path)]) == 0
        capsys.readouterr()
        assert json.loads(out_path.read_text())["traceEvents"]


# ---------------------------------------------------------------------------
# the acceptance test: ONE trace across retry + batch + fused dispatch
# ---------------------------------------------------------------------------

def _key_owned_by(pool, rid: str) -> str:
    """A request key whose consistent-hash owner is ``rid`` (all replicas
    idle, so the ring owner routes it deterministically)."""
    for k in range(2000):
        h = pool._key_hash(str(k))
        with pool._lock:
            start = bisect.bisect_left(pool._points, h) % len(pool._ring)
            owner = pool._ring[start][1]
        if owner == rid:
            return str(k)
    raise AssertionError(f"no key found for replica {rid}")


class TestEndToEndTrace:
    def test_one_trace_across_kill_retry_batch_and_fusion(self, tmp_path):
        from nnstreamer_tpu.service import ServiceFabric, ServiceManager

        obs_ctx.enable_tracing()
        mgr = ServiceManager(jitter_seed=0)
        # the replica stage: a fused device pair (two transforms) feeding
        # a serving batcher — so one request produces a fused-segment
        # span AND a batch span inside the replica pipeline
        stage = ("tensor_transform mode=arithmetic option=add:1 "
                 "! tensor_transform mode=arithmetic option=add:1 "
                 "! tensor_serving framework=jax "
                 "model=builtin://scaler?factor=2 max-wait-ms=2")
        # health_poll_s high: the pool must discover the kill through the
        # FAILED ATTEMPT (the retry path under test), not a health tick
        fab = ServiceFabric(mgr, "obs-fab", stage, CAPS, replicas=3,
                            health_poll_s=10.0, quarantine_base_s=0.5)
        fab.start()
        try:
            for i in range(4):  # warm the replicas' compile caches
                fab.request([np.zeros(4, np.float32)], key=f"w{i}",
                            timeout=60.0)
            key = _key_owned_by(fab.pool, "obs-fab-r1")
            fab.kill_replica(1)
            time.sleep(0.2)
            out = fab.request([np.ones(4, np.float32)], key=key,
                              timeout=30.0)
            # (1+1+1)*2: the answer proves both transforms and the model ran
            np.testing.assert_allclose(np.asarray(out.tensors[0]),
                                       np.full(4, 6.0, np.float32))
            time.sleep(0.3)  # let the replica-side spans land

            path = tmp_path / "trace.json"
            obs_ctx.export_chrome_trace(str(path))
            events = json.loads(path.read_text())["traceEvents"]

            roots = [e for e in events
                     if e["name"] == "fabric.request:obs-fab"
                     and e["args"].get("key") == key]
            assert len(roots) == 1
            root = roots[0]["args"]
            trace_id = root["trace_id"]

            # every span of the story shares ONE trace id
            attempts = [e for e in events
                        if e["args"].get("parent_span_id") == root["span_id"]]
            assert len(attempts) == 2, attempts
            failed = [e for e in attempts
                      if e["args"]["status"].startswith("error:")]
            ok = [e for e in attempts if e["args"]["status"] == "ok"]
            assert len(failed) == 1 and len(ok) == 1
            assert failed[0]["name"] == "attempt:obs-fab-r1"
            ok_span_id = ok[0]["args"]["span_id"]

            batches = [
                e for e in events if e["cat"] == "serving"
                and e["name"].startswith("batch:")
                and any(ln["span_id"] == ok_span_id
                        for ln in e["args"]["links"])]
            assert batches, "no batch span linked to the request span"
            assert batches[0]["args"]["trace_id"] == trace_id

            fused = [e for e in events if e["cat"] == "fused"
                     and e["args"].get("parent_span_id") == ok_span_id]
            assert fused, "no fused-segment span parented on the attempt"
            assert fused[0]["args"]["trace_id"] == trace_id
            assert fused[0]["name"].startswith("fused:")
        finally:
            fab.stop()
            mgr.shutdown()


# ---------------------------------------------------------------------------
# tracer churn under fabric traffic (satellite: NNS_TSAN target)
# ---------------------------------------------------------------------------

class TestTracerChurnUnderTraffic:
    def test_install_uninstall_while_fabric_serves(self):
        """Install/uninstall tracers and toggle span tracing while a
        3-replica fabric serves sustained traffic: zero request errors
        (and, under NNS_TSAN=1, zero sanitizer violations via the
        session-wide assertion fixture)."""
        from nnstreamer_tpu.service import ServiceFabric, ServiceManager

        mgr = ServiceManager(jitter_seed=0)
        fab = ServiceFabric(
            mgr, "churn-fab",
            "tensor_filter framework=jax model=builtin://scaler?factor=2",
            CAPS, replicas=3, health_poll_s=0.05)
        fab.start()
        errors: list = []
        stop = threading.Event()

        def client(idx: int) -> None:
            i = 0
            while not stop.is_set():
                i += 1
                try:
                    fab.request([np.full(4, 1.0, np.float32)],
                                key=f"c{idx}:{i}", timeout=8.0)
                except Exception as e:  # noqa: BLE001 - errors ARE the gate
                    errors.append(f"{type(e).__name__}: {e}")
        workers = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(3)]
        try:
            fab.request([np.zeros(4, np.float32)], key="warm", timeout=60.0)
            for t in workers:
                t.start()
            deadline = time.monotonic() + 1.5
            while time.monotonic() < deadline:
                nns_trace.install_tracers(["proctime", "chrometrace"])
                obs_ctx.enable_tracing()
                time.sleep(0.05)
                nns_trace.uninstall_tracers()
                obs_ctx.disable_tracing()
                time.sleep(0.02)
        finally:
            stop.set()
            for t in workers:
                t.join(timeout=10.0)
            fab.stop()
            mgr.shutdown()
        assert not errors, errors[:5]
