"""Continuous profiler + SLO engine tests (ISSUE 8).

Covers: quantile-digest accuracy against exact percentiles (documented
error bounds, merge-equals-pooled), windowed request series, per-element
attribution matching a golden traced run, fused-segment + queue-wait
attribution, profile-artifact save/load/merge/diff round-trips, the SLO
engine's multi-window burn-rate math, and the acceptance scenario:
injected slow-replica chaos fires a p99 burn-rate alert, records a
flight event, exports ``nns_slo_burn_rate``, flips the service
DEGRADED — and recovers when the chaos clears.
"""
import json
import random
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu.obs import flight as obs_flight
from nnstreamer_tpu.obs import metrics as obs_metrics
from nnstreamer_tpu.obs import profile as obs_profile
from nnstreamer_tpu.obs import slo as obs_slo
from nnstreamer_tpu.obs.profile import (
    ProfileArtifact,
    ProfileStore,
    QuantileDigest,
    WindowedSeries,
    topology_hash,
)
from nnstreamer_tpu.utils import trace as nns_trace

CAPS = "other/tensors,format=static,dimensions=4,types=float32"

# named elements: auto-generated names carry a process-global counter,
# which would change the topology hash between two parses of the same
# line — artifact keys rely on stable names
CHAIN3 = ("tensor_src name=src num-buffers={n} framerate=0 dimensions=8 "
          "types=float32 "
          "! tensor_transform name=t1 mode=arithmetic option=add:1 "
          "! tensor_transform name=t2 mode=arithmetic option=mul:2 "
          "! tensor_transform name=t3 mode=arithmetic option=add:3 "
          "! queue name=q ! tensor_sink name=out")


@pytest.fixture(autouse=True)
def _clean_profile():
    yield
    obs_profile.stop()
    obs_profile.disable_recording()
    obs_profile.reset()
    nns_trace.uninstall_tracers()


def _launch(line: str):
    from nnstreamer_tpu.runtime.parse import parse_launch

    return parse_launch(line)


# ---------------------------------------------------------------------------
# quantile digest: accuracy, merge, serialization
# ---------------------------------------------------------------------------

def _exact_quantile(sorted_xs, q):
    return sorted_xs[int(round(q * (len(sorted_xs) - 1)))]


class TestQuantileDigest:
    @pytest.mark.parametrize("dist", ["uniform", "lognormal", "bimodal"])
    def test_accuracy_within_documented_bounds(self, dist):
        """p50/p90/p99 within the documented alpha relative-error bound
        against exact percentiles, on three sample shapes."""
        rng = random.Random(42)
        n = 20000
        if dist == "uniform":
            xs = [rng.uniform(0.0001, 0.5) for _ in range(n)]
        elif dist == "lognormal":
            xs = [rng.lognormvariate(-6.0, 1.0) for _ in range(n)]
        else:  # bimodal: fast path + slow tail, the shape SLOs care about
            xs = [rng.gauss(0.002, 0.0002) if rng.random() < 0.9
                  else rng.gauss(0.25, 0.02) for _ in range(n)]
            xs = [abs(x) for x in xs]
        alpha = 0.01
        d = QuantileDigest(alpha)
        for x in xs:
            d.add(x)
        xs.sort()
        for q in (0.5, 0.9, 0.99):
            exact = _exact_quantile(xs, q)
            est = d.quantile(q)
            # documented: relative error <= alpha; a hair of slack for
            # the rank-discretization of the exact side
            assert abs(est - exact) <= alpha * 1.5 * exact + 1e-9, (
                f"{dist} q={q}: exact={exact} est={est}")
        assert d.count == n
        assert abs(d.sum - sum(xs)) < 1e-6

    def test_merge_equals_pooled_digest(self):
        """Merging replica digests is EXACT: bucket-identical to the
        digest of the pooled samples (the property artifacts and the SLO
        windows rely on)."""
        rng = random.Random(7)
        a_s = [rng.lognormvariate(-5, 0.8) for _ in range(5000)]
        b_s = [rng.uniform(0.001, 0.2) for _ in range(3000)]
        a, b, pooled = (QuantileDigest(0.01) for _ in range(3))
        for x in a_s:
            a.add(x)
            pooled.add(x)
        for x in b_s:
            b.add(x)
            pooled.add(x)
        a.merge(b)
        assert a == pooled  # bucket-identical: every quantile answer equal
        assert a.quantile(0.99) == pooled.quantile(0.99)
        assert a.sum == pytest.approx(pooled.sum, rel=1e-12)

    def test_serialization_roundtrip(self):
        d = QuantileDigest(0.02)
        for x in (0.001, 0.01, 0.5, 0.0):
            d.add(x)
        back = QuantileDigest.from_dict(
            json.loads(json.dumps(d.to_dict())))
        assert back == d
        assert back.quantile(0.5) == d.quantile(0.5)

    def test_count_above(self):
        d = QuantileDigest(0.01)
        for _ in range(90):
            d.add(0.01)
        for _ in range(10):
            d.add(1.0)
        assert d.count_above(0.1) == 10
        assert d.count_above(2.0) == 0
        assert d.count_above(0.0) == 100

    def test_zero_bucket_and_validation(self):
        d = QuantileDigest(0.01)
        d.add(0.0)
        d.add(-1.0)  # clamped
        assert d.quantile(0.5) == 0.0
        with pytest.raises(ValueError):
            QuantileDigest(0.9)
        with pytest.raises(ValueError):
            d.quantile(1.5)
        with pytest.raises(ValueError):
            d.merge(QuantileDigest(0.05))


class TestWindowedSeries:
    def test_window_selects_trailing_cells(self):
        ws = WindowedSeries(alpha=0.01, horizon_s=60.0, resolution_s=1.0)
        ws.observe(0.01, ok=True, now=100.2)
        ws.observe(0.02, ok=False, now=101.5)
        ws.observe(0.5, ok=True, now=109.9)
        dig, ok, err = ws.window(3.0, now=110.0)
        assert dig.count == 1 and ok == 1 and err == 0  # only the 109.9
        dig, ok, err = ws.window(15.0, now=110.0)
        assert dig.count == 3 and ok == 2 and err == 1
        # old cells age out of the window entirely
        dig, ok, err = ws.window(3.0, now=200.0)
        assert dig.count == 0 and ok == 0 and err == 0
        assert ws.snapshot()["count"] == 3
        assert ws.snapshot()["errors"] == 1

    def test_ring_reuse_overwrites_stale_epochs(self):
        ws = WindowedSeries(alpha=0.01, horizon_s=4.0, resolution_s=1.0)
        ws.observe(0.01, now=10.0)
        # same ring slot, much later epoch: the stale cell must not leak
        # into the new epoch's window
        ws.observe(0.02, now=10.0 + ws._n)
        dig, ok, _ = ws.window(1.0, now=10.0 + ws._n)
        assert dig.count == 1 and ok == 1


# ---------------------------------------------------------------------------
# attribution: elements (golden tracer), fused segments, queue waits
# ---------------------------------------------------------------------------

class TestProfilerAttribution:
    def test_element_attribution_matches_golden_traced_run(self):
        """The profiler rides the same pad-hop hook as the proctime
        tracer — per-element totals from both must agree exactly."""
        obs_profile.start()
        golden = nns_trace.install_tracers(["proctime"])[0]
        pipe = _launch(
            "tensor_src name=gsrc num-buffers=50 dimensions=8 "
            "types=float32 ! tensor_debug name=gdbg output-mode=none "
            "! tensor_sink name=gout")
        pipe.run(timeout=60)
        obs_profile.stop()
        gold = golden.results()
        for el in ("gdbg", "gout"):
            s = obs_profile.default_profiler.series(
                "element", f"{pipe.name}:{el}")
            assert s is not None, f"no profiler series for {el}"
            assert s.count == gold[el]["buffers"]
            assert abs(s.total_s - gold[el]["total_s"]) < 1e-9

    def test_fused_and_queue_attribution(self):
        """A 3-stage fused chain reports per-segment host dispatch (every
        buffer), sampled device latency (every 16th), and the queue hop
        reports wait + depth; the segment digest matches the segment's
        own golden counters."""
        obs_profile.start()
        pipe = _launch(CHAIN3.format(n=64))
        pipe.run(timeout=120)
        obs_profile.stop()
        segs = pipe.fused_segments
        assert len(segs) == 1 and segs[0].name == "t1..t3"
        st = segs[0].stats
        fused = obs_profile.default_profiler.series(
            "fused", f"{pipe.name}:t1..t3")
        assert fused is not None
        assert fused.count == st["dispatches"] == 64
        assert abs(fused.total_s - st["total_s"]) < 1e-9
        dev = obs_profile.default_profiler.series(
            "fused_device", f"{pipe.name}:t1..t3")
        assert dev is not None and dev.count == 64 // 16
        qw = obs_profile.default_profiler.series(
            "queue_wait", f"{pipe.name}:q")
        assert qw is not None and qw.count == 64
        assert qw.depth is not None
        snap = obs_profile.snapshot()
        assert f"{pipe.name}:t1..t3" in snap["durations"]["fused"]
        assert snap["durations"]["queue_wait"][f"{pipe.name}:q"][
            "p99_ms"] >= 0.0

    def test_disabled_profiler_records_nothing(self):
        pipe = _launch(
            "tensor_src name=dsrc num-buffers=5 dimensions=4 "
            "types=float32 ! queue name=dq ! tensor_sink name=dout")
        pipe.run(timeout=30)
        snap = obs_profile.snapshot()
        assert not snap["active"]
        assert not snap["durations"]
        assert not snap["requests"]


# ---------------------------------------------------------------------------
# profile artifacts: capture / save / load / merge / diff / store
# ---------------------------------------------------------------------------

class TestProfileArtifacts:
    def test_capture_save_load_merge_roundtrip(self, tmp_path):
        """The acceptance round-trip: two runs of the same topology
        capture artifacts under ONE key; save → load → merge yields the
        pooled counts with per-segment attribution intact."""
        obs_profile.start()
        pipe_a = _launch(CHAIN3.format(n=32))
        pipe_a.run(timeout=120)
        art_a = ProfileArtifact.capture(pipe_a, model_version="v1")
        obs_profile.reset()
        pipe_b = _launch(CHAIN3.format(n=48))
        pipe_b.run(timeout=120)
        art_b = ProfileArtifact.capture(pipe_b, model_version="v1")
        obs_profile.stop()

        assert art_a.key == art_b.key  # same topology + caps + model
        assert art_a.key["topology"] == topology_hash(pipe_a)
        p_a, p_b = tmp_path / "a.json", tmp_path / "b.json"
        art_a.save(str(p_a))
        art_b.save(str(p_b))
        back_a = ProfileArtifact.load(str(p_a))
        assert back_a.key == art_a.key
        assert back_a.entries["fused"]["t1..t3"]["count"] == 32
        # per-segment attribution matches the golden fused-segment
        # counters of run A
        assert (back_a.entries["fused"]["t1..t3"]["total_s"]
                == pytest.approx(pipe_a.fused_segments[0].stats["total_s"],
                                 abs=1e-9))
        merged = back_a.merge(ProfileArtifact.load(str(p_b)))
        assert merged.entries["fused"]["t1..t3"]["count"] == 80
        assert merged.entries["element"]["q"]["count"] == 80
        # merged digest == pooled digest (exact merge)
        pooled = art_a.entries["fused"]["t1..t3"]["digest"].copy()
        pooled.merge(art_b.entries["fused"]["t1..t3"]["digest"])
        assert merged.entries["fused"]["t1..t3"]["digest"] == pooled
        summary = merged.summary()
        assert {"count", "p50_ms", "p99_ms", "total_s"} <= set(
            summary["fused"]["t1..t3"])

    def test_merge_rejects_different_key(self):
        a = ProfileArtifact({"topology": "x", "caps": "", "model_version":
                             "1"}, {})
        b = ProfileArtifact({"topology": "y", "caps": "", "model_version":
                             "1"}, {})
        with pytest.raises(ValueError):
            a.merge(b)

    def test_topology_hash_stable_and_distinct(self):
        p1 = _launch(CHAIN3.format(n=1))
        p2 = _launch(CHAIN3.format(n=9))  # props differ, topology same
        p3 = _launch("tensor_src name=src num-buffers=1 dimensions=8 "
                     "types=float32 ! tensor_sink name=out")
        assert topology_hash(p1) == topology_hash(p2)
        assert topology_hash(p1) != topology_hash(p3)

    def test_topology_hash_stable_for_auto_named_elements(self):
        """Auto-generated element names embed a process-global counter;
        the hash (and artifact entry names) must use positional aliases
        so a restart/replica parsing the same line gets the SAME key."""
        line = ("tensor_src num-buffers=4 dimensions=4 types=float32 "
                "! tensor_transform mode=arithmetic option=add:1 "
                "! tensor_sink")
        p1, p2 = _launch(line), _launch(line)
        assert topology_hash(p1) == topology_hash(p2)
        obs_profile.start()
        p1.run(timeout=30)
        art1 = ProfileArtifact.capture(p1)
        obs_profile.reset()
        p2.run(timeout=30)
        art2 = ProfileArtifact.capture(p2)
        obs_profile.stop()
        assert art1.key == art2.key
        # entry names are canonical (type@index), identical across runs
        assert set(art1.entries["element"]) == set(art2.entries["element"])
        merged = art1.merge(art2)  # must not raise, must align entries
        for name, e in merged.entries["element"].items():
            assert "@" in name
            assert e["count"] == 8

    def test_diff_reports_deltas(self):
        d1, d2 = QuantileDigest(0.01), QuantileDigest(0.01)
        for _ in range(100):
            d1.add(0.010)
            d2.add(0.020)
        key = {"topology": "t", "caps": "c", "model_version": "v1"}
        a = ProfileArtifact(key, {"fused": {"s": {
            "count": 100, "total_s": 1.0, "digest": d1}}})
        b = ProfileArtifact({**key, "model_version": "v2"},
                            {"fused": {"s": {
                                "count": 100, "total_s": 2.0,
                                "digest": d2}}})
        diff = a.diff(b)
        row = diff["fused"]["s"]
        assert row["delta_p50_ms"] == pytest.approx(10.0, rel=0.05)
        assert row["a"]["count"] == row["b"]["count"] == 100

    def test_store_accumulates_across_saves(self, tmp_path):
        d = QuantileDigest(0.01)
        d.add(0.01)
        key = {"topology": "abc", "caps": "c", "model_version": "v"}
        store = ProfileStore(str(tmp_path / "profiles"))
        art = ProfileArtifact(key, {"element": {"e": {
            "count": 1, "total_s": 0.01, "digest": d}}})
        store.save(art)
        store.save(ProfileArtifact(key, {"element": {"e": {
            "count": 2, "total_s": 0.02, "digest": d.copy()}}}))
        loaded = store.load(key)
        assert loaded is not None
        assert loaded.entries["element"]["e"]["count"] == 3
        listed = store.list()
        assert len(listed) == 1 and listed[0]["topology"] == "abc"
        assert store.load({**key, "topology": "zzz"}) is None


# ---------------------------------------------------------------------------
# request series: serving scheduler + outcomes
# ---------------------------------------------------------------------------

class TestRequestSeries:
    def test_scheduler_records_latency_and_outcomes(self):
        from nnstreamer_tpu.serving import Scheduler

        obs_profile.enable_recording()
        sched = Scheduler(lambda x: x + 1, bucket_sizes=(1, 2),
                          max_wait_s=0.001, name="prof-sched")
        try:
            for _ in range(4):
                sched([np.ones((1, 4), np.float32)], timeout=30.0)
        finally:
            sched.close()
        obs_profile.stop()
        ws = obs_profile.default_profiler.request_series(
            f"serving:{sched.name}")
        assert ws is not None
        snap = ws.snapshot()
        assert snap["count"] == 4 and snap["errors"] == 0
        assert snap["p99_ms"] > 0.0

    def test_failed_requests_count_as_errors(self):
        from nnstreamer_tpu.serving import Scheduler
        from nnstreamer_tpu.serving.request import ServingError

        class _Boom:
            compiles = 0

            def __call__(self, *xs):
                raise RuntimeError("backend on fire")

        obs_profile.enable_recording()
        sched = Scheduler(executor=_Boom(), bucket_sizes=(1,),
                          max_wait_s=0.001, name="prof-boom")
        try:
            with pytest.raises(ServingError):
                sched([np.ones((1, 4), np.float32)], timeout=30.0)
        finally:
            sched.close()
        obs_profile.stop()
        ws = obs_profile.default_profiler.request_series(
            f"serving:{sched.name}")
        assert ws is not None and ws.snapshot()["errors"] == 1


# ---------------------------------------------------------------------------
# SLO engine: burn-rate math, transitions, service flips
# ---------------------------------------------------------------------------

class TestSloEngine:
    def test_latency_burn_breach_and_recovery(self):
        obs_profile.enable_recording()
        eng = obs_slo.SloEngine(name="unit")
        eng.add(obs_slo.SLObjective(
            "u-p99", kind="latency", series="unit:lat", target=0.99,
            threshold_s=0.1, windows=((2.0, 4.0, 2.0),)))
        now = 1000.0
        p = obs_profile.default_profiler
        for _ in range(100):
            p.record_request("unit:lat", 0.01, now=now)
        st = eng.evaluate(now=now)[0]
        assert not st["alerting"]
        assert st["windows"][0]["burn_short"] == 0.0
        # 30% of requests over threshold: burn = 0.3/0.01 = 30 >= 2
        for _ in range(43):
            p.record_request("unit:lat", 0.5, now=now)
        st = eng.evaluate(now=now)[0]
        assert st["alerting"]
        assert st["windows"][0]["burn_short"] == pytest.approx(30.0, rel=0.1)
        assert st["windows"][0]["breaching"]
        events = [e for e in obs_flight.dump(last=32) if e["kind"] == "slo"]
        assert any(e["name"] == "breach" and e["data"]["slo"] == "u-p99"
                   for e in events)
        # gauges on the metrics plane
        text = obs_metrics.render()
        assert 'nns_slo_burn_rate{slo="u-p99",window="2s"}' in text
        assert 'nns_slo_alerting{slo="u-p99"} 1' in text
        # windows roll past the bad samples: good traffic, later clock
        for _ in range(50):
            p.record_request("unit:lat", 0.01, now=now + 10.0)
        st = eng.evaluate(now=now + 10.0)[0]
        assert not st["alerting"]
        assert any(e["name"] == "recover"
                   for e in obs_flight.dump(last=32) if e["kind"] == "slo")

    def test_error_rate_objective(self):
        obs_profile.enable_recording()
        eng = obs_slo.SloEngine(name="unit-err")
        eng.add(obs_slo.SLObjective(
            "u-err", kind="error_rate", series="unit:err", target=0.999,
            windows=((2.0, 4.0, 5.0),)))
        p = obs_profile.default_profiler
        now = 2000.0
        for i in range(100):
            p.record_request("unit:err", 0.01, ok=(i % 10 != 0), now=now)
        st = eng.evaluate(now=now)[0]
        # 10% errors against a 0.1% budget: burn 100x
        assert st["alerting"]
        assert st["windows"][0]["burn_short"] == pytest.approx(100.0,
                                                               rel=0.1)

    def test_availability_objective_alerts_without_degrading(self):
        from nnstreamer_tpu.service import ServiceManager

        mgr = ServiceManager()
        try:
            mgr.register("avail-svc",
                         "tensor_src num-buffers=1 dimensions=4 "
                         "types=float32 ! tensor_sink")
            eng = obs_slo.SloEngine(manager=mgr, name="unit-avail")
            eng.add(obs_slo.SLObjective(
                "u-avail", kind="availability", service="avail-svc",
                target=0.99, windows=((2.0, 4.0, 1.0),)))
            now = 3000.0
            st = None
            for i in range(5):  # service never started: every sample bad
                st = eng.evaluate(now=now + i * 0.2)[0]
            assert st["series"] == "availability:avail-svc"
            assert st["alerting"]
            # alert-only: availability breaches never flip the service
            assert mgr.get("avail-svc").state.value == "registered"
        finally:
            mgr.shutdown()

    def test_breach_degrades_service_and_recovery_restores(self):
        """The health-path halves in isolation: READY -> DEGRADED via
        mark_degraded_external on breach (no supervisor restart), back
        to READY on recovery — only for the service the engine flipped."""
        from nnstreamer_tpu.service import ServiceManager, ServiceState

        mgr = ServiceManager()
        try:
            svc = mgr.register(
                "slo-flip",
                "tensor_src num-buffers=-1 framerate=500 dimensions=4 "
                "types=float32 ! tensor_sink")
            svc.start(wait=True)
            assert svc.state is ServiceState.READY
            obs_profile.enable_recording()
            eng = obs_slo.SloEngine(manager=mgr, name="unit-flip")
            eng.add(obs_slo.SLObjective(
                "u-flip", kind="latency", series="unit:flip",
                target=0.99, threshold_s=0.05, service="slo-flip",
                windows=((2.0, 4.0, 2.0),)))
            p = obs_profile.default_profiler
            now = 4000.0
            for _ in range(50):
                p.record_request("unit:flip", 0.5, now=now)
            eng.evaluate(now=now)
            assert svc.state is ServiceState.DEGRADED
            assert "slo 'u-flip'" in svc.state_reason
            restarts_before = svc.supervisor.restarts
            for _ in range(50):
                p.record_request("unit:flip", 0.001, now=now + 10.0)
            eng.evaluate(now=now + 10.0)
            assert svc.state is ServiceState.READY
            # no supervisor involvement either way
            assert svc.supervisor.restarts == restarts_before
        finally:
            mgr.shutdown()

    def test_two_objectives_hold_service_until_both_recover(self):
        """One service bound by two objectives: the first recovery must
        NOT flip the service READY while the second still breaches."""
        from nnstreamer_tpu.service import ServiceManager, ServiceState

        mgr = ServiceManager()
        try:
            svc = mgr.register(
                "slo-hold",
                "tensor_src num-buffers=-1 framerate=500 dimensions=4 "
                "types=float32 ! tensor_sink")
            svc.start(wait=True)
            obs_profile.enable_recording()
            eng = obs_slo.SloEngine(manager=mgr, name="unit-hold")
            eng.add(obs_slo.SLObjective(
                "hold-lat", kind="latency", series="unit:hold-a",
                target=0.99, threshold_s=0.05, service="slo-hold",
                windows=((2.0, 4.0, 2.0),)))
            eng.add(obs_slo.SLObjective(
                "hold-err", kind="error_rate", series="unit:hold-b",
                target=0.99, service="slo-hold",
                windows=((2.0, 4.0, 2.0),)))
            p = obs_profile.default_profiler
            now = 5000.0
            for _ in range(50):
                p.record_request("unit:hold-a", 0.5, now=now)    # slow
                p.record_request("unit:hold-b", 0.01, ok=False,
                                 now=now)                        # erroring
            eng.evaluate(now=now)
            assert svc.state is ServiceState.DEGRADED
            # latency series heals, error series keeps burning
            for _ in range(50):
                p.record_request("unit:hold-a", 0.001, now=now + 10.0)
                p.record_request("unit:hold-b", 0.01, ok=False,
                                 now=now + 10.0)
            sts = {s["name"]: s for s in eng.evaluate(now=now + 10.0)}
            assert not sts["hold-lat"]["alerting"]
            assert sts["hold-err"]["alerting"]
            assert svc.state is ServiceState.DEGRADED  # still held down
            # both healed: now the service comes back
            for _ in range(50):
                p.record_request("unit:hold-b", 0.01, now=now + 20.0)
            eng.evaluate(now=now + 20.0)
            assert svc.state is ServiceState.READY
        finally:
            mgr.shutdown()

    def test_stop_does_not_starve_engine_recording(self):
        """profile.start()/stop() capture sessions and SLO-engine
        recording are independent halves of ACTIVE."""
        eng = obs_slo.SloEngine(name="unit-halves")
        eng.start()
        try:
            assert obs_profile.ACTIVE
            obs_profile.start()
            obs_profile.stop()  # capture session ends...
            assert obs_profile.ACTIVE  # ...engine recording survives
        finally:
            eng.stop()
        assert not obs_profile.ACTIVE  # last engine off -> fast path

    def test_objective_validation(self):
        with pytest.raises(ValueError):
            obs_slo.SLObjective("x", kind="nope", series="s")
        with pytest.raises(ValueError):
            obs_slo.SLObjective("x", kind="latency", series="")
        with pytest.raises(ValueError):
            obs_slo.SLObjective("x", kind="availability")
        with pytest.raises(ValueError):
            obs_slo.SLObjective("x", series="s", target=1.5)
        with pytest.raises(ValueError):
            obs_slo.SLObjective("x", series="s",
                                windows=((5.0, 1.0, 1.0),))


# ---------------------------------------------------------------------------
# the acceptance scenario: slow-replica chaos end to end
# ---------------------------------------------------------------------------

class TestEndToEndSloChaos:
    def test_slow_replica_breach_degrade_then_recover(self):
        """Inject a slow replica into a 3-replica fabric under traffic:
        the p99 burn-rate alert fires, a flight event lands,
        ``nns_slo_burn_rate`` appears on /metrics, the bound service
        flips DEGRADED — then recovers when the chaos clears."""
        from nnstreamer_tpu.elements.fault import net_chaos
        from nnstreamer_tpu.service import (ServiceFabric, ServiceManager,
                                            ServiceState)

        mgr = ServiceManager(jitter_seed=0)
        fab = ServiceFabric(
            mgr, "slo-fab",
            "tensor_filter framework=jax model=builtin://scaler?factor=2",
            CAPS, replicas=3, health_poll_s=30.0)
        fab.start()
        eng = obs_slo.SloEngine(manager=mgr, tick_s=0.1, name="e2e")
        eng.add(obs_slo.SLObjective(
            "e2e-p99", kind="latency", series="fabric:slo-fab",
            target=0.95, threshold_s=0.1, service="slo-fab-r1",
            windows=((1.0, 2.5, 2.0),)))
        slow_port = None
        stop = threading.Event()
        errors: list = []

        def client() -> None:
            i = 0
            while not stop.is_set():
                i += 1
                try:
                    fab.request([np.ones(4, np.float32)], key=f"k{i}",
                                timeout=10.0)
                except Exception as e:  # noqa: BLE001 - errors ARE a gate
                    errors.append(f"{type(e).__name__}: {e}")
        t = threading.Thread(target=client, daemon=True)
        try:
            for i in range(6):  # warm every replica's compile cache
                fab.request([np.zeros(4, np.float32)], key=f"w{i}",
                            timeout=60.0)
            eng.start()
            slow_port = fab._bound_port(fab.services()[1])
            net_chaos.delay_ms(slow_port, 250)
            t.start()

            svc = mgr.get("slo-fab-r1")
            deadline = time.monotonic() + 20.0
            while (svc.state is not ServiceState.DEGRADED
                   and time.monotonic() < deadline):
                time.sleep(0.1)
            assert svc.state is ServiceState.DEGRADED, (
                f"no DEGRADED flip; status={eng.status()}")
            status = next(s for s in eng.status() if s["name"] == "e2e-p99")
            assert status["alerting"]
            slo_events = [e for e in obs_flight.dump(last=64)
                          if e["kind"] == "slo"]
            assert any(e["name"] == "breach"
                       and e["data"]["slo"] == "e2e-p99"
                       for e in slo_events)
            text = obs_metrics.render()
            assert 'nns_slo_burn_rate{slo="e2e-p99"' in text
            assert 'nns_slo_alerting{slo="e2e-p99"} 1' in text

            # -- chaos clears: burn drains, the engine restores READY --
            net_chaos.delay_ms(slow_port, 0)
            deadline = time.monotonic() + 20.0
            while (svc.state is not ServiceState.READY
                   and time.monotonic() < deadline):
                time.sleep(0.1)
            assert svc.state is ServiceState.READY, (
                f"no recovery; status={eng.status()}")
            assert any(e["name"] == "recover"
                       for e in obs_flight.dump(last=64)
                       if e["kind"] == "slo")
            assert not errors, errors[:5]
        finally:
            stop.set()
            t.join(timeout=10.0)
            eng.stop()
            if slow_port is not None:
                net_chaos.delay_ms(slow_port, 0)
            fab.stop()
            mgr.shutdown()


# ---------------------------------------------------------------------------
# surfaces: /profile endpoint, CLI verbs, bucket presets
# ---------------------------------------------------------------------------

class TestSurfaces:
    def test_profile_endpoint_and_flight_pipeline_filter(self):
        from nnstreamer_tpu.service import (ControlClient, ControlServer,
                                            ServiceManager)

        obs_profile.enable_recording()
        obs_profile.default_profiler.record_request("ep:series", 0.01)
        obs_profile.stop()
        mgr = ServiceManager()
        srv = ControlServer(mgr).start()
        try:
            client = ControlClient(srv.endpoint)
            data = client.profile()
            assert "profile" in data and "slo" in data
            assert "ep:series" in data["profile"]["requests"]
            # satellite: ?pipeline= filter parity with flight.dump
            obs_flight.record("test", "ep-a", pipeline="pipe-a")
            obs_flight.record("test", "ep-b", pipeline="pipe-b")
            events = client.flight(last=500, pipeline="pipe-a")["events"]
            assert events and all(e["pipeline"] == "pipe-a" for e in events)
        finally:
            srv.stop()
            mgr.shutdown()

    def test_obs_cli_profile_slo_top_and_flight_flag(self, capsys,
                                                     tmp_path):
        from nnstreamer_tpu.__main__ import main

        # artifact emission via the CLI (a ProfileArtifact as JSON)
        out = tmp_path / "art.json"
        rc = main(["obs", "profile", "--launch", CHAIN3.format(n=24),
                   "--out", str(out), "--model-version", "cli-v1"])
        assert rc == 0
        assert "t1..t3" in capsys.readouterr().out
        art = json.loads(out.read_text())
        assert art["kind"] == "nns-profile"
        assert art["key"]["model_version"] == "cli-v1"
        assert art["entries"]["fused"]["t1..t3"]["count"] == 24

        # merge + diff verbs round-trip the artifact APIs
        merged = tmp_path / "merged.json"
        assert main(["obs", "profile", "--merge", str(out), str(out),
                     "--out", str(merged)]) == 0
        capsys.readouterr()
        assert json.loads(merged.read_text())["entries"]["fused"][
            "t1..t3"]["count"] == 48
        assert main(["obs", "profile", "--diff", str(out),
                     str(merged)]) == 0
        assert "delta_p99_ms" in capsys.readouterr().out

        assert main(["obs", "profile"]) == 0
        snap = json.loads(capsys.readouterr().out)
        assert "durations" in snap

        assert main(["obs", "slo"]) == 0
        capsys.readouterr()
        assert main(["obs", "top"]) == 0
        top = capsys.readouterr().out
        assert "nns obs top" in top
        assert "FUSED SEGMENTS" in top

        obs_flight.record("test", "cli-pf", pipeline="cli-pipe")
        assert main(["obs", "flight", "--pipeline", "cli-pipe",
                     "--last", "8"]) == 0
        out_text = capsys.readouterr().out
        assert "cli-pf" in out_text

    def test_slo_aligned_bucket_presets(self):
        from nnstreamer_tpu.service.fabric import ReplicaPool

        stage = obs_metrics.Histogram.LATENCY_BUCKETS_STAGE
        req = obs_metrics.Histogram.LATENCY_BUCKETS_REQUEST
        for preset in (stage, req):
            assert list(preset) == sorted(preset)
            assert len(set(preset)) == len(preset)
        # common SLO thresholds sit ON request-bucket edges
        for edge in (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0):
            assert edge in req
        pool = ReplicaPool("bucket-pool", CAPS)
        try:
            assert set(req) <= set(pool._latency_hist.buckets)
        finally:
            pool.close()
        # the profiler histograms ride the stage preset
        assert obs_profile._STAGE_HIST.buckets == tuple(sorted(stage))
        assert obs_profile._REQUEST_HIST.buckets == tuple(sorted(req))
