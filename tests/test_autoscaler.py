"""Closed-loop autoscaling + process-isolated replicas (ISSUE 12).

Covers the control-loop edge cases the issue gates on — hysteresis (no
flap on oscillating load), per-direction cooldown enforcement, scale-in
blocked by memory headroom, the respawn circuit breaker giving up
cleanly while the pool keeps serving, shed-at-ceiling emitting TYPED
admission errors (never timeouts) — plus the fabric/procreplica
actuators, the ControlClient idempotent-GET retry satellite, and the
observability surfaces (gauges, autoscale flight events, obs top
section, /profile block).
"""
import json
import socket
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu.obs import flight as obs_flight
from nnstreamer_tpu.obs import metrics as obs_metrics
from nnstreamer_tpu.obs import profile as obs_profile
from nnstreamer_tpu.service import (
    Autoscaler,
    AutoscalerConfig,
    ControlClient,
    ControlServer,
    ProcReplicaSet,
    ReplicaPool,
    ServiceError,
    ServiceFabric,
    ServiceManager,
)
from nnstreamer_tpu.service import autoscaler as autoscaler_mod
from nnstreamer_tpu.serving.queue import RequestQueue
from nnstreamer_tpu.serving.request import (
    AdmissionError,
    OverloadShedError,
    Request,
)

CAPS = "other/tensors,format=static,dimensions=4,types=float32"


# ---------------------------------------------------------------------------
# fakes: a deterministic scaling target driven by tick(now=...)
# ---------------------------------------------------------------------------

class FakePool:
    name = "fakepool"

    def __init__(self):
        self.shed = None
        self.evicted = []

    def set_overload_shed(self, p):
        self.shed = p

    def clear_overload_shed(self):
        self.shed = None

    def evict(self, rid, reason):
        self.evicted.append((rid, reason))

    def remove(self, rid):
        pass


class FakeTarget:
    def __init__(self, n=1):
        self.n = n
        self.pool = FakePool()
        self.events = []

    def replica_count(self):
        return self.n

    def scale_out(self):
        self.n += 1
        self.events.append(("out", self.n))
        return f"r{self.n}"

    def scale_in(self):
        self.n -= 1
        self.events.append(("in", self.n))
        return f"r{self.n + 1}"


class FakeProcTarget(FakeTarget):
    """Subprocess-flavored fake: scripted deaths + respawn outcomes."""

    def __init__(self, n=2):
        super().__init__(n)
        self.dead_queue = []       # rids reap_dead hands out, once each
        self.respawn_results = []  # scripted respawn() outcomes (FIFO)
        self.respawn_calls = []
        self.discarded = []

    def reap_dead(self):
        out, self.dead_queue = self.dead_queue, []
        return out

    def respawn(self, rid):
        self.respawn_calls.append(rid)
        return self.respawn_results.pop(0) if self.respawn_results else True

    def discard(self, rid):
        self.discarded.append(rid)
        self.n -= 1


def _cfg(**kw):
    base = dict(min_replicas=1, max_replicas=3, latency_slo_s=0.1,
                target=0.9, short_window_s=5.0, long_window_s=20.0,
                scale_out_burn=2.0, scale_in_burn=0.5, min_samples=5,
                scale_out_cooldown_s=3.0, scale_in_cooldown_s=6.0,
                respawn_backoff_base_s=0.5, respawn_backoff_factor=2.0,
                respawn_backoff_max_s=4.0, max_respawns=3,
                respawn_window_s=30.0)
    base.update(kw)
    return AutoscalerConfig(**base)


def _scaler(target, cfg=None, mem=0.1, profiler=None):
    prof = profiler or obs_profile.Profiler()
    return prof, Autoscaler(target, cfg or _cfg(), name="t",
                            series="fabric:fake", profiler=prof,
                            memory_fraction_fn=lambda: mem)


def _feed(prof, t, n=20, latency=0.5, span=1.0):
    """n samples ending at time t (bad by default: 0.5 > slo 0.1)."""
    for i in range(n):
        prof.record_request("fabric:fake", latency,
                            ok=True, now=t - span + span * i / n)


T0 = 1000.0


class TestControlLoop:
    def test_config_validation(self):
        with pytest.raises(ValueError):
            AutoscalerConfig(min_replicas=3, max_replicas=2)
        with pytest.raises(ValueError):
            AutoscalerConfig(scale_in_burn=2.0, scale_out_burn=2.0)
        with pytest.raises(ValueError):
            AutoscalerConfig(target=1.5)
        with pytest.raises(ValueError):
            AutoscalerConfig(memory_max_fraction=0.0)
        with pytest.raises(ValueError):
            AutoscalerConfig(short_window_s=10.0, long_window_s=5.0)

    def test_scale_out_on_hot_short_window_only(self):
        """The loop acts BEFORE the multi-window alert: the long window
        is still mostly cool when the short one crosses the threshold."""
        tgt = FakeTarget(1)
        prof, a = _scaler(tgt)
        # long window has plenty of GOOD history; the last seconds go bad
        _feed(prof, T0 - 6, n=100, latency=0.01, span=12.0)
        _feed(prof, T0, n=20, latency=0.5, span=2.0)
        d = a.tick(now=T0)
        assert d["action"] == "scale_out"
        assert tgt.n == 2
        # the long-window burn was NOT required to be hot
        assert d["burn_long"] < a.config.scale_out_burn

    def test_no_scale_on_few_samples(self):
        tgt = FakeTarget(1)
        prof, a = _scaler(tgt)
        _feed(prof, T0, n=3, latency=0.5)  # hot but below min_samples=5
        assert a.tick(now=T0)["action"] == "hold"
        assert tgt.n == 1

    def test_cooldown_enforced(self):
        tgt = FakeTarget(1)
        prof, a = _scaler(tgt)
        _feed(prof, T0, n=20)
        assert a.tick(now=T0)["action"] == "scale_out"
        _feed(prof, T0 + 1, n=20)
        assert a.tick(now=T0 + 1)["action"] == "hold"  # inside cooldown
        _feed(prof, T0 + 4, n=20)
        assert a.tick(now=T0 + 4)["action"] == "scale_out"  # expired
        assert tgt.n == 3

    def test_hysteresis_no_flap_on_oscillating_load(self):
        """Burn oscillating BETWEEN the scale-in and scale-out
        thresholds must produce zero scale events: the dead band plus
        per-direction cooldowns absorb it."""
        tgt = FakeTarget(2)
        prof, a = _scaler(tgt)
        t = T0
        for step in range(30):
            # alternate ~1.1x and ~0.9x burn around neither threshold:
            # bad_frac 0.11 -> burn 1.1 (< out 2.0), 0.09 -> 0.9 (> in 0.5)
            frac = 0.11 if step % 2 == 0 else 0.09
            bad = int(20 * frac)
            _feed(prof, t, n=20 - bad, latency=0.01, span=0.9)
            _feed(prof, t, n=bad, latency=0.5, span=0.9)
            a.tick(now=t)
            t += 1.0
        assert tgt.events == []
        assert tgt.n == 2

    def test_scale_in_requires_all_windows_cool(self):
        tgt = FakeTarget(2)
        prof, a = _scaler(tgt)
        # short window clean, long window still holds bad samples
        _feed(prof, T0 - 8, n=40, latency=0.5, span=4.0)
        _feed(prof, T0, n=40, latency=0.01, span=4.0)
        d = a.tick(now=T0)
        assert d["action"] == "hold"
        assert d["burn_long"] > a.config.scale_in_burn
        # once the long window ages out, the shrink happens
        d = a.tick(now=T0 + 25.0)
        assert d["action"] == "scale_in"
        assert tgt.n == 1

    def test_scale_in_blocked_by_memory_headroom(self):
        """Shrinking concentrates load: used × n/(n-1) must stay under
        the watermark, else the shrink is refused and counted."""
        tgt = FakeTarget(2)
        prof, a = _scaler(tgt, mem=0.6)  # projected 0.6*2/1 = 1.2 > 0.85
        d = a.tick(now=T0 + 100)  # empty windows = cool
        assert d["action"] == "blocked:memory"
        assert tgt.n == 2
        assert a.snapshot()["blocked_by_memory"] == 1
        ev = [e for e in obs_flight.dump(last=64)
              if e["kind"] == "autoscale" and e["name"] == "scalein_blocked"]
        assert ev and ev[-1]["data"]["projected_fraction"] > 0.85

    def test_scale_out_blocked_by_memory_arms_shed(self):
        tgt = FakeTarget(1)
        prof, a = _scaler(tgt, mem=0.9)
        _feed(prof, T0, n=20)
        d = a.tick(now=T0)
        assert d["action"] == "blocked:memory"
        assert tgt.n == 1
        assert tgt.pool.shed == a.config.shed_priority
        assert a.snapshot()["blocked_by_memory"] == 1

    def test_shed_at_ceiling_and_disarm_on_cool(self):
        tgt = FakeTarget(3)  # already at max
        prof, a = _scaler(tgt)
        _feed(prof, T0, n=20)
        assert a.tick(now=T0)["action"] == "blocked:ceiling"
        assert tgt.pool.shed == a.config.shed_priority
        assert a.shed_armed()
        # cool windows -> disarm (and later scale in)
        a.tick(now=T0 + 60.0)
        assert tgt.pool.shed is None
        assert not a.shed_armed()

    def test_desired_replicas_bounded(self):
        tgt = FakeTarget(3)
        prof, a = _scaler(tgt)
        _feed(prof, T0, n=20)
        d = a.tick(now=T0)
        assert d["desired"] == 3  # wants more, bounded at max
        snap = a.snapshot()
        assert snap["desired_replicas"] == 3

    def test_decision_records_inputs(self):
        tgt = FakeTarget(1)
        prof, a = _scaler(tgt)
        _feed(prof, T0, n=20)
        a.tick(now=T0)
        ev = [e for e in obs_flight.dump(last=64)
              if e["kind"] == "autoscale" and e["name"] == "scale_out"]
        assert ev
        data = ev[-1]["data"]
        for key in ("burn_short", "burn_long", "samples_short",
                    "memory_used_fraction", "out_cooldown_s",
                    "in_cooldown_s", "shed_armed", "replicas"):
            assert key in data, key


class TestRespawn:
    def test_respawn_backoff_schedule(self):
        """Failed respawns are retried on an exponential schedule, not
        every tick."""
        tgt = FakeProcTarget(2)
        prof, a = _scaler(tgt)
        tgt.dead_queue = ["r-a"]
        tgt.respawn_results = [False, False, True]
        a.tick(now=T0)                    # reap + attempt 1 (fails)
        assert tgt.respawn_calls == ["r-a"]
        a.tick(now=T0 + 0.2)              # inside 0.5s backoff: no attempt
        assert len(tgt.respawn_calls) == 1
        a.tick(now=T0 + 0.6)              # attempt 2 (fails, backoff 1.0)
        assert len(tgt.respawn_calls) == 2
        a.tick(now=T0 + 1.0)              # inside backoff
        assert len(tgt.respawn_calls) == 2
        a.tick(now=T0 + 1.7)              # attempt 3 (succeeds)
        assert len(tgt.respawn_calls) == 3
        # success parks the schedule: no further attempts while alive
        a.tick(now=T0 + 10.0)
        assert len(tgt.respawn_calls) == 3
        snap = a.snapshot()
        assert snap["respawns"] == 1
        assert snap["respawn_failures"] == 2

    def test_respawn_circuit_breaker_gives_up_cleanly(self):
        """A crash-looping replica exhausts max_respawns inside the
        window: the identity is DISCARDED, the loop keeps ticking, and
        the remaining replicas keep the pool serving."""
        tgt = FakeProcTarget(2)
        cfg = _cfg(max_respawns=3, respawn_window_s=100.0,
                   respawn_backoff_base_s=0.1, respawn_backoff_max_s=0.2)
        prof, a = _scaler(tgt, cfg=cfg)
        t = T0
        # every respawn "succeeds" but the replica dies again at once
        for _ in range(3):
            tgt.dead_queue = ["r-b"]
            a.tick(now=t)
            t += 1.0
        assert len(tgt.respawn_calls) == 3
        # 4th death exceeds max_respawns=3 -> breaker opens
        tgt.dead_queue = ["r-b"]
        a.tick(now=t)
        assert tgt.discarded == ["r-b"]
        assert a.snapshot()["respawn_gave_up"] == 1
        ev = [e for e in obs_flight.dump(last=64)
              if e["kind"] == "autoscale" and e["name"] == "respawn_gave_up"]
        assert ev
        # the loop is still healthy: later ticks decide normally
        assert a.tick(now=t + 5.0)["action"] in ("hold", "scale_in")

    def test_inprocess_target_skips_respawn_plumbing(self):
        tgt = FakeTarget(1)  # no reap_dead attr
        prof, a = _scaler(tgt)
        assert a.tick(now=T0)["action"] == "hold"


class TestTypedShedding:
    def test_pool_shed_is_typed_admission_error_not_timeout(self):
        """The ceiling gate: an armed pool refuses sheddable requests
        IMMEDIATELY with the typed error — not after a timeout."""
        pool = ReplicaPool("shedpool", CAPS)
        try:
            pool.set_overload_shed(1)
            t0 = time.monotonic()
            with pytest.raises(OverloadShedError) as ei:
                pool.request([np.ones(4, np.float32)], key="k",
                             timeout=5.0, priority=1)
            assert time.monotonic() - t0 < 0.5  # fail-fast, no timeout
            assert isinstance(ei.value, AdmissionError)
            assert pool.snapshot()["shed_overload"] == 1
            assert pool.snapshot()["overload_shed"] == 1
        finally:
            pool.close()

    def test_pool_shed_spares_high_priority(self):
        pool = ReplicaPool("shedpool2", CAPS)
        try:
            pool.set_overload_shed(1)
            # priority 0 is NOT shed: it proceeds to routing (and fails
            # differently — no replicas — proving it passed the guard)
            with pytest.raises(Exception) as ei:
                pool.request([np.ones(4, np.float32)], key="k",
                             timeout=0.3, priority=0)
            assert not isinstance(ei.value, OverloadShedError)
            pool.clear_overload_shed()
            assert pool.overload_shed() is None
        finally:
            pool.close()

    def test_serving_queue_overload_hook(self):
        """The serving-plane admission hook: an armed RequestQueue sheds
        at-or-below-cutoff priorities typed, spares the rest."""
        q = RequestQueue(max_depth=8)
        q.set_overload(2)
        req = Request([np.ones((1, 4), np.float32)], priority=2)
        with pytest.raises(OverloadShedError):
            q.put(req)
        assert req.done() and isinstance(req.error, OverloadShedError)
        assert q.shed_overload == 1
        ok = Request([np.ones((1, 4), np.float32)], priority=0)
        q.put(ok)       # below the cutoff: admitted
        assert q.depth() == 1
        q.clear_overload()
        assert q.overload_min_priority() is None
        q.put(Request([np.ones((1, 4), np.float32)], priority=5))
        assert q.depth() == 2

    def test_autoscaler_arms_attached_serving_queue(self):
        tgt = FakeTarget(3)
        prof, a = _scaler(tgt)
        q = RequestQueue(max_depth=8)
        a.add_shed_queue(q)
        _feed(prof, T0, n=20)
        a.tick(now=T0)
        assert q.overload_min_priority() == a.config.shed_priority
        a.tick(now=T0 + 60.0)  # cool -> disarm everywhere
        assert q.overload_min_priority() is None


class TestObservability:
    def test_gauges_and_counters_rendered(self):
        tgt = FakeTarget(1)
        prof, a = _scaler(tgt)
        _feed(prof, T0, n=20)
        a.tick(now=T0)
        text = obs_metrics.render()
        assert 'nns_autoscaler_replicas{autoscaler="t"} 2' in text
        assert 'nns_autoscaler_desired_replicas{autoscaler="t"}' in text
        assert ('nns_autoscaler_scale_events_total{autoscaler="t",'
                'direction="out"}') in text
        assert "nns_autoscaler_blocked_by_memory_total" in text

    def test_render_top_autoscaler_section(self):
        tgt = FakeTarget(2)
        prof, a = _scaler(tgt)
        a.tick(now=T0)
        text = obs_profile.render_top({}, [], autoscale=[a.snapshot()])
        assert "AUTOSCALER [t]" in text
        assert "blocked_by_memory=0" in text
        assert "burn" in text

    def test_profile_route_carries_autoscale_block(self):
        tgt = FakeTarget(1)
        prof, a = _scaler(tgt)
        a.tick(now=T0)
        mgr = ServiceManager()
        server = ControlServer(mgr).start()
        try:
            data = ControlClient(server.endpoint).profile()
            names = [s["name"] for s in data.get("autoscale", [])]
            assert "t" in names
        finally:
            server.stop()
            mgr.shutdown()

    def test_snapshot_all_lists_live_autoscalers(self):
        tgt = FakeTarget(1)
        prof, a = _scaler(tgt)
        assert any(s["name"] == "t"
                   for s in autoscaler_mod.snapshot_all())

    def test_stop_leaves_scrape_surfaces(self):
        """A stopped controller's rows leave snapshot_all()/the metrics
        scrape at stop(), not when GC collects the weak ref (the PR 10
        unregister-at-stop stance)."""
        tgt = FakeTarget(1)
        prof = obs_profile.Profiler()
        a = Autoscaler(tgt, _cfg(tick_s=0.05), name="t-stop",
                       series="fabric:fake", profiler=prof,
                       memory_fraction_fn=lambda: 0.1)
        a.start()
        assert any(s["name"] == "t-stop"
                   for s in autoscaler_mod.snapshot_all())
        a.stop()
        assert not any(s["name"] == "t-stop"
                       for s in autoscaler_mod.snapshot_all())
        # restart re-registers (and must not double-spawn loops)
        a.start()
        assert any(s["name"] == "t-stop"
                   for s in autoscaler_mod.snapshot_all())
        a.stop()


# ---------------------------------------------------------------------------
# ControlClient retry satellite
# ---------------------------------------------------------------------------

def _flaky_http_server(fail_first_n: int, body: bytes = b'{"ok": true}',
                       status: int = 200):
    """A raw TCP server whose first N connections die mid-exchange
    (connection closed before any response — a restarting replica's
    control endpoint), then answers real HTTP responses (``status``)."""
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(8)
    srv.settimeout(0.2)  # accept wakes periodically so shutdown() joins
    port = srv.getsockname()[1]
    seen = []
    stop = threading.Event()

    def run():
        while not stop.is_set():
            try:
                conn, _ = srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            seen.append(1)
            try:
                conn.recv(4096)
                if len(seen) > fail_first_n:
                    reason = "OK" if status == 200 else "Err"
                    conn.sendall(
                        f"HTTP/1.1 {status} {reason}\r\n".encode()
                        + b"Content-Type: application/json\r\n"
                        + b"Content-Length: "
                        + str(len(body)).encode() + b"\r\n\r\n" + body)
            finally:
                conn.close()

    t = threading.Thread(target=run, name="flaky-http", daemon=True)
    t.start()

    def shutdown():
        stop.set()
        srv.close()
        t.join(timeout=2.0)

    return port, seen, shutdown


class TestControlClientRetry:
    def test_get_rides_out_connection_reset(self):
        port, seen, shutdown = _flaky_http_server(fail_first_n=2)
        try:
            c = ControlClient(f"http://127.0.0.1:{port}", timeout=5.0,
                              retries=2)
            assert c.healthz() == {"ok": True}
            assert len(seen) == 3  # 2 failures + 1 success
        finally:
            shutdown()

    def test_get_retry_budget_is_bounded(self):
        port, seen, shutdown = _flaky_http_server(fail_first_n=99)
        try:
            c = ControlClient(f"http://127.0.0.1:{port}", timeout=5.0,
                              retries=2)
            with pytest.raises(ServiceError):
                c.healthz()
            assert len(seen) == 3  # 1 + retries, never more
        finally:
            shutdown()

    def test_post_never_retries(self):
        port, seen, shutdown = _flaky_http_server(fail_first_n=99)
        try:
            c = ControlClient(f"http://127.0.0.1:{port}", timeout=5.0,
                              retries=2)
            with pytest.raises(ServiceError):
                c.stop("svc")  # POST /services/svc/stop
            assert len(seen) == 1  # a verb that may have run must not rerun
        finally:
            shutdown()

    def test_metrics_text_retries(self):
        body = b"# HELP x\nx 1\n"
        port, seen, shutdown = _flaky_http_server(fail_first_n=1, body=body)
        try:
            c = ControlClient(f"http://127.0.0.1:{port}", timeout=5.0,
                              retries=2)
            assert c.metrics_text() == body.decode()
            assert len(seen) == 2
        finally:
            shutdown()

    def test_http_error_response_is_definitive_not_retried(self):
        """A served 4xx/5xx is an ANSWER: both _call and metrics_text
        must raise immediately instead of burning the retry budget on a
        server that is reachable."""
        port, seen, shutdown = _flaky_http_server(
            fail_first_n=0, body=b'{"error": "nope"}', status=404)
        try:
            c = ControlClient(f"http://127.0.0.1:{port}", timeout=5.0,
                              retries=2)
            with pytest.raises(ServiceError, match="nope"):
                c.healthz()
            assert len(seen) == 1
            with pytest.raises(ServiceError, match="404"):
                c.metrics_text()
            assert len(seen) == 2  # one more connection, no retries
        finally:
            shutdown()


# ---------------------------------------------------------------------------
# live actuators
# ---------------------------------------------------------------------------

class TestServiceFabricScaling:
    def test_scale_out_and_in_under_traffic(self):
        mgr = ServiceManager(jitter_seed=0)
        mgr.models.define("m", {"1": "builtin://scaler?factor=2"},
                          active="1")
        fab = ServiceFabric(
            mgr, "elastic", "tensor_filter framework=jax "
            "model=registry://m", CAPS, replicas=1,
            quarantine_base_s=0.1, health_poll_s=0.05)
        try:
            fab.start()
            assert fab.replica_count() == 1
            out = fab.request([np.ones(4, np.float32)], key="w",
                              timeout=30.0)
            assert np.allclose(np.asarray(out.tensors[0]), 2.0)
            errors = []
            stop = threading.Event()

            def traffic():
                i = 0
                while not stop.is_set():
                    i += 1
                    try:
                        fab.request([np.ones(4, np.float32)],
                                    key=f"t{i}", timeout=10.0)
                    except Exception as e:  # noqa: BLE001
                        errors.append(str(e))
                    stop.wait(0.01)

            t = threading.Thread(target=traffic, name="fabric:traffic:e",
                                 daemon=True)
            t.start()
            rid = fab.scale_out()
            assert fab.replica_count() == 2
            assert rid in fab.pool.replicas()
            time.sleep(0.5)
            removed = fab.scale_in()
            assert fab.replica_count() == 1
            assert removed == rid  # newest goes first
            assert removed not in fab.pool.replicas()
            time.sleep(0.3)
            stop.set()
            t.join(timeout=15.0)
            assert errors == []
        finally:
            fab.stop()
            mgr.shutdown()

    def test_scale_in_skips_canary_replica(self):
        mgr = ServiceManager(jitter_seed=0)
        mgr.models.define("m", {"1": "builtin://scaler?factor=2",
                                "2": "builtin://scaler?factor=3"},
                          active="1")
        fab = ServiceFabric(
            mgr, "elastic2", "tensor_filter framework=jax "
            "model=registry://m", CAPS, replicas=2,
            quarantine_base_s=0.1, health_poll_s=0.05)
        try:
            fab.start()
            fab.request([np.ones(4, np.float32)], key="w", timeout=30.0)
            fab.canary("m", "2", 0.3)  # canary rides _services[0]
            canary_rid = fab.pool.snapshot()["canary"]["replica"]
            removed = fab.scale_in()
            assert removed != canary_rid
            assert fab.replica_count() == 1
        finally:
            fab.stop()
            mgr.shutdown()


@pytest.mark.thread_leak_ok
class TestProcReplicaE2E:
    def test_spawn_kill_respawn_readmit_zero_errors(self):
        """The subprocess lifecycle gate: spawn → READY join → serve →
        SIGKILL → reap/evict → autoscaler respawn → readmit, with
        traffic flowing the whole time and zero client-visible errors.
        (thread_leak_ok: the subprocess owns its own threads; parent-side
        stdout readers are joined by terminate(), but a SIGKILLed
        child's reader drains on its own schedule.)"""
        ps = ProcReplicaSet(
            "t-e2e", "tensor_filter framework=jax "
            "model=registry://m", CAPS, replicas=2,
            models={"m": {"versions": {"1": "builtin://scaler?factor=2"},
                          "active": "1"}},
            quarantine_base_s=0.2, health_poll_s=0.05)
        cfg = _cfg(min_replicas=2, max_replicas=2,
                   respawn_backoff_base_s=0.2)
        scaler = Autoscaler(ps, cfg, name="t-e2e")
        try:
            ps.start()
            assert ps.replica_count() == 2
            snap = ps.snapshot()
            assert all(p["alive"] for p in snap["processes"])
            out = ps.request([np.ones(4, np.float32)], key="k",
                             timeout=30.0)
            assert np.allclose(np.asarray(out.tensors[0]), 2.0)
            # control-endpoint liveness through the retrying client
            with ps._lock:
                slot0 = ps._slots[ps._order[0]]
            assert slot0.proc.healthy(timeout=5.0)
            scaler.start()
            errors = []
            stop = threading.Event()

            def traffic():
                i = 0
                while not stop.is_set():
                    i += 1
                    try:
                        ps.request([np.ones(4, np.float32)],
                                   key=f"t{i}", timeout=15.0)
                    except Exception as e:  # noqa: BLE001
                        errors.append(f"{type(e).__name__}: {e}")
                    stop.wait(0.02)

            t = threading.Thread(target=traffic, name="fabric:traffic:p",
                                 daemon=True)
            t.start()
            killed = ps.kill_replica(0)
            deadline = time.monotonic() + 90.0
            while time.monotonic() < deadline:
                snap = ps.pool.snapshot()
                if (snap["readmissions"] >= 1
                        and scaler.snapshot()["respawns"] >= 1):
                    break
                time.sleep(0.2)
            stop.set()
            t.join(timeout=20.0)
            snap = ps.pool.snapshot()
            asnap = scaler.snapshot()
            assert snap["evictions"] >= 1
            assert asnap["respawns"] >= 1
            assert snap["readmissions"] >= 1
            assert errors == []
            # the respawned process answers under the SAME ring identity
            assert killed in ps.pool.replicas()
            procs = ps.snapshot()["processes"]
            assert sum(1 for p in procs if p["alive"]) == 2
        finally:
            scaler.stop()
            ps.stop()


@pytest.mark.thread_leak_ok
class TestProcReplicaRestartWindow:
    def test_in_child_restart_keeps_advertised_port(self):
        """An in-child service restart (operator stop/start through the
        replica's control endpoint) re-binds the PINNED port, so every
        ring resolver's address stays valid and traffic resumes without
        a respawn — the restart window the retrying ControlClient and
        the quarantine probe are built to ride out."""
        ps = ProcReplicaSet(
            "t-pin", "tensor_filter framework=jax "
            "model=builtin://scaler?factor=2", CAPS, replicas=1,
            quarantine_base_s=0.2, health_poll_s=0.05)
        try:
            ps.start()
            ps.request([np.ones(4, np.float32)], key="a", timeout=30.0)
            rid = ps.services()[0]
            with ps._lock:
                proc = ps._slots[rid].proc
            port0 = proc.address()[1]
            c = proc.control(timeout=10.0)
            c.stop(proc.info["name"])
            c.start(proc.info["name"])
            deadline = time.monotonic() + 30.0
            served = False
            while time.monotonic() < deadline and not served:
                try:
                    ps.request([np.ones(4, np.float32)], key="b",
                               timeout=5.0)
                    served = True
                except Exception:  # noqa: BLE001 - restart window
                    time.sleep(0.2)
            assert served
            assert proc.alive()
            assert proc.address()[1] == port0  # same advertised port
        finally:
            ps.stop()


@pytest.mark.thread_leak_ok
def test_replica_without_a_device_fails_typed_and_at_once(monkeypatch):
    """A chip belongs to one process: a replica child whose jax backend
    will not initialize exits naming what it could not get, and
    wait_ready raises the typed error long before its timeout. (A
    backend name jax does not know stands in for the held chip.)"""
    from nnstreamer_tpu.service.procreplica import (
        EXIT_NO_DEVICE,
        ProcReplica,
        ReplicaDeviceError,
    )

    monkeypatch.setenv("JAX_PLATFORMS", "nochip")  # the child inherits it
    proc = ProcReplica("tensor_filter framework=jax "
                       "model=builtin://scaler?factor=2", CAPS).spawn()
    t0 = time.monotonic()
    try:
        with pytest.raises(ReplicaDeviceError, match="nochip"):
            proc.wait_ready(timeout=120.0)
        assert time.monotonic() - t0 < 60.0
        assert proc.returncode == EXIT_NO_DEVICE
    finally:
        proc.terminate()


class TestReplicaRunnerCLI:
    def test_replica_verb_wired(self):
        from nnstreamer_tpu.__main__ import main

        with pytest.raises(SystemExit):
            main(["replica", "--help"])

    def test_replica_requires_stage_and_caps(self, capsys):
        from nnstreamer_tpu.__main__ import main

        with pytest.raises(SystemExit):
            main(["replica"])

    def test_ready_line_roundtrip(self):
        from nnstreamer_tpu.service.procreplica import READY_PREFIX

        payload = {"name": "r", "pid": 1, "host": "127.0.0.1",
                   "query_port": 5, "control_port": 6}
        line = READY_PREFIX + json.dumps(payload)
        assert line.startswith(READY_PREFIX)
        assert json.loads(line[len(READY_PREFIX):]) == payload
