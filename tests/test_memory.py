"""Device-memory observability plane (obs/memory.py) + its consumers:
static per-stage byte estimates and the artifact ``memory`` section
(capture → save → load → merge keeps max-watermark semantics), the
planner's byte-feasibility auto-cap, serving memory admission shedding,
the memory SLO kind, flight category filtering, ProfileStore GC, and
the explicit metrics unregister sweep on Pipeline.stop()."""
import json
import os
import time

import numpy as np
import pytest

from nnstreamer_tpu.obs import flight as obs_flight
from nnstreamer_tpu.obs import memory as obs_memory
from nnstreamer_tpu.obs import metrics as obs_metrics
from nnstreamer_tpu.obs import profile as obs_profile
from nnstreamer_tpu.obs.slo import SloEngine, SLObjective
from nnstreamer_tpu.runtime.parse import parse_launch
from nnstreamer_tpu.runtime.placement import Planner, StagePlacement
from nnstreamer_tpu.serving.request import MemoryPressureError
from nnstreamer_tpu.serving.scheduler import Scheduler

SRC = ("tensor_src num-buffers={n} dimensions=8 types=float32 "
       "pattern=counter ")
ADD = "tensor_transform mode=arithmetic option=add:1 "
MATMUL = "tensor_filter framework=jax model=builtin://matmul?n=8 "

FUSED = (SRC + f"! {ADD}! {MATMUL}! queue name=q0 max-size-buffers=16 "
         f"! {MATMUL}! tensor_sink name=out max-stored=1")


@pytest.fixture(autouse=True)
def _clean_memory_plane():
    obs_memory.reset()
    yield
    obs_memory.stop()
    obs_memory.reset()


def run_accounted(n=40):
    obs_memory.start()
    try:
        pipe = parse_launch(FUSED.format(n=n))
        pipe.run(timeout=60)
    finally:
        obs_memory.stop()
    return pipe


# ---------------------------------------------------------------------------
# accountant + static estimates
# ---------------------------------------------------------------------------

class TestAccountant:
    def test_max_watermark_per_field(self):
        acc = obs_memory.MemoryAccountant()
        acc.record_stage("p:a..b", "fused", temp_bytes=100, param_bytes=10)
        acc.record_stage("p:a..b", "fused", temp_bytes=40, param_bytes=70)
        cell = acc.stage("p:a..b")
        assert cell["temp_bytes"] == 100
        assert cell["param_bytes"] == 70
        assert cell["total_bytes"] == 170  # per-field max, then summed

    def test_disabled_accounting_records_nothing(self):
        assert not obs_memory.ACTIVE
        pipe = parse_launch(FUSED.format(n=20))
        pipe.run(timeout=60)
        assert obs_memory.accountant().stages() == {}

    def test_fused_and_filter_estimates_recorded(self):
        run_accounted()
        stages = obs_memory.accountant().stages()
        fused = [c for c in stages.values() if c["kind"] == "fused"]
        assert fused and any(c["total_bytes"] > 0 for c in fused)
        # the singleton matmul filter reports its 8x8 f32 weight params
        filt = [c for c in stages.values()
                if c["kind"] == "filter" and c["param_bytes"] > 0]
        assert filt and filt[0]["param_bytes"] >= 8 * 8 * 4
        # and the model URI footprint landed
        assert obs_memory.accountant().models().get(
            "builtin://matmul?n=8", 0) >= 8 * 8 * 4

    def test_callable_param_nbytes_walks_closures(self):
        w = np.ones((16, 4), np.float32)

        def model(x):
            return x @ w

        assert obs_memory.callable_param_nbytes(model) == w.nbytes

    def test_device_sampling_and_budget_fraction(self):
        obs_memory.set_budget(None)
        rows = obs_memory.sample_devices()
        assert rows and all(r["used_fraction"] == 0.0 for r in rows)
        try:
            obs_memory.set_budget(1)  # 1 byte: any live array crosses
            import jax.numpy as jnp

            keep = jnp.ones((64,), jnp.float32)  # noqa: F841
            frac = obs_memory.used_fraction()
            assert frac > 1.0
            # the watermark crossing landed as a memory flight event
            events = obs_flight.dump(category="memory")
            assert any(e["name"] == "watermark" for e in events)
        finally:
            obs_memory.set_budget(None)


# ---------------------------------------------------------------------------
# artifact round-trip (capture -> save -> load -> merge = max-watermark)
# ---------------------------------------------------------------------------

class TestArtifactMemorySection:
    def test_capture_save_load_merge_roundtrip(self, tmp_path):
        pipe = run_accounted()
        art = obs_profile.ProfileArtifact.capture(pipe)
        assert art.memory, "capture must carry the memory section"
        # prefix stripped: keys are canonical stage names
        assert all(not k.startswith(pipe.name) for k in art.memory)
        path = str(tmp_path / "a.json")
        art.save(path)
        back = obs_profile.ProfileArtifact.load(path)
        assert back.memory == art.memory

        # merge keeps the per-field MAXIMUM (watermark), never sums
        other = obs_profile.ProfileArtifact.from_dict(
            json.loads(json.dumps(art.to_dict())))
        key = next(iter(other.memory))
        other.memory[key]["temp_bytes"] = \
            art.memory[key].get("temp_bytes", 0) + 1000
        other.memory[key]["param_bytes"] = 0
        merged = back.merge(other)
        assert merged.memory[key]["temp_bytes"] == \
            art.memory[key].get("temp_bytes", 0) + 1000
        assert merged.memory[key]["param_bytes"] == \
            art.memory[key].get("param_bytes", 0)
        # total_bytes is recomputed from the merged field maxes, not
        # maxed independently (replicas peaking on DIFFERENT fields
        # would otherwise understate the footprint the planner reads)
        assert merged.memory[key]["total_bytes"] == sum(
            merged.memory[key].get(f, 0) for f in obs_memory.FIELDS)

    def test_merge_total_recomputed_across_fields(self):
        a = obs_profile.ProfileArtifact(
            {"topology": "t", "caps": "", "model_version": ""}, {},
            memory={"s": {"kind": "fused", "temp_bytes": 10,
                          "total_bytes": 10}})
        b = obs_profile.ProfileArtifact(
            {"topology": "t", "caps": "", "model_version": ""}, {},
            memory={"s": {"kind": "fused", "param_bytes": 8,
                          "total_bytes": 8}})
        a.merge(b)
        assert a.memory["s"]["total_bytes"] == 18

    def test_store_roundtrip_preserves_memory(self, tmp_path):
        pipe = run_accounted()
        art = obs_profile.ProfileArtifact.capture(pipe)
        store = obs_profile.ProfileStore(str(tmp_path))
        store.save(art)
        store.save(obs_profile.ProfileArtifact.capture(pipe))  # merge path
        back = store.load(art.key)
        assert back is not None and back.memory == art.memory

    def test_old_artifacts_without_memory_load(self, tmp_path):
        pipe = run_accounted()
        d = obs_profile.ProfileArtifact.capture(pipe).to_dict()
        del d["memory"]  # pre-PR-10 artifact on disk
        back = obs_profile.ProfileArtifact.from_dict(d)
        assert back.memory == {}


# ---------------------------------------------------------------------------
# planner byte-feasibility auto-cap
# ---------------------------------------------------------------------------

class TestPlannerByteCap:
    COSTS = (4.0, 2.0, 2.0, 1.0)
    BYTES = (100, 10, 10, 100)

    def _stages(self):
        return [StagePlacement(k, [k], 0, c, c, "profile", bytes=b)
                for k, c, b in zip("abcd", self.COSTS, self.BYTES)]

    def test_infeasible_optimum_rejected_feasible_optimum_chosen(self):
        """The latency optimum pairs a(4.0,100B) with d(1.0,100B) for
        max 5.0 — but 200B outgrows the 110B budget. The planner must
        reject it and take the best FEASIBLE assignment (max 6.0)."""
        stages = self._stages()
        load, mem, feasible = Planner(devices=[None, None])._assign(
            stages, 2, budgets=[110, 110])
        assert feasible
        assert max(load) == pytest.approx(6.0)
        assert all(b <= 110 for b in mem)

    def test_unconstrained_without_budgets(self):
        stages = self._stages()
        load, _, feasible = Planner(devices=[None, None])._assign(
            stages, 2, budgets=[None, None])
        assert feasible  # vacuously: no budget -> no constraint
        assert max(load) == pytest.approx(5.0)

    def test_wholly_infeasible_relaxes_and_reports(self):
        stages = self._stages()
        load, _, feasible = Planner(devices=[None, None])._assign(
            stages, 2, budgets=[50, 50])  # single 100B stage can't fit
        assert not feasible
        assert max(load) == pytest.approx(5.0)  # fell back to latency-only
        events = obs_flight.dump(category="memory")
        assert any(e["name"] == "placement_infeasible" for e in events)

    def test_lpt_regime_relaxes_loudly_never_silently_over_budget(self):
        """17 stages × 2 devices exceeds the exact-search limit (2^17 >
        64k), so LPT runs. When the packing cannot fit the budgets the
        result must report byte_feasible=False with the flight event —
        never a silently over-budget 'feasible' plan."""
        stages = [StagePlacement(f"s{i}", [f"s{i}"], 0, 1.0, 1.0,
                                 "profile", bytes=10) for i in range(17)]
        load, _, feasible = Planner(devices=[None, None])._assign(
            stages, 2, budgets=[50, 50])  # 170B total > 100B capacity
        assert not feasible
        events = obs_flight.dump(category="memory")
        assert any(e["name"] == "placement_infeasible" for e in events)
        # with headroom LPT packs under budget and reports feasible
        stages = [StagePlacement(f"s{i}", [f"s{i}"], 0, 1.0, 1.0,
                                 "profile", bytes=10) for i in range(17)]
        _, mem, feasible = Planner(devices=[None, None])._assign(
            stages, 2, budgets=[90, 90])
        assert feasible and all(b <= 90 for b in mem)

    def test_plan_stages_carry_bytes_and_balance_reports(self):
        art = obs_profile.ProfileArtifact(
            {"topology": "t", "caps": "", "model_version": ""}, {},
            memory={"a": {"kind": "filter", "total_bytes": 128}})
        # bytes resolve through _stage_bytes at plan time
        from nnstreamer_tpu.runtime.placement import _stage_bytes

        class _El:
            auto_named = False
            name = "a"

        assert _stage_bytes(art, [_El()]) == 128
        assert _stage_bytes(None, [_El()]) == 0

    @staticmethod
    def _referee(stages, n_dev, budget):
        """(least makespan among the assignments that fit, least budget
        under which any fits), by brute force over the plan's own table."""
        import itertools

        best = tightest = None
        for combo in itertools.product(range(n_dev), repeat=len(stages)):
            load, mem = [0.0] * n_dev, [0] * n_dev
            for st, dev in zip(stages, combo):
                load[dev] += st.cost_ms
                mem[dev] += st.bytes
            if tightest is None or max(mem) < tightest:
                tightest = max(mem)
            if max(mem) <= budget and (best is None or max(load) < best):
                best = max(load)
        return best, tightest

    def test_a_captured_artifact_and_a_stated_budget_cap_the_plan(self):
        """The whole loop a deployment runs: one profiled, accounted run
        captures the artifact; a planner given ONLY that and an HBM
        budget (no stage-count knob) makes a plan that fits and is the
        latency optimum among the assignments that fit — under a
        generous budget and under the tightest one that admits a plan,
        where the cap binds and one device cannot hold every stage."""
        import jax

        mm = "! " + MATMUL
        four = (SRC + f"! {ADD}{mm * 4}! queue name=q0 ! {ADD}{mm * 2}"
                f"! queue name=q1 ! {ADD}{mm * 2}! queue name=q2 "
                f"! {ADD}{mm}! tensor_sink name=out max-stored=1")
        obs_profile.reset()
        obs_profile.start()
        obs_memory.start()
        try:
            pipe = parse_launch(four.format(n=40))
            pipe.run(timeout=120)
        finally:
            obs_profile.stop()
            obs_memory.stop()
        art = obs_profile.ProfileArtifact.capture(pipe)
        obs_profile.reset()
        assert art.memory, "the accounted run captured no memory section"
        devices = jax.devices()[:2]

        def fits_and_is_the_optimum(budget):
            plan = Planner(devices=devices, hbm_budget_bytes=budget).plan(
                parse_launch(four.format(n=40)), artifact=art)
            best, tightest = self._referee(plan.stages, 2, budget)
            assert plan.balance["byte_feasible"]
            assert plan.balance["max_device_bytes"] <= budget
            assert plan.balance["max_stage_ms"] == pytest.approx(
                best, abs=1e-6)
            return plan, tightest

        total = sum(c.get("total_bytes", 0) for c in art.memory.values())
        plan, tightest = fits_and_is_the_optimum(2 * total)
        assert plan.source == "profile"
        assert len(plan.stages) == 4 and all(s.bytes > 0 for s in plan.stages)
        plan, _ = fits_and_is_the_optimum(tightest)
        assert plan.balance["stage_bytes_total"] > tightest  # the cap binds

    def test_auto_budget_from_env(self, monkeypatch):
        monkeypatch.setenv(obs_memory.BUDGET_ENV, "4096")
        budgets = Planner(devices=[None, None]).device_budgets()
        assert budgets == [4096, 4096]
        monkeypatch.delenv(obs_memory.BUDGET_ENV)
        assert Planner(devices=[None]).device_budgets() == [None]


# ---------------------------------------------------------------------------
# serving admission: typed memory shedding
# ---------------------------------------------------------------------------

class TestMemoryAdmission:
    def test_guard_sheds_typed_and_releases(self):
        frame = np.zeros((2, 32), np.float32)
        guard = obs_memory.AdmissionGuard(
            budget_bytes=frame.nbytes * 8, watermark=1.0, overhead=1.0,
            name="t1")
        sched = Scheduler(fn=lambda x: x + 1, bucket_sizes=(2,),
                          max_depth=512, name="mem-shed",
                          autostart=False, memory_guard=guard)
        try:
            pending = []
            shed = 0
            for _ in range(32):
                try:
                    pending.append(sched.submit([frame]))
                except MemoryPressureError:
                    shed += 1
            assert shed > 0, "flood past the budget must shed"
            assert len(pending) == 8  # exactly what fits under watermark
            assert guard.peak_bytes <= guard.limit_bytes
            sched.start()
            for req in pending:
                req.result(timeout=30.0)
        finally:
            sched.close()
        assert guard.inflight_bytes == 0  # every reservation released
        snap = sched.metrics.snapshot()
        assert snap["shed_memory"] == shed
        assert snap["failed"] == 0
        events = obs_flight.dump(category="memory")
        assert any(e["name"] == "admission_shed" for e in events)

    def test_reservation_released_on_close_and_queue_shed(self):
        frame = np.zeros((1, 16), np.float32)
        guard = obs_memory.AdmissionGuard(
            budget_bytes=frame.nbytes * 100, watermark=1.0,
            overhead=1.0, name="t2")
        sched = Scheduler(fn=lambda x: x, bucket_sizes=(1,),
                          max_depth=64, name="mem-close",
                          autostart=False, memory_guard=guard)
        reqs = [sched.submit([frame]) for _ in range(5)]
        assert guard.inflight_bytes == 5 * frame.nbytes
        sched.close()
        for r in reqs:
            with pytest.raises(Exception):
                r.result(timeout=1.0)
        assert guard.inflight_bytes == 0

    def test_paged_page_reservation_and_refcounts_drain(self):
        # paged serving reserves PAGES against the guard (the resource
        # that actually runs out), and _release_mem fires on every exit
        # path — so the guard ledger and the pool refcounts must drain
        # TOGETHER: zero inflight bytes, zero held pages, every page
        # refcount back to zero
        from nnstreamer_tpu.models.lm_serving import tiny
        from nnstreamer_tpu.models.transformer import init_params
        from nnstreamer_tpu.serving import DecodeScheduler, PagedLMEngine

        cfg = tiny.cfg
        eng = PagedLMEngine(cfg, init_params(cfg, seed=0), slots=2,
                            page_size=8, pages=16, chunk=16,
                            share_prefixes=False)
        # 9-token prompt + 7 steps = 16 positions = 2 pages per request;
        # budget 4 pages -> exactly two requests fit under the watermark
        guard = obs_memory.AdmissionGuard(
            budget_bytes=eng.page_bytes * 4, watermark=1.0,
            overhead=1.0, name="pages")
        sched = DecodeScheduler(eng, name="mem-paged",
                                memory_guard=guard)
        prompt = np.arange(1, 10, dtype=np.int32)
        done, shed = [], 0
        try:
            for _ in range(6):
                try:
                    done.append(sched.submit(prompt, steps=7))
                except MemoryPressureError:
                    shed += 1
            assert shed > 0, "flood past the page budget must shed"
            assert len(done) == 2
            assert guard.inflight_bytes == 2 * 2 * eng.page_bytes
            for r in done:
                r.result(timeout=120.0)
        finally:
            sched.close()
        assert guard.inflight_bytes == 0
        assert eng.pool.used_pages == 0
        assert all(eng.pool.refcount(p) == 0
                   for p in range(1, eng.pool.pages + 1))

    def test_no_guard_no_change(self):
        sched = Scheduler(fn=lambda x: x * 2, bucket_sizes=(1,),
                          name="mem-off")
        try:
            out = sched([np.ones((1, 4), np.float32)], timeout=30.0)
            assert np.allclose(np.asarray(out[0]), 2.0)
        finally:
            sched.close()


# ---------------------------------------------------------------------------
# memory SLO kind
# ---------------------------------------------------------------------------

class TestMemorySlo:
    def test_memory_objective_breaches_and_recovers(self):
        prof = obs_profile.Profiler()
        engine = SloEngine(profiler=prof, name="mem-slo")
        obj = SLObjective(name="hbm-headroom", kind="memory",
                          target=0.9, threshold_s=0.85,
                          windows=((5.0, 10.0, 1.0),))
        engine.add(obj)
        assert obj.series == "memory:devices"
        try:
            obs_memory.set_budget(1)  # everything crosses 85% headroom
            import jax.numpy as jnp

            keep = jnp.ones((64,), jnp.float32)  # noqa: F841
            now = time.monotonic()
            for i in range(10):
                engine.evaluate(now=now + i)
            status = engine.status()[0]
            assert status["alerting"]
            # budget off -> fraction 0.0 -> every short window cools
            obs_memory.set_budget(None)
            for i in range(30):
                engine.evaluate(now=now + 10 + i)
            assert not engine.status()[0]["alerting"]
        finally:
            obs_memory.set_budget(None)
            engine.stop()

    def test_memory_objective_validation(self):
        with pytest.raises(ValueError):
            SLObjective(name="bad", kind="memory", threshold_s=2.0)
        obj = SLObjective(name="ok", kind="memory", threshold_s=0.9,
                          series="memory:custom")
        assert obj.series == "memory:custom"


# ---------------------------------------------------------------------------
# satellites: flight category, store GC, metrics unregister sweep
# ---------------------------------------------------------------------------

class TestFlightCategory:
    def test_dump_category_filter(self):
        obs_flight.record("memory", "watermark", {"device": "cpu:0"})
        obs_flight.record("pipeline", "playing", {}, pipeline="p1")
        mem_events = obs_flight.dump(category="memory")
        assert mem_events and all(e["kind"] == "memory"
                                  for e in mem_events)
        both = obs_flight.dump(category="memory", pipeline="p1")
        assert both == []  # filters compose (AND)

    def test_http_and_client_category(self):
        from nnstreamer_tpu.service import (
            ControlClient,
            ControlServer,
            ServiceManager,
        )

        obs_flight.record("memory", "watermark", {"device": "cpu:0"})
        mgr = ServiceManager()
        server = ControlServer(mgr).start()
        try:
            client = ControlClient(server.endpoint)
            events = client.flight(category="memory")["events"]
            assert events and all(e["kind"] == "memory" for e in events)
            # the /memory route serves the accounting snapshot
            snap = client.memory()["memory"]
            assert "devices" in snap and "stages" in snap
        finally:
            server.stop()
            mgr.shutdown()


class TestStoreGC:
    def _artifact(self, topo: str) -> obs_profile.ProfileArtifact:
        return obs_profile.ProfileArtifact(
            {"topology": topo, "caps": "", "model_version": ""}, {})

    def test_lru_prune_on_save_keeps_active_key(self, tmp_path):
        store = obs_profile.ProfileStore(str(tmp_path), max_artifacts=3)
        for i in range(5):
            art = self._artifact(f"topo{i}")
            store.save(art)
            os.utime(store.path_for(art.key), (1000 + i, 1000 + i))
        active = self._artifact("active")
        store.save(active)
        remaining = {e["topology"] for e in store.list()}
        assert len(remaining) == 3
        assert "active" in remaining, "the just-saved key must survive"
        assert "topo0" not in remaining and "topo1" not in remaining

    def test_explicit_prune_verb_semantics(self, tmp_path):
        store = obs_profile.ProfileStore(str(tmp_path))
        for i in range(4):
            art = self._artifact(f"t{i}")
            store.save(art)
            os.utime(store.path_for(art.key), (1000 + i, 1000 + i))
        removed = store.prune(2)
        assert len(removed) == 2
        assert len(store.list()) == 2
        assert store.prune(2) == []  # already under the bound

    def test_unbounded_store_never_prunes(self, tmp_path):
        store = obs_profile.ProfileStore(str(tmp_path))
        for i in range(4):
            store.save(self._artifact(f"t{i}"))
        assert len(store.list()) == 4
        assert store.prune(None) == []

    def test_default_store_reads_max_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv(obs_profile.STORE_ENV, str(tmp_path))
        monkeypatch.setenv(obs_profile.STORE_MAX_ENV, "7")
        assert obs_profile.default_store().max_artifacts == 7


class TestUnregisterSweep:
    def test_stopped_pipeline_rows_leave_the_scrape(self):
        pipe = parse_launch(FUSED.format(n=30))
        pipe.run(timeout=60)  # run() stops at EOS — rows must be gone
        text = obs_metrics.render()
        assert f'pipeline="{pipe.name}"' not in text, \
            "stopped pipeline's nns_fused_* rows must not be scraped"

    def test_playing_pipeline_rows_present_then_swept(self):
        pipe = parse_launch(FUSED.format(n=400))
        pipe.play()
        try:
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if any(s.stats["dispatches"] for s in pipe.fused_segments):
                    break
                time.sleep(0.01)
            assert f'pipeline="{pipe.name}"' in obs_metrics.render()
        finally:
            pipe.stop()
        assert f'pipeline="{pipe.name}"' not in obs_metrics.render()


# ---------------------------------------------------------------------------
# surfaces: snapshot, gauges, obs top MEMORY section
# ---------------------------------------------------------------------------

class TestSurfaces:
    def test_snapshot_shape_and_queue_bytes(self):
        pipe = run_accounted()
        snap = obs_memory.snapshot()
        assert set(snap) >= {"active", "stages", "models", "devices",
                             "queues", "serving", "budget_bytes"}
        # 8-float32 frames: negotiated caps give a 32-byte frame size
        pipe2 = parse_launch(FUSED.format(n=30))
        pipe2.play()
        try:
            deadline = time.monotonic() + 30
            q = pipe2.get("q0")
            while time.monotonic() < deadline:
                if q.sink_pads[0].caps is not None:
                    break
                time.sleep(0.01)
            qb = obs_memory.queue_bytes(pipe2)
            assert qb["q0"]["frame_bytes"] == 8 * 4
        finally:
            pipe2.stop()

    def test_memory_gauges_render(self):
        run_accounted()
        text = obs_metrics.render()
        assert "nns_memory_stage_bytes" in text
        assert "nns_memory_device_bytes" in text
        assert "nns_serving_shed_memory_total" in text

    def test_render_top_memory_section(self):
        run_accounted()
        out = obs_profile.render_top({}, [], memory=obs_memory.snapshot())
        assert "MEMORY (devices)" in out
        assert "MEMORY (stage estimates)" in out
