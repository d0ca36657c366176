"""AOT compile-artifact subsystem (nnstreamer_tpu/aot): shape-poly
export/one-trace bucket coverage, cache key correctness under hot swap
and canary promote, corrupt-artifact resilience, fused/singleton load
paths, placement-plan artifact refs, lint and obs surfaces."""
import json
import os
import time

import numpy as np
import pytest

from nnstreamer_tpu import aot
from nnstreamer_tpu.runtime.parse import parse_launch

SRC = ("tensor_src num-buffers=6 dimensions=8 types=float32 "
       "pattern=counter ")
ADD = "tensor_transform mode=arithmetic option=add:1 "
SCALER = "tensor_filter framework=jax model=builtin://scaler?factor=2 "

FUSED_LINE = (SRC + f"! {ADD}! {SCALER}! tensor_sink name=out "
              "max-stored=16")


@pytest.fixture
def cache_root(tmp_path, monkeypatch):
    """A fresh env-configured compile cache."""
    root = tmp_path / "aotcache"
    monkeypatch.setenv(aot.CACHE_ENV, str(root))
    monkeypatch.delenv(aot.CACHE_MAX_ENV, raising=False)
    aot.reset_stats()
    yield root


def pull_bytes(pipe, name="out"):
    out = pipe.get(name)
    vals = []
    while True:
        b = out.pull(timeout=0.2)
        if b is None:
            return vals
        vals.append(tuple(np.ascontiguousarray(np.asarray(t)).tobytes()
                          for t in b.tensors))


# ---------------------------------------------------------------------------
# export machinery: one shape-poly artifact covers every bucket
# ---------------------------------------------------------------------------

class TestExport:
    def test_poly_artifact_one_trace_covers_buckets(self):
        """THE recompile-storm retirement: the model's Python fn traces
        ONCE (at export); every serving bucket then runs through the
        deserialized program with zero further traces."""
        traces = []

        def model(x):
            traces.append(1)
            return (x * 2.0,)

        blob, meta, fresh = aot.export_stage(
            model, (np.ones((2, 8), np.float32),), poly=True)
        assert meta["poly"] is True
        assert meta["in_avals"][0]["shape"] == ["b", 8]
        loaded = aot.load_artifact(blob)
        assert loaded.poly is True
        for bucket in (1, 2, 4, 8, 16):
            out = loaded.call(np.ones((bucket, 8), np.float32))
            assert out[0].shape == (bucket, 8)
            np.testing.assert_allclose(np.asarray(out[0]), 2.0)
        assert len(traces) == 1  # one compilation across ALL buckets

    def test_plain_jit_traces_once_a_bucket(self):
        """What the artifact retires (NNL008's recompile storm): the same
        model under a plain ``jax.jit`` traces anew at every bucket."""
        import jax

        traces = []

        def model(x):
            traces.append(1)
            return (x * 2.0,)

        jitted = jax.jit(model)
        for bucket in (1, 2, 4, 8, 16):
            jitted(np.ones((bucket, 8), np.float32))
        assert len(traces) == 5

    def test_compatibility_contract(self):
        blob, _meta, _ = aot.export_stage(
            lambda x: (x + 1,), (np.ones((2, 4), np.float32),), poly=True)
        loaded = aot.load_artifact(blob)
        assert loaded.compatible((np.ones((9, 4), np.float32),))
        # trailing dim / dtype / rank / arity mismatches all refuse
        assert not loaded.compatible((np.ones((9, 5), np.float32),))
        assert not loaded.compatible((np.ones((9, 4), np.int32),))
        assert not loaded.compatible((np.ones((9,), np.float32),))
        assert not loaded.compatible((np.ones((9, 4), np.float32),) * 2)

    def test_static_fallback_when_poly_rejected(self):
        """A computation that needs the concrete batch value cannot
        lower symbolically: export falls back to a static artifact for
        the observed signature (still kills the restart cold start)."""
        import jax.numpy as jnp

        def model(x):
            # a host-built constant sized by the batch: np.arange needs
            # the concrete value, which a symbolic dim cannot give
            ramp = np.arange(x.shape[0], dtype=np.float32)
            return (x * jnp.asarray(ramp)[:, None],)

        blob, meta, _ = aot.export_stage(
            model, (np.ones((2, 4), np.float32),), poly=True)
        assert meta["poly"] is False
        loaded = aot.load_artifact(blob)
        assert loaded.compatible((np.ones((2, 4), np.float32),))
        assert not loaded.compatible((np.ones((3, 4), np.float32),))

    def test_fabricate_inputs_substitutes_batch(self):
        meta = {"in_avals": [{"shape": ["b", 3, 2], "dtype": "float32"},
                             {"shape": [5], "dtype": "int32"}]}
        ins = aot.fabricate_inputs(meta, batch=1)
        assert ins[0].shape == (1, 3, 2) and ins[0].dtype == np.float32
        assert ins[1].shape == (5,) and ins[1].dtype == np.int32


# ---------------------------------------------------------------------------
# the cache: roundtrip, corruption, GC
# ---------------------------------------------------------------------------

class TestCompileCache:
    KEY = {"topology": "t0", "caps": "c", "model_version": "1",
           "device": "cpu:8", "jax": "x"}

    def _one(self, root, key=None, stage="s0", digest="d0"):
        cache = aot.CompileCache(str(root))
        blob, meta, _ = aot.export_stage(
            lambda x: (x * 3.0,), (np.ones((2, 4), np.float32),))
        cache.save(key or self.KEY, stage, digest, blob, meta)
        return cache

    def test_roundtrip_hit_and_miss(self, cache_root):
        cache = self._one(cache_root)
        loaded = cache.load(self.KEY, "s0", "d0")
        assert loaded is not None
        out = loaded.call(np.ones((5, 4), np.float32))
        np.testing.assert_allclose(np.asarray(out[0]), 3.0)
        # any key component change misses: model version here
        assert cache.load({**self.KEY, "model_version": "2"},
                          "s0", "d0") is None
        assert cache.load(self.KEY, "s0", "OTHER") is None
        assert aot.STATS["hits"] == 1 and aot.STATS["misses"] == 2

    def test_corrupt_blob_evicts_and_recompiles(self, cache_root):
        cache = self._one(cache_root)
        (path,) = [e["path"] for e in cache.list()]
        with open(path, "r+b") as fh:  # flip bytes mid-artifact
            fh.seek(10)
            fh.write(b"\xde\xad\xbe\xef")
        assert cache.load(self.KEY, "s0", "d0") is None  # never a crash
        assert not os.path.exists(path)  # quarantined
        assert aot.STATS["evictions"] >= 1

    def test_truncated_meta_evicts(self, cache_root):
        cache = self._one(cache_root)
        (path,) = [e["path"] for e in cache.list()]
        mpath = path[:-len(".jaxexport")] + ".meta.json"
        with open(mpath, "w") as fh:
            fh.write('{"kind": "nns-aot", "sch')  # torn write
        assert cache.load(self.KEY, "s0", "d0") is None
        assert not os.path.exists(path)

    def test_lru_prune_and_env_bound(self, cache_root, monkeypatch):
        cache = aot.CompileCache(str(cache_root))
        blob, meta, _ = aot.export_stage(
            lambda x: (x,), (np.ones((1, 2), np.float32),))
        for i in range(3):
            cache.save({**self.KEY, "topology": f"t{i}"}, "s", "d",
                       blob, meta)
            now = time.time() + i  # strict mtime order, fs-resolution-proof
            p = cache.path_for({**self.KEY, "topology": f"t{i}"}, "s", "d")
            os.utime(p, (now, now))
        removed = cache.prune(2)
        assert len(removed) == 1 and "t0" in removed[0]
        assert len(cache.list()) == 2
        monkeypatch.setenv(aot.CACHE_MAX_ENV, "1")
        bounded = aot.default_cache()
        assert bounded.max_artifacts == 1
        bounded.save({**self.KEY, "topology": "t9"}, "s", "d", blob, meta)
        assert len(bounded.list()) == 1  # save() applied the bound

    def test_evict_by_key(self, cache_root):
        cache = self._one(cache_root)
        assert cache.evict(self.KEY, "s0", "d0") is True
        assert cache.list() == []
        assert cache.evict(self.KEY, "s0", "d0") is False

    def test_save_lock_excludes_concurrent_writer(self, cache_root):
        """N cold replicas sharing one cache dir export the SAME key at
        once: a held writer lock makes the losers skip (interleaved
        blob/meta replace pairs would land a torn pair the next load
        sha-evicts), a crashed writer's stale lock is broken."""
        cache = aot.CompileCache(str(cache_root))
        blob, meta, _ = aot.export_stage(
            lambda x: (x * 3.0,), (np.ones((2, 4), np.float32),))
        path = cache.path_for(self.KEY, "s0", "d0")
        os.makedirs(str(cache_root), exist_ok=True)
        open(path + ".lock", "w").close()  # another writer mid-save
        cache.save(self.KEY, "s0", "d0", blob, dict(meta))
        assert not os.path.exists(path)
        assert aot.STATS["exports"] == 0  # skipped, not counted
        # a stale lock (crashed writer) is broken and the save lands
        past = time.time() - 2 * cache._LOCK_STALE_S
        os.utime(path + ".lock", (past, past))
        cache.save(self.KEY, "s0", "d0", blob, dict(meta))
        assert os.path.exists(path)
        assert not os.path.exists(path + ".lock")
        assert cache.load(self.KEY, "s0", "d0") is not None


# ---------------------------------------------------------------------------
# fused-segment + singleton-filter load paths
# ---------------------------------------------------------------------------

class TestPipelineIntegration:
    def test_fused_export_then_hit_with_byte_parity(self, cache_root):
        """Cold run exports, warm run loads — and the artifact-served
        stream is byte-identical to the unfused host reference (the
        fused-vs-host parity contract holds for artifact-loaded
        segments)."""
        p1 = parse_launch(FUSED_LINE)
        p1.run(timeout=30)
        (seg1,) = p1.fused_segments
        assert seg1.stats["aot_exports"] == 1
        assert seg1.stats["aot_hits"] == 0

        p2 = parse_launch(FUSED_LINE)
        p2.run(timeout=30)
        (seg2,) = p2.fused_segments
        assert seg2.stats["aot_hits"] == 1
        assert seg2.stats["aot_exports"] == 0

        p3 = parse_launch(FUSED_LINE, fuse=False)
        p3.run(timeout=30)
        assert pull_bytes(p2) == pull_bytes(p3)

        entries = aot.default_cache().list()
        assert any(e["poly"] for e in entries)

    def test_singleton_filter_backend_export_then_hit(self, cache_root):
        """A lone filter (no fused segment) rides the jax_backend hook:
        the second open of the same model loads the artifact."""
        from nnstreamer_tpu.backends.base import FilterProperties
        from nnstreamer_tpu.backends.jax_backend import JaxBackend

        props = FilterProperties(model="builtin://scaler?factor=2")
        b1 = JaxBackend()
        b1.open(props)
        out = b1.invoke([np.ones((2, 8), np.float32)])
        np.testing.assert_allclose(np.asarray(out[0]), 2.0)
        assert b1.aot_state() == "export"
        b2 = JaxBackend()
        b2.open(FilterProperties(model="builtin://scaler?factor=2"))
        out = b2.invoke([np.ones((4, 8), np.float32)])  # other bucket
        np.testing.assert_allclose(np.asarray(out[0]), 2.0)
        assert b2.aot_state() == "hit"
        # a DIFFERENT model must key differently — never a false hit
        b3 = JaxBackend()
        b3.open(FilterProperties(model="builtin://scaler?factor=5"))
        out = b3.invoke([np.ones((2, 8), np.float32)])
        np.testing.assert_allclose(np.asarray(out[0]), 5.0)
        assert b3.aot_state() == "export"
        for b in (b1, b2, b3):
            b.close()

    def test_guard_memoizes_probe_and_lowers(self, cache_root,
                                             monkeypatch):
        """The artifact guard's compatibility probe runs once per NEW
        signature (never per frame), and the served closure lowers for
        the memory accountant (memory_analysis must not silently degrade
        to param-only under NNS_AOT_CACHE)."""
        from nnstreamer_tpu.aot.export import LoadedArtifact
        from nnstreamer_tpu.backends.base import FilterProperties
        from nnstreamer_tpu.backends.jax_backend import JaxBackend

        calls = []
        real = LoadedArtifact.compatible

        def counting(self, args):
            calls.append(1)
            return real(self, args)
        monkeypatch.setattr(LoadedArtifact, "compatible", counting)
        b = JaxBackend()
        b.open(FilterProperties(model="builtin://scaler?factor=2"))
        for _ in range(4):
            b.invoke([np.ones((2, 8), np.float32)])
        assert sum(calls) == 1  # probed once, memoized thereafter
        b.invoke([np.ones((4, 8), np.float32)])  # new bucket: one more
        assert sum(calls) == 2
        assert b.memory_analysis([np.ones((2, 8), np.float32)]) \
            is not None
        b.close()

    def test_stablehlo_backend_joins_fused_segment(self, cache_root,
                                                   tmp_path):
        """An artifact-loaded stablehlo filter is traceable and fuses;
        parity vs the unfused run holds."""
        from nnstreamer_tpu.backends.stablehlo_backend import (
            export_callable,
        )

        path = str(tmp_path / "quad.jaxexport")
        export_callable(lambda x: x * 4.0,
                        [np.ones((8,), np.float32)], path, poly=False)
        line = (SRC + f"! {ADD}! tensor_filter framework=stablehlo "
                f"model={path} ! tensor_sink name=out max-stored=16")
        fused = parse_launch(line)
        fused.run(timeout=30)
        (seg,) = fused.fused_segments
        assert seg.stats["dispatches"] > 0  # did NOT defuse
        plain = parse_launch(line, fuse=False)
        plain.run(timeout=30)
        assert pull_bytes(fused) == pull_bytes(plain)


# ---------------------------------------------------------------------------
# cache-key correctness under hot swap / canary promote
# ---------------------------------------------------------------------------

class TestHotSwapKeying:
    def _drain_vals(self, out, cap=512):
        # bounded: the source is infinite, so an unbounded drain of a
        # still-live pipeline would race the producer forever
        vals = []
        for _ in range(cap):
            b = out.pull(timeout=0.2)
            if b is None:
                return vals
            vals.append(float(np.asarray(b.tensors[0])[0]))
        return vals

    def test_registry_swap_misses_old_key_never_stale(self, cache_root):
        """A registry:// hot swap MUST land on a new cache key: the old
        version's artifact is evicted at commit and the post-swap stream
        serves the new model (extends the PR 5 staleness regression for
        the artifact plane)."""
        from nnstreamer_tpu.service import ServiceManager, ServiceState

        mgr = ServiceManager(jitter_seed=7)
        try:
            mgr.models.define("aslot", {"1": "builtin://scaler?factor=2"},
                              active="1")
            svc = mgr.register(
                "aot-swap",
                "tensor_src num-buffers=-1 framerate=400 dimensions=4 "
                "types=float32 pattern=counter "
                "! tensor_transform mode=arithmetic option=add:0 "
                "! tensor_filter framework=jax model=registry://aslot "
                "name=f ! tensor_sink name=out max-stored=512").start()
            deadline = time.monotonic() + 20
            (seg,) = svc.pipeline.fused_segments
            while (seg.stats["dispatches"] < 3
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            assert seg.stats["aot_exports"] == 1
            cache = aot.default_cache()
            (old_path,) = [e["path"] for e in cache.list()
                           if e["stage"] != "filter"]
            mgr.models.add_version("aslot", "2",
                                   "builtin://scaler?factor=5")
            mgr.models.swap("aslot", "2")
            assert not os.path.exists(old_path)  # evicted at commit
            out = svc.pipeline.get("out")
            n = out.buffer_count
            while (out.buffer_count < n + 10
                   and time.monotonic() < deadline
                   and svc.state is ServiceState.READY):
                time.sleep(0.02)
            vals = self._drain_vals(out)
            assert vals, "no output after swap"
            seen5 = any(v != 0.0 and v % 5.0 == 0.0 and v % 2.0 != 0.0
                        for v in vals)
            assert seen5, f"swap never took in artifact path: {vals[-10:]}"
            # post-swap rebuild exported under the NEW key
            assert seg.stats["aot_exports"] == 2
        finally:
            mgr.shutdown()

    def test_canary_promote_misses_old_key(self, cache_root):
        """Promote flips backends through commit_model: the rebuilt
        segment re-keys on the candidate's resolved model — the primary's
        artifact is never served for the promoted version."""
        from nnstreamer_tpu.service import ServiceManager

        mgr = ServiceManager(jitter_seed=9)
        try:
            mgr.models.define("cslot2", {"1": "builtin://scaler?factor=2"},
                              active="1")
            svc = mgr.register(
                "aot-canary",
                "tensor_src num-buffers=-1 framerate=400 dimensions=4 "
                "types=float32 pattern=counter "
                "! tensor_transform mode=arithmetic option=add:0 "
                "! tensor_filter framework=jax model=registry://cslot2 "
                "name=f ! tensor_sink name=out max-stored=512").start()
            deadline = time.monotonic() + 20
            (seg,) = svc.pipeline.fused_segments
            while (seg.stats["dispatches"] < 3
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            exports_before = seg.stats["aot_exports"]
            mgr.models.add_version("cslot2", "2",
                                   "builtin://scaler?factor=3")
            mgr.models.canary("cslot2", "2", 0.5)
            router = svc.pipeline.get("f").backend
            while (router.canary_invokes < 2
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            mgr.models.promote_canary("cslot2")
            d0 = seg.stats["dispatches"]
            while (seg.stats["dispatches"] <= d0
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            out = svc.pipeline.get("out")
            time.sleep(0.1)
            vals = self._drain_vals(out)
            tail = [v for v in vals[-5:] if v != 0.0]
            assert tail and all(v % 3.0 == 0.0 for v in tail), \
                f"promoted model not serving: {vals[-10:]}"
            # the promoted generation re-exported under its own key
            assert seg.stats["aot_exports"] > exports_before
        finally:
            mgr.shutdown()


# ---------------------------------------------------------------------------
# replica warmup: shape-poly fabrication + skip flight event
# ---------------------------------------------------------------------------

class TestReplicaWarmup:
    def test_flexible_caps_skip_emits_flight_event(self, tmp_path,
                                                   monkeypatch):
        monkeypatch.delenv(aot.CACHE_ENV, raising=False)
        from nnstreamer_tpu.obs import flight as obs_flight
        from nnstreamer_tpu.service.procreplica import _warmup_self

        _warmup_self("127.0.0.1", 1, "other/tensors,format=flexible")
        events = [e for e in obs_flight.dump(last=64)
                  if e["kind"] == "replica"
                  and e["name"] == "warmup_skipped"]
        assert events, "skip must land in the flight ring, not just a log"
        assert "caps not static" in events[-1]["data"]["reason"]

    def test_artifact_fabricates_warmup_inputs(self, cache_root):
        """With a cached shape-poly artifact, a non-static batch no
        longer forbids warmup: the artifact's in_avals supply batch-1
        shapes."""
        from nnstreamer_tpu.service.procreplica import _aot_warmup_inputs

        pipe = parse_launch(FUSED_LINE)
        pipe.run(timeout=30)  # exports the segment artifact
        inputs = _aot_warmup_inputs(pipe)
        assert inputs is not None
        assert inputs[0].shape == (1,) or inputs[0].shape[0] == 1 \
            or inputs[0].shape == (8,)
        # the fused artifact's input is the (8,)-shaped stream tensor;
        # a symbolic leading dim would have been substituted by 1
        assert inputs[0].dtype == np.float32

    def test_warmup_prefers_head_stage_artifact(self, cache_root):
        """Several artifacts share one topology (multi-segment
        pipeline): fabrication must pick the HEAD stage's avals — the
        wire input matches the head, a downstream segment's shapes would
        fail negotiation — not whichever meta filename hashes first."""
        from nnstreamer_tpu.service.procreplica import _aot_warmup_inputs

        line = ("tensor_src num-buffers=4 dimensions=3:4 types=float32 "
                "! tensor_transform mode=arithmetic option=add:1 name=t1 "
                "! tensor_transform mode=transpose option=1:0 name=t3 "
                "! queue "
                "! tensor_transform mode=arithmetic option=mul:2 name=t4 "
                "! tensor_transform mode=arithmetic option=add:5 name=t5 "
                "! tensor_sink name=s")
        pipe = parse_launch(line)
        pipe.run(timeout=30)
        stages = {m["stage"] for m in aot.default_cache().metas()}
        assert stages == {"t1..t3", "t4..t5"}
        inputs = _aot_warmup_inputs(pipe)
        # dimensions=3:4 wires (4, 3) buffers: head t1..t3 avals are
        # (b, 3); the downstream transposed segment's are (b, 4)
        assert inputs is not None and inputs[0].shape == (1, 3)


# ---------------------------------------------------------------------------
# placement-plan artifact refs + obs/lint surfaces
# ---------------------------------------------------------------------------

class TestSurfaces:
    def test_placement_plan_references_artifacts(self, cache_root):
        from nnstreamer_tpu.runtime.placement import PlacementPlan, Planner

        pipe = parse_launch(FUSED_LINE)
        pipe.run(timeout=30)
        plan = Planner().plan(pipe, artifact=Planner.NO_ARTIFACT)
        assert plan.aot, "plan must reference the exported artifact"
        stage, fname = next(iter(plan.aot.items()))
        assert any(s.stage == stage for s in plan.stages)
        assert os.path.exists(os.path.join(str(cache_root), fname))
        # the refs survive the serialized hand-off (kind=nns-placement)
        back = PlacementPlan.from_dict(
            json.loads(json.dumps(plan.to_dict())))
        assert back.aot == plan.aot

    def test_nnl015_reports_coverage_and_never_gates(self, cache_root):
        from nnstreamer_tpu.analysis import Severity, lint_launch
        from nnstreamer_tpu.analysis.cli import main as lint_main

        pipe = parse_launch(FUSED_LINE)
        pipe.run(timeout=30)
        diags = [d for d in lint_launch(FUSED_LINE) if d.rule == "NNL015"]
        assert len(diags) == 1
        assert diags[0].severity is Severity.INFO
        assert "shape-poly" in diags[0].message
        assert lint_main(["--strict", FUSED_LINE]) == 0

    def test_nnl015_absent_without_cache(self, monkeypatch):
        monkeypatch.delenv(aot.CACHE_ENV, raising=False)
        from nnstreamer_tpu.analysis import lint_launch

        assert not [d for d in lint_launch(FUSED_LINE)
                    if d.rule == "NNL015"]

    def test_nnl008_cross_references_aot_retirement(self):
        from nnstreamer_tpu.analysis import lint_launch

        line = ("tensor_src num-buffers=2 dimensions=8 types=float32 "
                "pattern=counter ! tensor_filter framework=jax "
                "model=builtin://scaler?factor=2 invoke-dynamic=true "
                "! other/tensors,format=flexible ! tensor_filter "
                "framework=jax model=builtin://add?value=1 "
                "! tensor_sink")
        diags = [d for d in lint_launch(line) if d.rule == "NNL008"]
        assert diags, "flexible->jitted filter must still trip NNL008"
        assert "NNS_AOT_CACHE" in diags[0].hint
        assert "docs/aot.md" in diags[0].hint

    def test_snapshot_and_top_section(self, cache_root):
        from nnstreamer_tpu.obs import profile as obs_profile

        pipe = parse_launch(FUSED_LINE)
        pipe.run(timeout=30)
        snap = aot.snapshot()
        assert snap["active"] is True
        assert snap["artifacts"] >= 1
        assert snap["counters"]["exports"] >= 1
        top = obs_profile.render_top({}, [], aot=snap)
        assert "AOT COMPILE CACHE" in top

    def test_prom_counters_and_bytes_gauge(self, cache_root):
        from nnstreamer_tpu.obs import metrics as obs_metrics

        def exports_total(text):
            # process-cumulative counter: earlier tests contribute too,
            # so assert the delta across THIS export
            line = [ln for ln in text.splitlines()
                    if ln.startswith("nns_aot_cache_exports_total")][0]
            return float(line.split()[-1])

        before = exports_total(obs_metrics.render())
        pipe = parse_launch(FUSED_LINE)
        pipe.run(timeout=30)
        text = obs_metrics.render()
        assert exports_total(text) == before + 1
        # the collector refreshes the bytes gauge from disk at scrape
        line = [ln for ln in text.splitlines()
                if ln.startswith("nns_aot_artifact_bytes")][0]
        assert float(line.split()[-1]) > 0
