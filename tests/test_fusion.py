"""Device-segment fusion compiler (runtime/fusion.py): planning, byte
parity fused vs fuse=False across representative pipelines, cache
invalidation on caps/hot-swap/restart, defuse fallback, lint wiring."""
import os
import time

import numpy as np
import pytest

from nnstreamer_tpu.analysis import Severity, lint_launch
from nnstreamer_tpu.runtime.fusion import plan_segments
from nnstreamer_tpu.runtime.parse import parse_launch
from nnstreamer_tpu.runtime.pipeline import Pipeline


SRC = ("tensor_src num-buffers=6 dimensions=8 types=float32 "
       "pattern=counter ")
ADD = "tensor_transform mode=arithmetic option=add:1 "
MUL = "tensor_transform mode=arithmetic option=mul:2 "
SCALER = "tensor_filter framework=jax model=builtin://scaler?factor=2 "


def probe_sinks(pipe):
    """Per-sink record streams: buffers as raw bytes, serialized events
    by type (CAPS records the caps string) — the parity suite compares
    these fused vs unfused, per sink (cross-branch interleave is thread
    timing, not semantics)."""
    records = {}
    for el in pipe.sinks:
        seq = records[el.name] = []

        def render(buf, _seq=seq, _el=el):
            _seq.append(("buf", tuple(
                np.ascontiguousarray(t).tobytes()
                for t in buf.as_numpy().tensors)))
            type(_el).render(_el, buf)

        def hse(pad, event, _seq=seq, _el=el):
            caps = event.data.get("caps") if event.data else None
            _seq.append(("event", event.type.name,
                         str(caps) if caps is not None else ""))
            type(_el).handle_sink_event(_el, pad, event)

        el.render = render
        el.handle_sink_event = hse
    return records


def run_probed(line, fuse, timeout=40.0):
    pipe = parse_launch(line, fuse=fuse)
    records = probe_sinks(pipe)
    pipe.run(timeout=timeout)
    return pipe, records


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------

class TestPlanning:
    def test_linear_device_run_becomes_one_segment(self):
        pipe = parse_launch(SRC + f"! {ADD}! {MUL}! {SCALER}! tensor_sink")
        plan = plan_segments(pipe)
        assert len(plan.segments) == 1
        assert len(plan.segments[0]) == 3

    def test_queue_breaks_segments(self):
        pipe = parse_launch(
            SRC + f"! {ADD}! {MUL}! queue ! {ADD}! {MUL}! tensor_sink")
        plan = plan_segments(pipe)
        assert len(plan.segments) == 2
        assert all(len(s) == 2 for s in plan.segments)
        assert "queue boundary" in plan.barriers[
            next(n for n in pipe.elements if n.startswith("queue"))]

    def test_single_device_element_is_not_a_segment(self):
        pipe = parse_launch(SRC + f"! {ADD}! tensor_sink")
        assert plan_segments(pipe).segments == []

    def test_tee_and_if_and_serving_are_barriers(self):
        pipe = parse_launch(
            SRC + "! tee name=t "
            "t. ! queue ! tensor_if compared-value=a-value "
            "compared-value-option=0:0 operator=ge supplied-value=0 "
            "then=passthrough else=skip ! tensor_sink name=a "
            "t. ! queue ! tensor_serving model=builtin://scaler?factor=2 "
            "! tensor_sink name=b")
        plan = plan_segments(pipe)
        reasons = " | ".join(plan.barriers.values())
        assert "tee fan-out" in reasons
        assert "tensor_if dynamic routing" in reasons
        assert "FUSABLE=False" in reasons

    def test_filter_prop_disqualifiers_are_barriers(self):
        for prop, key in (("invoke-dynamic=true", "invoke-dynamic"),
                          ("suspend=50", "suspend"),
                          ("sync-invoke=true", "sync-invoke"),
                          ("latency-report=true", "latency profiling")):
            pipe = parse_launch(
                SRC + f"! {ADD}! {SCALER[:-1]} {prop} ! tensor_sink")
            plan = plan_segments(pipe)
            assert plan.segments == []
            assert any(key in r for r in plan.barriers.values()), (prop, plan)

    def test_pure_device_cycle_is_rejected_not_fused(self):
        """A manually linked ring of fusable device elements must never
        become a segment (a fused tail pushing into its own head would
        recurse unboundedly)."""
        from nnstreamer_tpu.elements.transform import TensorTransform

        a = TensorTransform(name="a", mode="arithmetic", option="add:1")
        b = TensorTransform(name="b", mode="arithmetic", option="mul:2")
        pipe = Pipeline().add(a, b)
        a.link(b)
        b.link(a)
        plan = plan_segments(pipe)
        assert plan.segments == []
        assert any("cycle" in r for r in plan.barriers.values())

    def test_fuse_false_and_env_escape_hatch(self, monkeypatch):
        pipe = parse_launch(SRC + f"! {ADD}! {MUL}! tensor_sink", fuse=False)
        pipe.run(timeout=30)
        assert pipe.fused_segments == []
        monkeypatch.setenv("NNS_NO_FUSE", "1")
        assert Pipeline().fuse is False
        monkeypatch.delenv("NNS_NO_FUSE")
        assert Pipeline().fuse is True


# ---------------------------------------------------------------------------
# byte-parity suite: fused output must be IDENTICAL to fuse=False
# ---------------------------------------------------------------------------

PARITY_LINES = {
    "transform_chain_3":
        SRC + f"! {ADD}! {MUL}! tensor_transform mode=typecast "
        "option=float32 ! tensor_sink name=out",
    "device_chain_8":
        SRC + "! " + "! ".join([ADD] * 4 + [MUL] * 4) + "! tensor_sink name=out",
    "filter_chain":
        SRC + f"! {SCALER}! tensor_filter framework=jax "
        "model=builtin://add?value=3 ! tensor_sink name=out",
    "mixed_transform_filter":
        SRC + f"! {ADD}! {SCALER}! {MUL}! tensor_sink name=out",
    "arith_chain_options":
        SRC + "! tensor_transform mode=arithmetic "
        "option=typecast:float32,add:-0.5,mul:2 ! tensor_transform "
        "mode=clamp option=0:100 ! tensor_sink name=out",
    "queue_boundary":
        SRC + f"! {ADD}! {MUL}! queue ! {MUL}! {ADD}! tensor_sink name=out",
    "tee_two_fused_branches":
        SRC + "! tee name=t "
        f"t. ! queue ! {ADD}! {MUL}! tensor_sink name=a "
        f"t. ! queue ! {MUL}! {MUL}! tensor_sink name=b",
    "tensor_if_between_segments":
        SRC + f"! {ADD}! {MUL}! tensor_if compared-value=a-value "
        "compared-value-option=0:0 operator=gt supplied-value=4 "
        f"then=passthrough else=skip ! {ADD}! {MUL}! tensor_sink name=out",
    "tensor_if_branch_pads":
        SRC + f"! {ADD}! tensor_if name=tif compared-value=a-value "
        "compared-value-option=0:0 operator=lt supplied-value=4 "
        "then=passthrough else=passthrough "
        f"tif.src_0 ! queue ! {ADD}! {MUL}! tensor_sink name=then_out "
        f"tif.src_1 ! queue ! {MUL}! {ADD}! tensor_sink name=else_out",
    "mux_fan_in":
        "tensor_mux name=m sync-mode=slowest "
        f"! {ADD}! {MUL}! tensor_sink name=out "
        "tensor_src num-buffers=4 dimensions=2 types=float32 "
        "pattern=counter ! m.sink_0 "
        "tensor_src num-buffers=4 dimensions=3 types=float32 "
        "pattern=counter ! m.sink_1",
    "demux_fan_out":
        "tensor_src num-buffers=4 dimensions=2.3.4 types=float32 "
        f"pattern=counter ! {ADD}! tensor_demux name=d "
        f"d.src_0 ! queue ! {ADD}! {MUL}! tensor_sink name=a "
        f"d.src_1 ! queue ! {MUL}! {MUL}! tensor_sink name=b",
    "apply_indices_multi_tensor":
        "tensor_src num-buffers=5 dimensions=4.4 types=float32 "
        "pattern=counter ! tensor_transform mode=arithmetic "
        "option=add:1 apply=0 ! tensor_transform mode=arithmetic "
        "option=mul:3 apply=1 ! tensor_sink name=out",
    "combinations_passthrough":
        "tensor_src num-buffers=5 dimensions=4.4 types=float32 "
        "pattern=counter ! tensor_filter framework=jax "
        "model=builtin://scaler?factor=2 input-combination=0 "
        f"output-combination=i1,o0 ! {ADD}! tensor_sink name=out",
    "capsfilter_mid_chain":
        SRC + "! tensor_transform mode=typecast option=float32 "
        f"! other/tensors ! {ADD}! tensor_sink name=out",
    "flexible_stream_chain":
        "tensor_src num-buffers=5 dimensions=8 types=float32 "
        "pattern=counter ! tensor_filter framework=jax "
        "model=builtin://scaler?factor=2 invoke-dynamic=true "
        f"! {ADD}! {MUL}! tensor_sink name=out",
    "sparse_host_sandwich":
        SRC + f"! {ADD}! {MUL}! tensor_sparse_enc ! tensor_sparse_dec "
        f"! {MUL}! {ADD}! tensor_sink name=out",
    "shared_backend_key":
        SRC + "! tensor_filter framework=jax "
        "model=builtin://scaler?factor=2 shared-tensor-filter-key=fkey "
        "! tensor_filter framework=jax "
        "model=builtin://scaler?factor=2 shared-tensor-filter-key=fkey "
        "! tensor_sink name=out",
    "device_born_stream":
        "tensor_src device=true num-buffers=5 dimensions=8 "
        f"types=float32 pattern=counter ! {ADD}! {MUL}! {SCALER}"
        "! tensor_sink name=out",
}


@pytest.mark.parametrize("name", sorted(PARITY_LINES))
def test_fusion_byte_parity(name):
    """Fused output must be byte-identical to fuse=False, with identical
    per-sink event sequences and EOS ordering."""
    line = PARITY_LINES[name]
    fused_pipe, fused = run_probed(line, fuse=True)
    plain_pipe, plain = run_probed(line, fuse=False)
    assert plain_pipe.fused_segments == []
    assert fused.keys() == plain.keys()
    for sink in fused:
        assert fused[sink] == plain[sink], f"{name}: sink {sink} diverged"
        # the stream actually flowed and terminated
        kinds = [r[0] for r in fused[sink]]
        assert kinds.count("buf") > 0 or name == "tensor_if_branch_pads"
        assert ("event", "EOS", "") == fused[sink][-1]


def test_parity_suite_actually_fuses():
    """Guard against the suite silently testing nothing: the representative
    pipelines must install fused segments (where one is planned)."""
    fused_pipe, _ = run_probed(PARITY_LINES["device_chain_8"], fuse=True)
    (seg,) = fused_pipe.fused_segments
    assert seg.stats["elements"] == 8
    assert seg.stats["dispatches"] == 6
    assert seg.stats["retraces"] == 1  # one composed trace, six dispatches
    # fused pseudo-element stats reach the health-snapshot surface
    assert any(k.startswith("fused:") for k in fused_pipe.element_stats())


# ---------------------------------------------------------------------------
# runtime fallback + donation
# ---------------------------------------------------------------------------

class TestRuntimeFallback:
    def test_pinned_backend_defuses_gracefully(self):
        """A device-pinned backend can't inline into a composed jit: the
        segment defuses at resolve time and the per-element path serves
        every buffer (byte-identical, no errors)."""
        line = (SRC + f"! {ADD}! tensor_filter framework=jax "
                "model=builtin://scaler?factor=2 custom=device:0 "
                "! tensor_sink name=out")
        fused_pipe, fused = run_probed(line, fuse=True)
        _, plain = run_probed(line, fuse=False)
        assert fused == plain
        (seg,) = fused_pipe.fused_segments
        assert seg.stats["defused"] == 1
        assert seg.stats["dispatches"] == 0

    def test_donation_enabled_only_behind_fresh_device_producer(self):
        # an unfusable profiling filter feeds a fused transform pair: its
        # outputs are fresh single-owner device arrays -> donation on
        line = (SRC + f"! {SCALER[:-1]} latency-report=true ! {ADD}! {MUL}"
                "! tensor_sink name=out")
        fused_pipe, fused = run_probed(line, fuse=True)
        _, plain = run_probed(line, fuse=False)
        assert fused == plain
        (seg,) = fused_pipe.fused_segments
        assert seg._donate is True
        # tee-fed segments must NOT donate (buffers shared across branches)
        pipe2, _ = run_probed(PARITY_LINES["tee_two_fused_branches"],
                              fuse=True)
        assert all(s._donate is False for s in pipe2.fused_segments)

    def test_donation_blocked_by_transitive_aliasing(self):
        """jit output-aliasing pierces one producer: output-combination
        i<N> passthrough re-emits the producer's INPUT arrays, which a
        tee further upstream still shares — the transitive safety walk
        must refuse donation even though the direct producer looks like
        a fresh device element."""
        line = (SRC + "! tee name=t "
                "t. ! queue ! tensor_filter framework=jax "
                "model=builtin://scaler?factor=2 input-combination=0 "
                "output-combination=i0 latency-report=true "
                f"! {ADD}! {MUL}! tensor_sink name=a "
                "t. ! queue ! tensor_sink name=b")
        fused_pipe, fused = run_probed(line, fuse=True)
        _, plain = run_probed(line, fuse=False)
        assert fused == plain
        (seg,) = fused_pipe.fused_segments
        assert seg._donate is False

    def test_canary_router_defuses_and_promote_refuses(self):
        """A canary router must NOT be fused around: the segment defuses
        for the canary window (so the canary actually receives its
        traffic share) and re-fuses after promote."""
        from nnstreamer_tpu.service import ServiceManager, ServiceState

        mgr = ServiceManager(jitter_seed=5)
        try:
            mgr.models.define(
                "cslot", {"1": "builtin://scaler?factor=2"}, active="1")
            svc = mgr.register(
                "canary-fused",
                "tensor_src num-buffers=-1 framerate=400 dimensions=4 "
                "types=float32 pattern=counter "
                "! tensor_transform mode=arithmetic option=add:0 "
                "! tensor_filter framework=jax model=registry://cslot "
                "name=f ! tensor_sink name=out max-stored=64").start()
            deadline = time.monotonic() + 20
            while (svc.state is not ServiceState.READY
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            (seg,) = svc.pipeline.fused_segments
            while seg.stats["dispatches"] < 3 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert seg.stats["dispatches"] >= 3  # fused pre-canary
            mgr.models.add_version("cslot", "2",
                                   "builtin://scaler?factor=2")
            mgr.models.canary("cslot", "2", 0.5)
            router = svc.pipeline.get("f").backend
            while (router.canary_invokes < 3
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            # the canary received live traffic => the segment defused
            assert router.canary_invokes >= 3
            assert seg.stats["defused"] >= 1
            mgr.models.promote_canary("cslot")
            d0 = seg.stats["dispatches"]
            while (seg.stats["dispatches"] <= d0
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            assert seg.stats["dispatches"] > d0  # re-fused after promote
        finally:
            mgr.shutdown()


# ---------------------------------------------------------------------------
# cache invalidation: caps, hot swap, restart
# ---------------------------------------------------------------------------

class TestInvalidation:
    def test_commit_model_invalidates_mid_stream(self):
        """A hot swap through filter.commit_model must retrace the fused
        segment: outputs flip from factor 2 to factor 3, interleaving
        only at the flip point."""
        pipe = parse_launch(
            "tensor_src num-buffers=-1 framerate=300 dimensions=4 "
            f"types=float32 pattern=counter ! {ADD}! tensor_filter "
            "framework=jax model=builtin://scaler?factor=2 name=f "
            "! tensor_sink name=out max-stored=512")
        f = pipe.get("f")
        out = pipe.get("out")
        pipe.play()
        try:
            deadline = time.monotonic() + 10
            while out.buffer_count < 5 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert out.buffer_count >= 5
            (seg,) = pipe.fused_segments
            assert seg.stats["dispatches"] >= 5
            prepared = f.prepare_model("builtin://scaler?factor=3")
            old = f.commit_model(prepared, "builtin://scaler?factor=3")
            f.release_prepared(old)
            n_at_swap = out.buffer_count
            while (out.buffer_count < n_at_swap + 5
                   and time.monotonic() < deadline):
                time.sleep(0.01)
        finally:
            pipe.stop()
        vals = []
        while True:
            b = out.pull(timeout=0.2)
            if b is None:
                break
            v = np.asarray(b.tensors[0])
            i = v[0] / v[0] * 0 + (v[0])  # first component
            vals.append(float(i))
        # every output is (counter+1)*2 or (counter+1)*3; the *3 regime
        # appears (the swap took) and once it starts it never reverts
        factors = []
        for k, v in enumerate(vals):
            expect2, expect3 = (k + 1) * 2.0, (k + 1) * 3.0
            assert v in (expect2, expect3), (k, v)
            factors.append(2 if v == expect2 else 3)
        assert 3 in factors
        first3 = factors.index(3)
        assert all(x == 3 for x in factors[first3:])
        assert seg.stats["retraces"] >= 2  # pre-swap trace + post-swap trace

    def test_caps_renegotiation_invalidates(self):
        """Replaying a pipeline re-announces caps; the fresh run must
        re-resolve (no stale callable across play/stop/play)."""
        pipe = parse_launch(SRC + f"! {ADD}! {MUL}! tensor_sink name=out")
        pipe.run(timeout=30)
        (seg1,) = pipe.fused_segments
        n1 = seg1.stats["dispatches"]
        assert n1 == 6
        pipe.run(timeout=30)  # replay
        (seg2,) = pipe.fused_segments
        assert seg2 is not seg1  # fresh plan per play()
        assert seg2.stats["dispatches"] == 6
        assert pipe.get("out").buffer_count >= 6

    def _crash_restart_swap(self, mgr, slot):
        """Shared scenario for the staleness regressions: tensor_fault
        crash → supervised restart → registry:// hot swap mid-stream.
        Returns (post-restart fused segment, drained first-component
        values) — the caller asserts its plane's staleness contract."""
        from nnstreamer_tpu.service import RestartPolicy, ServiceState

        mgr.models.define(
            slot, {"1": "builtin://scaler?factor=2"}, active="1")
        svc = mgr.register(
            f"fused-crash-swap-{slot}",
            "tensor_src num-buffers=200 framerate=400 dimensions=4 "
            "types=float32 pattern=counter "
            "! tensor_transform mode=arithmetic option=add:0 "
            f"! tensor_filter framework=jax model=registry://{slot} "
            "name=f "
            "! tensor_fault name=flt crash-at-buffer=12 "
            "! tensor_sink name=out max-stored=512",
            restart=RestartPolicy(mode="on-failure",
                                  backoff_base_s=0.05, jitter=0.0))
        svc.start()
        # wait for the crash + restart to complete (restarts == 1)
        deadline = time.monotonic() + 20
        while (svc.supervisor.restarts < 1
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert svc.supervisor.restarts == 1
        # the restarted run serves through a FRESH fused segment:
        # wait until it actually dispatched post-restart traffic
        seg = None
        while time.monotonic() < deadline:
            segs = svc.pipeline.fused_segments
            if segs and segs[0].stats["dispatches"] > 0:
                seg = segs[0]
                break
            time.sleep(0.02)
        assert seg is not None, "restarted run never fused/dispatched"
        out = svc.pipeline.get("out")
        # now hot-swap the registry slot mid-stream
        mgr.models.add_version(slot, "2", "builtin://scaler?factor=5")
        mgr.models.swap(slot, "2")
        n_at_swap = out.buffer_count
        while (out.buffer_count < n_at_swap + 10
               and time.monotonic() < deadline
               and svc.state is ServiceState.READY):
            time.sleep(0.02)
        vals = []
        for _ in range(512):  # bounded: the pipeline may still be live
            b = out.pull(timeout=0.2)
            if b is None:
                break
            vals.append(float(np.asarray(b.tensors[0])[0]))
        return seg, vals

    @staticmethod
    def _assert_swap_took(vals):
        # every value is counter*2 (pre-swap) or counter*5 (post);
        # a stale fused callable would keep emitting *2 forever
        assert vals, "no output after restart+swap"
        seen5 = False
        for v in vals:
            assert v % 2.0 == 0.0 or v % 5.0 == 0.0
            if v != 0.0 and v % 5.0 == 0.0 and v % 2.0 != 0.0:
                seen5 = True
        assert seen5, f"swap never took effect in fused path: {vals[-10:]}"

    def test_supervised_restart_and_registry_swap_not_stale(self):
        """Satellite regression: a tensor_fault crash triggers a
        supervised restart, then a registry:// hot swap — neither may
        serve a stale fused callable (values track the ACTIVE model)."""
        from nnstreamer_tpu.service import ServiceManager

        mgr = ServiceManager(jitter_seed=3)
        try:
            _seg, vals = self._crash_restart_swap(mgr, "fmodel")
            self._assert_swap_took(vals)
        finally:
            mgr.shutdown()

    def test_restart_and_swap_not_stale_with_aot_artifacts(
            self, tmp_path, monkeypatch):
        """The same staleness regression on the ARTIFACT plane: with the
        AOT compile cache active, the supervised restart loads the
        exported artifact (hit, no recompile) and the hot swap re-keys —
        the old version's compiled program is evicted at commit and the
        stream still tracks the active model (never a stale artifact)."""
        from nnstreamer_tpu import aot
        from nnstreamer_tpu.service import ServiceManager

        monkeypatch.setenv(aot.CACHE_ENV, str(tmp_path / "aot"))
        aot.reset_stats()
        mgr = ServiceManager(jitter_seed=3)
        try:
            seg, vals = self._crash_restart_swap(mgr, "fmodel2")
            self._assert_swap_took(vals)
            # restart served through the cache; the swap re-exported
            # under the new resolved-model digest and evicted the old
            assert seg.stats["aot_hits"] >= 1, seg.stats
            assert seg.stats["aot_exports"] >= 1, seg.stats
            assert aot.STATS["evictions"] >= 1
        finally:
            mgr.shutdown()


# ---------------------------------------------------------------------------
# QoS throttle gate on the fused path
# ---------------------------------------------------------------------------

def test_throttle_gate_drops_on_fused_path():
    pipe = parse_launch(
        "tensor_src num-buffers=30 framerate=300 dimensions=4 "
        f"types=float32 pattern=counter ! {ADD}! tensor_filter "
        "framework=jax model=builtin://scaler?factor=2 name=f "
        "! tensor_sink name=out max-stored=64")
    f = pipe.get("f")
    f._throttle_delay_s = 0.05  # as a tensor_rate QoS event would set
    pipe.run(timeout=30)
    out = pipe.get("out")
    (seg,) = pipe.fused_segments
    assert seg.stats["dispatches"] > 0
    # 30 frames at ~300fps against a 20fps throttle: most frames drop
    assert out.buffer_count < 30
    assert out.buffer_count >= 1


# ---------------------------------------------------------------------------
# lint wiring (NNL013 plan report, NNL010 barrier naming)
# ---------------------------------------------------------------------------

class TestLintWiring:
    def test_nnl013_reports_plan_and_never_gates(self, capsys):
        from nnstreamer_tpu.analysis.cli import main as lint_main

        line = SRC + f"! {ADD}! {MUL}! tensor_sink"
        diags = lint_launch(line)
        infos = [d for d in diags if d.rule == "NNL013"]
        assert len(infos) == 1
        assert infos[0].severity is Severity.INFO
        assert "one XLA dispatch" in infos[0].message
        # info findings do not gate, even under --strict
        assert lint_main(["--strict", line]) == 0
        capsys.readouterr()

    def test_nnl013_silent_when_fusion_disabled(self):
        from nnstreamer_tpu.analysis import lint_pipeline

        line = SRC + f"! {ADD}! {MUL}! tensor_sink"
        pipe = parse_launch(line, fuse=False)
        assert not [d for d in lint_pipeline(pipe) if d.rule == "NNL013"]
        pipe_on = parse_launch(line)
        assert [d for d in lint_pipeline(pipe_on) if d.rule == "NNL013"]

    def test_nnl010_names_the_fusion_barrier(self):
        diags = lint_launch(
            SRC + f"! {ADD}! {MUL}! tensor_sparse_enc ! tensor_sparse_dec "
            f"! {MUL}! tensor_sink")
        msgs = [d.message for d in diags if d.rule == "NNL010"]
        assert msgs and all("fusion barrier:" in m for m in msgs)
