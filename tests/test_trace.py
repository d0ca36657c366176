"""Tracer subsystem tests (reference analog: GstShark tracer usage per
tools/tracing/README.md; activation via env like GST_TRACERS)."""
import os
import subprocess
import sys

import numpy as np
import pytest

from nnstreamer_tpu.runtime.parse import parse_launch
from nnstreamer_tpu.utils import trace


@pytest.fixture(autouse=True)
def _clean_tracers():
    yield
    trace.uninstall_tracers()


def _run_pipeline():
    pipe = parse_launch(
        "tensor_src num-buffers=5 dimensions=8 types=float32 pattern=ones "
        "! queue ! tensor_transform mode=arithmetic option=mul:2 "
        "! tensor_sink name=out"
    )
    pipe.run(timeout=20)
    return pipe


class TestTracers:
    def test_proctime_and_framerate(self):
        trace.install_tracers(["proctime", "framerate"])
        _run_pipeline()
        res = trace.trace_results()
        proc = res["proctime"]
        # the transform element did measurable per-buffer work
        t_key = next(k for k in proc if "transform" in k)
        assert proc[t_key]["buffers"] == 5
        assert proc[t_key]["total_s"] >= 0
        fr = res["framerate"]
        assert any(v["frames"] == 5 for v in fr.values())

    def test_interlatency_stamps_and_measures(self):
        trace.install_tracers(["interlatency"])
        _run_pipeline()
        res = trace.trace_results()["interlatency"]
        assert res, "no interlatency records"
        # downstream pads observed positive source-to-pad latency
        assert all(v["avg_ms"] >= 0 for v in res.values())
        assert any(v["buffers"] == 5 for v in res.values())

    def test_queuelevel(self):
        trace.install_tracers(["queuelevel"])
        _run_pipeline()
        res = trace.trace_results()["queuelevel"]
        assert any("queue" in k for k in res)

    def test_unknown_tracer_rejected(self):
        with pytest.raises(ValueError, match="unknown tracer"):
            trace.install_tracers(["warpdrive"])

    def test_disabled_means_no_overhead_hook(self):
        assert trace.ACTIVE is False
        _run_pipeline()
        assert trace.trace_results() == {}

    def test_custom_tracer(self):
        seen = []

        class Mine(trace.Tracer):
            NAME = "mine"

            def buffer_flow(self, pad, buf, elapsed_s):
                seen.append(pad.full_name)

            def results(self):
                return {"n": len(seen)}

        trace.install_tracer(Mine())
        _run_pipeline()
        assert trace.trace_results()["mine"]["n"] > 0


class TestDotDump:
    def test_dot_dump_on_play(self, tmp_path, monkeypatch):
        monkeypatch.setenv("NNS_DOT_DIR", str(tmp_path))
        pipe = parse_launch(
            "tensor_src num-buffers=1 dimensions=2 ! tensor_sink name=out")
        pipe.run(timeout=20)
        dots = list(tmp_path.glob("*.dot"))
        assert len(dots) == 1
        text = dots[0].read_text()
        assert "tensor_src" in text and "->" in text


class TestEnvActivation:
    def test_nns_tracers_env(self, tmp_path):
        code = (
            "import sys; sys.path.insert(0, %r)\n"
            "from nnstreamer_tpu.runtime.parse import parse_launch\n"
            "from nnstreamer_tpu.utils import trace\n"
            "pipe = parse_launch('tensor_src num-buffers=2 dimensions=2 "
            "! tensor_sink name=o')\n"
            "pipe.run(timeout=20)\n"
            "res = trace.trace_results()\n"
            "assert 'proctime' in res and 'framerate' in res, res\n"
            "print('ENV_OK')\n"
        ) % os.getcwd()
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120,
            env={"PATH": "/usr/bin:/bin", "HOME": "/tmp",
                 "JAX_PLATFORMS": "cpu",
                 "NNS_TRACERS": "proctime;framerate"},
        )
        assert "ENV_OK" in r.stdout, r.stderr


class TestHwAccel:
    """utils/hw_accel.py: what counts as the TPU, and where a Pallas TPU
    kernel may run. Platform selection itself is jax's."""

    def test_tpu_platform_is_stock_pjrt_only(self):
        from nnstreamer_tpu.utils.hw_accel import TPU_PLATFORMS, is_tpu_platform

        assert TPU_PLATFORMS == ("tpu",)
        assert is_tpu_platform("tpu") and not is_tpu_platform("cpu")

    def test_pallas_interprets_on_cpu_only(self):
        from nnstreamer_tpu.utils.hw_accel import pallas_interpret

        assert pallas_interpret("tpu") is False   # Mosaic on the chip
        assert pallas_interpret("cpu") is True    # the CPU tests
        with pytest.raises(ValueError, match="gpu"):
            pallas_interpret("gpu")               # never quietly interpret


class TestChromeTrace:
    def test_spans_written_and_loadable(self, tmp_path):
        import json

        from nnstreamer_tpu.runtime.parse import parse_launch
        from nnstreamer_tpu.utils import trace

        tracer = trace.ChromeTraceTracer(path=str(tmp_path / "t.json"))
        trace.install_tracer(tracer)
        try:
            pipe = parse_launch(
                "tensor_src num-buffers=5 dimensions=4 types=float32 "
                "! tensor_transform mode=typecast option=float32 name=tt "
                "! tensor_sink name=out")
            pipe.run(timeout=20)
        finally:
            trace.uninstall_tracers()
        path = tracer.save()
        assert path is not None
        events = json.load(open(path))["traceEvents"]
        assert len(events) >= 10  # 5 buffers x 2 downstream hops
        names = {e["name"] for e in events}
        assert "tt" in names and "out" in names
        for e in events:
            assert e["ph"] == "X" and e["dur"] >= 0
