"""tensor_filter + backends + registry tests (reference analog:
tests/nnstreamer_filter_*/ and filter-conformance suite,
tests/nnstreamer_filter_extensions_common/)."""
import os
import textwrap

import numpy as np
import pytest

from nnstreamer_tpu.backends.base import FilterProperties
from nnstreamer_tpu.backends.custom_easy import register_custom_easy, unregister_custom_easy
from nnstreamer_tpu.core import MessageType, TensorsInfo
from nnstreamer_tpu.core.tensors import TensorSpec
from nnstreamer_tpu.registry.config import reset_config
from nnstreamer_tpu.registry.subplugin import SubpluginKind, get as get_subplugin
from nnstreamer_tpu.runtime.parse import parse_launch


class TestJaxBackendPipelines:
    def test_passthrough(self):
        pipe = parse_launch(
            "tensor_src num-buffers=3 dimensions=4:4 types=float32 pattern=counter "
            "! tensor_filter framework=jax model=builtin://passthrough "
            "! tensor_sink name=out"
        )
        sink = pipe.get("out")
        pipe.play()
        b = sink.pull(timeout=10)
        pipe.wait(timeout=15)
        pipe.stop()
        assert np.allclose(np.asarray(b.tensors[0]), 0.0)
        assert sink.buffer_count == 3

    def test_scaler_values(self):
        pipe = parse_launch(
            "tensor_src num-buffers=2 dimensions=8 types=float32 pattern=ones "
            "! tensor_filter framework=jax model=builtin://scaler?factor=3 name=f "
            "! tensor_sink name=out"
        )
        sink = pipe.get("out")
        pipe.play()
        b = sink.pull(timeout=10)
        pipe.wait(timeout=15)
        pipe.stop()
        assert np.allclose(np.asarray(b.tensors[0]), 3.0)
        # stats recorded
        stats = pipe.get("f").stats.snapshot()
        assert stats["total_invokes"] == 2
        assert stats["avg_dispatch_latency_ms"] > 0

    def test_out_caps_negotiated_from_model(self):
        pipe = parse_launch(
            "tensor_src num-buffers=1 dimensions=10:1 types=float32 pattern=random "
            "! tensor_filter framework=jax model=builtin://argmax "
            "! tensor_sink name=out"
        )
        sink = pipe.get("out")
        pipe.play()
        b = sink.pull(timeout=10)
        pipe.wait(timeout=15)
        pipe.stop()
        # argmax over (1,10) -> (1,) int32
        assert np.asarray(b.tensors[0]).dtype == np.int32
        assert np.asarray(b.tensors[0]).shape == (1,)
        caps = sink.sinkpad.caps
        assert "int32" in str(caps)

    def test_model_file_py(self, tmp_path):
        model = tmp_path / "double.py"
        model.write_text(textwrap.dedent("""
            import jax.numpy as jnp
            def model(x):
                return (x * 2).astype(jnp.float32)
        """))
        pipe = parse_launch(
            f"tensor_src num-buffers=1 dimensions=5 types=float32 pattern=ones "
            f"! tensor_filter framework=auto model={model} ! tensor_sink name=out"
        )
        sink = pipe.get("out")
        pipe.play()
        b = sink.pull(timeout=10)
        pipe.wait(timeout=15)
        pipe.stop()
        assert np.allclose(np.asarray(b.tensors[0]), 2.0)

    def test_input_output_combination(self):
        # two input tensors; model sees only #1; output = [input0, model_out0]
        pipe = parse_launch(
            "tensor_src num-buffers=1 dimensions=2.3 types=float32 pattern=ones "
            "! tensor_filter framework=jax model=builtin://scaler?factor=5 "
            "input-combination=1 output-combination=i0,o0 "
            "! tensor_sink name=out"
        )
        sink = pipe.get("out")
        pipe.play()
        b = sink.pull(timeout=10)
        pipe.wait(timeout=15)
        pipe.stop()
        assert b.num_tensors == 2
        assert np.asarray(b.tensors[0]).shape == (2,)      # passthrough input 0
        assert np.allclose(np.asarray(b.tensors[0]), 1.0)
        assert np.asarray(b.tensors[1]).shape == (3,)      # scaled input 1
        assert np.allclose(np.asarray(b.tensors[1]), 5.0)

    def test_shape_mismatch_errors(self):
        pipe = parse_launch(
            "tensor_src num-buffers=1 dimensions=4 types=float32 "
            "! tensor_filter framework=custom-easy model=fixed_in "
            "! tensor_sink"
        )
        register_custom_easy(
            "fixed_in",
            lambda ins: ins,
            in_info=TensorsInfo.of(TensorSpec((8,), "float32")),
            out_info=TensorsInfo.of(TensorSpec((8,), "float32")),
        )
        try:
            pipe.play()
            msg = pipe.bus.wait_for((MessageType.ERROR,), timeout=5)
            pipe.stop()
            assert msg is not None and "!=" in msg.data["error"]
        finally:
            unregister_custom_easy("fixed_in")

    def test_reload_model(self):
        pipe = parse_launch(
            "appsrc name=in caps=other/tensors,format=static,dimensions=2,types=float32 "
            "! tensor_filter framework=jax model=builtin://scaler?factor=2 name=f "
            "! tensor_sink name=out"
        )
        src, sink, filt = pipe.get("in"), pipe.get("out"), pipe.get("f")
        pipe.play()
        src.push_buffer(np.ones(2, np.float32))
        b1 = sink.pull(timeout=10)
        filt.reload_model("builtin://scaler?factor=10")
        src.push_buffer(np.ones(2, np.float32))
        b2 = sink.pull(timeout=10)
        src.end_of_stream()
        pipe.wait(timeout=10)
        pipe.stop()
        assert np.allclose(np.asarray(b1.tensors[0]), 2.0)
        assert np.allclose(np.asarray(b2.tensors[0]), 10.0)


class TestPropertyBreadth:
    """Reference tensor_filter_common.c property parity additions."""

    def test_invoke_dynamic_flexible_caps(self):
        from nnstreamer_tpu.runtime.parse import parse_launch

        pipe = parse_launch(
            "tensor_src num-buffers=2 dimensions=4 types=float32 "
            "! tensor_filter framework=jax model=builtin://argmax "
            "invoke-dynamic=true name=f ! tensor_sink name=out")
        got = []
        pipe.get("out").connect(got.append)
        pipe.run(timeout=30)
        caps = pipe.get("out").sinkpad.caps
        assert "flexible" in str(caps)
        assert len(got) == 2

    def test_suspend_unloads_and_resumes(self):
        import time as _time

        from nnstreamer_tpu.runtime.parse import parse_launch

        pipe = parse_launch(
            "appsrc name=in caps=other/tensors,format=static,dimensions=4,types=float32 "
            "! tensor_filter framework=jax model=builtin://scaler?factor=2 "
            "suspend=120 name=f ! tensor_sink name=out")
        got = []
        pipe.get("out").connect(got.append)
        pipe.play()
        f = pipe.get("f")
        src = pipe.get("in")
        src.push_buffer(np.ones(4, np.float32))
        deadline = _time.monotonic() + 5
        while not got and _time.monotonic() < deadline:
            _time.sleep(0.01)
        assert got
        # idle past the suspend window: framework unloads
        deadline = _time.monotonic() + 5
        while f.backend is not None and _time.monotonic() < deadline:
            _time.sleep(0.02)
        assert f.backend is None, "framework not suspended while idle"
        # next buffer transparently reopens
        src.push_buffer(np.full(4, 3.0, np.float32))
        deadline = _time.monotonic() + 5
        while len(got) < 2 and _time.monotonic() < deadline:
            _time.sleep(0.01)
        assert len(got) == 2
        assert np.allclose(np.asarray(got[1].tensors[0]), 6.0)
        src.end_of_stream()
        pipe.wait(timeout=10)
        pipe.stop()

    def test_forced_output_dims(self):
        """output-dims/types declare model info for opaque backends."""
        from nnstreamer_tpu.core import TensorsInfo
        from nnstreamer_tpu.elements.filter import TensorFilter

        f = TensorFilter(framework="custom-easy", model="noop",
                         output_dims="4", output_types="float32")
        forced = f._forced_info(f.props["output_dims"], f.props["output_types"])
        assert isinstance(forced, TensorsInfo)
        assert forced.specs[0].shape == (4,)

    def test_config_file_merges_custom(self, tmp_path):
        from nnstreamer_tpu.elements.filter import TensorFilter

        cfg = tmp_path / "f.conf"
        cfg.write_text("# comment\nfactor:5\n")
        f = TensorFilter(framework="jax", model="builtin://scaler",
                         custom="device:0", config_file=str(cfg))
        assert f._custom_with_config_file() == "device:0,factor:5"

    def test_is_updatable_false_refuses_reload(self):
        from nnstreamer_tpu.elements.filter import TensorFilter
        from nnstreamer_tpu.runtime.element import ElementError

        f = TensorFilter(framework="jax", model="builtin://scaler",
                         is_updatable=False)
        with pytest.raises(ElementError):
            f.reload_model("builtin://add")

    def test_readonly_latency_throughput_props(self):
        from nnstreamer_tpu.runtime.parse import parse_launch

        pipe = parse_launch(
            "tensor_src num-buffers=8 dimensions=4 types=float32 "
            "! tensor_filter framework=jax model=builtin://scaler?factor=2 "
            "sync-invoke=true name=f ! tensor_sink name=out")
        pipe.run(timeout=30)
        f = pipe.get("f")
        assert f.get_property("latency") > 0
        assert f.get_property("throughput") > 0

    def test_settable_latency_mode_flag(self):
        """``latency=1`` is a SETTABLE mode flag (reference
        tensor_filter.c:366-510) forcing per-invoke device profiling; the
        getter still reads back the measured value."""
        from nnstreamer_tpu.runtime.parse import parse_launch

        pipe = parse_launch(
            "tensor_src num-buffers=8 dimensions=4 types=float32 "
            "! tensor_filter framework=jax model=builtin://scaler?factor=2 "
            "latency=1 throughput=1 name=f ! tensor_sink name=out")
        pipe.run(timeout=30)
        f = pipe.get("f")
        assert f.props["latency"] == 1
        assert f.get_property("latency") > 0  # measured ms, not the flag
        # every invoke after the first was device-sampled
        assert f.stats.snapshot()["recent_device_latency_ms"] > 0


class TestInvokeStats:
    def test_device_latency_sampled_separately(self):
        """Dispatch time is recorded per invoke; true device-complete
        latency is sampled every Nth invoke (VERDICT r1 #9: latency_report
        must be comparable to the reference's synchronous invoke stats,
        tensor_filter.c:366-510)."""
        from nnstreamer_tpu.runtime.parse import parse_launch

        pipe = parse_launch(
            "tensor_src num-buffers=25 dimensions=8 types=float32 "
            "! tensor_filter framework=jax model=builtin://scaler?factor=2 "
            "name=f latency-sampling=5 ! tensor_sink name=out")
        pipe.run(timeout=30)
        snap = pipe.get("f").stats.snapshot()
        assert snap["total_invokes"] == 25
        assert snap["recent_dispatch_latency_ms"] > 0
        # sampled at invokes 5,10,15,20 (first invoke excluded: compile)
        assert snap["recent_device_latency_ms"] > 0

    def test_sampling_disabled(self):
        from nnstreamer_tpu.runtime.parse import parse_launch

        pipe = parse_launch(
            "tensor_src num-buffers=5 dimensions=8 types=float32 "
            "! tensor_filter framework=jax model=builtin://scaler?factor=2 "
            "name=f latency-sampling=0 ! tensor_sink name=out")
        pipe.run(timeout=30)
        snap = pipe.get("f").stats.snapshot()
        assert snap["recent_device_latency_ms"] == 0.0


class TestCustomEasy:
    def test_register_invoke(self):
        register_custom_easy("halve", lambda ins: [np.asarray(x) / 2 for x in ins])
        try:
            pipe = parse_launch(
                "tensor_src num-buffers=1 dimensions=4 types=float32 pattern=ones "
                "! tensor_filter framework=custom-easy model=halve ! tensor_sink name=out"
            )
            sink = pipe.get("out")
            pipe.play()
            b = sink.pull(timeout=10)
            pipe.wait(timeout=10)
            pipe.stop()
            assert np.allclose(np.asarray(b.tensors[0]), 0.5)
        finally:
            unregister_custom_easy("halve")


class TestPythonBackend:
    def test_filter_class(self, tmp_path):
        model = tmp_path / "pyfilter.py"
        model.write_text(textwrap.dedent("""
            import numpy as np
            class Filter:
                def invoke(self, inputs):
                    return [np.flip(x, axis=-1) for x in inputs]
        """))
        pipe = parse_launch(
            f"tensor_src num-buffers=1 dimensions=3 types=float32 pattern=zeros "
            f"! tensor_filter framework=python model={model} ! tensor_sink name=out"
        )
        sink = pipe.get("out")
        pipe.play()
        b = sink.pull(timeout=10)
        pipe.wait(timeout=10)
        pipe.stop()
        assert b is not None


class TestStableHlo:
    def test_export_roundtrip(self, tmp_path):
        from nnstreamer_tpu.backends.stablehlo_backend import export_callable

        path = str(tmp_path / "model.jaxexport")
        export_callable(lambda x: x * 4.0, [np.ones((2, 2), np.float32)], path)
        pipe = parse_launch(
            f"tensor_src num-buffers=1 dimensions=2:2 types=float32 pattern=ones "
            f"! tensor_filter framework=auto model={path} ! tensor_sink name=out"
        )
        sink = pipe.get("out")
        pipe.play()
        b = sink.pull(timeout=10)
        pipe.wait(timeout=10)
        pipe.stop()
        assert np.allclose(np.asarray(b.tensors[0]), 4.0)
        # model info came from the exported signature
        assert "2:2" in str(sink.sinkpad.caps)


class TestSharedModel:
    def test_shared_backend_instance(self):
        pipe = parse_launch(
            "tensor_src num-buffers=2 dimensions=2 types=float32 pattern=ones name=s ! tee name=t "
            "t. ! queue ! tensor_filter framework=jax model=builtin://scaler?factor=2 "
            "shared-tensor-filter-key=k1 name=f1 ! tensor_sink name=o1 "
            "t. ! queue ! tensor_filter framework=jax model=builtin://scaler?factor=2 "
            "shared-tensor-filter-key=k1 name=f2 ! tensor_sink name=o2"
        )
        pipe.play()
        pipe.wait(timeout=15)
        f1, f2 = pipe.get("f1"), pipe.get("f2")
        assert f1.backend is f2.backend  # one opened model, two elements
        pipe.stop()


class TestConfig:
    def test_priority_and_env_override(self, tmp_path, monkeypatch):
        ini = tmp_path / "conf.ini"
        ini.write_text("[filter]\nframework_priority_py = python\n")
        cfg = reset_config(str(ini))
        try:
            assert cfg.framework_priority("m.py") == ["python"]
            monkeypatch.setenv("NNS_TPU_FILTER_FRAMEWORK_PRIORITY_PY", "jax")
            assert cfg.framework_priority("m.py") == ["jax"]  # env beats ini
        finally:
            reset_config()

    def test_defaults(self):
        cfg = reset_config()
        assert cfg.framework_priority("model.pt") == ["torch"]
        assert cfg.framework_priority("model.jaxexport") == ["stablehlo"]


class TestSubpluginRegistry:
    def test_lookup_and_aliases(self):
        jax_cls = get_subplugin(SubpluginKind.FILTER, "jax")
        assert get_subplugin(SubpluginKind.FILTER, "xla-tpu") is jax_cls

    def test_unknown(self):
        with pytest.raises(KeyError, match="no filter subplugin"):
            get_subplugin(SubpluginKind.FILTER, "tensorrt")


class TestSingleShot:
    def test_invoke(self):
        from nnstreamer_tpu.single import SingleShot

        with SingleShot("jax", "builtin://scaler?factor=2") as s:
            out = s.invoke(np.ones((2, 2), np.float32))
            assert np.allclose(np.asarray(out[0]), 2.0)
            info = s.set_input_info(TensorsInfo.of(TensorSpec((2, 2), "float32")))
            assert info.specs[0].shape == (2, 2)
        assert s.stats.total_invokes == 1

    def test_accelerator_without_a_matching_device_raises(self):
        """An explicit accelerator= request is a placement contract: with
        no device of that platform among the ones jax selected it raises,
        as custom=mesh does — never a quiet run on devices[0]."""
        from nnstreamer_tpu.single import SingleShot

        with pytest.raises(ValueError, match="accelerator=tpu: no tpu "
                                             "devices present"):
            SingleShot("jax", "builtin://scaler?factor=2",
                       accelerator="tpu")
        with SingleShot("jax", "builtin://scaler?factor=2",
                        accelerator="cpu") as s:
            assert s.invoke(np.ones((1, 2), np.float32))


class TestShapeBucketing:
    def test_signature_tracking_and_warning(self, caplog):
        """Flexible streams recompile per shape; the backend surfaces it
        (SURVEY §7 hard part: shape dynamism vs XLA)."""
        import logging

        from nnstreamer_tpu.single import SingleShot

        with SingleShot("jax", "builtin://scaler?factor=2",
                        custom="max_signatures:3") as s:
            with caplog.at_level(logging.WARNING, logger="nnstreamer_tpu"):
                for n in (1, 2, 3, 4):
                    s.invoke(np.zeros((n, 2), np.float32))
            info = s.backend.compile_cache_info()
            assert info["signatures"] == 4
            assert any("distinct input signatures" in r.message
                       for r in caplog.records)
