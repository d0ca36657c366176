"""First-class JAX/XLA filter backend (L4).

This plays the role of the reference's *entire* backend family
(ext/nnstreamer/tensor_filter/ — tflite/TF/torch/TensorRT/EdgeTPU/... each
wrapping another runtime): here the pipeline's execution engine *is* XLA.
Models are jax-traceable callables; each distinct input signature is jit
compiled once and cached (shape-bucketed compile cache — the redesign of the
reference's per-frame dynamic dispatch), inputs are async ``device_put``, and
outputs remain device-resident jax Arrays so downstream jitted stages never
bounce through host memory (the reference's per-frame map/copy cost,
tensor_filter.c:702-816, is the overhead we delete).

Model sources accepted by the ``model`` property:
  * ``builtin://<name>[?k=v...]`` — deterministic fake models mirroring the
    reference's test fixtures (tests/nnstreamer_example/custom_example_*):
    passthrough, scaler (factor=), add (value=), average, argmax, matmul.
  * ``<path>.py`` — a python file defining ``model(*tensors)`` (jax-traceable)
    and optionally ``IN_INFO``/``OUT_INFO`` (TensorsInfo) declarations.
  * ``<module>:<attr>`` — import path to a callable.
A callable may also be handed directly via ``set_model_callable`` (used by
the model zoo in ``nnstreamer_tpu.models``).
"""
from __future__ import annotations

import importlib
import os
import threading
import urllib.parse
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..analysis import sanitizer as _san
from ..core import DataType, TensorsInfo
from ..core.tensors import TensorSpec
from ..registry.config import get_config
from ..utils.log import logger
from .base import (
    Accelerator,
    BackendEvent,
    FilterBackend,
    FilterProperties,
    register_backend,
)


def _apply_serve_knobs(entry, custom: dict, model: str):
    """``custom=serve_dtype:bfloat16,cache_len:640`` on a module:attr
    entry: rebuild the (dataclass) entry with the serving-efficiency
    fields (models/lm_serving.py — bf16 weights+KV cache, right-sized
    cache). Mirrors tensor_generate's serve-dtype/cache-len launch
    props on the whole-sequence tensor_filter surface."""
    sd = custom.get("serve_dtype")
    cl = custom.get("cache_len")
    if not sd and not cl:
        return entry
    import dataclasses

    kw = {}
    if sd:
        kw["serve_dtype"] = sd
    if cl:
        try:
            kw["cache_len"] = int(cl)
        except ValueError:
            raise ValueError(f"custom=cache_len:{cl!r} is not an integer")
        if kw["cache_len"] < 0:
            raise ValueError(f"custom=cache_len:{cl} must be >= 0")
    fields = ({f.name for f in dataclasses.fields(entry)}
              if dataclasses.is_dataclass(entry)
              and not isinstance(entry, type) else set())
    if not fields >= kw.keys():
        raise ValueError(
            f"custom serve_dtype/cache_len need a dataclass model entry "
            f"with those fields; {model} is {type(entry).__name__}")
    return dataclasses.replace(entry, **kw)



def _builtin_models() -> Dict[str, Callable[[dict], Callable]]:
    import jax.numpy as jnp

    def passthrough(_):
        return lambda *xs: xs

    def scaler(params):
        f = float(params.get("factor", 2.0))
        return lambda *xs: tuple(x * f for x in xs)

    def add(params):
        v = float(params.get("value", 1.0))
        return lambda *xs: tuple(x + v for x in xs)

    def average(_):
        # reference custom_example_average: mean over all non-batch axes
        return lambda *xs: tuple(
            jnp.mean(x, axis=tuple(range(1, x.ndim)), keepdims=True) for x in xs
        )

    def argmax(_):
        return lambda *xs: tuple(
            jnp.argmax(x, axis=-1).astype(jnp.int32) for x in xs
        )

    def matmul(params):
        n = int(params.get("n", 64))
        import jax
        w = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.float32)
        return lambda x: (x @ w,)

    def mlp(params):
        # a model with a KNOWN heavy compile (threefry weight
        # initialization folds at XLA compile time: seconds of compile
        # for a few-KB StableHLO module) — the compile-bound stand-in
        # for a restart against the AOT cache (docs/aot.md; the
        # contract is tests/test_aot.py): cold pays the full
        # trace+compile, a warm NNS_AOT_CACHE restart loads the
        # artifact. Deterministic: weights derive from fixed PRNG keys.
        import jax

        n = int(params.get("n", 256))
        layers = int(params.get("layers", 12))

        def one(x):
            h = x.reshape(x.shape[0], -1).astype(jnp.float32)
            w_in = jax.random.normal(
                jax.random.PRNGKey(layers + 1), (h.shape[1], n),
                jnp.float32)
            h = jnp.tanh(h @ (w_in * 0.1))
            for i in range(layers):
                w = jax.random.normal(
                    jax.random.PRNGKey(i), (n, n), jnp.float32)
                h = jnp.tanh(h @ (w * 0.05))
            w_out = jax.random.normal(
                jax.random.PRNGKey(layers + 2), (n, 1), jnp.float32)
            return h @ w_out

        return lambda *xs: tuple(one(x) for x in xs)

    def sleeper(params):
        # a model with a KNOWN fixed service time (host callback sleeps
        # inside the jitted computation, so it costs per INVOKE, not per
        # trace): the deterministic capacity limiter the autoscaler
        # load-ramp chaos leg saturates — ms of real work per
        # request without burning CPU (tools/chaos.py load-ramp)
        import time as _time

        import jax

        ms = float(params.get("ms", 5.0))
        f = float(params.get("factor", 1.0))

        def one(x):
            def host(v):
                _time.sleep(ms / 1e3)
                return v

            y = jax.pure_callback(
                host, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            return y * jnp.asarray(f, x.dtype)

        return lambda *xs: tuple(one(x) for x in xs)

    return {
        "passthrough": passthrough,
        "scaler": scaler,
        "add": add,
        "average": average,
        "argmax": argmax,
        "matmul": matmul,
        "mlp": mlp,
        "sleeper": sleeper,
    }


def _as_tuple(out) -> tuple:
    if isinstance(out, (list, tuple)):
        return tuple(out)
    return (out,)


def parse_mesh_spec(spec: str, devices):
    """Parse a ``mesh:`` spec string into a `jax.sharding.Mesh` over
    ``devices`` — shared by the filter backend (``custom=mesh:...``) and
    the streaming generator element (``tensor_generate mesh=...``).

    Accepted: ``dp=<N>`` | ``auto``/``all`` (dp over every device) |
    ``<D>x<T>`` (2-D dp×tp). Raises ValueError with an actionable message
    on anything else or when the device count is insufficient.
    """
    from jax.sharding import Mesh

    spec = spec.strip().lower()
    n: Optional[int] = None
    tp = 1
    if spec in ("auto", "all", "dp=all", "dp=auto"):
        n = len(devices)
    elif spec.startswith("dp="):
        try:
            n = int(spec[3:])
        except ValueError:
            pass
    elif "x" in spec:  # mesh:DxT — 2-D dp×tp for shard-aware entries
        try:
            d_s, t_s = spec.split("x", 1)
            n, tp = int(d_s), int(t_s)
        except ValueError:
            n = None
    if n is None or tp < 1:
        raise ValueError(
            f"mesh spec {spec!r} — expected 'dp=<N>', 'auto', or "
            "'<D>x<T>' (dp×tp)")
    total = n * tp
    if not 1 <= total <= len(devices):
        raise ValueError(
            f"mesh spec {spec} needs {total} devices, out of range "
            f"(1..{len(devices)} local devices)")
    if tp == 1:
        return Mesh(np.asarray(devices[:total]), ("dp",))
    return Mesh(np.asarray(devices[:total]).reshape(n, tp), ("dp", "tp"))


@register_backend
class JaxBackend(FilterBackend):
    NAME = "jax"
    ALIASES = ("xla", "xla-tpu", "jax-tpu", "jax-cpu")
    ACCELERATORS = (Accelerator.AUTO, Accelerator.TPU, Accelerator.CPU, Accelerator.GPU)
    REENTRANT = True  # jitted executables are safe to call concurrently

    def __init__(self):
        super().__init__()
        self._fn: Optional[Callable] = None
        self._in_info: Optional[TensorsInfo] = None
        self._out_info: Optional[TensorsInfo] = None
        self._jit: Optional[Callable] = None
        self._device = None
        self._signatures: set = set()  # (shape, dtype) tuples seen
        self._max_signatures = 32
        self._sig_warned = False
        self._mesh = None  # custom=mesh:... — in-pipeline sharded invoke
        self._batch_sharding = None
        self._mesh_warned = False
        # AOT compile cache (nnstreamer_tpu/aot): "hit" | "export" when
        # this backend serves through a cached/exported artifact, None on
        # the plain-jit path (cache off, mesh mode, export refused)
        self._aot_state: Optional[str] = None
        # double-buffered host→device staging for the PINNED path only
        # (transport/staging.py); the default-device fast path never
        # pays an explicit put and never builds one
        self._stager = None

    # -- open/close ---------------------------------------------------------
    def open(self, props: FilterProperties) -> None:
        super().open(props)
        import jax

        self._select_device(props)
        # mesh BEFORE model load: shard-aware entries (make_sharded) need
        # the mesh at build time to place their params
        mesh_spec = props.custom_dict().get("mesh")
        if mesh_spec is not None:
            if props.custom_dict().get("device") is not None:
                # pinning must stay pinning (_select_device) — a mesh built
                # from devices[0:n] would silently override the pin
                raise ValueError(
                    "custom=device:N and custom=mesh:... are mutually "
                    "exclusive (a mesh shards over devices[0..N-1]; pin "
                    "stages OR shard one stage, not both)")
            self._setup_mesh(mesh_spec)
        # cheap property validation before the (possibly expensive) model
        # build — a bad knob must not cost a full param init first
        max_sig = props.custom_dict().get("max_signatures", "32")
        try:
            self._max_signatures = int(max_sig)
        except ValueError:
            raise ValueError(
                f"custom=max_signatures:{max_sig!r} is not an integer")
        model = props.model
        if self._fn is None:  # may be preset via set_model_callable
            self._fn = self._load_model(model, props)
        logger.info("jax backend opened model=%s device=%s mesh=%s",
                    model, self._device, self._mesh)

    def _select_device(self, props: FilterProperties) -> None:
        import jax

        devices = jax.devices()
        # True ONLY for the fully-automatic choice: host inputs then skip
        # the explicit device_put and the jit call's C++ argument
        # conversion places them on jax's configured default (measured
        # 65us vs 6.5us per invoke on passthrough). Any EXPLICIT placement
        # — custom=device:N (even 0) or an accelerator/platform request —
        # keeps the exact device_put: jax_default_device may point
        # elsewhere, and pinning must stay pinning.
        self._device_is_default = False
        # explicit stage placement: custom=device:N pins this filter to chip
        # N — consecutive pinned stages + queues = pipeline parallelism
        # (each stage's compute and HBM live on its own chip; inter-stage
        # buffers move device-to-device, never through host)
        idx = props.custom_dict().get("device")
        if idx is not None:
            try:
                i = int(idx)
            except ValueError:
                raise ValueError(
                    f"custom=device:{idx!r} is not a device index "
                    f"(expected 0..{len(devices) - 1})"
                )
            if not 0 <= i < len(devices):
                raise ValueError(
                    f"custom=device:{i} out of range ({len(devices)} devices)"
                )
            self._device = devices[i]
            return
        accel = props.accelerator
        want = get_config().get("jax", "default_device", "auto")
        if accel is not Accelerator.AUTO:
            want = accel.value
        if want in ("auto", ""):
            self._device = devices[0]
            self._device_is_default = True
            return
        matching = [d for d in devices if d.platform.startswith(want)]
        if not matching:
            raise ValueError(
                f"accelerator={want}: no {want} devices present (have "
                f"{sorted({d.platform for d in devices})})")
        self._device = matching[0]

    @property
    def device(self):
        """The chip this backend instance is pinned to."""
        return self._device

    @property
    def mesh(self):
        """The device mesh this backend shards over (None = single-device)."""
        return self._mesh

    @property
    def model_callable(self) -> Optional[Callable]:
        """The loaded jax-traceable model callable (None before open).
        The serving layer (elements/serving.py) jits this itself so its
        compile-count hook sees every trace; host-native programs
        (``host_native`` attr) must go through :meth:`invoke` instead."""
        return self._fn

    def _setup_mesh(self, spec: str) -> None:
        """``custom=mesh:dp=N`` / ``mesh:auto`` / ``mesh:DxT`` —
        in-pipeline sharded execution over the local device mesh (SURVEY
        §7: "inside a slice, sharded execution via pjit mesh"). The batch
        axis is device_put with a NamedSharding over ``dp`` and the SAME
        jitted callable runs GSPMD-partitioned: XLA splits the batch
        across chips and inserts the collectives, so ``tensor_aggregator
        → tensor_filter(mesh)`` uses every chip over ICI with zero
        topology plumbing in the launch line. This is the TPU-native
        replacement for the reference's shared-model DP idiom (a tee
        fanning out to N query clients;
        nnstreamer_plugin_api_filter.h:578-617 shared model table) — one
        process, one program, no per-chip pipelines.

        ``mesh:DxT`` builds a 2-D ``(dp=D, tp=T)`` mesh for shard-aware
        model entries (objects exposing ``make_sharded(mesh)``, e.g. the
        tensor-parallel LM serving entries in ``models/lm_serving.py``):
        the entry places its own params/cache PartitionSpecs over ``tp``
        while the backend still batch-shards inputs over ``dp``.
        """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        devices = jax.devices()
        # honor an explicit accelerator/platform request the same way
        # _select_device does — a mesh over devices the user opted out of
        # would be a silent placement override
        accel = self.props.accelerator if self.props else Accelerator.AUTO
        want = get_config().get("jax", "default_device", "auto")
        if accel is not Accelerator.AUTO:
            want = accel.value
        if want not in ("auto", ""):
            matching = [d for d in devices if d.platform.startswith(want)]
            if not matching:
                raise ValueError(
                    f"custom=mesh with accelerator={want}: no {want} "
                    f"devices present (have "
                    f"{sorted({d.platform for d in devices})})")
            devices = matching
        try:
            self._mesh = parse_mesh_spec(spec, devices)
        except ValueError as e:
            raise ValueError(f"custom=mesh:{e}") from None
        # batch axis (dim 0, the one the aggregator builds) shards over
        # dp; trailing axes replicate. On a 2-D mesh the tp axis belongs
        # to the model's own param/cache shardings, never the batch.
        self._batch_sharding = NamedSharding(self._mesh, PartitionSpec("dp"))

    def set_model_callable(self, fn: Callable,
                           in_info: Optional[TensorsInfo] = None,
                           out_info: Optional[TensorsInfo] = None) -> None:
        """Directly install a jax-traceable callable (model-zoo path)."""
        self._fn = fn
        self._in_info = in_info
        self._out_info = out_info

    def _load_model(self, model: str, props: FilterProperties) -> Callable:
        if model.startswith("builtin://"):
            parsed = urllib.parse.urlparse(model)
            name = parsed.netloc or parsed.path.lstrip("/")
            params = dict(urllib.parse.parse_qsl(parsed.query))
            params.update(props.custom_dict())
            builtins = _builtin_models()
            if name not in builtins:
                raise ValueError(
                    f"unknown builtin model '{name}' (have: {sorted(builtins)})"
                )
            return builtins[name](params)
        if model.endswith(".tflite") and os.path.exists(model):
            # run a .tflite file on XLA: flatbuffer parsed, weights
            # dequantized, graph re-emitted as jax (models/tflite_import.py)
            from ..models.tflite_import import load_tflite

            fn, self._in_info, self._out_info = load_tflite(
                model, props.custom_dict())
            return fn
        if model.endswith(".py") and os.path.exists(model):
            ns: Dict[str, Any] = {"__file__": model}
            with open(model) as fh:
                code = fh.read()
            exec(compile(code, model, "exec"), ns)  # noqa: S102 - user model file
            if "IN_INFO" in ns:
                self._in_info = ns["IN_INFO"]
            if "OUT_INFO" in ns:
                self._out_info = ns["OUT_INFO"]
            if "model" not in ns or not callable(ns["model"]):
                raise ValueError(f"{model}: must define a callable 'model'")
            return ns["model"]
        if ":" in model and not os.path.exists(model):
            mod_name, _, attr = model.partition(":")
            mod = importlib.import_module(mod_name)
            fn = getattr(mod, attr)
            fn = _apply_serve_knobs(fn, props.custom_dict(), model)
            if self._mesh is not None:
                # shard-aware entry: the model builds against the mesh
                # (tp PartitionSpecs on params/cache; lm_serving.py)
                sharded_maker = getattr(fn, "make_sharded", None)
                if sharded_maker is not None:
                    return sharded_maker(self._mesh)
            maker = getattr(fn, "make", None)
            return maker() if maker else fn
        raise ValueError(f"jax backend cannot load model '{model}'")

    def close(self) -> None:
        self._fn = None
        self._jit = None
        self._aot_state = None
        if self._stager is not None:
            self._stager.drain()
            self._stager = None
        super().close()

    def aot_state(self) -> Optional[str]:
        """Whether this backend serves through an AOT artifact: "hit"
        (loaded from the compile cache), "export" (freshly exported this
        open), or None (plain jit)."""
        return self._aot_state

    # -- info ---------------------------------------------------------------
    def get_model_info(self) -> Tuple[Optional[TensorsInfo], Optional[TensorsInfo]]:
        return self._in_info, self._out_info

    def set_input_info(self, in_info: TensorsInfo) -> TensorsInfo:
        """Derive output spec via ``jax.eval_shape`` — shape inference with
        zero FLOPs (the reference must probe backends with real invokes)."""
        import jax

        if getattr(self._fn, "host_native", False):
            # a native program has a fixed compiled contract; accept only
            # the recorded shapes (use quantized_exec:int8 for flexibility)
            if self._in_info is not None and [
                (tuple(s.shape), s.dtype) for s in in_info.specs
            ] == [(tuple(s.shape), s.dtype) for s in self._in_info.specs]:
                return self._out_info
            raise ValueError(
                "host-native model: input info is fixed at load "
                f"({self._in_info}); cannot retarget to {in_info}")

        specs = [
            jax.ShapeDtypeStruct(s.shape, s.dtype.np_dtype) for s in in_info.specs
        ]
        out = jax.eval_shape(lambda *xs: _as_tuple(self._fn(*xs)), *specs)
        self._in_info = in_info
        self._out_info = TensorsInfo.of(
            *(TensorSpec(o.shape, DataType.from_any(o.dtype)) for o in out)
        )
        return self._out_info

    # -- invoke -------------------------------------------------------------
    def _aot_guard(self, loaded) -> Callable:
        """Serve through the artifact while it covers the input, fall
        back to plain jit the moment a signature leaves its avals: a
        poly artifact symbolizes only the batch dim, so a flexible
        stream whose TRAILING dims vary (the NNL008 scenario) must keep
        the pre-AOT retrace-per-shape behavior — never an aval-mismatch
        error in the hot loop. The verdict is memoized per signature so
        the aval walk runs once per NEW shape (jit's own retrace
        cadence), not per frame; the probe only exists on the opt-in
        NNS_AOT_CACHE path — the cache-off invoke is untouched."""
        import jax

        fn = self._fn
        fallback = None
        verdicts: dict = {}

        def serve(*xs):
            nonlocal fallback
            sig = tuple((getattr(x, "shape", None), getattr(x, "dtype", None))
                        for x in xs)
            ok = verdicts.get(sig)
            if ok is None:
                if len(verdicts) > 512:  # flexible streams: bound the memo
                    verdicts.clear()
                ok = verdicts[sig] = loaded.compatible(xs)
            if ok:
                return loaded.call(*xs)
            if fallback is None:
                fallback = jax.jit(lambda *ys: _as_tuple(fn(*ys)))
            return fallback(*xs)
        # memory_analysis lowers the served program AOT for its estimate;
        # the exported module is what actually runs, so hand its jit
        # through (a closure has no .lower of its own)
        serve.lower = loaded.call.lower
        return serve

    def _aot_resolve(self, example_inputs) -> Optional[Callable]:
        """AOT compile-cache consult for the singleton-filter path
        (nnstreamer_tpu/aot): load this model's exported program keyed by
        (resolved model, custom knobs, trailing-dim signature, device
        signature), or export a fresh shape-poly artifact and serve
        through it — a supervised restart or replica spawn of the same
        filter then deserializes instead of tracing. None = plain jit
        (cache off / export refused)."""
        from .. import aot

        cache = aot.default_cache()
        if cache is None:
            return None
        shapes = [(tuple(np.shape(x)),
                   str(getattr(x, "dtype", None) or np.asarray(x).dtype))
                  for x in example_inputs]
        key, stage, digest = aot.backend_key(self, shapes)
        loaded = cache.load(key, stage, digest)
        if loaded is not None and loaded.compatible(tuple(example_inputs)):
            self._aot_state = "hit"
            return self._aot_guard(loaded)
        fn = self._fn
        try:
            blob, meta, fresh = aot.export_stage(
                lambda *xs: _as_tuple(fn(*xs)), tuple(example_inputs),
                poly=True)
        except aot.ExportError as e:
            logger.info("jax backend model=%s: AOT export refused (%s) — "
                        "serving plain jit",
                        self.props.model if self.props else "?", e)
            return None
        cache.save(key, stage, digest, blob, meta)
        self._aot_state = "export"
        return self._aot_guard(fresh)

    def _jitted(self, example_inputs=None) -> Callable:
        # jax.jit's own trace cache keys on input signatures — one wrapper
        # covers every shape bucket (recompiles per new signature, reuses
        # compiled executables otherwise)
        import jax

        if self._jit is None:
            if getattr(self._fn, "host_native", False):
                # host-native executor (e.g. quantized_exec:int8-native,
                # models/tflite_q8_native.py): a C++ program, not a jax
                # computation — invoke directly, never trace
                fn = self._fn
                self._jit = lambda *xs: _as_tuple(
                    fn(*(np.asarray(x) for x in xs)))
            else:
                if example_inputs is not None and self._mesh is None:
                    try:
                        self._jit = self._aot_resolve(example_inputs)
                    except Exception:  # noqa: BLE001 - cache != correctness
                        logger.exception(
                            "jax backend: AOT cache consult failed — "
                            "serving plain jit")
                if self._jit is None:
                    self._jit = jax.jit(lambda *xs: _as_tuple(self._fn(*xs)))
        return self._jit

    def memory_analysis(self, inputs):
        """AOT-compile the jitted invoke for this signature and hand the
        executable to the memory accountant. jax's jit cache already
        holds a compiled program for the signature after the first
        invoke; ``lower().compile()`` re-derives it once — acceptable on
        the accounting path (gated behind obs_memory.ACTIVE, once per
        backend open), never on the per-frame path."""
        if self._fn is None or getattr(self._fn, "host_native", False):
            return None
        if self._mesh is not None:
            return None  # GSPMD footprint is per-shard; skip for now
        try:
            return self._jitted().lower(*inputs).compile()
        except Exception:  # noqa: BLE001 - unloweredable signature
            return None

    def compile_cache_info(self) -> dict:
        """Shape-bucketing introspection (SURVEY §7 'hard parts': flexible
        streams recompile per signature; this makes that visible)."""
        return {
            "signatures": len(self._signatures),
            "max_signatures": self._max_signatures,
        }

    def _track_signature(self, inputs: List[Any]) -> None:
        # dtype objects are hashable — avoid str() per tensor per invoke
        # (this runs on the per-frame hot path)
        sig = tuple((getattr(x, "shape", None), getattr(x, "dtype", None))
                    for x in inputs)
        if sig in self._signatures:
            return
        self._signatures.add(sig)
        n = len(self._signatures)
        # >= with a once-flag: concurrent invokes on this REENTRANT backend
        # could jump past an exact-equality check and never warn
        if n >= self._max_signatures and not self._sig_warned:
            self._sig_warned = True
            logger.warning(
                "jax backend model=%s hit %d distinct input signatures — a "
                "flexible stream is forcing XLA recompiles per shape; "
                "bucket shapes upstream (tensor_aggregator / pad) or raise "
                "custom=max_signatures:N to silence",
                self.props.model if self.props else "?", n)

    def _stage_pinned(self, inputs: List[Any]) -> List[Any]:
        """Stage host inputs onto the pinned chip through the two-slot
        stager; re-targets (and drops stale slots) when the placement
        planner moved this backend to another device."""
        from ..transport.staging import DoubleBufferedStager

        s = self._stager
        if s is None:
            s = self._stager = DoubleBufferedStager(self._device)
        elif s.device is not self._device:
            s.retarget(self._device)
        return s.stage(inputs)

    def invoke(self, inputs: List[Any]) -> List[Any]:
        import jax

        if self._fn is None:
            raise RuntimeError("jax backend: invoke before open")
        self._track_signature(inputs)
        if getattr(self._fn, "host_native", False):
            # host program: the wrapper converts to numpy anyway — any
            # device staging here would be an H2D+D2H round trip per frame
            return list(self._jitted()(*inputs))
        if self._mesh is not None:
            return self._invoke_sharded(inputs)
        pinned = self._device is not None and not self._device_is_default
        if pinned and any(not hasattr(x, "addressable_shards")
                          for x in inputs):
            # pinned stage: the host arrays ride the double-buffered
            # stager (transport/staging.py) — the async put for frame
            # N+1 is issued while frame N's handles stay parked, so the
            # transfer overlaps the previous dispatch's device compute
            # instead of serializing behind it ("staging:put" in the
            # XFER ledger, the accounted successor of the old per-call
            # backend:pinned_put)
            inputs = self._stage_pinned(inputs)
        device_inputs = []
        for x in inputs:
            if hasattr(x, "addressable_shards"):
                # device-resident already; move single-device arrays that sit
                # on the WRONG chip (upstream pinned stage) onto ours —
                # device-to-device (ICI on TPU), never through host. Sharded
                # multi-device arrays pass through untouched (pjit stages).
                # A fully-automatic backend makes no move either: host inputs
                # follow jax's configured default, and forcing devices[0]
                # here could split the call across two devices.
                devs = x.devices()
                if (pinned and len(devs) == 1 and devs != {self._device}):
                    x = jax.device_put(x, self._device)
            # default-device host arrays go straight to the jitted call —
            # its C++ argument conversion does the same H2D transfer with
            # far less Python dispatch (measured: explicit device_put makes
            # a passthrough invoke ~70us; raw jit call is ~6.5us)
            device_inputs.append(x)
        # NNS_XFERCHECK: the jitted region itself must not pull implicitly
        # (host inputs entering through the call's argument conversion are
        # H2D — legal; only implicit D2H is banned)
        with _san.no_implicit_d2h("backend:invoke"):
            out = self._jitted(device_inputs)(*device_inputs)
        return list(out)

    def _invoke_sharded(self, inputs: List[Any]) -> List[Any]:
        """Mesh mode: batch-shard each input over ``dp`` and run the same
        jitted callable GSPMD-partitioned. Inputs whose leading dim does
        not divide the dp axis (e.g. a partial EOS tail the aggregator
        let through) stay unsharded for that call — XLA still runs them
        correctly on the mesh-default device; correctness never depends
        on divisibility."""
        import jax

        # the batch axis shards over dp only; on a 2-D (dp, tp) mesh the
        # tp axis belongs to the model's own param/cache shardings
        n = dict(self._mesh.shape).get("dp", self._mesh.size)
        device_inputs = []
        for x in inputs:
            shape = getattr(x, "shape", None)
            if shape:  # batched tensor: shard when the mesh divides it
                if shape[0] % n == 0:
                    x = jax.device_put(x, self._batch_sharding)
                    if _san.XFER:
                        _san.note_transfer("backend:shard_put", "h2d",
                                           getattr(x, "nbytes", 0))
                elif not self._mesh_warned:
                    self._mesh_warned = True
                    logger.warning(
                        "jax mesh backend model=%s: input batch %s not "
                        "divisible by dp=%d — running this call "
                        "unsharded (size the upstream tensor_aggregator "
                        "to a multiple of the dp axis)",
                        self.props.model if self.props else "?", shape, n)
            # rank-0 scalars / non-array aux inputs have no batch axis to
            # shard: pass through (replicated by GSPMD), no warning
            device_inputs.append(x)
        with _san.no_implicit_d2h("backend:invoke_sharded"):
            out = self._jitted()(*device_inputs)
        return list(out)

    def fusion_callable(self):
        """Traceable per-frame callable for segment fusion. None (defuse)
        when invokes can't inline into a larger jit: host-native programs
        (a C++ executor, not a jax computation), mesh mode (GSPMD
        placement belongs to THIS stage's jit), or an explicitly pinned
        device (consecutive pinned stages are pipeline-parallelism — each
        stage must keep its own dispatch + device_put)."""
        fn = self._fn
        if fn is None or getattr(fn, "host_native", False):
            return None
        if self._mesh is not None:
            return None
        if self._device is not None and not self._device_is_default:
            return None
        return lambda *xs: _as_tuple(fn(*xs))

    def handle_event(self, event: BackendEvent, data: Optional[dict] = None) -> None:
        if event is BackendEvent.RELOAD_MODEL:
            # Reference RELOAD_MODEL (nnstreamer_plugin_api_filter.h:378-384):
            # old + new co-resident until swap completes.
            new_fn = self._load_model(self.props.model, self.props)
            self._fn = new_fn
            self._jit = None  # recompile against the new model
            self._aot_state = None  # re-key on next invoke (model
            # fingerprint covers on-disk weight changes)
