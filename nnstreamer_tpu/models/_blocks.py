"""Shared flax building blocks for the model zoo.

One definition of the MobileNet-v2-style blocks used by mobilenet_v2 /
ssd_mobilenet / deeplab / posenet (inference-mode BN folded to per-channel
scale+bias, relu6, NHWC, bfloat16-friendly). ``make_blocks`` is a factory so
jax/flax import stays lazy and the compute dtype is baked per model.
"""
from __future__ import annotations

from typing import Tuple


def init_params(model, input_shape, seed: int = 0):
    """Initialize a flax module's params CHEAPLY: one jitted init program
    (not hundreds of eager per-op dispatches) keyed with the rbg PRNG
    (threefry subgraphs per parameter dominate init compile time). For
    the demo models this cuts bring-up ~21s -> ~9s on a host CPU — which
    is measurement budget on the bench paths."""
    import jax
    import jax.numpy as jnp

    rng = jax.random.key(seed, impl="rbg")
    return jax.jit(model.init)(rng, jnp.zeros(input_shape, jnp.float32))




def resolve_compute_dtype(compute_dtype: str) -> str:
    """``auto`` → bfloat16 on accelerators with native bf16 compute
    (TPU: MXU-native; GPU: tensor-core bf16 since Ampere/ROCm CDNA —
    half the HBM reads either way), float32 on CPU (XLA-CPU *emulates*
    bf16). Explicit dtypes pass through."""
    if compute_dtype != "auto":
        return compute_dtype
    import jax

    from ..utils.hw_accel import is_tpu_platform

    # initializes the backend — the same init the model build right after
    # this triggers anyway; a backend failure propagates
    platform = jax.devices()[0].platform
    if is_tpu_platform(platform) or platform in ("gpu", "cuda", "rocm"):
        return "bfloat16"
    return "float32"


def make_blocks(compute_dtype: str = "auto"):
    """Returns ``(ConvBnRelu, InvertedResidual)`` flax Modules bound to the
    given compute dtype (``auto`` resolves per platform)."""
    compute_dtype = resolve_compute_dtype(compute_dtype)
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    cdt = jnp.dtype(compute_dtype)

    class ConvBnRelu(nn.Module):
        features: int
        kernel: Tuple[int, int] = (3, 3)
        strides: int = 1
        groups: int = 1
        dilation: int = 1
        act: bool = True

        @nn.compact
        def __call__(self, x):
            in_ch = x.shape[-1]
            if self.groups > 1 and self.groups == in_ch \
                    and self.features % in_ch == 0:
                # depthwise: shifted elementwise multiply-adds instead of
                # feature_group_count — XLA-CPU's grouped-conv lowering is
                # ~50x slower (measured, tflite_import.depthwise_shift_add)
                # and on TPU this fuses into VPU ops rather than issuing
                # 1-wide MXU matmuls. Kernel shape matches what flax would
                # create for the grouped conv: (kh, kw, 1, features).
                from .tflite_import import depthwise_shift_add

                kh, kw = self.kernel
                w = self.param("depthwise_kernel",
                               nn.initializers.lecun_normal(),
                               (kh, kw, 1, self.features))
                x = depthwise_shift_add(
                    x.astype(cdt), w.astype(cdt).transpose(2, 0, 1, 3),
                    (self.strides, self.strides), "SAME",
                    (self.dilation, self.dilation))
            else:
                x = nn.Conv(self.features, self.kernel, strides=self.strides,
                            padding="SAME", feature_group_count=self.groups,
                            kernel_dilation=self.dilation, use_bias=False,
                            dtype=cdt)(x)
            # inference-mode BN = per-channel scale + bias
            scale = self.param("bn_scale", nn.initializers.ones, (self.features,))
            bias = self.param("bn_bias", nn.initializers.zeros, (self.features,))
            x = x * scale.astype(cdt) + bias.astype(cdt)
            if self.act:
                x = jnp.minimum(jax.nn.relu(x), 6.0)  # relu6
            return x

    class InvertedResidual(nn.Module):
        features: int
        strides: int
        expand: int
        dilation: int = 1

        @nn.compact
        def __call__(self, x):
            in_ch = x.shape[-1]
            h = x
            if self.expand != 1:
                h = ConvBnRelu(in_ch * self.expand, (1, 1))(h)
            h = ConvBnRelu(in_ch * self.expand, (3, 3), strides=self.strides,
                           groups=in_ch * self.expand, dilation=self.dilation)(h)
            h = ConvBnRelu(self.features, (1, 1), act=False)(h)
            if self.strides == 1 and in_ch == self.features:
                h = h + x
            return h

    return ConvBnRelu, InvertedResidual


def make_u8_entry(base_entry, compute_dtype: str = "auto"):
    """uint8-input filter-entry wrapper: ((x/127.5)-1) normalization fused
    into the base entry's jitted graph. The pipeline then ships RAW uint8
    frames to the device — 4× less host→HBM traffic than pre-normalized
    float32 (HBM/PCIe bandwidth is the streaming bottleneck; the reference
    converts on CPU and pays full-width copies per frame,
    gsttensor_transform.c arithmetic mode). One definition for every model
    family's ``filter_model_u8``."""

    class _U8Entry:
        image_size = getattr(base_entry, "image_size", None)

        @staticmethod
        def make():
            import jax.numpy as jnp

            fn = base_entry.make()
            # normalization dtype: pass the base model's explicit dtype
            # when it was built with one; the default matches the
            # platform resolution the default-built entries use (u8
            # values are exact in bf16; f32 on CPU)
            dt = jnp.dtype(resolve_compute_dtype(compute_dtype))
            return lambda x: fn(x.astype(dt) * (1.0 / 127.5) - 1.0)

    return _U8Entry()
