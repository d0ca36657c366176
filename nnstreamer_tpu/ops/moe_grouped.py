"""The routed experts' products over one launch's rows, as the weights lie.

``parallel/moe_dropless.py`` ``experts_ffn`` routes and counts; this op
applies the experts it holds to the rows they were assigned:

* ``h (T, D)`` — the layer's normed rows;
* ``w_gate``, ``w_up`` ``(E, D, F)`` and ``w_down (E, F, D)`` — the experts
  held here, in the parameter tree's layout, untouched;
* ``flat (T, k)`` int32 — the held expert of every assignment, ``E`` for one
  that is served elsewhere or belongs to a row that is not live;
* ``weights (T, k)`` float32 — the router's weight of every assignment;
* ``sizes (E,)`` int32 — the assignments each held expert received.

It returns ``(T, D)`` float32: for every row the sum over its assignments
served here of ``weight * W_down(silu(W_gate h) * W_up h)``. The operands
of the three products take the weights' type, sums are float32, ``silu``
and the gate-up product are float32, and the hidden rows are rounded to
the weights' type once before the down product.

Two forms, chosen in one place (:func:`grouped_experts`) by
``utils.hw_accel.pallas_interpret``'s rule and, on a TPU, by the call's
static shapes (:func:`streams`):

* :func:`kernel_grouped_experts` — a Pallas TPU kernel for a launch of up
  to :data:`MAX_ROWS` rows (a decode step's 32, a prefill launch's 256),
  where the layer is a read of the reached experts' weights with a few
  rows riding on it. Which experts were reached rides as scalar prefetch;
  the three stacks stay in HBM and each reached expert's matrices are
  fetched once, in tiles of ``tile_f`` hidden columns (gate and up
  ``(D, tile_f)``, down ``(tile_f, D)``: whole matrices where two experts
  fit the buffers), by asynchronous copies into a ring of VMEM buffers,
  the next tile on its way while this one is multiplied. An expert that no
  row reached issues no copy and no product. Every row is offered to every
  reached expert and the result is weighted by the router's weight of that
  (row, expert) pair, zero where the row was not assigned: an expert holds
  at most one assignment a row, so ``T`` rows are all a group can hold,
  nothing is sorted, gathered or put back, and no routing can overflow
  anything. The ``(T, F)`` hidden rows never leave VMEM and the ``(T, D)``
  result stays resident across experts.
* :func:`plain_grouped_experts` — the assignments sorted by expert and
  three ``jax.lax.ragged_dot`` over them: what a launch of more rows runs
  (the dense offer multiplies ``T`` rows an expert whatever it was
  assigned, and past :data:`MAX_ROWS` nobody has measured that against
  the grouped product), what runs where ``D`` or ``F`` is not whole lanes
  or where a TPU kernel would only be interpreted, and the oracle the
  kernel is pinned to (``tests/test_moe_grouped.py``). XLA's grouped
  kernel follows the assignment rows, not the weights. Stand-alone on a
  v5e (``tools/moe_grouped_forms.py``, PR 32; ms a layer, grouped product
  → kernel, and the kernel's share of the HBM rate over the reached
  experts' bytes): 64 experts of ``2304 × 896`` top-8, 32 rows 4.74 → 1.10
  (87%), 256 rows 5.68 → 1.15 (84%); 128 of ``2048 × 768`` top-6, 32 rows
  1.91 → 1.27 (87%), 256 rows 4.53 → 1.71 (86%).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import hw_accel

#: most rows of a launch the kernel takes. A 128 x 128 weight tile costs
#: the matrix unit the larger of its load (128 cycles) and the rows' passage,
#: so up to 128 rows the products cost what they cost at one row, and at 256
#: they take about as long as the tile's bytes take from HBM (2304 x 896:
#: 16 us against 15 an expert on a v5e): the kernel still streams there
#: (module docstring). Past it the dense offer's products bind, and rows
#: that were never assigned are multiplied for nothing: not measured
MAX_ROWS = 256
#: most bytes of the ring of weight tiles in VMEM (every TPU generation
#: holds 64 MiB or more): two whole experts of ``3 × 2304 × 896`` or of
#: ``3 × 2048 × 768`` bfloat16 fit, so each matrix comes in one copy.
#: Stand-alone, tiles of 128–384 columns in rings of 2–4 read the same at 32
#: rows (1.10–1.11 and 1.27 ms) and 4–10% worse at 256 (1.23–1.27 against
#: 1.15, 1.70–1.90 against 1.71): the largest tile that fits is derived
BUFFER_BYTES = 32 * 1024 * 1024
#: VMEM beside the ring: the rows, the result and the products' temporaries
_HEADROOM_BYTES = 16 * 1024 * 1024


def tile_columns(D, F, itemsize, depth=2):
    """Hidden columns a tile: the largest divisor of ``F`` in whole lanes
    (128) of which ``depth`` tiles of the three matrices fit
    :data:`BUFFER_BYTES`; ``None`` where ``D`` or ``F`` is not whole lanes
    or not even one lane's columns fit."""
    if D % 128 or F % 128:
        return None
    lanes = F // 128
    for n in range(lanes, 0, -1):
        if lanes % n == 0 and depth * 3 * D * n * 128 * itemsize <= BUFFER_BYTES:
            return n * 128
    return None


def streams(T, D, F, dtype):
    """Whether a launch of these static shapes runs the kernel on a TPU."""
    return (T <= MAX_ROWS
            and tile_columns(D, F, jnp.dtype(dtype).itemsize) is not None)


def form(T, D, F, dtype):
    """``"kernel"`` or ``"ragged_dot"``: the form a launch of these static
    shapes runs in this process (``chip_smoke.py`` reports it)."""
    if hw_accel.pallas_interpret(jax.default_backend()):
        return "ragged_dot"
    return "kernel" if streams(T, D, F, dtype) else "ragged_dot"


def grouped_experts(h, w_gate, w_up, w_down, flat, weights, sizes):
    """The experts' products (module docstring), in the form this platform
    runs (:func:`form`): the kernel or the grouped product by shape on a
    TPU, the grouped product where the kernel would be interpreted."""
    _, D, F = w_gate.shape
    run = (kernel_grouped_experts
           if form(h.shape[0], D, F, w_gate.dtype) == "kernel"
           else plain_grouped_experts)
    return run(h, w_gate, w_up, w_down, flat, weights, sizes)


def tpu_grouped_experts(h, w_gate, w_up, w_down, flat, weights, sizes, *,
                        interpret=False):
    """What a TPU runs: the kernel where the shapes let the weights stream
    (:func:`streams`), the grouped product otherwise. ``interpret`` runs the
    kernel through the Pallas interpreter (tests on the CPU)."""
    _, D, F = w_gate.shape
    if streams(h.shape[0], D, F, w_gate.dtype):
        return kernel_grouped_experts(h, w_gate, w_up, w_down, flat, weights,
                                      sizes, interpret=interpret)
    return plain_grouped_experts(h, w_gate, w_up, w_down, flat, weights,
                                 sizes)


def plain_grouped_experts(h, w_gate, w_up, w_down, flat, weights, sizes):
    """Sort the assignments by expert, three grouped products over exactly
    the rows each expert received, undo the sort."""
    T, k = flat.shape
    held = w_gate.shape[0]
    # assignments not served here sort behind every held expert's, past
    # the last group: ragged_dot leaves their rows zero
    order = jnp.argsort(flat.reshape(T * k), stable=True)
    rows = h[order // k]                                   # (T*k, D)

    def product(x, w):
        # activations take the weights' type for the grouped product (on
        # the MXU a default-precision float32 product rounds them to
        # bfloat16 anyway); sums are kept in float32. The precision is
        # said outright: under a raised default the TPU's grouped kernel
        # refuses bfloat16 operands ("Bad lhs type")
        return jax.lax.ragged_dot(
            x.astype(w.dtype), w, sizes,
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)

    hidden = jax.nn.silu(product(rows, w_gate)) * product(rows, w_up)
    out = product(hidden, w_down)                          # (T*k, D)
    back = jnp.argsort(order)                              # undo the sort
    out = out[back].reshape(T, k, -1)
    return jnp.where((flat < held)[..., None],
                     out * weights[..., None], 0.0).sum(1)


def _kernel(meta_ref, x_ref, dw_ref, g_hbm, u_hbm, d_hbm, y_ref,
            gbuf, ubuf, dbuf, sems, *, tiles, tile_f, depth):
    units = meta_ref[0] * tiles  # (reached expert, tile of its columns)

    def copies(act, unit):
        # start, or wait for, one unit's three tiles into its ring buffer
        e = meta_ref[1 + unit // tiles]
        buf = unit % depth
        if tiles == 1:
            pairs = ((g_hbm.at[e], gbuf), (u_hbm.at[e], ubuf),
                     (d_hbm.at[e], dbuf))
        else:
            cols = pl.ds(pl.multiple_of((unit % tiles) * tile_f, 128), tile_f)
            pairs = ((g_hbm.at[e, :, cols], gbuf), (u_hbm.at[e, :, cols], ubuf),
                     (d_hbm.at[e, cols, :], dbuf))
        for i, (src, ring) in enumerate(pairs):
            getattr(pltpu.make_async_copy(src, ring.at[buf],
                                          sems.at[i, buf]), act)()

    for ahead in range(depth - 1):
        @pl.when(ahead < units)
        def _():
            copies("start", ahead)

    y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)

    def body(unit, _):
        # one place starts copies and one waits for them: the unit
        # depth - 1 ahead goes into the buffer the last product left
        @pl.when(unit + depth - 1 < units)
        def _():
            copies("start", unit + depth - 1)

        copies("wait", unit)
        buf = unit % depth
        x = x_ref[...]
        gate = jnp.dot(x, gbuf[buf], preferred_element_type=jnp.float32)
        up = jnp.dot(x, ubuf[buf], preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
        out = jnp.dot(hidden, dbuf[buf], preferred_element_type=jnp.float32)
        # this expert's column of the rows' weights: zero for a row that
        # was not assigned to it (or is not live), whose product is dropped
        e = meta_ref[1 + unit // tiles]
        at = jax.lax.broadcasted_iota(jnp.int32, dw_ref.shape, 1)
        w = jnp.sum(jnp.where(at == e, dw_ref[...], 0.0), axis=1,
                    keepdims=True)
        y_ref[...] += jnp.where(w != 0.0, w * out, 0.0)
        return 0

    jax.lax.fori_loop(0, units, body, 0)


@functools.partial(jax.jit, static_argnames=("tile_f", "depth", "interpret"))
def _call(h, w_gate, w_up, w_down, flat, weights, sizes, *, tile_f, depth,
          interpret):
    T0, D = h.shape
    held, _, F = w_gate.shape
    dtype = w_gate.dtype
    # a row's weight for every held expert: an expert appears at most once
    # among a row's assignments, so the sum picks, it does not add
    dense = jnp.where(flat[..., None] == jnp.arange(held, dtype=flat.dtype),
                      weights[..., None], 0.0).sum(1).astype(jnp.float32)
    x = h.astype(dtype)
    # rows of the products' left operand fill whole tile rows (16 bfloat16)
    T = -(-T0 // 16) * 16
    if T != T0:
        x = jnp.pad(x, ((0, T - T0), (0, 0)))
        dense = jnp.pad(dense, ((0, T - T0), (0, 0)))
    # the reached experts first, in their order; the kernel visits that many
    reached = sizes > 0
    meta = jnp.concatenate([
        reached.sum()[None], jnp.argsort(~reached, stable=True)
    ]).astype(jnp.int32)
    tiles = F // tile_f
    ring = depth * 3 * D * tile_f * dtype.itemsize

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, meta: (0,) * len(shape))

    y = pl.pallas_call(
        functools.partial(_kernel, tiles=tiles, tile_f=tile_f, depth=depth),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[whole((T, D)), whole((T, held))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * 3,
            out_specs=whole((T, D)),
            scratch_shapes=[
                pltpu.VMEM((depth, D, tile_f), dtype),
                pltpu.VMEM((depth, D, tile_f), dtype),
                pltpu.VMEM((depth, tile_f, D), dtype),
                pltpu.SemaphoreType.DMA((3, depth)),
            ]),
        out_shape=jax.ShapeDtypeStruct((T, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=ring + _HEADROOM_BYTES),
        interpret=interpret,
        name="grouped_experts",
    )(meta, x, dense, w_gate, w_up, w_down)
    return y[:T0]


def kernel_grouped_experts(h, w_gate, w_up, w_down, flat, weights, sizes, *,
                           tile_f=None, depth=2, interpret=False):
    """The Pallas kernel (module docstring). ``tile_f`` and ``depth`` (the
    ring's buffers) are derived from the shapes unless a test or a
    stand-alone timing names them; ``interpret`` runs the kernel through
    the Pallas interpreter (tests on the CPU)."""
    _, D, F = w_gate.shape
    if tile_f is None:
        tile_f = tile_columns(D, F, w_gate.dtype.itemsize, depth)
    if tile_f is None or F % tile_f:
        raise ValueError(
            f"grouped_experts: experts of {D} x {F} do not tile by 128 "
            f"columns into {BUFFER_BYTES} bytes of buffers")
    return _call(h, w_gate, w_up, w_down, flat, weights, sizes,
                 tile_f=int(tile_f), depth=int(depth), interpret=interpret)
