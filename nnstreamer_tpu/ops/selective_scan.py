"""The selective scan of a state-space (Mamba-1) layer, channel axis last.

A sequence keeps per layer a scan state ``h (N, Di)`` float32 (``N`` the
state size, ``Di`` the inner width: the channels lie along the lanes, so a
state of 16 × 5120 is whole tiles and nothing is padded) and advances it a
token at a time:

    h <- exp(dt * A) * h + (dt * u) (x) B        y = C . h + D * u

with ``dt, u (Di,)`` the token's step size and input, ``B, C (N,)`` its
input and output maps, ``A (N, Di)`` negative and ``D (Di,)`` constants of
the layer. Everything is float32.

Two callers, two shapes (``serving/lm_engine.py``), each in two forms
chosen in one place by ``utils.hw_accel.pallas_interpret``'s rule:

* :func:`slots_update` — one token of each of ``S`` slots, in place in the
  engine's array of every state layer's states ``(layers, S, N, Di)``:
  layer ``layer``'s rows of the slots in ``live`` advance, every other row
  of the array stays bit for bit, and ``y (S, Di)`` comes out.

  - :func:`kernel_slots_update` — a Pallas TPU kernel over blocks of eight
    slots by :data:`STEP_TILE` channels of that layer, the array aliased
    input to output: each state is read once and written once, and a dead
    slot's is written as it was read. The plain form compiles on a v5e to
    two passes over the old state (one fusion recomputes ``h'`` to reduce
    it to ``y``, the in-place update reads it again: the optimized module,
    ``tools/ssm_state_layout.py``, PR 33), 42 MB a layer more at 128 slots.
  - :func:`plain_slots_update` — :func:`step_update` on the layer's rows, a
    select, a ``dynamic_update_slice``: the CPU's form and the oracle.
* :func:`chunk_scan` — ``C`` rows of ONE slot (a prefill launch), of which
  the first ``n_valid`` are real: ``y (C, Di)`` and the state after the
  last real row.

  - :func:`kernel_chunk_scan` — a Pallas TPU kernel. The grid tiles the
    channels (:data:`TILE` lanes a program); a tile's state stays in
    registers while the program walks the rows eight at a time (reads
    ``u`` and ``dt``, writes ``y``), and is written once at the end. Rows
    past ``n_valid`` are skipped in whole groups of eight (their ``y`` is
    zero) and neutral inside the last group (``dt = 0``: ``exp(0) * h + 0``
    is ``h``, bit for bit). ``B`` and ``C`` ride replicated along 128
    lanes, so that a row's ``(N,)`` map is a column that multiplies the
    state without a transpose in the loop.
  - :func:`plain_chunk_scan` — ``lax.scan`` over the rows: what the CPU
    runs, what widths that are not whole lanes run, and the oracle the
    kernel is pinned to (``tests/test_jamba_serving.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import hw_accel

#: channels a program of the launch's kernel holds: a state of (16, 512)
#: float32 is eight registers, eight independent chains a row
TILE = 512
#: channels a program of the step's kernel holds (eight slots' states of
#: (16, 2560) float32 are 1.3 MB a block)
STEP_TILE = 2560
_ROWS = 8  # rows a group: one aligned load of u and dt, one store of y
_VMEM_BYTES = 32 * 1024 * 1024


def step_update(h, dt, u, a, b, c, d):
    """One token a slot: ``h (S, N, Di)``, ``dt, u (S, Di)``, ``a (N,
    Di)``, ``b, c (S, N)``, ``d (Di,)`` → ``(y (S, Di), h')``."""
    h = jnp.exp(dt[:, None, :] * a) * h + (dt * u)[:, None, :] * b[:, :, None]
    return (h * c[:, :, None]).sum(axis=1) + d * u, h


def plain_chunk_scan(h0, dt, u, a, b, c, d, n_valid):
    """``lax.scan`` over the rows of one slot's launch: ``h0 (N, Di)``,
    ``dt, u (C, Di)``, ``b, c (C, N)`` → ``(y (C, Di), h (N, Di))``. A row
    past ``n_valid`` leaves the state as it is (``dt = 0``)."""
    dt = jnp.where(jnp.arange(dt.shape[0])[:, None] < n_valid, dt, 0.0)

    def one(h, row):
        dt_t, u_t, b_t, c_t = row
        y, h = step_update(h[None], dt_t[None], u_t[None], a, b_t[None],
                           c_t[None], d)
        return h[0], y[0]

    h, y = jax.lax.scan(one, h0, (dt, u, b, c))
    return y, h


def _lanes(m):
    """A row's ``(N,)`` map as a column over 128 lanes, ``(rows, N) ->
    (rows, N, 128)``: made once, outside the kernels, so that inside them
    it multiplies a state without a transpose."""
    return jnp.broadcast_to(m.astype(jnp.float32)[:, :, None],
                            (*m.shape, 128))


def _token(h, dt_r, u_r, a, d, b_col, c_col):
    """One token of one sequence inside a kernel: ``h (N, tile)``, ``dt_r,
    u_r, d (1, tile)``, ``a (N, tile)``, ``b_col, c_col (N, 128)`` -> ``(y
    (1, tile), h')``."""
    reps = h.shape[1] // 128

    def wide(col):  # (N, 128) replicated along the lanes -> (N, tile)
        return col if reps == 1 else jnp.concatenate([col] * reps, axis=1)

    h = jnp.exp(dt_r * a) * h + (dt_r * u_r) * wide(b_col)
    return jnp.sum(h * wide(c_col), axis=0, keepdims=True) + d * u_r, h


def _kernel(nv_ref, u_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
            y_ref, h_ref):
    n_valid = nv_ref[0]
    a, d = a_ref[...], d_ref[...]
    y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)

    def group(g, h):
        r0 = pl.multiple_of(g * _ROWS, _ROWS)
        ub = u_ref[pl.ds(r0, _ROWS), :]
        at = r0 + jax.lax.broadcasted_iota(jnp.int32, ub.shape, 0)
        dtb = jnp.where(at < n_valid, dt_ref[pl.ds(r0, _ROWS), :], 0.0)
        ys = []
        for r in range(_ROWS):
            y, h = _token(h, dtb[r:r + 1, :], ub[r:r + 1, :], a, d,
                          b_ref[r0 + r], c_ref[r0 + r])
            ys.append(y)
        y_ref[pl.ds(r0, _ROWS), :] = jnp.concatenate(ys, axis=0)
        return h

    groups = (n_valid + _ROWS - 1) // _ROWS
    h_ref[...] = jax.lax.fori_loop(0, groups, group, h0_ref[...])


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _call(h0, dt, u, a, b, c, d, n_valid, *, tile, interpret):
    rows, width = u.shape
    n = h0.shape[0]
    by_rows = pl.BlockSpec((rows, tile), lambda j, nv: (0, j))
    by_state = pl.BlockSpec((n, tile), lambda j, nv: (0, j))
    whole = pl.BlockSpec((rows, n, 128), lambda j, nv: (0, 0, 0))
    y, h = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(width // tile,),
            in_specs=[by_rows, by_rows, whole, whole, by_state,
                      pl.BlockSpec((1, tile), lambda j, nv: (0, j)),
                      by_state],
            out_specs=[by_rows, by_state]),
        out_shape=[jax.ShapeDtypeStruct((rows, width), jnp.float32),
                   jax.ShapeDtypeStruct((n, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="selective_scan_chunk",
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), u, dt, _lanes(b),
      _lanes(c), a, d.reshape(1, width), h0)
    return y, h


def tiles(rows: int, width: int, most: int = TILE):
    """The channel tile a kernel takes for ``rows`` by ``width`` (at most
    ``most``), or None where it cannot (rows not whole groups of eight,
    channels not whole lanes): the plain form runs then."""
    if rows % _ROWS or width % 128:
        return None
    tile = min(most, width)
    while width % tile:
        tile -= 128
    return tile


def plain_slots_update(h_all, layer: int, live, dt, u, a, b, c, d):
    """``h_all (layers, S, N, Di)``, ``live (S,)`` bool, the rest as
    :func:`step_update` → ``(y (S, Di), h_all')``."""
    old = h_all[layer]
    y, new = step_update(old, dt, u, a, b, c, d)
    return y, h_all.at[layer].set(
        jnp.where(live[:, None, None], new, old))


def _slots_kernel(live_ref, u_ref, dt_ref, b_ref, c_ref, a_ref, d_ref,
                  h_ref, y_ref, ho_ref):
    first = pl.program_id(0) * _ROWS
    a, d = a_ref[...], d_ref[...]
    ys = []
    for r in range(_ROWS):
        old = h_ref[r]
        y, new = _token(old, dt_ref[r:r + 1, :], u_ref[r:r + 1, :], a, d,
                        b_ref[r], c_ref[r])
        ys.append(y)
        ho_ref[r] = jnp.where(live_ref[first + r] != 0, new, old)
    y_ref[...] = jnp.concatenate(ys, axis=0)


@functools.partial(jax.jit,
                   static_argnames=("layer", "tile", "interpret"))
def _slots_call(h_all, live, dt, u, a, b, c, d, *, layer, tile, interpret):
    slots, width = u.shape
    n = h_all.shape[2]
    by_rows = pl.BlockSpec((_ROWS, tile), lambda s, j, live: (s, j))
    cols = pl.BlockSpec((_ROWS, n, 128), lambda s, j, live: (s, 0, 0))
    by_state = pl.BlockSpec((n, tile), lambda s, j, live: (0, j))
    states = pl.BlockSpec((None, _ROWS, n, tile),
                          lambda s, j, live: (layer, s, 0, j))
    y, h_all = pl.pallas_call(
        _slots_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots // _ROWS, width // tile),
            in_specs=[by_rows, by_rows, cols, cols, by_state,
                      pl.BlockSpec((1, tile), lambda s, j, live: (0, j)),
                      states],
            out_specs=[by_rows, states]),
        out_shape=[jax.ShapeDtypeStruct((slots, width), jnp.float32),
                   jax.ShapeDtypeStruct(h_all.shape, h_all.dtype)],
        # the states (operand 7, the prefetched ``live`` counted) come out
        # where they went in: a block no program visits is never touched
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="selective_scan_step",
    )(live.astype(jnp.int32), u, dt, _lanes(b), _lanes(c), a,
      d.reshape(1, width), h_all)
    return y, h_all


def kernel_slots_update(h_all, layer: int, live, dt, u, a, b, c, d, *,
                        interpret=False):
    """The step's Pallas kernel (module docstring)."""
    return _slots_call(h_all, live, dt, u, a, b, c, d, layer=int(layer),
                       tile=tiles(*u.shape, STEP_TILE), interpret=interpret)


def tpu_slots_update(h_all, layer: int, live, dt, u, a, b, c, d, *,
                     interpret=False):
    """What a TPU runs: the kernel where the step's shape tiles."""
    if tiles(*u.shape, STEP_TILE) is None:
        return plain_slots_update(h_all, layer, live, dt, u, a, b, c, d)
    return kernel_slots_update(h_all, layer, live, dt, u, a, b, c, d,
                               interpret=interpret)


def slots_update(h_all, layer: int, live, dt, u, a, b, c, d):
    """One token a slot (module docstring), in the form this platform
    runs."""
    if hw_accel.pallas_interpret(jax.default_backend()):
        return plain_slots_update(h_all, layer, live, dt, u, a, b, c, d)
    return tpu_slots_update(h_all, layer, live, dt, u, a, b, c, d)


def kernel_chunk_scan(h0, dt, u, a, b, c, d, n_valid, *, interpret=False):
    """The Pallas kernel (module docstring); ``interpret`` runs it through
    the Pallas interpreter (tests on the CPU)."""
    return _call(h0, dt, u, a, b, c, d, n_valid,
                 tile=tiles(*u.shape), interpret=interpret)


def tpu_chunk_scan(h0, dt, u, a, b, c, d, n_valid, *, interpret=False):
    """What a TPU runs: the kernel where the launch's shape tiles."""
    if tiles(*u.shape) is None:
        return plain_chunk_scan(h0, dt, u, a, b, c, d, n_valid)
    return kernel_chunk_scan(h0, dt, u, a, b, c, d, n_valid,
                             interpret=interpret)


def chunk_scan(h0, dt, u, a, b, c, d, n_valid):
    """One slot's launch (module docstring), in the form this platform
    runs: Mosaic on a TPU, the plain form where the kernel would be
    interpreted."""
    if hw_accel.pallas_interpret(jax.default_backend()):
        return plain_chunk_scan(h0, dt, u, a, b, c, d, n_valid)
    return tpu_chunk_scan(h0, dt, u, a, b, c, d, n_valid)
