"""Decode-step attention over the page pool's rows, as they lie.

The paged engine's step (``serving/lm_engine.py`` ``_step``) has one shape
of attention for every model family: ``H`` queries a slot over one shared
line a token (the ``gpt`` family through its block-diagonal query, the
latent family by construction). :func:`paged_line_attention` is that op:

* ``q (S, H, Wk)`` float32 — a slot's queries over whole lines;
* ``kpool``, ``vpool`` ``(rows, page, W)`` bfloat16 — the engine's pools,
  untouched (a family with one kind of line passes its pool as both);
* ``rows (S, NB)`` int32 — the pool row of every block of every slot
  (``li * R + block_table``, made on the device);
* ``lengths (S,)`` int32 — the positions a slot sees, 0 for an empty slot;
* ``scale`` — what the scores are multiplied by;
* ``starts (S,)`` int32 — the first position a slot sees (a layer that
  looks back a window only); ``None`` is 0 for every slot. Blocks wholly
  below it are not read: their table entries may name any row.

It returns ``(S, H, Wv)`` float32: softmax(q · lines) · lines, zeros for an
empty slot. Float32 queries, scores, softmax and weighted sum over a
bfloat16 pool: the meaning of ``Precision.HIGHEST`` with nothing lowered.

Two forms, chosen in one place (:func:`paged_line_attention`) by
``utils.hw_accel.pallas_interpret``'s rule:

* :func:`kernel_line_attention` — a Pallas TPU kernel. ``rows`` and
  ``lengths`` are scalar prefetch, the pools stay in HBM, and a block of
  several pages at a time is fetched by asynchronous copies into
  double-buffered VMEM, the next block (of this slot or of the next live
  one) on its way while this one is contracted. Online softmax; a slot of
  length 0 does nothing, nothing before the block of a slot's first visible
  position or past its last block is read, the first block masks its head
  and the last its tail. The float32 operand of each product (the queries,
  the softmax's weights) is split into three bfloat16 terms stacked along
  the rows, so one pass of the pool's bfloat16 lines through the matrix
  unit gives the float32 product exactly (the lines are bfloat16 already:
  the three further passes of a float32 × float32 product would multiply
  zeros).
* :func:`plain_line_attention` — gather every slot's ``NB`` pages, mask,
  softmax: the oracle the kernel is pinned to (``tests/
  test_paged_attention.py``) and what runs where a TPU kernel would only be
  interpreted, so the CPU suites keep their token-exact parity with
  ``models.decoding.make_generate``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import hw_accel

#: most bytes of one pool's lines fetched a block. Pages per block is the
#: largest power of two that fits (8 pages of ``(16, 2048)`` bfloat16, 32 of
#: ``(16, 640)``): stand-alone on a v5e (``tools/paged_attention_forms.py``,
#: PR 28) 20 KB pages at 8, 16, 32 a block took 0.34, 0.28, 0.25 ms a layer
#: and 64 KB pages at 4, 8, 16 took 0.20, 0.13, 0.14
BLOCK_BYTES = 768 * 1024
_MASKED = -1e30


def paged_line_attention(q, kpool, vpool, rows, lengths, scale, starts=None):
    """The step's attention (module docstring), in the form this platform
    runs: Mosaic on a TPU, the plain form where the kernel would be
    interpreted."""
    if hw_accel.pallas_interpret(jax.default_backend()):
        return plain_line_attention(q, kpool, vpool, rows, lengths, scale,
                                    starts)
    return kernel_line_attention(q, kpool, vpool, rows, lengths, scale,
                                 starts)


def gathered_lines(pool, rows):
    """``rows (B, NB)`` of ``pool (rows, page, W)`` → ``(B, NB * page, W)``:
    logical position ``p`` of table ``b`` is line ``(b, p)``. One take
    straight from the pool. Block tables hold rows the pool handed out, so
    "clip" never clips; the default mode would mask the gathered copy
    against out-of-range ids, one more pass over it. Merging ``(NB, page)``
    moves nothing; splitting a line by head would (a re-tiled copy on a
    TPU), so only a program whose context is one slot's does that."""
    lines = jnp.take(pool, rows, axis=0, mode="clip")
    return lines.reshape(rows.shape[0], -1, pool.shape[-1])


def plain_line_attention(q, kpool, vpool, rows, lengths, scale, starts=None):
    """Gather, mask, softmax: every slot's whole block table."""
    exact = jax.lax.Precision.HIGHEST
    ck = gathered_lines(kpool, rows)
    cv = ck if vpool is kpool else gathered_lines(vpool, rows)
    att = jnp.einsum("shj,scj->shc", q, ck, precision=exact) * scale
    visible = jnp.arange(ck.shape[1])[None, :] < lengths[:, None]
    if starts is not None:
        visible &= jnp.arange(ck.shape[1])[None, :] >= starts[:, None]
    att = jax.nn.softmax(jnp.where(visible[:, None, :], att, _MASKED), axis=-1)
    out = jnp.einsum("shc,scj->shj", att, cv, precision=exact)
    return jnp.where((lengths > 0)[:, None, None], out, 0.0)


def _three_terms(x):
    """Float32 ``x`` as three bfloat16 terms whose sum is ``x``."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    low = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, low


def _kernel(rows_ref, meta_ref, q_ref, *refs, S, NB, PB, H, scale, shared):
    if shared:
        k_hbm, o_ref, kbuf, sems, q3_ref, p3_ref, m_ref, l_ref, state = refs
        v_hbm, vbuf = k_hbm, kbuf
    else:
        (k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, q3_ref, p3_ref, m_ref, l_ref,
         state) = refs
    pg = kbuf.shape[2]
    T = PB * pg
    s = pl.program_id(0)
    length = meta_ref[s]
    next_live = meta_ref[2 * S + s]
    start = meta_ref[3 * S + s]
    blocks = (length + T - 1) // T
    lo = start // T  # the block of the first visible position
    next_lo = meta_ref[3 * S + jnp.minimum(next_live, S - 1)] // T

    @pl.when(s == 0)
    def _():
        state[0] = 0  # the buffer the next block to contract lies in
        state[1] = 0  # whether that block's copies have been started

    def copies(act, slot, blk, buf):
        # start, or wait for, the copies of one block's pages into buffer
        # ``buf``. A loop over fours, called from two places, and not PB
        # copies spelled out at four: the kernel's text is traced and
        # lowered at every start-up, and 32 pages a block spelled out cost
        # the latent engine 3.7 s of set-up on a v5e's host (PR 28); not
        # unrolled at all, the step's attention took an eighth longer
        def page(j):
            # a block past the table's end repeats its last page: those
            # positions are past any length
            row = rows_ref[slot * NB + jnp.minimum(blk * PB + j, NB - 1)]
            pairs = ((k_hbm, kbuf),) if shared else ((k_hbm, kbuf),
                                                     (v_hbm, vbuf))
            for i, (hbm, vmem) in enumerate(pairs):
                getattr(pltpu.make_async_copy(
                    hbm.at[row], vmem.at[buf, j], sems.at[i, buf]), act)()

        def four(g, _):
            for u in range(group):
                page(g * group + u)
            return 0

        group = 4 if PB % 4 == 0 else 1
        jax.lax.fori_loop(0, PB // group, four, 0)

    @pl.when(blocks > 0)
    def _():
        first = state[0]
        fetched = state[1]  # 0 only for the call's first live slot
        state[1] = 1
        for i, term in enumerate(_three_terms(q_ref[...] * scale)):
            q3_ref[i * H:(i + 1) * H, :] = term
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

        def body(i, _):
            # one place starts copies and one waits for them: block i + 1
            # of this slot, or the next live slot's first, is on its way
            # while block i is contracted. i is lo - 1 once a call, for the
            # slot nobody fetched ahead for: that pass only starts block lo
            more = i + 1 < blocks

            @pl.when(more | (next_live < S))
            def _():
                copies("start", jnp.where(more, s, next_live),
                       jnp.where(more, i + 1, next_lo),
                       (first + i + 1 - lo) % 2)

            @pl.when(i >= lo)
            def _():
                contract(i, (first + i - lo) % 2)

            return 0

        def contract(i, buf):
            copies("wait", s, i, buf)
            k = kbuf[buf].reshape(T, kbuf.shape[-1])
            sc3 = jax.lax.dot_general(
                q3_ref[...], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # (3H, T)
            sc = sc3[:H] + sc3[H:2 * H] + sc3[2 * H:]
            at = i * T + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            sc = jnp.where((at >= start) & (at < length), sc, _MASKED)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
            # every block holds a visible position, so m_new is a score and
            # a masked one's weight is exp(-1e30 - m_new) == 0
            p = jnp.exp(sc - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
            m_ref[...] = m_new
            for j, term in enumerate(_three_terms(p)):
                p3_ref[j * H:(j + 1) * H, :] = term
            v = vbuf[buf].reshape(T, vbuf.shape[-1])
            o3 = jnp.dot(p3_ref[...], v,
                         preferred_element_type=jnp.float32)  # (3H, Wv)
            o_ref[...] = (alpha * o_ref[...]
                          + o3[:H] + o3[H:2 * H] + o3[2 * H:])

        jax.lax.fori_loop(lo + fetched - 1, blocks, body, 0)
        state[0] = (first + blocks - lo) % 2
        o_ref[...] = o_ref[...] / l_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("scale", "pages_per_block", "interpret"))
def _call(q, kpool, vpool, rows, lengths, starts, *, scale,
          pages_per_block, interpret):
    shared = vpool is None
    S, H0, Wk = q.shape
    NB = rows.shape[1]
    pg = kpool.shape[1]
    Wv = Wk if shared else vpool.shape[2]
    PB = pages_per_block
    # rows of the stacked bfloat16 operands start on a tile row (16)
    H = -(-H0 // 16) * 16
    if H != H0:
        q = jnp.pad(q, ((0, 0), (0, H - H0), (0, 0)))
    lengths = jnp.clip(lengths, 0, NB * pg)
    # a live slot sees a position: every block it visits holds one
    starts = (jnp.zeros_like(lengths) if starts is None
              else jnp.clip(starts, 0, jnp.maximum(lengths - 1, 0)))
    live = lengths > 0
    idx = jnp.arange(S, dtype=jnp.int32)
    # an empty slot's program touches nothing: its query and output blocks
    # are the last live slot's (no copy in or out for a block that stays),
    # and a live slot's last block fetches ahead for the next live one
    stay = jax.lax.cummax(jnp.where(live, idx, 0))
    next_live = jnp.concatenate([
        jax.lax.cummin(jnp.where(live, idx, S), reverse=True)[1:],
        jnp.full((1,), S, jnp.int32)])
    meta = jnp.concatenate([lengths, stay, next_live,
                            starts]).astype(jnp.int32)

    def block(width):
        return pl.BlockSpec((None, H, width),
                            lambda s, rows, meta: (meta[S + s], 0, 0))

    pools = (kpool,) if shared else (kpool, vpool)
    bufs = [pltpu.VMEM((2, PB, pg, p.shape[2]), p.dtype) for p in pools]
    out = pl.pallas_call(
        functools.partial(_kernel, S=S, NB=NB, PB=PB, H=H, scale=scale,
                          shared=shared),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[block(Wk)] + [pl.BlockSpec(memory_space=pl.ANY)
                                    for _ in pools],
            out_specs=block(Wv),
            scratch_shapes=[
                *bufs,
                pltpu.SemaphoreType.DMA((len(pools), 2)),
                pltpu.VMEM((3 * H, Wk), jnp.bfloat16),        # the queries
                pltpu.VMEM((3 * H, PB * pg), jnp.bfloat16),   # the weights
                pltpu.VMEM((H, 1), jnp.float32),              # running max
                pltpu.VMEM((H, 1), jnp.float32),              # running sum
                pltpu.SMEM((2,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((S, H, Wv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_line_attention",
    )(rows.reshape(-1).astype(jnp.int32), meta, q, *pools)
    return jnp.where(live[:, None, None], out[:, :H0], 0.0)


def kernel_line_attention(q, kpool, vpool, rows, lengths, scale, starts=None,
                          *, pages_per_block=None, interpret=False):
    """The Pallas kernel (module docstring). ``pages_per_block`` is derived
    from the line's bytes unless a test or a stand-alone timing names it;
    ``interpret`` runs the kernel through the Pallas interpreter (tests on
    the CPU)."""
    if pages_per_block is None:
        page_bytes = kpool.shape[1] * kpool.shape[2] * kpool.dtype.itemsize
        fit = max(1, BLOCK_BYTES // page_bytes)
        pages_per_block = min(rows.shape[1], 1 << (fit.bit_length() - 1))
    return _call(q, kpool, None if vpool is kpool else vpool, rows, lengths,
                 starts, scale=float(scale), pages_per_block=int(pages_per_block),
                 interpret=interpret)
