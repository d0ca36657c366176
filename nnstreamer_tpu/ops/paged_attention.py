"""Decode-step attention over the page pool's rows, as they lie.

The paged engine's step (``serving/lm_engine.py`` ``_step``) has one shape
of attention for every model family: ``H`` queries a slot over one shared
line a token (the ``gpt`` family through its block-diagonal query, the
latent family by construction). :func:`paged_line_attention` is that op:

* ``q (S, H, Wk)`` float32 — a slot's queries, in one of two operand
  forms, told apart by their width: over whole lines (``Wk`` the pool's
  ``W``: the block-diagonal query, the latent family's absorbed one), or
  head-wide (``Wk = W / KV``, where a line holds ``KV`` key heads side by
  side: the queries as the heads have them, the ``H / KV`` rows of one key
  head together, key head after key head), each key head's rows then
  contracted with that head's ``W / KV`` values of a line alone: the same
  sums without the block-diagonal query's zeros.
  :func:`contracts_by_head` says from a call's shapes which form pays; a
  family asks it and lays its rows out so (``models/families.py``);
* ``kpool``, ``vpool`` ``(rows, page, W)`` bfloat16 — the engine's pools,
  untouched (a family with one kind of line passes its pool as both);
* ``rows (S, NB)`` int32 — the pool row of every block of every slot
  (``li * R + block_table``, made on the device);
* ``lengths (S,)`` int32 — the positions a slot sees, 0 for an empty slot;
* ``scale`` — what the scores are multiplied by;
* ``starts (S,)`` int32 — the first position a slot sees (a layer that
  looks back a window only); ``None`` is 0 for every slot. Pages wholly
  below it are not read: their table entries may name any row.

A verify round has several queries a slot (``queries=K``, ``serving/
lm_engine.py`` ``_round``): ``q (S, K * H, Wk)``, row ``r * H + n`` head
``n`` of the slot's ``r``-th query (head-wide: row ``(g * K + r) * G + n``
the ``n``-th of key head ``g``'s ``G`` query heads, order ``(KV, K, G)``),
which stands ``r`` positions after the
first and sees ``lengths + r`` positions; ``starts (S, K)`` then gives each
query's first position. The lines of all ``K`` positions are in the pool
before the call, so a later query sees an earlier one's line and no earlier
one a later's. ``K = 1`` is the step, and compiles to the kernel it had
before rounds existed.

It returns ``(S, H, Wv)`` float32 (head-wide: ``(S, H, Wv / KV)``, the rows
in the order they came): softmax(q · lines) · lines, zeros for an
empty slot. Float32 queries, scores, softmax and weighted sum over a
bfloat16 pool: the meaning of ``Precision.HIGHEST`` with nothing lowered.

Two forms, chosen in one place (:func:`paged_line_attention`) by
``utils.hw_accel.pallas_interpret``'s rule:

* :func:`kernel_line_attention` — a Pallas TPU kernel. ``rows`` and the
  slots' scalars are scalar prefetch, the pools stay in HBM. A slot's walk
  starts at the first page that holds a visible position
  (:func:`visible_pages`: the rule the host counts ``pages_fetched`` by)
  and goes on in blocks of several pages to the last such page. Only those
  pages are copied, page by page, into a ring of VMEM buffers; the copies
  of a block signal one semaphore a pool, so a whole block is waited for
  once, by a descriptor of the buffer (the sum of their bytes), and a
  slot's last block page by page. The fetches run on from a live slot's
  last block to the next live slot's first, two blocks ahead of the block
  being contracted: each pass of the one loop starts a block's copies,
  waits for the block at hand, and multiplies it. A slot's last block is
  multiplied over a quarter of a block's lines when it holds no more
  pages; what lies in a buffer where no page was copied is zero or an
  older page, never bits the call did not put there (the values' buffers
  are zeroed once a call: a weight of 0 times NaN would be NaN). Online
  softmax; a slot of length 0 does nothing; the first block masks its
  head and the last its tail. The float32 operand of each product (the
  queries, the softmax's weights) is split into three bfloat16 terms
  stacked along the rows, so one pass of the pool's bfloat16 lines through
  the matrix unit gives the float32 product exactly (the lines are
  bfloat16 already: the three further passes of a float32 × float32
  product would multiply zeros). Head-wide rows change the two products
  and nothing else: key head ``g``'s stacked rows against lanes ``g * W /
  KV ..`` of the block's key lines, its softmax terms against the same
  lanes of the value lines, one pair of products a key head, spelled out
  (``KV`` static lane offsets); the walk, the ring, the waits, the mask
  and the softmax are over all rows at once as before, and ``KV = 1`` is
  the whole-line kernel, instruction for instruction.
* :func:`plain_line_attention` — gather every slot's ``NB`` pages, mask,
  softmax: the oracle the kernel is pinned to (``tests/
  test_paged_attention.py``) and what runs where a TPU kernel would only be
  interpreted, so the CPU suites keep their token-exact parity with
  ``models.decoding.make_generate``.

A prefill launch (``_prefill_chunk``) has the other shape: ``C`` rows of ONE
slot. :func:`chunk_line_attention` is its op, one form everywhere: a loop
over the blocks of pages that hold a position some row of the launch sees
(:func:`chunk_walk`: the rule the host counts ``ctx_read`` by), each block's
lines taken from the pool through the slot's rows and split by key head,
online softmax in float32 across blocks. How many blocks is a value of the
call, so one compiled launch serves every ``start``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import hw_accel

#: most bytes of one pool's lines fetched a block. Pages per block is the
#: largest power of two that fits (16 pages of ``(16, 2048)`` bfloat16, 64 of
#: ``(16, 640)``, ``(16, 512)`` or ``(64, 128)``). Every block costs one chain
#: of product, maximum, exponential, product that nothing overlaps, so a
#: larger block is faster until its buffers (three a pool) crowd VMEM.
#: Stand-alone on a v5e at the cells' lengths (``tools/
#: paged_attention_forms.py``, PR 37), ms a layer at 16, 32, 64 pages a
#: block: kanana 0.241, 0.211, 0.206; a mellum full layer 0.953, 0.776,
#: 0.694 and a window layer 0.213, 0.180, 0.165; jamba 0.609, 0.557, 0.566;
#: at 8, 16 pages of 64 KB: opt saturated 0.119, 0.120, every slot full
#: 0.375, 0.376
BLOCK_BYTES = 1280 * 1024
_MASKED = -1e30


def contracts_by_head(stacked_rows: int, key_heads: int,
                      head_width: int) -> bool:
    """Whether a call whose slot stacks ``stacked_rows`` bfloat16 query rows
    (3 terms x queries x heads) over lines of ``key_heads`` heads of
    ``head_width`` values contracts each key head's rows with that head's
    part of a line alone (head-wide operands), or all rows with whole lines
    (the block-diagonal query). The one place the form is chosen: a family
    asks before it lays its step's queries out.

    A tile of lines takes the matrix unit its own load (128 rows' worth)
    however few rows ride through, so whole lines cost one pass of every
    tile while a slot's stacked rows fit a tile, and ``ceil(rows / 128)``
    passes beyond; by head every tile is loaded once for ``rows /
    key_heads`` rows, and the block-diagonal query and result, ``key_heads``
    times the heads' own, are never built. By head pays where the rows pass
    a tile more than once and a head's rows fill a bfloat16 tile (16); the
    head's part of a line must be whole lanes (128) to be sliced where it
    lies.

    Stand-alone on a v5e (``tools/paged_attention_forms.py``, PR 48), ms a
    layer, whole lines | by head, at the cells' lengths and with every slot
    full: K-EXAONE's round (384 rows, 48 a head) full kind 1.259 | 0.594 and
    3.044 | 1.574, window kind 0.461 | 0.258 and 0.468 | 0.240; its step
    (192 rows, 24 a head) full 0.953 | 0.694 and 1.905 | 1.584, window
    0.271 | 0.240 and 0.231 | 0.236; a mellum full layer (96 rows, 24 a
    head) 0.725 | 0.607 and 1.351 | 1.155: by head pays there too, left to
    its own issue (a round of rows more than a tile is the rule's line for
    now); ``opt_1.3b`` (96 rows, 3 a head of 64, padded to 16) 0.121 | 0.137
    saturated, 0.031 | 0.035 chat, 0.376 | 0.375 full
    """
    return (key_heads > 1 and head_width % 128 == 0 and stacked_rows > 128
            and stacked_rows // key_heads >= 16)


def paged_line_attention(q, kpool, vpool, rows, lengths, scale, starts=None,
                         queries=1):
    """The step's attention (module docstring), in the form this platform
    runs: Mosaic on a TPU, the plain form where the kernel would be
    interpreted."""
    if hw_accel.pallas_interpret(jax.default_backend()):
        return plain_line_attention(q, kpool, vpool, rows, lengths, scale,
                                    starts, queries)
    return kernel_line_attention(q, kpool, vpool, rows, lengths, scale,
                                 starts, queries=queries)


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


def plain_line_attention(q, kpool, vpool, rows, lengths, scale, starts=None,
                         queries=1):
    """Gather, mask, softmax: every slot's whole block table. Both operand
    forms: whole-line queries, or head-wide ones over each key head's part
    of the gathered lines."""
    exact = jax.lax.Precision.HIGHEST
    ck = gathered_lines(kpool, rows)
    cv = ck if vpool is kpool else gathered_lines(vpool, rows)
    S, H, Dk = q.shape
    KV = ck.shape[2] // Dk            # key heads whose rows lie together
    if KV > 1:
        q = q.reshape(S, KV, H // KV, Dk)
        ck, cv = (c.reshape(S, c.shape[1], KV, -1) for c in (ck, cv))
        att = jnp.einsum("sgrj,scgj->sgrc", q, ck, precision=exact)
        att = att.reshape(S, H, -1) * scale
    else:
        att = jnp.einsum("shj,scj->shc", q, ck, precision=exact) * scale
    if queries == 1:
        visible = jnp.arange(ck.shape[1])[None, :] < lengths[:, None]
        if starts is not None:
            visible &= jnp.arange(ck.shape[1])[None, :] >= starts[:, None]
        visible = visible[:, None, :]
    else:
        # (S, K, ctx), then every head of a query alike, in every key
        # head's rows
        at = jnp.arange(ck.shape[1])
        visible = at < (lengths[:, None] + jnp.arange(queries))[..., None]
        if starts is not None:
            visible &= at >= starts[..., None]
        visible = jnp.tile(jnp.repeat(visible, H // KV // queries, axis=1),
                           (1, KV, 1))
    att = jax.nn.softmax(jnp.where(visible, att, _MASKED), axis=-1)
    if KV > 1:
        out = jnp.einsum("sgrc,scgj->sgrj", att.reshape(S, KV, H // KV, -1),
                         cv, precision=exact).reshape(S, H, -1)
    else:
        out = jnp.einsum("shc,scj->shj", att, cv, precision=exact)
    return jnp.where((lengths > 0)[:, None, None], out, 0.0)


def _three_terms(x):
    """Float32 ``x`` as three bfloat16 terms whose sum is ``x``."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    low = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, low


def visible_pages(lengths, starts, page):
    """The kernel's rule, a slot: the first page of its table that holds a
    visible position and how many pages from there on do (0 for an empty
    slot). ``lengths`` and ``starts`` are arrays of one shape, numpy's on
    the host or jax's on the device: the kernel's scalars are this
    function's output, and so is the engine's ``pages_fetched``."""
    first = starts // page
    return first, (-(-lengths // page) - first) * (lengths > 0)


def pages_fetched(lengths, starts, page):
    """Pages one call of the kernel copies from a pool, over all slots
    (host arrays): every page that holds a visible position, once."""
    return int(visible_pages(lengths, starts, page)[1].sum())


#: most bytes of one block's float32 scores in a launch's walk: a block is
#: the largest power of two of pages whose scores, ``rows of queries x
#: positions``, fit (256 positions for 32 heads x 256 rows). The walk reads
#: whole blocks, so a larger one wastes more positions past a launch's last
#: row and a smaller one pays the loop's fixed cost more often
SCORE_BYTES = 8 * 1024 * 1024


def chunk_block_pages(query_rows: int, page: int, blocks: int) -> int:
    """Pages a block of a launch's walk: from the launch's query rows
    (heads x rows) and the page's positions, by ``SCORE_BYTES``; at least
    one page and at most the slot's ``blocks``."""
    fit = max(1, SCORE_BYTES // (4 * query_rows * page))
    return min(blocks, 1 << (fit.bit_length() - 1))


def chunk_walk(start, n_valid, span, page, pages_per_block):
    """The walk's rule, a launch of rows ``start .. start + n_valid - 1``
    in a layer whose queries look back ``span`` positions (the window, or
    the serving limit where a layer sees everything): the first page of the
    slot's table that holds a position the launch's first row sees, and how
    many blocks of ``pages_per_block`` pages from there on reach the last
    row's. Python or numpy integers on the host, jax's on the device: the
    loop's bounds are this function's output, and so is the engine's
    ``ctx_read``."""
    first = start - span + 1
    first = first * (first > 0) // page
    pages = -(-(start + n_valid) // page) - first
    return first, -(-pages // pages_per_block)


def chunk_line_attention(q, kpool, vpool, rows, start, n_valid, scale, span,
                         *, precision=jax.lax.Precision.HIGHEST,
                         pages_per_block=None):
    """One slot's launch over the lines its slot holds (module docstring).

    ``q (C, KV, G, Wk // KV)`` float32: row ``c`` is position ``start + c``,
    ``G`` query heads read each of the ``KV`` key heads that lie side by
    side in a line; ``rows (NB,)`` the pool row of every block of the slot;
    ``start``, ``n_valid`` int32 scalars; ``span`` how far back a query
    sees (static). Row ``c`` sees positions ``p`` with ``0 <= start + c - p
    < span``; a padded row (``c >= n_valid``) sees what the last real row
    sees, so no line past the launch's own is ever scored. Returns ``(C,
    KV, G, Wv // KV)`` float32. ``precision`` is that of the two products
    (queries by keys, the softmax's weights by values), a
    ``jax.lax.Precision`` or its name: ``HIGHEST`` is float32 arithmetic over the bfloat16 pool with nothing lowered, as the
    step's kernel has it; a family whose launch has always multiplied at
    jax's default says so (``family.chunk_precision``).
    ``pages_per_block`` is derived unless a test or a stand-alone timing
    names it.

    The walk is a jitted function of its own, so a program that calls it
    once a layer traces and lowers it once a shape and not once a layer
    (the launch's trace is paid at every start-up)."""
    C, KV, G, _ = q.shape
    PB = pages_per_block or chunk_block_pages(KV * G * C, kpool.shape[1],
                                              rows.shape[0])
    return _walk(q, kpool, None if vpool is kpool else vpool, rows, start,
                 n_valid, scale=float(scale), span=int(span),
                 precision=jax.lax.Precision(precision or "default"),
                 pages_per_block=int(PB))


@functools.partial(jax.jit, static_argnames=("scale", "span", "precision",
                                             "pages_per_block"))
def _walk(q, kpool, vpool, rows, start, n_valid, *, scale, span, precision,
          pages_per_block):
    C, KV, G, _ = q.shape
    NB, pg, PB = rows.shape[0], kpool.shape[1], pages_per_block
    T = PB * pg
    first, blocks = chunk_walk(start, n_valid, span, pg, PB)
    # row c sees position p where 0 <= seen[c] - p < span; ``seen`` from
    # the walk's first position on, and a block's positions from its own
    seen = (jnp.minimum(jnp.arange(C), n_valid - 1) + start - first * pg)
    at = jnp.arange(T)
    # the walk's last block may run past the table: padded with its last
    # row, whose positions there are past every row's
    rows = jnp.concatenate([rows, jnp.broadcast_to(rows[-1:], (PB,))])
    q = q * scale
    # the program is loaded at every start-up by the count of its
    # instructions (PERF.md section 6, PR 42), and this body is there once
    # a layer: one slice of the table, one plain gather a pool (the table
    # holds rows the pool handed out, so nothing is clamped or wrapped),
    # one comparison a side of the mask
    take = jax.lax.GatherDimensionNumbers(
        offset_dims=(1, 2), collapsed_slice_dims=(0,), start_index_map=(0,))

    def lines(pool, r):
        got = jax.lax.gather(
            pool, r[:, None], take, (1, *pool.shape[1:]),
            mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
        return got.reshape(T, KV, -1)

    def block(b, carry):
        m, l, o = carry                       # (KV, C, G), ..., (.., Wv/KV)
        r = jax.lax.dynamic_slice(rows, (first + b * PB,), (PB,))
        k = lines(kpool, r)
        v = k if vpool is None else lines(vpool, r)
        # (C, KV, G, D) x (T, KV, D) -> (KV, C, G, T)
        sc = jax.lax.dot_general(q, k, (((3,), (2,)), ((1,), (1,))),
                                 precision=precision,
                                 preferred_element_type=jnp.float32)
        back = (seen - b * T)[:, None] - at[None, :]
        visible = back >= 0
        if span < NB * pg:    # a layer that looks back a window only
            visible &= back < span
        sc = jnp.where(visible[:, None, :], sc, _MASKED)
        # a row that has seen nothing yet keeps m at _MASKED and weighs the
        # block's lines by 1; the first score it sees wipes that (alpha 0),
        # and every row sees its own position before the walk ends
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        o = alpha[..., None] * o + jax.lax.dot_general(
            p, v, (((3,), (0,)), ((0,), (1,))), precision=precision,
            preferred_element_type=jnp.float32)
        return m_new, alpha * l + p.sum(axis=-1), o

    Wv = (kpool if vpool is None else vpool).shape[2]
    m, l, o = jax.lax.fori_loop(0, blocks, block, (
        jnp.full((KV, C, G), _MASKED, jnp.float32),
        jnp.zeros((KV, C, G), jnp.float32),
        jnp.zeros((KV, C, G, Wv // KV), jnp.float32)))
    return jnp.moveaxis(o / l[..., None], 0, 1)


def _kernel(rows_ref, meta_ref, q_ref, *refs, S, NB, PB, sizes, R, scale,
            shared, K=1, Hq=None, KV=1):
    # ``KV`` key heads' rows, ``R`` each, one head after the other: every
    # product below is a key head's rows by that head's part of the lines
    # (``KV = 1``: all rows by whole lines)
    if shared:
        k_hbm, o_ref, kbuf, sems, q3_ref, p3_ref, m_ref, l_ref, state = refs
        v_hbm, vbuf = k_hbm, kbuf
    else:
        (k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, q3_ref, p3_ref, m_ref, l_ref,
         state) = refs
    pairs = ((k_hbm, kbuf),) if shared else ((k_hbm, kbuf), (v_hbm, vbuf))
    ring, pg = kbuf.shape[0], kbuf.shape[2]
    s = pl.program_id(0)
    length = meta_ref[s]
    start = meta_ref[3 * S + s]
    first = meta_ref[4 * S + s]   # the first page that holds a visible line
    count = meta_ref[5 * S + s]   # and how many from there on do

    @pl.when(s == 0)
    def _():
        state[0] = 0  # the buffer the next block to contract lies in
        state[1] = 0  # the buffer the next block to fetch goes to
        state[2] = meta_ref[6 * S]  # the slot of the next block to fetch
        state[3] = 0  # and which of that slot's blocks it is
        # a block's buffer holds stale lines where no page was copied into
        # it (the tail of a slot's last block): their weight is 0, and 0
        # times whatever bits lie there must be 0
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)

    def fetch(*_):
        # start the copies of the next block in the call's order, the
        # blocks of every live slot one after the other, and move the
        # cursor on. Only pages that hold a visible position are copied.
        # Loops over fours and then ones, traced in two places, and not PB
        # copies spelled out: the kernel's text is traced and lowered at
        # every start-up, and 32 pages a block spelled out cost the latent
        # engine 3.7 s of set-up on a v5e's host (PR 28); not unrolled at
        # all, the step's attention took an eighth longer
        slot = state[2]

        @pl.when(slot < S)
        def _():
            blk, buf = state[3], state[1]
            pages = meta_ref[5 * S + slot]
            n = jnp.minimum(PB, pages - blk * PB)
            base = slot * NB + meta_ref[4 * S + slot] + blk * PB

            def page(j):
                row = rows_ref[base + j]
                for i, (hbm, vmem) in enumerate(pairs):
                    pltpu.make_async_copy(hbm.at[row], vmem.at[buf, j],
                                          sems.at[i, buf]).start()

            def several(g, _):
                for u in range(group):
                    page(g * group + u)
                return 0

            def one(j, _):
                page(j)
                return 0

            group = 4 if PB % 4 == 0 else 1
            whole = n // group
            jax.lax.fori_loop(0, whole, several, 0)
            jax.lax.fori_loop(whole * group, n, one, 0)
            last = (blk + 1) * PB >= pages
            state[3] = jnp.where(last, 0, blk + 1)
            state[2] = jnp.where(last, meta_ref[2 * S + slot], slot)
            state[1] = jnp.where(buf + 1 == ring, 0, buf + 1)
        return 0

    def arrived(buf, n):
        # a whole block's copies signalled one semaphore a pool: one wait
        # for the sum of their bytes; a slot's last block page by page
        @pl.when(n == PB)
        def _():
            for i, (_, vmem) in enumerate(pairs):
                pltpu.make_async_copy(vmem.at[buf], vmem.at[buf],
                                      sems.at[i, buf]).wait()

        @pl.when(n < PB)
        def _():
            def one(j, _):
                for i, (_, vmem) in enumerate(pairs):
                    pltpu.make_async_copy(vmem.at[buf, j], vmem.at[buf, j],
                                          sems.at[i, buf]).wait()
                return 0

            jax.lax.fori_loop(0, n, one, 0)

    def stack(ref, terms, width):
        # a key head's three terms one under the other, head after head
        for j, term in enumerate(terms):
            for g in range(KV):
                ref[(3 * g + j) * R:(3 * g + j + 1) * R, :width] = \
                    term[g * R:(g + 1) * R]

    def contract(at0, buf, size):
        # the products over the first ``size`` pages of buffer ``buf``,
        # whose first line is position ``at0``
        T = size * pg

        def lines(ref, g):
            # key head ``g``'s part of the block's lines: whole lanes
            width = ref.shape[-1] // KV
            return ref[buf, :size, :, g * width:(g + 1) * width].reshape(
                T, width)

        def products(terms, width, g, lines, over):
            # head ``g``'s three terms by its lines, one under the other:
            # their sum is the float32 product of its rows
            return jax.lax.dot_general(
                terms[3 * R * g:3 * R * (g + 1), :width], lines,
                (((1,), (over,)), ((), ())),
                preferred_element_type=jnp.float32)      # (3R, ...)

        sc = []
        for g in range(KV):
            sc3 = products(q3_ref, q3_ref.shape[1], g, lines(kbuf, g), 1)
            sc.append(sc3[:R] + sc3[R:2 * R] + sc3[2 * R:])
        sc = sc[0] if KV == 1 else jnp.concatenate(sc, axis=0)   # (H, T)
        at = at0 + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        if K == 1:
            sc = jnp.where((at >= start) & (at < length), sc, _MASKED)
        else:
            # row r * Hq + n of a key head's is the slot's r-th query: one
            # position more visible a query, from that query's own first
            # position
            row = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
            if KV > 1:
                row = jax.lax.rem(row, R)
            lo = jnp.full(sc.shape, start, jnp.int32)
            hi = jnp.full(sc.shape, length, jnp.int32)
            for r in range(1, K):
                later = row >= r * Hq
                lo = jnp.where(later, meta_ref[6 * S + 1 + (r - 1) * S + s],
                               lo)
                hi = hi + later.astype(jnp.int32)
            sc = jnp.where((at >= lo) & (at < hi), sc, _MASKED)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        # every block holds a position the slot's first query sees, so its
        # m_new is a score and a masked one's weight is exp(-1e30 - m_new)
        # == 0. A later query may see nothing in the walk's first block
        # (its first position lies a page on): it weighs that block's
        # lines by 1 until the first score it sees wipes them (alpha 0),
        # and it sees its own position before the walk ends
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        stack(p3_ref, _three_terms(p), T)
        for g in range(KV):
            own = slice(g * R, (g + 1) * R)
            o3 = products(p3_ref, T, g, lines(vbuf, g), 0)
            o_ref[own, :] = (alpha[own] * o_ref[own, :]
                             + o3[:R] + o3[R:2 * R] + o3[2 * R:])

    @pl.when(count > 0)
    def _():
        stack(q3_ref, _three_terms(q_ref[...] * scale), q3_ref.shape[1])
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

        @pl.when(s == meta_ref[6 * S])
        def _():
            # the call's first live slot: nothing is on its way yet
            jax.lax.fori_loop(0, ring - 1, fetch, 0)

        def body(b, _):
            # ring - 1 blocks are on their way; one more is started, then
            # one wait for block b's bytes, then its products
            fetch()
            buf = state[0]
            n = jnp.minimum(PB, count - b * PB)
            arrived(buf, n)
            for size, below in zip(sizes, (*sizes[1:], 0)):
                @pl.when((n > below) & (n <= size))
                def _(size=size):
                    contract((first + b * PB) * pg, buf, size)
            state[0] = jnp.where(buf + 1 == ring, 0, buf + 1)
            return 0

        jax.lax.fori_loop(0, (count + PB - 1) // PB, body, 0)
        o_ref[...] = o_ref[...] / l_ref[...]


#: blocks on their way while one is contracted
_AHEAD = 2


@functools.partial(jax.jit,
                   static_argnames=("scale", "pages_per_block", "interpret",
                                    "queries"))
def _call(q, kpool, vpool, rows, lengths, starts, *, scale,
          pages_per_block, interpret, queries=1):
    shared = vpool is None
    K = queries
    S, H0, Dk = q.shape
    NB = rows.shape[1]
    pg = kpool.shape[1]
    # the key heads whose rows are contracted apart: 1 where the queries
    # span whole lines
    KV = kpool.shape[2] // Dk
    Dv = (kpool if shared else vpool).shape[2] // KV
    PB = pages_per_block
    # a slot's last block is contracted over a quarter of a block's lines
    # when it holds no more pages than that: 128 positions at the least
    short = max(PB // 4, -(-128 // pg))
    sizes = (PB, short) if short < PB else (PB,)
    # rows of the stacked bfloat16 operands start on a tile row (16)
    R0 = H0 // KV
    R = -(-R0 // 16) * 16
    H = KV * R
    if R != R0:
        # every key head's rows padded apart
        q = jnp.pad(q.reshape(S * KV, R0, Dk),
                    ((0, 0), (0, R - R0), (0, 0))).reshape(S, H, Dk)
    lengths = jnp.clip(lengths, 0, NB * pg)
    if K == 1:
        # a live slot sees a position: every block it visits holds one
        starts = (jnp.zeros_like(lengths) if starts is None
                  else jnp.clip(starts, 0, jnp.maximum(lengths - 1, 0)))
        last, later = lengths, ()
    else:
        # the walk runs from the first query's first position to the last
        # query's last; each query's own bounds ride in ``meta``
        seen = lengths[:, None] + jnp.arange(K, dtype=lengths.dtype)
        starts = (jnp.zeros_like(seen) if starts is None
                  else jnp.clip(starts, 0, jnp.maximum(seen - 1, 0)))
        last = jnp.where(lengths > 0,
                         jnp.minimum(lengths + (K - 1), NB * pg), 0)
        starts, later = starts[:, 0], tuple(starts[:, 1:].T)
    live = lengths > 0
    idx = jnp.arange(S, dtype=jnp.int32)
    # an empty slot's program touches nothing: its query and output blocks
    # are the last live slot's (no copy in or out for a block that stays),
    # and the fetches run on from a live slot's last block to the next
    # live slot's first
    stay = jax.lax.cummax(jnp.where(live, idx, 0))
    after = jax.lax.cummin(jnp.where(live, idx, S), reverse=True)
    next_live = jnp.concatenate([after[1:], jnp.full((1,), S, jnp.int32)])
    meta = jnp.concatenate([lengths, stay, next_live, starts,
                            *visible_pages(last, starts, pg),
                            after[:1], *later]).astype(jnp.int32)

    def block(width):
        return pl.BlockSpec((None, H, width),
                            lambda s, rows, meta: (meta[S + s], 0, 0))

    pools = (kpool,) if shared else (kpool, vpool)
    bufs = [pltpu.VMEM((_AHEAD + 1, PB, pg, p.shape[2]), p.dtype)
            for p in pools]
    out = pl.pallas_call(
        functools.partial(_kernel, S=S, NB=NB, PB=PB, sizes=sizes, R=R,
                          scale=scale, shared=shared, K=K, Hq=R0 // K, KV=KV),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[block(Dk)] + [pl.BlockSpec(memory_space=pl.ANY)
                                    for _ in pools],
            out_specs=block(Dv),
            scratch_shapes=[
                *bufs,
                pltpu.SemaphoreType.DMA((len(pools), _AHEAD + 1)),
                pltpu.VMEM((3 * H, Dk), jnp.bfloat16),        # the queries
                pltpu.VMEM((3 * H, PB * pg), jnp.bfloat16),   # the weights
                pltpu.VMEM((H, 1), jnp.float32),              # running max
                pltpu.VMEM((H, 1), jnp.float32),              # running sum
                pltpu.SMEM((4,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((S, H, Dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_line_attention",
    )(rows.reshape(-1).astype(jnp.int32), meta, q, *pools)
    if KV > 1 and R != R0:
        out = out.reshape(S * KV, R, Dv)[:, :R0].reshape(S, H0, Dv)
    return jnp.where(live[:, None, None], out[:, :H0], 0.0)


def kernel_line_attention(q, kpool, vpool, rows, lengths, scale, starts=None,
                          *, queries=1, pages_per_block=None,
                          interpret=False):
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
                 interpret=interpret, queries=int(queries))
