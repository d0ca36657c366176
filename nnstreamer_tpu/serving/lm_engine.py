"""The paged continuous-decode engine (L6 serving ← models/families.py).

The batched-generation paths in ``models/lm_serving.py`` decode a FIXED
batch: everyone prefills together, everyone steps together, the batch
drains before the next one forms. Continuous batching needs per-slot
independence: each sequence has its own position and lifetime. Here one
compiled program steps every slot, each against its own block table into
a shared page pool and its own position: the math of S independent
batch-1 decoders, issued as ONE device call per token.

Join protocol (``engine.DecodeEngine``, driven by ``DecodeScheduler``):

* ``admit_start(slot, prompt, steps)`` queues the prompt and
  ``prefill_tick()`` ingests one fixed-size chunk of one pending prompt a
  call: the chunk is the ONLY compiled prefill shape, so ``compile_count``
  stays flat across prompt lengths. Its width is the engine's to choose
  (``prefill_width``): as wide as the chip's ridge, where the chip is known.
  A launch runs no head. Behind a prompt's last launch ``_seed`` makes
  the first token on the device (the head over the launch's one last real
  row) and puts it into the decode carry there; with a step in flight the
  pass's step is dispatched behind them before the token is pulled, so the
  device never waits for the host to have read it (``prefill_tick``);
* ``step()``: one decode step over ALL slots. Inactive slots write to a
  null page and read nothing (static shapes are the point). The engine
  keeps ONE step in flight: a call dispatches the next step and only then
  brings home the tokens of the one before, so the device runs while the
  host routes, retires and admits (``PagedLMEngine.step``); ``collect()``
  brings home what is in flight for a caller that wants each step's own
  tokens;
* ``step_tokens()``: where the family drafts (``family.drafts``), the pass
  is a *round* in place of a step: the draft verified over two positions a
  slot and the next one drafted, one device call, 1 or 2 tokens a slot
  (``PagedLMEngine._step_tokens``; ``step`` then raises). One round in
  flight, as a step is;
* ``release(slot)`` returns the slot's pages to the pool.

Greedy (argmax) decoding only — sampling policy belongs to the caller's
model entry; the scheduler contract is deterministic token streams. The
tests' reference is ``models.decoding.make_generate`` (tests/
test_kv_paged.py ``_dense_baseline``).

Spans (``obs.context.span``, always on, docs/observability.md): each call
of a program is split where the host does three different things,
``engine.<step|chunk>.prepare`` (page bookkeeping, padding), ``.dispatch``
(uploads and the jitted call until it returns) and ``.pull`` (the device's
answer brought to the host; a step's pull is the wait for the step BEFORE
the one just dispatched; a chunk's pull is a prompt's first token, behind
the pass's step where that rode ahead). ``host_s`` and ``pull_s`` are the
running sums of the first two and of the third. Start-up has its own: the
serving entry
builds the engine under ``setup.engine``
(``models.lm_serving.make_continuous``), and the first call of each jitted
program runs under a ``program.first_call`` child of the span that made it,
where the compile account (``obs.context.compile_account``) writes what jax
spent tracing, lowering and compiling or loading it.
"""
from __future__ import annotations

import itertools
import math
from typing import Optional

import numpy as np

from ..obs import context as obs_context
from ..obs import memory as obs_memory
from ..utils import flops
from .engine import DecodeEngine
from .request import ServingError

_engine_ids = itertools.count()


def prefill_width(chunk: int, max_seq: int, page_size: int,
                  weight_bytes: int, device=None) -> int:
    """The one compiled width of a prefill launch, in tokens.

    A launch reads every weight once whatever its width, so under the
    chip's ridge its time is the reading and more rows are free: ``rows =
    peak FLOP/s / HBM bytes/s x weight_bytes / 2`` is where a dense product
    (2 FLOPs a weight a row) stops being bound by it. The width is ``rows``
    rounded up to a power of two, then to whole pages, never under ``chunk``
    and at most ``max_seq`` (v5e, bfloat16: 240.5 -> 256). Where the chip
    is unknown (``utils.flops.ridge_flops_per_byte`` is None: the CPU)
    ``chunk`` stands as given; a TPU missing from the tables raises."""
    ridge = flops.ridge_flops_per_byte(device)
    width = chunk
    if ridge is not None:
        rows = 1 << (math.ceil(ridge * weight_bytes / 2) - 1).bit_length()
        width = max(chunk, -(-rows // page_size) * page_size)
    return min(width, max_seq)


def write_rows(pool, row0, dest, offs, lines):
    """``lines (..., width)`` -> position ``offs`` of page ``dest`` of the
    layer whose rows start at ``row0``: a scatter on the pool's two leading
    axes, one update a line (a step's and a verify round's form: their
    lines are single positions of different slots)."""
    return pool.at[row0 + dest, offs].set(lines.astype(pool.dtype))


def write_pages(pool, row0, pages, own, lines):
    """A launch's lines into ``pool`` a page at a time: ``lines (n, page,
    width)``, the launch's rows cut into the pages they fill, go to rows
    ``row0 + pages (n,)`` of the pool's leading axis as ONE scatter of ``n``
    updates, each a whole page, where the row form issues an update a line
    (a scatter costs its updates, about 0.2 us each on a v5e whatever
    their bytes). ``own (n, page, 1)`` says which positions are the
    launch's: at the others a page keeps what it held (in a prompt's last
    page the positions a decode step has yet to write; all of a layer's
    null page, where the pages wholly past the launch's rows go). The
    launch starts on a page's edge (``admit_start`` sees to it)."""
    import jax.numpy as jnp

    rows = row0 + pages
    return pool.at[rows].set(
        jnp.where(own, lines.astype(pool.dtype), pool[rows]))


class _LowersShort:
    """A jitted program whose last argument joined it later: ``lower`` also
    takes the argument list as it was before (``short`` arguments; what the
    benchmark's drivers lower ``_step`` by to read its operations' scopes,
    and they are not this tree's to edit) and appends the missing one's
    shape, so that what they compile is the program that runs."""

    def __init__(self, jitted, short: int, last: tuple):
        self._jitted, self._short, self._last = jitted, short, last

    def __call__(self, *args):
        return self._jitted(*args)

    def lower(self, *args):
        if len(args) == self._short:
            import jax

            shape, dtype = self._last
            # on the device its neighbours are described for, if any
            args += (jax.ShapeDtypeStruct(
                shape, dtype, sharding=getattr(args[2], "sharding", None)),)
        return self._jitted.lower(*args)


class PagedLMEngine(DecodeEngine):
    """Block-table paged continuous decoder (the ROADMAP item 4 engine).

    No slot owns a ``max_seq`` cache: the engine draws fixed-size pages
    from a :class:`~.kv_pool.KVPagePool` and addresses them through
    per-slot block tables, gathered/scattered inside the jitted programs:

    * **model family** — what a layer is and what it keeps per token comes
      from ``models/families.py`` (``family_of(cfg)``, by the
      configuration's type): the embedding, per layer the query side and
      the lines to write, the finish of each attention form, the feed-forward,
      the head, and the pool's geometry (``cache_lines``: one width per
      pool). The GPT block keeps keys and values (two pools); the
      DeepSeek-V3 block keeps one latent line that every head reads.
    * **layer kinds** — a family says of each layer whether it is
      ``"full"`` (a query sees every earlier position) or ``"window"`` (the
      last ``family.window`` only). Each kind present has its own block
      table (``_bts[kind]``, ``(slots, blocks)``), its own page allocator
      (``pools_by_kind[kind]``, a ``KVPagePool``) and its own pool arrays,
      whose rows belong to the layers of that kind alone: a page id names
      one row in every layer of its kind and nothing in the other's. A
      live slot holds every page of its context in the full kind and, in
      the window kind, only those some future query can still see (and the
      launch in flight during prefill: ``held_blocks["window"]`` at most);
      the others go back to the window kind's allocator as it advances
      (``_release_behind``, before every chunk and step). A family whose
      layers are all full (``gpt``, ``deepseek_v3``) has one table, one
      allocator and the programs it had before kinds existed.
    * **state layers** — a family may say ``"state"`` of a layer: a mixer
      that keeps a state a *sequence* and no line a token
      (``family.state_lines``: shapes and dtypes). Such layers have no
      table, no allocator and no pages: per entry of ``state_lines`` one
      device array ``(state layers, slots, *shape)`` (``_states``), donated
      through ``_step`` and ``_prefill_chunk`` beside the pools. A launch
      reads its slot's rows, or zeros where it starts a sequence (``start
      == 0``: whatever the slot held before is gone), and writes them
      back, so a prompt's last launch hands the step its state; a padded
      row of a launch moves nothing (the family's ``mix_chunk``: its step
      size is zero and the conv's next inputs are taken before row
      ``n_valid``); the step advances the slots in ``mask`` and stores for
      every other slot what it read, bit for bit (``mix_step``).
      ``release`` leaves the rows to the next admission's reset;
      ``preempt`` returns them beside the pages and ``restore`` puts both
      back. A fixed cost a slot, not a cost a token: ``cache_bytes``,
      ``memory_bytes()["state"]`` and ``state_stats()`` count it,
      ``projected_page_bytes`` charges no request for it. Prefix sharing
      is refused for such a family: a hit would need the state as it was
      at the prefix's last token.
    * **attention layers with a state** — a family may also say that its
      *attention* layers keep a state a slot beside the lines a token
      keeps there (``family.slot_lines``; ``zaya``: the last rows its
      projection's convolutions and value shift reach back to). One more
      array ``(attention layers, slots, *shape)`` per entry, behind the
      state layers' in ``_states`` and treated as they are everywhere on
      the host (donated through both programs, zeroed by the launch that
      starts a sequence, carried by ``preempt`` / ``restore``, counted in
      ``cache_bytes``, ``memory_bytes()["state"]`` and ``state_stats()``,
      prefix sharing refused). In the programs the family's ``project``
      takes a batch entry's rows and how many of the entry's rows are
      real, and returns them as they stand after the last real one: in
      ``_step`` one real row for a slot in ``mask`` and none for any other
      (whose rows come back as read, bit for bit), in ``_prefill_chunk``
      the launch's ``n_valid``, so that what is stored is the state after
      row ``n_valid - 1``.
    * **the stack's carry and merge** — a family may thread one opaque
      value from each layer's feed-forward to the next's
      (``family.open_stack``, ``ffn_carry``; ``zaya``'s router
      activations), and says what the residual stream does with a part's
      output (``family.merge``; ``x + y`` for every family but ``zaya``).
      The engine looks into neither.
    * **passes** — a family may run its stack several times a token with
      the same weights (``family.passes``; ``ouro``): it keeps a line for
      every pass of every layer, so wherever this class counts the layers
      of a kind (``kind_layers``, the pools' rows, ``token_bytes``,
      ``page_bytes``, ``projected_page_bytes``, ``memory_bytes``, the page
      movers, preempt blobs, copy-on-write) it counts ``passes x layers``
      pass-layers, and pass ``t`` of the ``i``-th layer of a kind owns the
      rows of pass-layer ``t * layers of the kind + i`` and reads no other
      pass's. The passes are ONE ``fori_loop`` in ``_step`` and in
      ``_prefill_chunk`` whose body is the stack and whose carry holds the
      pools (aliased in and out: nothing of a pool's size is copied), the
      activations, the counts and what the family carries between passes
      (``open_passes`` / ``close_pass`` / ``exit_rows``: the engine never
      looks into it). A family with one pass takes no loop and has the
      programs it had before passes existed. Prefix sharing serves such a
      family as any other (a page carries every pass-layer's lines);
      speculative verification does not.
    * **rounds** — a family may draft (``family.drafts``: ``exaone_moe``'s
      MTP layer, one token): then the decode program is ``_round`` and the
      engine is its own burst engine (``step_tokens``, ``acceptance_rate``,
      ``spec_rounds | proposed | accepted | emitted``; no wrapper, no
      host-side draft). The device carries ``[token, draft, position]`` a
      slot from round to round. A round (a) runs ``[token, draft]`` at
      ``position, position + 1`` through the stack, writing both positions'
      lines into every kind's pool through its own table and attending
      with two queries a slot (``paged_line_attention(queries=2)``: the
      second row sees the first's line, inside the window on a window
      layer; nothing of ``max_seq`` positions is gathered); (b) takes the
      stack's best token after each row, ``c0, c1``; the draft holds iff
      ``c0 == draft`` and the position budget allows two; emits ``[c0]`` or
      ``[draft, c1]``; (c) runs the drafting block (``family.mtp_input |
      mtp_block | mtp_head``) on the committed rows, ``(x at position,
      c0)`` and, where the draft held, ``(x at position + 1, c1)``, writes
      their lines into the cache layer kept for it behind the stack's
      (``kind_layers[draft_kind]`` counts it), and takes the next draft
      from the last committed row; (d) advances the position by 1 or 2. A
      rejected position's lines stay hidden behind the position (the
      ``<= position`` rule of every attention form) until the next round
      overwrites them, and a window layer's pages go back behind the
      *committed* position only. ``_prefill_chunk`` also runs the drafting
      block, shifted by one token (row ``i`` pairs with token ``i + 1``, the
      prompt's last row with the first token), computes the head on the
      launch's last row alone and leaves ``[first token, first draft]``.
      The round's answer is ``[n_emit | c0 c1 | next draft]`` a slot in
      one small array, pulled like a step's tokens, the expert layers'
      counts behind it (the stack's and the drafting block's summed). The
      host dispatches the next round before it has read the last one's
      answer: its ``_pos`` and ``_left`` are then bounds (a round in flight
      counted as one token a slot) that ``collect`` makes exact, so it
      keeps one position more writable a slot and counts ``pages_read |
      fetched`` from the bound. ``draft=False`` builds the engine as if
      the family drafted nothing (``_step``, no drafting block's cache
      layer): the stream a test holds the round's to, token for token.
    * **pool layout** — per kind of layer and kind of line ``(layers of
      the kind * (pages+1), page, width)`` device arrays: one row per page
      of one layer, one contiguous ``width`` line per token, so row-major
      order IS the order every program touches it and the donated pool
      goes in and comes out of each program in the array's own layout (no
      relayout copy). The ``i``-th layer of a kind owns rows
      ``i*(pages+1) ..``; its row 0 is that layer's null page, the sink
      inactive/pad writes route to (no branches in the scatter). A slot's
      logical position ``p`` lives at ``(i*(pages+1) + block_table[p //
      page], p % page)``. No program slices a layer out of the pool: a
      step's write is a scatter on the two leading axes (``write_rows``),
      a launch's one of whole pages on the first (``write_pages``, where
      its width is whole pages), a chunk's context read one ``take`` of
      the slot's rows, a step's attention a walk over the rows themselves.
    * **serving limit** — ``max_seq``, the positions a slot may hold: the
      family's ``max_positions`` (the ``gpt`` family's position table, the
      rotary families' ``max_position_embeddings``) or ``max_positions=``
      below it. A verify round attends over that many padded positions.
      A prefill chunk reads whole blocks of pages from the first page its
      first row sees (position 0 in a full layer, the window's first in a
      window layer) to its last row's, and a decode step the pages each
      live slot holds, in a window layer from the window's first page on:
      neither reads more (``ops/paged_attention.py``; ``chunk_ctx`` and
      the ``engine.chunk.prepare`` span count the positions of the one,
      ``attn_pages`` and the ``engine.step.prepare`` span the pages of the
      other, by kind, against the padding).
    * **chunked prefill** — ``admit_start`` queues the prompt and
      ``prefill_tick`` ingests ONE fixed-size chunk per call, so a long
      prompt interleaves with running decode instead of stalling the
      batch, and the chunk size is the only compiled prefill shape
      (``compile_count`` is flat across prompt lengths — the NNL008
      churn fix). ``chunk=`` means "ingest at least this many tokens a
      launch": the width in use, ``engine.chunk``, is ``prefill_width``'s,
      as wide as the chip's ridge where the chip is known (a narrower
      launch reads every weight for fewer tokens) and ``chunk`` itself on
      the CPU.
    * **COW prefix sharing** — identical prompt prefixes resolve to the
      same pages via the pool's registry; ``_ensure_writable`` copies a
      shared page before any write lands in it, so divergence never
      perturbs the sibling stream. Refused (``NotImplementedError``) for
      a family with window layers: a registered prefix would have to keep
      the pages of both kinds that a hit needs.
    * **preempt/restore** — ``preempt`` pulls the pages a slot holds of
      every kind to host and frees them; ``restore`` re-allocates (every
      kind or none) and uploads byte-exact, so memory pressure never drops
      a request.

    Parity contract: a position a slot does not see has exact-zero softmax
    weight (masked at -1e30 in the gathered forms and in a block of a
    chunk's walk, never read by the step's kernel), so on the CPU, where
    the step's attention runs in its plain form over ``max_seq`` gathered
    positions, the paged engine is token-exact against
    ``models.decoding.make_generate`` (asserted in test_kv_paged.py).
    A chunk's walk and, on a TPU, the step's kernel sum their online
    softmax in another order: agreement to float32 rounding, tokens on a
    TPU under ``chip_smoke.near_tie``.
    """

    def __init__(self, cfg, params, slots: int = 4, page_size: int = 16,
                 pages=None, chunk: int = 32,
                 share_prefixes: bool = True,
                 max_positions: Optional[int] = None,
                 draft: bool = True):
        if slots < 1:
            raise ValueError(f"slots={slots} must be >= 1")
        import functools

        import jax
        import jax.numpy as jnp

        from ..models.families import family_of, kept_state
        from ..ops.paged_attention import (
            chunk_block_pages,
            chunk_line_attention,
            chunk_walk,
            gathered_lines,
            paged_line_attention,
            pages_fetched,
        )
        from .kv_pool import KVPagePool

        fam = family_of(cfg)
        # the serving limit on positions is the engine's: at most what the
        # family can address (a learned position table is a weight)
        max_seq = (fam.max_positions if max_positions is None
                   else max_positions)
        if not 1 <= max_seq <= fam.max_positions:
            raise ValueError(
                f"max_positions={max_seq} outside 1..{fam.max_positions}, "
                f"the {fam.name} family's limit for this configuration")
        page_size = min(page_size, max_seq)
        if max_seq % page_size:
            raise ValueError(
                f"max_seq {max_seq} must divide by page_size {page_size}")
        # the kinds of layer this family has, "full" first: each has its
        # own block table, page allocator and pool arrays
        kinds = tuple(k for k in ("full", "window") if k in fam.layer_kinds)
        if share_prefixes and "window" in kinds:
            raise NotImplementedError(
                f"lm_engine: prefix sharing does not serve the {fam.name} "
                f"family yet (a hit would need the pages of both kinds of "
                f"layer that a registered prefix keeps); build it with "
                f"share_prefixes=False")
        # the state a slot keeps beside its pages, by where it lives: in
        # state layers (no attention there) or in the attention layers
        stateful = kept_state(fam)
        if share_prefixes and stateful:
            raise NotImplementedError(
                f"lm_engine: prefix sharing does not serve the {fam.name} "
                f"family yet (a hit would need {stateful} as it was at the "
                f"prefix's last token, and no snapshot of it is kept); "
                f"build it with share_prefixes=False")
        if fam.passes > 1 and stateful:
            raise NotImplementedError(
                f"lm_engine: the {fam.name} family runs its stack "
                f"{fam.passes} times a token and keeps {stateful}; a state "
                f"a pass of a layer is not kept")
        # the tokens a layer of the family drafts a pass (``family.drafts``:
        # its MTP layer's one). ``draft=False`` builds the engine as if the
        # family drafted nothing, ``_step`` and no MTP line: what a test
        # holds the round's stream to, not a serving knob
        D = self.drafts = fam.drafts if draft else 0
        if D > 1 or (D and (fam.passes > 1 or stateful)):
            raise NotImplementedError(
                f"lm_engine: the {fam.name} family drafts {D} tokens a pass"
                f"{', runs its stack several times' * (fam.passes > 1)}"
                f"{(', keeps ' + stateful) * bool(stateful)}; the round "
                f"verifies one draft of a family with one pass and no state "
                f"a slot")
        self.cfg = cfg
        self.family = fam
        self.kinds = kinds
        self.max_seq = max_seq
        # the family's stored form is what the programs read; the leaves it
        # re-laid are the ones that are no longer the caller's
        given = jax.tree_util.tree_leaves(params)
        self.params = params = fam.stored(params)
        relaid = [b for a, b in zip(given, jax.tree_util.tree_leaves(params))
                  if a is not b]
        self.relaid = {"matrices": len(relaid),
                       "bytes": int(sum(b.nbytes for b in relaid))}
        self.slots = slots
        self.page_size = page_size
        self.blocks_per_slot = max_seq // page_size
        self.chunk = prefill_width(
            chunk, max_seq, page_size,
            jnp.dtype(params["embed"].dtype).itemsize)
        self.share_prefixes = share_prefixes
        self.compile_count = 0
        self.host_s = self.pull_s = 0.0  # under the spans below, summed
        self._ran: set = set()           # the programs called once (_run)
        self._jnp = jnp
        self._jax = jax
        self._pages_fetched = pages_fetched

        NB = self.blocks_per_slot
        pg = page_size
        C = self.chunk
        window = fam.window
        # the blocks a slot may hold at once: everything in a full layer; in
        # a window layer what some future query still sees plus the launch
        # in flight, ceil((window + width) / page) + 1 at most
        self.held_blocks = {
            kind: NB if kind == "full"
            else min(NB, -(-(window + C) // pg) + 1) for kind in kinds}
        if pages is None:  # every slot's most at once
            pages = {kind: slots * self.held_blocks[kind] for kind in kinds}
        elif not isinstance(pages, dict):
            if len(kinds) > 1:
                raise ValueError(
                    f"pages={pages}: the {fam.name} family has layers of "
                    f"kinds {kinds}; give the pages of each, "
                    f"pages={{kind: count}}")
            pages = {kinds[0]: pages}
        if set(pages) != set(kinds):
            raise ValueError(f"pages {sorted(pages)} for kinds {kinds}")
        self._mem_name = f"lm_engine#{next(_engine_ids)}"

        # the pool's geometry, from the family and from nowhere else: per
        # kind of layer, one device array per kind of line a token keeps
        cache_dtype = params["embed"].dtype
        item = jnp.dtype(cache_dtype).itemsize
        self.line_widths = tuple(int(w) for w in fam.cache_lines)
        P = len(self.line_widths)
        K = len(kinds)
        # layer li is the index[li]-th layer of its kind
        layers_of = dict.fromkeys((*kinds, "state"), 0)
        index = []
        for kind in fam.layer_kinds:
            index.append(layers_of[kind])
            layers_of[kind] += 1
        # a state layer keeps no pages: its count stands beside the kinds'
        self.state_layers = layers_of.pop("state")
        # a family that runs its stack ``passes`` times a token keeps a
        # line for every pass of every layer: from here on a kind's count
        # is of pass-layers, and pass t of the i-th layer of a kind is
        # pass-layer ``t * stack_of[kind] + i`` (one pass: the layers)
        T = self.passes = fam.passes
        stack_of = dict(layers_of)
        layers_of = {kind: T * n for kind, n in layers_of.items()}
        if D:
            # the drafting block keeps lines too: one more cache layer of
            # its kind, behind the stack's
            layers_of[fam.draft_kind] += D
        self.kind_layers = layers_of
        self.pass_layers = sum(layers_of.values())
        # how many of a step's (a round's) kernel calls, one a pass-layer,
        # contract by key head: the kernel's rule as the family asked it
        self.attn_by_head = self.pass_layers * fam.step_by_head(1 + D)
        # rows of one layer: its null page 0, then the kind's pages
        R = {kind: pages[kind] + 1 for kind in kinds}
        self.token_bytes = (sum(layers_of.values())
                            * sum(self.line_widths) * item)
        self.pools_by_kind = {
            kind: KVPagePool(
                pages[kind], page_size, kind=kind,
                name=self._mem_name + ("" if kind == kinds[0]
                                       else f".{kind}"),
                line_widths=self.line_widths,
                token_bytes=layers_of[kind] * sum(self.line_widths) * item)
            for kind in kinds}
        self.pool = self.pools_by_kind[kinds[0]]
        self.page_bytes = self.pool.page_bytes
        # flat, kind by kind: kind k's arrays are [k * P, (k + 1) * P)
        self._pools = tuple(
            jnp.zeros((layers_of[kind] * R[kind], page_size, w), cache_dtype)
            for kind in kinds for w in self.line_widths)
        # what a slot keeps in the state layers, whatever its length: one
        # array (state layers, slots, *shape) per entry of the family's
        # ``state_lines``, the i-th state layer's rows at [i]
        # and what a slot keeps in the attention layers beside its lines
        # (the family's ``slot_lines``): arrays (attention layers, slots,
        # *shape) behind the state layers', the i-th attention layer's rows
        # at [i]. The first ``NM`` arrays are the state layers'
        self.slot_layers = sum(stack_of.values()) if fam.slot_lines else 0
        self._states = tuple(
            jnp.zeros((n, slots, *shape),
                      cache_dtype if dtype is None else dtype)
            for n, lines in ((self.state_layers, fam.state_lines),
                             (self.slot_layers, fam.slot_lines))
            for shape, dtype in lines if n)
        NS = len(self._states)
        NM = len(fam.state_lines) if self.state_layers else 0
        self.state_slot_bytes = int(sum(
            s.nbytes for s in self._states) // slots) if NS else 0
        ctx = NB * page_size  # == max_seq: what a verify round gathers

        # host mirrors. The block tables, ``_pos`` and ``_mask`` are
        # authoritative and ride into every step as numpy arguments
        # (copies: a program in flight may read an argument in place);
        # ``_pos`` advances when a step is dispatched. ``_tok`` is the last
        # token the host has seen of a slot: one token behind the device's
        # carry ``_tok_dev`` while a step is in flight, so nothing uploads
        # it whole. Where the host knows a slot's next input token and the
        # device does not (a join, a restore, a verify round) it says so in
        # ``_join`` and the next step merges it on the device
        self._bts = {kind: np.zeros((slots, NB), np.int32) for kind in kinds}
        # first block a slot still holds in a window layer
        self._held_from = np.zeros((slots,), np.int64)
        self._tok = np.zeros((slots, 1), np.int32)
        self._pos = np.zeros((slots,), np.int32)
        self._mask = np.zeros((slots,), bool)
        self._join = np.full((slots,), -1, np.int32)
        # decode steps a slot's request may still take (``steps`` less the
        # prompt's own token, less the steps dispatched): a slot whose last
        # token is in flight is left out of the next dispatch
        self._left = np.zeros((slots,), np.int64)
        self._tok_dev = jnp.asarray(self._tok)  # an upload: no program
        if D:
            # a round's carry is ``[token, draft, position]`` a slot, all
            # on the device: a round leaves it there for the next, so the
            # next is dispatched before the host knows what this one
            # accepted. ``_pos`` and ``_left`` are then bounds (a round in
            # flight counted as one token a slot) that ``collect`` makes
            # exact; ``next_draft`` is the draft the host last saw of a slot
            self.next_draft = np.zeros((slots,), np.int32)
            self._join = np.full((slots, 3), -1, np.int32)
            self._tok_dev = jnp.zeros((slots, 3), jnp.int32)
            self.step_tokens = self._step_tokens
        # running sums over rounds (a burst engine's account,
        # ``serving/engine.py``): rounds dispatched, drafts proposed (one a
        # live slot a round), drafts the stack agreed with, tokens emitted
        self.spec_rounds = self.spec_proposed = self.spec_accepted = 0
        self.spec_emitted = 0
        # the step in flight, ``(its tokens on the device, the slots it
        # stepped, its prepare span)``; behind it the step that a joining
        # ``prefill_tick`` dispatched for the pass's ``step()`` to find
        # (``_ride``); and the tokens a drain brought home early, a step's
        # an entry and the oldest first, kept for the next ``step()`` calls
        # to return (``-1``: none for this slot)
        self._flight: Optional[tuple] = None
        self._ahead: Optional[tuple] = None
        self._kept: "list[np.ndarray]" = []
        self._no_tokens = np.full((slots, 1 + D) if D else (slots,), -1,
                                  np.int32)
        # running sums (``counters``): steps dispatched while another's
        # tokens were still on the device, drains that ``preempt``,
        # ``restore``, ``verify_commit`` or ``close`` forced, slot-steps
        # whose token was dropped (the one step an EOS ending runs over),
        # and the prompts whose last launch had the pass's step dispatched
        # behind it before its token was pulled, beside those that had not
        self.run_ahead = {"steps_ahead": 0, "steps_collected_early": 0,
                          "surplus_steps": 0, "joins_ahead": 0,
                          "joins_drained": 0}
        self._pending: "dict[int, dict]" = {}  # slot -> chunked-prefill state
        # slot -> [when its first chunk was dispatched, chunks so far]:
        # outlives _pending, until the slot is released (prefill_stamp)
        self._lane: "dict[int, list]" = {}
        # what the family's expert layers counted (``family.counters``),
        # running sums by program, and the device arrays of the chunks
        # whose counts have not been pulled yet
        self.layer_counts = {call: dict.fromkeys(fam.counters, 0)
                             for call in ("step", "chunk")}
        self._chunk_counts: list = []
        # running sums over decode steps: the pages the live slots held
        # (what a step's attention reads in a layer, the mean over layers
        # where kinds differ) and slots x blocks_per_slot; by kind beside
        # them, and the pages given back behind the window so far
        self.attn_pages = {"attn_pages_read": 0, "attn_pages_fetched": 0,
                           "attn_pages_padded": 0,
                           **{f"attn_pages_{what}_{kind}": 0
                              for what in ("read", "fetched")
                              for kind in kinds if len(kinds) > 1}}
        self.window_pages_released = 0
        # running sums over decode steps: the slots whose state a step
        # advanced, and every slot's (what it read and wrote)
        self.state_slots = ({"state_slots_live": 0, "state_slots": 0}
                            if NS else {})

        self.cache_bytes = int(sum(
            a.nbytes for a in (*self._pools, *self._states)))
        self.param_bytes = obs_memory.tree_nbytes(params)
        obs_memory.track_serving(self)

        NC = len(fam.counters)

        def _attention(kind, row0, blk, x, pos, dests, offs, pools, unbatch,
                       attend, write, kept=(), rows=None):
            # one attention layer of ``kind`` whose rows start at ``row0``:
            # write the new lines into its kind's arrays, attend over the
            # slots' lines; what comes back is the residual stream, and
            # ``kept``, the batch entries' rows of what the family's
            # attention layers keep a slot (``()`` for most), as the layer
            # leaves them; ``rows``: how many of each entry's rows are real
            k = kinds.index(kind)
            with jax.named_scope(fam.attention_scopes[kind]):
                q, lines, kept = fam.project_slot(blk, x, pos, kind, kept,
                                                  rows)
                mine = tuple(
                    write(pool, row0, dests[k], offs, unbatch(line))
                    for pool, line in zip(pools[k * P:(k + 1) * P], lines))
                pools = pools[:k * P] + mine + pools[(k + 1) * P:]
                x = fam.merge(blk, x, attend(kind, row0, blk, q, mine),
                              "attention")
                return x, pools, kept

        def _stack(p, x, pos, live, dests, offs, pools, unbatch, attend,
                   states=(), mix=None, first=None, write=write_rows,
                   slot_rows=(None, None, None)):
            # the skeleton every program shares: per layer, by its kind.
            # An attention layer: write the new lines into its kind's
            # arrays (``dests``: the page of each row, by kind), attend
            # over the slots' lines (``attend(kind, row0, blk, q, pools of
            # the kind)``: what the residual adds). A state layer:
            # ``mix(i, blk, x, states)`` reads the rows' state of the i-th
            # state layer, calls the family and writes it back. Then feed
            # forward. ``unbatch`` strips the axis a program's lines do
            # not have. ``first``: by kind, the first pass-layer of the
            # pass at hand, a traced scalar (``None``: the family's one
            # pass, whose rows are constants of the program). ``write``: the
            # form of the write, ``write_rows`` or ``write_pages``.
            # ``slot_rows``: for a family whose attention layers keep a
            # state a slot, ``(read, store, rows)``: ``read(array, i)`` the
            # batch entries' rows of the i-th attention layer, ``store(
            # array, i, new)`` the array with them put back, ``rows`` how
            # many of each entry's rows are real. What the family carries
            # down the stack (``family.open_stack``; ``None`` for most) is
            # opened here and goes from each feed-forward to the next's
            counts = jnp.zeros((NC,), jnp.int32) if NC else None
            states, kept = states[:NM], states[NM:]
            read, store, rows = slot_rows
            carry = fam.open_stack(p, x)
            layer = 0  # attention layers so far
            for li, blk in enumerate(fam.blocks(p)):
                kind = fam.layer_kinds[li]
                if kind == "state":
                    y, states = mix(index[li], blk, x, states)
                    x = fam.merge(blk, x, y, "state")
                else:
                    row0 = index[li] * R[kind] if first is None else (
                        (first[kind] + index[li]) * R[kind])
                    x, pools, left = _attention(
                        kind, row0, blk, x, pos, dests, offs, pools, unbatch,
                        attend, write,
                        tuple(read(a, layer) for a in kept), rows)
                    kept = tuple(store(a, layer, new)
                                 for a, new in zip(kept, left))
                    layer += 1
                y, c, carry = fam.ffn_carry(blk, x, live, carry)
                x = fam.merge(blk, x, y, "ffn")
                if c is not None:
                    counts = counts + c
            return x, pools, counts, (*states, *kept)

        def _passes(p, x, pos, live, dests, offs, pools, unbatch, attend,
                    states=(), mix=None, write=write_rows,
                    slot_rows=(None, None, None)):
            # a family whose tokens run the stack several times: ONE loop
            # over the passes in the program, its body the stack. The
            # pools go round as the loop's carry (written and read at the
            # pass's own rows, where they lie), beside the activations,
            # the counts and what the family carries from pass to pass;
            # ``close_pass`` ends each one and ``exit_rows`` says what the
            # head reads. No layer of such a family keeps a state
            def one(t, carry):
                x, pools, counts, kept = carry
                x, pools, c, _ = _stack(
                    p, x, pos, live, dests, offs, pools, unbatch, attend,
                    first={kind: t * n for kind, n in stack_of.items()},
                    write=write)
                x, kept, closed = fam.close_pass(p, x, kept, t, live)
                for more in (c, closed):
                    counts = counts if more is None else counts + more
                return (x, pools, counts, kept)

            _, pools, counts, kept = jax.lax.fori_loop(
                0, T, one, (x, pools, jnp.zeros((NC,), jnp.int32),
                            fam.open_passes(x)))
            return (fam.exit_rows(kept), pools, counts if NC else None,
                    states)

        _layers = _stack if T == 1 else _passes

        def _step(p, token, pos, mask, *rest):
            self.compile_count += 1  # trace-time only: one step program
            bts, pools = rest[:K], rest[K:K + K * P]
            states, join = rest[K + K * P:-1], rest[-1]
            S = token.shape[0]
            # ``token`` is the carry the last step left on the device; where
            # the host knows better (``join >= 0``) its token goes in
            token = jnp.where(join[:, None] >= 0, join[:, None], token)
            lp = jnp.clip(pos, 0, max_seq - 1)
            x = fam.embed(p, token[:, 0], lp)[:, None, :]  # (S,1,D)
            bidx = jnp.clip(pos // pg, 0, NB - 1)
            dests = tuple(jnp.where(mask & (pos < max_seq),
                                    bt[jnp.arange(S), bidx], 0)
                          for bt in bts)
            offs = pos % pg
            # what a slot sees: its positions up to the one just written,
            # nothing for a slot that is not live; in a window layer from
            # the window's first position on
            lengths = jnp.where(mask, jnp.minimum(pos + 1, max_seq), 0)
            starts = {"full": None}
            if window is not None:
                starts["window"] = jnp.maximum(lengths - window, 0)

            def attend(kind, row0, blk, q, pools):
                # the step's one attention form (ops/paged_attention.py):
                # the family's queries over whole lines, read from the
                # pool's rows where they lie, from each slot's first
                # visible position as far as its length
                o = paged_line_attention(
                    fam.step_queries(q), pools[0], pools[-1],
                    row0 + bts[kinds.index(kind)], lengths,
                    fam.attention_scale, starts[kind])
                return fam.step_output(blk, o)

            def mix(i, blk, x, states):
                # one token of every slot through the i-th state layer:
                # the family advances the rows [i, slots in mask] of the
                # arrays where they lie and stores, for a slot outside
                # ``mask``, what it read (a select in the pass that reads
                # and writes the live slots' state: bit for bit)
                y, states = fam.mix_step(blk, x[:, 0], states, i, mask)
                return y[:, None], states

            # a slot's rows in an attention layer that keeps a state:
            # every slot's are read and stored, and the family moves those
            # of the slots in ``mask`` alone (one real row; none for any
            # other slot, whose rows come back as they were read)
            slot_rows = (lambda a, i: a[i],
                         lambda a, i, new: a.at[i].set(new.astype(a.dtype)),
                         mask.astype(jnp.int32))

            x, pools, counts, states = _layers(
                p, x, lp[:, None], mask[:, None], dests, offs, pools,
                lambda line: line[:, 0], attend, states, mix,
                slot_rows=slot_rows)
            with jax.named_scope("head"):
                logits = fam.head(p, x[:, 0])
            out = jnp.argmax(logits, -1).astype(jnp.int32)
            token = jnp.where(mask[:, None], out[:, None], token)
            if NC:  # the counts ride home behind the tokens: one transfer
                out = jnp.concatenate([out, counts])
            return (out, token, *pools, *states)

        self._step = functools.partial(
            _LowersShort(jax.jit(_step, donate_argnums=(
                1, *range(4 + K, 4 + K + K * P + NS))),
                4 + K + K * P + NS, ((slots,), jnp.int32)), params)

        def _draft(p, x, toks, pos, live, dests, offs, pools, unbatch,
                   attend, write=write_rows):
            # the family's drafting block over the stack's output ``x`` at
            # ``pos`` and the tokens after them (``toks``): its input, one
            # block of ``draft_kind`` whose lines go to the cache layer
            # behind the stack's, its feed-forward. Returns the block's
            # output rows, the pools and what its expert layer counted
            kind = fam.draft_kind
            u = fam.mtp_input(p, x, toks)
            blk = fam.mtp_block(p)
            with jax.named_scope("mtp.block"):
                u, pools, _ = _attention(
                    kind, stack_of[kind] * R[kind], blk, u, pos, dests, offs,
                    pools, unbatch, attend, write)
                y, c, _ = fam.ffn_carry(blk, u, live, fam.open_stack(p, u))
            return u + y, pools, c

        def _round(p, carry, mask, *rest):
            # a drafting family's decode program: verify the draft and
            # draft the next, ONE call. ``carry (S, 3)`` is ``[token,
            # draft, position]`` a slot as the last round left it; the
            # stack runs both tokens at ``position, position + 1`` (the
            # second row sees the first's line), the draft is accepted
            # where the stack's best token after the first row is the
            # draft, and the drafting block runs on the committed rows.
            # A rejected position's lines stay hidden behind the position
            # until the next round overwrites them
            self.compile_count += 1  # trace-time only: one round program
            bts, pools, join = rest[:K], rest[K:K + K * P], rest[-1]
            S, Q = carry.shape[0], 2
            # where the host knows better (a join, a restore) its row goes in
            carry = jnp.where(join[:, :1] >= 0, join, carry)
            toks, pos = carry[:, :Q], carry[:, Q]
            q_pos = pos[:, None] + jnp.arange(Q)[None, :]        # (S, Q)
            lp = jnp.clip(q_pos, 0, max_seq - 1)
            x = fam.embed(p, toks, lp)                           # (S, Q, D)
            live = mask[:, None] & (q_pos < max_seq)
            at = (jnp.arange(S)[:, None], lp // pg)

            def dests_of(rows):  # the page of each row, by kind
                return tuple(jnp.where(rows, bt[at], 0) for bt in bts)

            offs = lp % pg
            lengths = jnp.where(mask, jnp.minimum(pos + 1, max_seq), 0)
            starts = {"full": None}
            if window is not None:
                starts["window"] = jnp.maximum(
                    lengths[:, None] + jnp.arange(Q)[None, :] - window, 0)

            def attend(kind, row0, blk, q, pools):
                # the step's attention form with two queries a slot
                o = paged_line_attention(
                    fam.step_queries(q), pools[0], pools[-1],
                    row0 + bts[kinds.index(kind)], lengths,
                    fam.attention_scale, starts[kind], queries=Q)
                return fam.step_output(blk, o)

            def unbatch(line):
                return line

            x, pools, counts, _ = _stack(
                p, x, lp, live, dests_of(live), offs, pools, unbatch, attend)
            with jax.named_scope("head"):
                logits = fam.head(p, x.reshape(S * Q, -1))
            best = jnp.argmax(logits, -1).astype(jnp.int32).reshape(S, Q)
            budget = max_seq - pos                   # positions left
            runs = mask & (budget > 0)
            ok = runs & (best[:, 0] == toks[:, 1]) & (budget > 1)
            n_emit = runs.astype(jnp.int32) + ok
            # the drafting block on the committed rows: ``(x at position,
            # the token after it)``, the second only where the draft held
            commit = jnp.stack([runs, ok], axis=1)
            u, pools, c = _draft(p, x, best, lp, commit, dests_of(commit),
                                 offs, pools, unbatch, attend)
            if c is not None:
                counts = counts + c
            last = jnp.maximum(n_emit - 1, 0)[:, None]
            u = jnp.take_along_axis(u, last[..., None], axis=1)[:, 0]
            draft = jnp.argmax(fam.mtp_head(p, u), -1).astype(jnp.int32)
            token = jnp.take_along_axis(best, last, axis=1)[:, 0]
            carry = jnp.where(runs[:, None], jnp.stack(
                [token, draft, pos + n_emit], axis=1), carry)
            # ``[n_emit | the stack's two tokens | the next draft]`` a slot
            # in one small array (and the counts behind it): one pull
            out = jnp.concatenate(
                [n_emit[:, None], best, draft[:, None]], axis=1).reshape(-1)
            if NC:
                out = jnp.concatenate([out, counts])
            return (out, carry, *pools)

        if D:
            self._round = functools.partial(
                jax.jit(_round, donate_argnums=(
                    1, *range(3 + K, 3 + K + K * P))), params)

        # a launch's attention walks the blocks its slot holds
        # (``chunk_line_attention``): how far back a layer of each kind
        # sees, and the pages of one block of the walk
        KV, G = fam.chunk_heads
        span = self._span = {"full": max_seq, "window": window}
        PB = self.chunk_block_pages = chunk_block_pages(KV * G * C, pg, NB)
        self._chunk_walk = chunk_walk
        # a launch of whole pages writes its lines a page at a time
        # (``write_pages``, bound here as the ops above are); a width the
        # page does not divide, row by row
        self.chunk_pages = C // pg if C % pg == 0 else None
        pages_form = write_pages

        def _prefill_chunk(p, toks, start, n_valid, *rest):
            # toks (C,) padded; ingest positions start..start+n_valid-1 of
            # ONE slot. C is static — the only compiled prefill shape.
            self.compile_count += 1  # trace-time only: once per engine
            bts, pools = rest[:K], rest[K:K + K * P]
            # a family that keeps a state a slot: the slot, then the arrays
            slot, states = (rest[K + K * P], rest[K + K * P + 1:]) if NS \
                else (None, ())
            q_pos = start + jnp.arange(C)
            valid = jnp.arange(C) < n_valid
            lp = jnp.clip(q_pos, 0, max_seq - 1)
            dests = tuple(jnp.where(valid, bt[lp // pg], 0) for bt in bts)
            if self.chunk_pages:
                # ``start`` is on a page's edge: every ``pg`` rows fill one
                # page, named by their first row's entry of ``dests`` (the
                # null page where the whole page is past ``n_valid``), and
                # ``offs`` says which of its positions the launch owns
                dests = tuple(d[::pg] for d in dests)
                offs, write = valid.reshape(-1, pg, 1), pages_form

                def unbatch(line):
                    return line[0].reshape(-1, pg, line.shape[-1])
            else:
                offs, write = lp % pg, write_rows

                def unbatch(line):
                    return line[0]
            x = fam.embed(p, toks, lp)[None]        # (1, C, D)

            def attend(kind, row0, blk, q, pools):
                # the launch's one attention form (ops/paged_attention.py):
                # its rows' queries by key head over the blocks the slot
                # holds, read from the pool's rows where they lie
                o = chunk_line_attention(
                    q[0].reshape(C, KV, G, -1), pools[0], pools[-1],
                    row0 + bts[kinds.index(kind)], start, n_valid,
                    fam.attention_scale, span[kind],
                    precision=fam.chunk_precision, pages_per_block=PB)
                return fam.chunk_output(blk, o.reshape(1, C, KV * G, -1))

            def was(s, i):
                # the slot's rows of layer ``i`` as the launch finds them:
                # zero where it starts a sequence, whatever the slot held
                return jnp.where(start == 0, jnp.zeros_like(s[i, slot]),
                                 s[i, slot])

            def mix(i, blk, x, states):
                # one slot's launch through the i-th state layer; the
                # family leaves the state at the last real row (rows past
                # n_valid move neither part of it)
                old = tuple(was(s, i) for s in states)
                y, new = fam.mix_chunk(blk, x[0], n_valid, old)
                states = tuple(s.at[i, slot].set(n.astype(s.dtype))
                               for s, n in zip(states, new))
                return y[None], states

            # an attention layer that keeps a state: the one slot's rows,
            # of which the family leaves the state after row n_valid - 1
            slot_rows = (lambda a, i: was(a, i)[None],
                         lambda a, i, new: a.at[i, slot].set(
                             new[0].astype(a.dtype)),
                         n_valid[None])

            x, pools, counts, states = _layers(
                p, x, lp[None], valid[None], dests, offs, pools,
                unbatch, attend, states, mix, write=write,
                slot_rows=slot_rows)
            if D:
                # a drafting family's launch also runs its drafting block,
                # shifted by one token: row i pairs with token i + 1, the
                # launch's last real row with ``rest[-1]``, the prompt's
                # next token, or (below zero: the prompt ends here) with
                # the first token, which only that row's scores are needed
                # for. It leaves ``[first token, first draft]``
                end = n_valid - 1
                with jax.named_scope("head"):
                    first = jnp.argmax(fam.head(p, x[0, end][None]),
                                       -1).astype(jnp.int32)
                after = jnp.concatenate([toks[1:], toks[:1]]).at[end].set(
                    jnp.where(rest[-1] >= 0, rest[-1], first[0]))
                u, pools, c = _draft(
                    p, x, after[None], lp[None], valid[None], dests, offs,
                    pools, unbatch, attend, write)
                if c is not None:
                    counts = counts + c
                draft = jnp.argmax(fam.mtp_head(p, u[0, end][None]),
                                   -1).astype(jnp.int32)
                ends = jnp.concatenate([first, draft])
                return (ends, *((counts,) if NC else ()), *pools)
            # no head here: the launch hands back its rows as the stack
            # left them, of which ``_seed`` makes the prompt's first token
            # where the prompt ends in this launch (from the last real row;
            # any other launch's rows are read by nobody). All of them, not
            # that row: a slice here sinks up through the last layers'
            # row-wise operations and the compiler then schedules the whole
            # launch otherwise, 0.3 ms slower at the ``opt_1.3b`` cells'
            # sizes (PERF.md section 6, PR 49)
            return (x[0], *((counts,) if NC else ()), *pools, *states)

        self._prefill_chunk = functools.partial(
            jax.jit(_prefill_chunk, donate_argnums=(
                *range(4 + K, 4 + K + K * P),
                *range(5 + K + K * P, 5 + K + K * P + NS))), params)

        def _seed(p, carry, slot, ends, at):
            # behind a prompt's last launch: what it left on the device goes
            # into the decode carry's row of the slot that joins, and the
            # next ``_step`` / ``_round`` reads it there before the host has
            # seen it. The launch left its rows, and the head over the last
            # real one, row ``at``, makes the prompt's first token here; a
            # drafting family's launch needs the token itself and left
            # ``[first token, first draft]``, behind which a round's carry
            # holds the position, ``at (1,)``. Returns the carry and the
            # token (and draft) that went in
            if D:
                return carry.at[slot].set(jnp.concatenate([ends, at])), ends
            with jax.named_scope("head"):
                first = jnp.argmax(fam.head(p, ends[at][None]),
                                   -1).astype(jnp.int32)
            return carry.at[slot].set(first), first

        self._seed = functools.partial(
            jax.jit(_seed, donate_argnums=1), params)

        # page movers, one set per kind of layer (compiled when first used)
        def movers(kind):
            layer_rows = jnp.arange(layers_of[kind]) * R[kind]  # null pages

            def _copy_page(dst, src, *pools):
                self.compile_count += 1  # trace-time only: the COW primitive
                return tuple(
                    pool.at[layer_rows + dst].set(pool[layer_rows + src])
                    for pool in pools)

            def _gather_pages(pages_row, *pools):
                # (n,) page ids -> (layers, n, pg, width) blobs (preempt)
                rows = layer_rows[:, None] + pages_row[None, :]
                return tuple(pool[rows] for pool in pools)

            def _scatter_pages(dest_row, blobs, *pools):
                rows = layer_rows[:, None] + dest_row[None, :]
                return tuple(pool.at[rows].set(blob.astype(pool.dtype))
                             for pool, blob in zip(pools, blobs))

            donated = tuple(range(2, 2 + P))
            return {"copy": jax.jit(_copy_page, donate_argnums=donated),
                    "gather": jax.jit(_gather_pages),
                    "scatter": jax.jit(_scatter_pages,
                                       donate_argnums=donated)}

        self._movers = {kind: movers(kind) for kind in kinds}

        # a slot's state to the host and back (preempt / restore)
        def _put_state(slot, blobs, *states):
            return tuple(s.at[:, slot].set(b.astype(s.dtype))
                         for s, b in zip(states, blobs))

        self._get_state = jax.jit(
            lambda slot, *states: tuple(s[:, slot] for s in states))
        self._put_state = jax.jit(
            _put_state, donate_argnums=tuple(range(2, 2 + NS)))

        def _verify(p, toks, pos, mask, bt, *pools):
            # speculative verification: score K tokens per slot in ONE
            # call — toks (S, K) = [carry, draft...], positions
            # pos..pos+K-1. Writes their K/V (host rolls back rejected
            # positions by simply not advancing pos past them: the
            # <=pos visibility mask hides them until overwritten).
            self.compile_count += 1  # trace-time only: once per K
            S, K = toks.shape
            q_pos = pos[:, None] + jnp.arange(K)[None, :]     # (S, K)
            lp = jnp.clip(q_pos, 0, max_seq - 1)
            # overflow rows (q_pos >= max_seq) route to the null page so
            # they can never clobber the real tail position
            dest = jnp.where(mask[:, None] & (q_pos < max_seq),
                             bt[jnp.arange(S)[:, None], lp // pg], 0)
            offs = lp % pg
            x = fam.embed(p, toks, lp)
            positions = jnp.arange(ctx)
            visible = (positions[None, None, :] <= q_pos[:, :, None])

            def attend(kind, row0, blk, q, pools):
                # K queries a slot over a gathered copy of its block table
                ctxs = tuple(gathered_lines(pool, row0 + bt)
                             for pool in pools)
                return fam.attend_verify(blk, q, ctxs, visible)

            x, pools, _, _ = _layers(
                p, x, lp, jnp.broadcast_to(mask[:, None], (S, K)), (dest,),
                offs, pools, lambda line: line, attend)
            logits = fam.head(p, x)  # (S, K, V)
            return (logits, *pools)

        def _verify_commit(p, toks, pos, mask, bt, *pools):
            # fused speculative round: verify K tokens AND resolve greedy
            # acceptance on device. Greedy acceptance emits the target's
            # own argmax prefix (accepted drafts match it by definition,
            # the correction IS it), so the host needs only (pred, n_emit)
            # — one tiny int pull, no logits download.
            logits, *pools = _verify(p, toks, pos, mask, bt, *pools)
            K = toks.shape[1]
            pred = jnp.argmax(logits, -1).astype(jnp.int32)   # (S, K)
            budget = max_seq - pos                            # emit ceiling
            # accept proposal i (column i+1) while every earlier one
            # matched and the emit budget allows position i+1
            ok = ((toks[:, 1:] == pred[:, :-1])
                  & (jnp.arange(K - 1)[None, :] < (budget - 1)[:, None]))
            j = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
            n_emit = jnp.where(mask & (budget > 0), j + 1, 0)
            # pack [n_emit | pred] into ONE (S, K+1) array: the host does
            # a single tiny pull per round instead of two
            out = jnp.concatenate([n_emit[:, None], pred], axis=1)
            return (out, *pools)

        if fam.serves_verify:  # families whose layers are all of one kind
            self._verify_commit = functools.partial(
                jax.jit(_verify_commit,
                        donate_argnums=tuple(range(5, 5 + P))), params)

    def _run(self, name: str, program, *args):
        """Call a jitted program of this engine. The first call of each
        runs under a ``program.first_call`` span, a child of whatever span
        made the call: jax traces, lowers and compiles (or loads) a program
        when it is first called, and the compile account charges those
        seconds to the innermost span open. Later calls open nothing."""
        if name in self._ran:
            return program(*args)
        self._ran.add(name)
        with obs_context.span("program.first_call", program=name):
            return program(*args)

    def _move(self, kind: str, mover: str, *args):
        """One of a kind's page movers (``copy``, ``gather``, ``scatter``)."""
        return self._run(f"{mover}.{kind}", self._movers[kind][mover], *args)

    @property
    def _bt(self):
        """The block table of the first kind of layer (the only one, for a
        family whose layers are all alike)."""
        return self._bts[self.kinds[0]]

    def _tables(self, slot=None) -> tuple:
        """The block tables by kind, as the programs take them: copies.
        The host edits its tables while the program that took them is
        still in flight (a step always is, a chunk is not waited for), and
        on the CPU a program may read a numpy argument in place."""
        return tuple((bt if slot is None else bt[slot]).copy()
                     for bt in self._bts.values())

    def _kind_pools(self, kind: str) -> tuple:
        k, P = self.kinds.index(kind), len(self.line_widths)
        return self._pools[k * P:(k + 1) * P]

    def _set_kind_pools(self, kind: str, pools) -> None:
        k, P = self.kinds.index(kind), len(self.line_widths)
        self._pools = (*self._pools[:k * P], *pools,
                       *self._pools[(k + 1) * P:])

    # the two-pool (keys, values) family's pools by their old names
    @property
    def _kpool(self):
        return self._pools[0]

    @property
    def _vpool(self):
        return self._pools[1]

    def _keep(self, arrays) -> None:
        """What a program gave back for what it was donated: the pools,
        then the state layers' arrays."""
        P = len(self._pools)
        self._pools, self._states = tuple(arrays[:P]), tuple(arrays[P:])

    def _hand_over(self, slot: int, pos: int, left: int) -> None:
        """``slot`` is live from the next step on, at ``pos`` and with
        ``left`` steps to take: the host's mirrors say so. Its input token
        reaches the device's carry by one of two ways. A prompt's last
        launch left it on the device, and ``_seed`` put it into the carry
        there (``prefill_tick``); ``restore`` knows it on the host and says
        so in ``_join``, which the next ``_step`` / ``_round`` merges.
        Nothing is uploaded whole in either: with a step in flight ``_tok``
        is one token old for every other slot, and a whole-array upload
        would roll them back. Block tables are not device-resident either:
        they ride into every call as numpy arguments (the committed-call
        conversion is ~10x cheaper than a device mirror that page-boundary
        crossings would re-upload mid-decode)."""
        self._pos[slot] = pos
        self._left[slot] = left
        self._mask[slot] = True

    def _ride(self) -> bool:
        """After a prompt's last launch and its ``_seed`` are dispatched
        and before its token is pulled: dispatch the pass's step, the
        slot that joins in it, behind them, so that the device has work
        queued while the host waits for four bytes. The ``step()`` /
        ``step_tokens()`` of the same pass finds it in ``_ahead`` and
        dispatches nothing. True where it rode ahead. It does not where
        nothing is in flight to run ahead of (no caller is stepping: the
        first pass after idle, a caller that collects every step, a wrapper
        that verifies instead of stepping), where an earlier ride has not
        been taken up, where no slot has a token to make, or where the pool
        cannot supply the step's pages (``step()`` raises that again where
        the scheduler handles it): then the pass is the plain one, the
        token pulled before anything else is dispatched."""
        from .kv_pool import PagePoolExhausted

        if self._flight is None or self._ahead is not None:
            return False
        who = self._mask & (self._left > 0)
        if not who.any():
            return False
        try:
            self._ahead = self._dispatch(who)
        except PagePoolExhausted:
            return False
        return True

    def _drain(self) -> None:
        """Before anything reads or moves a slot's sequence state
        (``preempt``, ``restore``, ``verify_commit``, ``close``): whatever
        is in flight comes home first, and its tokens are kept for the next
        ``step()`` calls to return, a step's a call."""
        while self._flight is not None or self._ahead is not None:
            self._kept.append(self._wait())
            self.run_ahead["steps_collected_early"] += 1

    # -- page bookkeeping -----------------------------------------------------
    def _ensure_writable(self, slot: int, lo: int, hi: int) -> None:
        """Make blocks covering logical positions [lo, hi) exclusively
        owned by ``slot`` in every kind of layer: allocate missing pages,
        COW-copy shared ones. Raises PagePoolExhausted (caller sheds or
        preempts)."""
        if hi <= lo:
            return
        blocks = range(lo // self.page_size, (hi - 1) // self.page_size + 1)
        for kind, pool in self.pools_by_kind.items():
            bt = self._bts[kind]
            for b in blocks:
                page = int(bt[slot, b])
                if page == 0:
                    # ownership lands in the block table atomically with
                    # the alloc: release(slot) walks the tables on every
                    # exit path
                    # nnlint: disable=NNL302
                    bt[slot, b] = pool.alloc(1)[0]  # pairs-with: release (slot exit)
                elif pool.is_shared(page):
                    new = pool.alloc(1)[0]  # pairs-with: release (slot exit)
                    try:
                        self._set_kind_pools(kind, self._move(
                            kind, "copy", new, page,
                            *self._kind_pools(kind)))
                    except BaseException:
                        pool.release([new])  # copy failed: page never owned
                        raise
                    pool.release([page])  # drop OUR ref; sibling keeps its page
                    bt[slot, b] = new
                    pool.note_cow()

    def _release_behind(self, slot: int, pos: int) -> None:
        """Give back the pages of ``slot``'s window layers that no query at
        ``pos`` or later can see: the blocks wholly below ``pos - window +
        1``. The full layers keep everything."""
        if "window" not in self.kinds:
            return
        keep = max(pos - self.family.window + 1, 0) // self.page_size
        first = int(self._held_from[slot])
        if keep <= first:
            return
        row = self._bts["window"][slot]
        gone = [int(p) for p in row[first:keep] if p]
        self.pools_by_kind["window"].release(gone)  # pairs-with: alloc (_ensure_writable)
        row[first:keep] = 0
        self._held_from[slot] = keep
        self.window_pages_released += len(gone)

    def _note_counts(self, call: str, counts) -> dict:
        """Add what the family's expert layers counted in one call of a
        program (``family.counters``, in order) to ``layer_counts``;
        returns that call's counts, for the span of the pull that brought
        them (``counters`` sums both programs')."""
        got = dict(zip(self.family.counters, map(int, counts)))
        total = self.layer_counts[call]
        for k, v in got.items():
            total[k] += v
        return got

    def _pull_chunk_counts(self) -> dict:
        """The counts of the chunks dispatched since the last pull, summed
        (their programs have finished: a later program's answer is here)."""
        if not self._chunk_counts:
            return {}
        pending, self._chunk_counts = self._chunk_counts, []
        total = dict.fromkeys(self.layer_counts["chunk"], 0)
        for counts in self._jax.device_get(pending):
            for k, v in self._note_counts("chunk", counts).items():
                total[k] += v
        return total

    def counters(self) -> dict:
        """The running sums the scheduler's metrics take per pass: the
        pages the steps' attention read (``attn_pages``), how the steps
        ran ahead (``run_ahead``) and what an expert family's layers
        counted (``layer_counts``), its two programs added up."""
        total = dict(self.attn_pages)
        if "window" in self.kinds:
            total["window_pages_released"] = self.window_pages_released
        total.update(self.state_slots)
        total.update(self.run_ahead)
        for counts in self.layer_counts.values():
            for k, v in counts.items():
                total[k] = total.get(k, 0) + v
        return total

    def projected_page_bytes(self, tokens: int, steps: int) -> int:
        """Worst-case pool bytes a request needs (no sharing assumed) —
        the AdmissionGuard reservation unit (pages, not dense slots): by
        kind of layer, its pages at their bytes; a window layer holds
        ``held_blocks["window"]`` at most however long the request. A
        state layer's state is a fixed cost a slot, resident whether the
        slot is live or not: no request is charged for it."""
        n = -(-(tokens + steps) // self.page_size)
        return sum(min(n, self.held_blocks[kind]) * pool.page_bytes
                   for kind, pool in self.pools_by_kind.items())

    # -- scheduler contract ---------------------------------------------------
    def validate(self, tokens: np.ndarray, steps: int) -> None:
        if tokens.ndim != 1 or tokens.size == 0:
            raise ValueError(
                f"prompt must be non-empty 1-D tokens, got {tokens.shape}")
        if tokens.size + steps > self.max_seq:
            raise ValueError(
                f"prompt ({tokens.size}) + steps ({steps}) exceeds "
                f"max_seq {self.max_seq}")

    def admit_start(self, slot: int, tokens: np.ndarray, steps: int) -> None:
        """Queue a prompt for chunked prefill (``prefill_tick`` drives
        it). Shared-prefix pages are mapped in immediately; only the
        uncovered tail is recomputed."""
        if self._mask[slot] or slot in self._pending:
            raise ServingError(f"slot {slot} already active")
        tokens = np.asarray(tokens, np.int32)
        self.validate(tokens, steps)
        covered = 0
        if self.share_prefixes:
            pages, covered = self.pool.lookup_prefix(tokens)
            if pages:
                self._bt[slot, :len(pages)] = pages
                # always recompute >=1 position: the final prompt token's
                # logits seed the first generated token. From a page's edge:
                # a launch writes whole pages (``write_pages``), and a cover
                # of the whole prompt would leave its start mid-page
                covered = min(covered, (tokens.size - 1)
                              // self.page_size * self.page_size)
        self._pending[slot] = {"tokens": tokens, "next": covered,
                               "steps": steps}
        self._lane.pop(slot, None)

    def chunk_ctx(self, start: int, n_valid: int) -> "tuple[int, int]":
        """``(ctx_read, ctx_padded)`` of a launch of rows ``start .. start +
        n_valid - 1``: the positions its attention layers read, every
        layer's added up (whole blocks of the walk from the first page a
        row sees, by ``ops.paged_attention.chunk_walk``), and what a
        gathered copy of everything a slot may hold would have (the serving
        limit a full layer, the held blocks a window layer)."""
        pg, PB = self.page_size, self.chunk_block_pages
        read = padded = 0
        for kind, n in self.kind_layers.items():
            blocks = self._chunk_walk(start, n_valid, self._span[kind], pg,
                                      PB)[1]
            read += n * blocks * PB * pg
            padded += n * self.held_blocks[kind] * pg
        return read, padded

    def chunk_lines(self, n_valid: int) -> "tuple[int, int]":
        """``(lines_rows, lines_updates)`` of a launch of ``n_valid`` rows:
        the lines it writes, every pool's of every pass-layer, and the
        scatter updates it issues for them: one a line in the row form,
        one a page the rows cover in the page form (``write_pages``)."""
        writes = self.pass_layers * len(self.line_widths)
        updates = -(-n_valid // self.page_size) if self.chunk_pages \
            else n_valid
        return writes * n_valid, writes * updates

    def prefill_stamp(self, slot: int) -> "tuple[float, int]":
        """``(first_chunk_t, chunks)`` of the prompt in ``slot``: when its
        first chunk was dispatched (``time.monotonic``; until then it
        waited in the lane behind older prompts) and how many chunks have
        run. Kept until the slot is released."""
        return tuple(self._lane[slot])

    def prefill_tick(self) -> "list[tuple[int, int]]":
        """Ingest ONE chunk of ONE pending prompt (oldest first);
        returns [(slot, first_token)] when that prompt completes, else
        []. The scheduler calls this once per loop pass so prefill
        interleaves with running decode instead of stalling it.

        Behind a prompt's last launch ``_seed`` makes its first token on
        the device and puts it into the decode carry there. Where a step is in
        flight, the pass's step, the slot that joins in it, is dispatched
        behind them (``_ride``), and only then is the token pulled: the
        device has the launch and a step queued while the host waits, and
        the pass's ``step()`` only collects. The token is still returned
        by this call."""
        if not self._pending:
            return []
        jnp = self._jnp
        slot = next(iter(self._pending))
        st = self._pending[slot]
        tokens, start = st["tokens"], st["next"]
        n_valid = min(self.chunk, tokens.size - start)
        attrs = {"slot": slot, "start": start, "n_valid": n_valid}
        with obs_context.span("engine.chunk.prepare", width=self.chunk,
                              passes=self.passes,
                              pass_layers=self.pass_layers,
                              **attrs) as prepare:
            self._release_behind(slot, start)
            self._ensure_writable(slot, start, start + n_valid)
            padded = np.zeros((self.chunk,), np.int32)
            padded[:n_valid] = tokens[start:start + n_valid]
            # how far the launch's attention follows what its slot holds
            # (the walk's own rule, ``ops.paged_attention.chunk_walk``)
            prepare.attrs["ctx_read"], prepare.attrs["ctx_padded"] = \
                self.chunk_ctx(start, n_valid)
            # the lines the launch writes and the updates they go in
            prepare.attrs["lines_rows"], prepare.attrs["lines_updates"] = \
                self.chunk_lines(n_valid)
            last = start + n_valid == tokens.size
            state_args = ()
            if self._states:  # the launch that starts a sequence zeroes it
                prepare.attrs["state_reset"] = int(start == 0)
                state_args = (jnp.asarray(slot, jnp.int32), *self._states)
            elif self.drafts:
                # the token after the launch's last row, which the drafting
                # block pairs it with: the prompt's next, or none yet
                state_args = (jnp.asarray(
                    -1 if last else tokens[start + n_valid], jnp.int32),)
        with obs_context.span("engine.chunk.dispatch", **attrs) as dispatch:
            ends, *rest = self._run(
                "_prefill_chunk", self._prefill_chunk, jnp.asarray(padded),
                jnp.asarray(start, jnp.int32),
                jnp.asarray(n_valid, jnp.int32), *self._tables(slot),
                *self._pools, *state_args)
            if self.family.counters:
                # pulled with the next answer that is pulled anyway (this
                # prompt's last chunk, or the next step's tokens)
                self._chunk_counts.append(rest.pop(0))
            self._keep(rest)
            if last:
                # the prompt's first token, made of the launch's answer
                # where it lies (its last real row; a drafting launch's
                # own token and draft, with the position behind them) and
                # put into the decode carry's row of the slot
                self._tok_dev, ends = self._run(
                    "_seed", self._seed, self._tok_dev,
                    np.asarray(slot, np.int32), ends,
                    np.asarray([tokens.size] if self.drafts
                               else n_valid - 1, np.int32))
        self.host_s += prepare.dur_s + dispatch.dur_s
        lane = self._lane.setdefault(slot, [dispatch.start_s, 0])
        lane[1] += 1
        st["next"] = start + n_valid
        if not last:
            return []
        # prompt complete: the slot is live, and the pass's step rides
        # behind the launch where it can, before the host waits for either
        del self._pending[slot]
        self._hand_over(slot, tokens.size, st["steps"] - 1)
        ahead = self._ride()
        self.run_ahead["joins_ahead" if ahead else "joins_drained"] += 1
        with obs_context.span("engine.chunk.pull", ahead=int(ahead),
                              **attrs) as pull:
            # nnlint: disable=NNL101 — the prompt's first token (and, where
            # the family drafts, its first draft): the scheduler routes it
            # from this call's answer
            first, *draft = map(int, self._jax.device_get(ends))
            pull.attrs.update(self._pull_chunk_counts())
        self.pull_s += pull.dur_s
        # the host's mirrors of what ``_seed`` put into the carry
        self._tok[slot, 0] = first
        if self.drafts:
            self.next_draft[slot] = draft[0]
        if self.share_prefixes:
            # register FULL pages only: a later prompt sharing just the
            # prefix (not the tail) still hits, and registered pages are
            # immutable — this stream's future writes land at positions
            # >= tokens.size, past every registered page (COW guards the
            # page-aligned case where position size-1 is in the last
            # registered page)
            nb_full = tokens.size // self.page_size
            if nb_full:
                self.pool.register_prefix(
                    tokens,
                    [int(p) for p in self._bt[slot, :nb_full] if p],
                    nb_full * self.page_size)
        return [(slot, first)]

    def step(self) -> np.ndarray:
        """One paged decode step over every slot, run one step ahead: this
        call prepares and dispatches the step of the slots that still have
        a token to make, and only then brings home (``collect``) the tokens
        of the step dispatched by the call before, so the device works
        while the caller routes them. A slot that was not in that step has
        ``-1`` in the answer (it joined since, or nothing was in flight).
        A slot whose request has its last token in flight (``steps`` of
        ``admit_start``) is left out of the dispatch; an ending the engine
        cannot foresee (EOS) costs one step whose token ``release`` drops.

        May raise PagePoolExhausted when an active slot crosses into a
        page the pool cannot supply (scheduler preempts a victim and
        retries): from prepare, before the step in flight is touched."""
        if self.drafts:
            raise TypeError(
                f"lm_engine: the {self.family.name} family drafts, so a pass "
                f"yields 1 or 2 tokens a slot: call step_tokens(), not "
                f"step()")
        return self._advance()

    def _advance(self) -> np.ndarray:
        """Dispatch the next step or round, unless this pass's joining
        ``prefill_tick`` has (``_ride``), then bring home the one before."""
        ahead, self._ahead = self._ahead, None
        if ahead is None:
            who = self._mask & (self._left > 0)
            ahead = self._dispatch(who) if who.any() else None
        tok = self.collect()
        # behind a step still in flight only where a drain kept two steps'
        # tokens and this call returned the older: the next call takes it up
        if self._flight is None:
            self._flight = ahead
        else:
            self._ahead = ahead
        return tok

    def _step_tokens(self) -> "list[list[int]]":
        """A drafting engine's pass (``DecodeEngine.step_tokens``, bound
        where the family drafts): one round over every slot, run one round
        ahead as ``step`` is. This call dispatches the next round from the
        carry the last one left on the device, before anyone knows what
        that one accepted, and then brings home (``collect``) the tokens of
        the round dispatched by the call before: per slot the 1 or 2 it
        emitted, ``[]`` for a slot that was not in it. Until a round is
        collected the host counts it as one token a slot (``_pos`` and
        ``_left`` are bounds): it keeps the pages of one position more
        writable, gives a window layer's pages back behind the lower bound
        only, and a request's last round may be followed by one whose
        tokens the scheduler drops."""
        return [[int(t) for t in row if t >= 0] for row in self._advance()]

    def acceptance_rate(self) -> float:
        """Drafts the stack agreed with over drafts proposed, so far."""
        return self.spec_accepted / max(self.spec_proposed, 1)

    def _dispatch(self, who: np.ndarray) -> tuple:
        """Prepare and dispatch one step (a drafting engine: one round) of
        the slots in ``who``; returns what ``_flight`` holds of it."""
        slots = np.flatnonzero(who)
        live = len(slots)
        D = self.drafts
        # the positions a pass may write from a slot's ``_pos`` on: one, a
        # round's second, and one more where a round in flight may have
        # advanced the slot a position past the host's bound
        reach = np.full(who.shape, 1 + D)
        if D and self._flight is not None:
            reach += self._flight[1]
        with obs_context.span("engine.step.prepare", live=live,
                              passes=self.passes,
                              pass_layers=self.pass_layers) as prepare:
            for s in slots:
                if self._pos[s] < self.max_seq:
                    self._release_behind(int(s), int(self._pos[s]))
                    self._ensure_writable(
                        int(s), int(self._pos[s]),
                        min(int(self._pos[s] + reach[s]), self.max_seq))
            # how far the step's attention follows what is visible: the
            # pages the live slots hold against every slot's whole table
            # (by kind of layer where kinds differ: a window layer reads
            # from the window's first page on)
            # ``pages_fetched`` beside them is what the step's kernel
            # copies, by its own rule (ops/paged_attention.py)
            # a round's second query sees one position more, and its walk
            # starts at the first query's first position (from the host's
            # bound: an accepted draft in flight puts them one position low)
            seen = np.minimum(self._pos[slots] + 1 + D, self.max_seq)
            first = {"full": np.zeros_like(seen)}
            by_kind = {"full": int((-(-seen // self.page_size)).sum())}
            if "window" in self.kinds:
                first["window"] = np.maximum(
                    np.minimum(self._pos[slots] + 1, self.max_seq)
                    - self.family.window, 0)
                by_kind["window"] = by_kind["full"] - int(
                    (first["window"] // self.page_size).sum())
            fetched = {kind: self._pages_fetched(seen, first[kind],
                                                 self.page_size)
                       for kind in by_kind}
            layers = self.pass_layers
            padded = self.slots * self.blocks_per_slot
            prepare.attrs["attn_by_head"] = self.attn_by_head
            prepare.attrs["pages_padded"] = padded
            self.attn_pages["attn_pages_padded"] += padded
            for what, pages in (("read", by_kind), ("fetched", fetched)):
                mean = round(sum(pages[kind] * n for kind, n
                                 in self.kind_layers.items()) / layers)
                prepare.attrs[f"pages_{what}"] = mean
                self.attn_pages[f"attn_pages_{what}"] += mean
                if len(self.kinds) > 1:
                    for kind in self.kinds:
                        prepare.attrs[f"pages_{what}_{kind}"] = pages[kind]
                        self.attn_pages[f"attn_pages_{what}_{kind}"] += \
                            pages[kind]
            if len(self.kinds) > 1:
                prepare.attrs["window_pages_released"] = \
                    self.window_pages_released
            if self._states:
                # the step reads and writes every slot's state and
                # advances the live ones'
                prepare.attrs.update(state_slots_live=live,
                                     state_slots=self.slots)
                self.state_slots["state_slots_live"] += live
                self.state_slots["state_slots"] += self.slots
            if D:
                # a round's account: two rows a live slot, one draft each;
                # ``collect`` adds what it accepted and emitted
                prepare.attrs.update(rounds=1, rows=2 * live, proposed=live,
                                     accepted=0, emitted=0)
                self.spec_rounds += 1
                self.spec_proposed += live
        ahead = int(self._flight is not None)
        with obs_context.span("engine.step.dispatch", live=live,
                              ahead=ahead) as dispatch:
            # every host argument a copy: the mirrors move on below and
            # at the next join or release, while this step may still read
            if D:
                tok_dev, self._tok_dev, *rest = self._run(
                    "_round", self._round, self._tok_dev, who.copy(),
                    *self._tables(), *self._pools, self._join.copy())
            else:
                tok_dev, self._tok_dev, *rest = self._run(
                    "_step", self._step,
                    self._tok_dev, self._pos.copy(), who.copy(),
                    *self._tables(), *self._pools, *self._states,
                    self._join.copy())
            self._keep(rest)
        self.host_s += prepare.dur_s + dispatch.dur_s
        self.run_ahead["steps_ahead"] += ahead
        self._pos += who
        self._left -= who
        self._join[:] = -1
        return tok_dev, who, prepare

    def collect(self) -> np.ndarray:
        """The oldest tokens no ``step()`` has returned yet, ``(slots,)``
        with ``-1`` where a slot has none: those a drain kept or, with none
        kept, those of the step in flight, waited for here. "``step()``,
        then ``collect()``" is the synchronous step: each step's own
        tokens, nothing left in flight."""
        return self._kept.pop(0) if self._kept else self._wait()

    def _wait(self) -> np.ndarray:
        """The tokens of the oldest step in flight, waited for here (``-1``
        everywhere with none in flight); the step that rode behind it, if
        any, is the one in flight from here on."""
        tok = self._no_tokens.copy()
        flight, self._flight, self._ahead = self._flight, self._ahead, None
        live = 0 if flight is None else int(flight[1].sum())
        with obs_context.span("engine.step.pull", live=live) as pull:
            if flight is not None:
                tok_dev, who, prepared = flight
                # nnlint: disable=NNL101 — one (slots,) pull per decode
                # step: the scheduler needs host ints to append/retire
                # (documented contract); explicit device_get, so it stays
                # legal under the NNS_XFERCHECK disallow scopes and lands
                # in the byte ledger
                got = self._jax.device_get(tok_dev)
                if self.family.counters:
                    # an expert family's counts came home behind the tokens
                    n = self.slots * (4 if self.drafts else 1)
                    got, counts = got[:n], got[n:]
                    pull.attrs.update(self._note_counts("step", counts))
                    self._pull_chunk_counts()
                if self.drafts:
                    self._commit(got.reshape(self.slots, 4), who, tok,
                                 prepared)
                else:
                    self._tok[who, 0] = tok[who] = got[who]
            pull.attrs["no_token"] = int(
                (self._mask & (tok.reshape(self.slots, -1)[:, 0] < 0)).sum())
        self.pull_s += pull.dur_s
        return tok

    def _commit(self, got, who, tok, prepared) -> None:
        """A round's answer, ``[n_emit | the stack's two tokens | the next
        draft]`` a slot, for the slots in ``who``: their tokens into
        ``tok (slots, 2)``, the host's mirrors made exact (the dispatch
        counted one token a slot), the round's account closed."""
        n_emit = np.where(who, got[:, 0], 0)
        for q in range(2):
            took = n_emit > q
            tok[took, q] = got[took, 1 + q]
        ran = n_emit > 0
        self._tok[ran, 0] = got[ran, np.maximum(n_emit[ran], 1)]
        self.next_draft[ran] = got[ran, 3]
        self._pos[who] += n_emit[who] - 1
        self._left[who] -= n_emit[who] - 1
        accepted, emitted = int((n_emit == 2).sum()), int(n_emit.sum())
        self.spec_accepted += accepted
        self.spec_emitted += emitted
        prepared.attrs.update(accepted=accepted, emitted=emitted)

    def verify_commit(self, draft: np.ndarray):
        """Fused speculative round: verify ``draft`` (slots, K) AND
        resolve greedy acceptance + carry advance on device in ONE call.
        Returns ``(pred, n_emit)`` — slot ``s`` emitted
        ``pred[s, :n_emit[s]]`` (accepted drafts equal the target argmax
        by definition; the last entry is the correction). Column 0 of
        ``draft`` must be each slot's carry token, columns 1.. the
        proposals. No logits come home, and nothing is uploaded but
        ``draft``, the positions and the mask."""
        self._drain()
        K = draft.shape[1]
        for s in np.flatnonzero(self._mask):
            lo = int(self._pos[s])
            self._ensure_writable(int(s), lo,
                                  min(lo + K, self.max_seq))
        # np arrays passed straight to the jit call: the committed-call
        # conversion is ~10x cheaper than a standalone jnp.asarray. The
        # host's mirrors are exact here (nothing is in flight)
        packed, *pools = self._run(
            "_verify_commit", self._verify_commit,
            np.ascontiguousarray(draft, np.int32), self._pos.copy(),
            self._mask.copy(), self._bt.copy(), *self._pools)
        self._pools = tuple(pools)
        # nnlint: disable=NNL101 — ONE (slots, K+1) int pull per
        # speculative round (the emitted burst)
        packed = self._jax.device_get(packed)
        n_emit, pred = packed[:, 0], packed[:, 1:]
        for s in np.flatnonzero(n_emit):
            n = int(n_emit[s])
            self._pos[s] += n
            # the host knows the carry and the device does not: a later
            # ``step()`` takes it from here
            self._tok[s, 0] = self._join[s] = int(pred[s, n - 1])
        return pred, n_emit

    def _drop_pages(self, slot: int) -> None:
        """Return every page ``slot`` holds, of every kind of layer."""
        for kind, pool in self.pools_by_kind.items():
            bt = self._bts[kind]
            pool.release([int(p) for p in bt[slot] if p])  # pairs-with: alloc/ref (admit path)
            bt[slot] = 0
        self._held_from[slot] = 0

    def _leave(self, slot: int) -> np.ndarray:
        """``slot`` is out of every later step; returns the tokens it is
        owed, a row a step that a drain kept and no ``step()`` has
        returned yet (``-1``: none of that step; a drafting engine: a
        round's two, ``-1`` where it emitted fewer)."""
        self._mask[slot] = False
        self._join[slot] = -1
        self._left[slot] = 0
        owed = np.array([kept[slot] for kept in self._kept], np.int32)
        for kept in self._kept:
            kept[slot] = -1
        return owed

    def release(self, slot: int) -> None:
        # a state layer's rows stay as they are: the launch that starts the
        # slot's next sequence zeroes them. A step in flight may still
        # write the slot's line and advance its state: safe by the device's
        # order (the pools are donated from program to program, so whatever
        # writes these pages or rows next runs after it), and its token is
        # dropped here
        with obs_context.span("engine.release", slot=slot):
            self._pending.pop(slot, None)
            self._lane.pop(slot, None)
            self._drop_pages(slot)
            dropped = sum(bool((owed >= 0).any())
                          for owed in self._leave(slot))
            for flight in (self._flight, self._ahead):
                if flight is not None and flight[1][slot]:
                    flight[1][slot] = False
                    dropped += 1
            self.run_ahead["surplus_steps"] += dropped
            self._tok[slot, 0] = 0
            self._pos[slot] = 0

    # -- preemption -----------------------------------------------------------
    def _held_span(self, kind: str, slot: int) -> slice:
        """The blocks of ``slot``'s table that may hold a page: all of a
        full layer's, ``held_blocks`` from the first one held of a window
        layer's."""
        n = self.held_blocks[kind]
        first = 0 if kind == "full" else min(int(self._held_from[slot]),
                                             self.blocks_per_slot - n)
        return slice(first, first + n)

    def preempt(self, slot: int) -> dict:
        """Evict a slot to host: pull the pages it holds of every kind of
        layer, free them, deactivate. The returned blob restores the
        request byte-exact later — deadline-aware memory pressure never
        DROPS work (contract with the scheduler + obs/memory watermark
        events)."""
        if not self._mask[slot]:
            raise ServingError(f"slot {slot} not active")
        self._drain()
        # ``left``: the steps its request may still take; ``owed``: the
        # tokens the drain brought home for it, which ``restore`` keeps for
        # the next ``step()`` calls to return
        blob = {"pages": (), "used": {}, "state": (), "held_from": int(
                    self._held_from[slot]),
                "tok": int(self._tok[slot, 0]), "pos": int(self._pos[slot]),
                "left": int(self._left[slot]),
                "draft": int(self.next_draft[slot]) if self.drafts else 0}
        with obs_context.span("engine.preempt", slot=slot) as sp:
            for kind in self.kinds:
                row = self._bts[kind][slot, self._held_span(kind, slot)]
                blobs = self._move(kind, "gather", row,
                                   *self._kind_pools(kind))
                # nnlint: disable=NNL101 — preemption IS the host transfer:
                # the victim's pages move to host RAM so the pool can be
                # re-used; restore uploads the same bytes
                blob["pages"] += tuple(self._jax.device_get(b)
                                       for b in blobs)
                blob["used"][kind] = row != 0
            if self._states:
                # the state goes with the pages: another sequence's first
                # launch in this slot zeroes the rows
                # nnlint: disable=NNL101 — as the pages above
                blob["state"] = tuple(self._jax.device_get(self._run(
                    "_get_state", self._get_state, slot, *self._states)))
                sp.attrs["state_bytes"] = self.state_slot_bytes
            self._drop_pages(slot)
            blob["owed"] = self._leave(slot)
        self.pool.note_preemption()
        return blob

    def restore(self, slot: int, blob: dict) -> None:
        """Re-admit a preempted request: fresh pages of every kind, byte-
        exact upload, decode resumes mid-sequence. Raises PagePoolExhausted
        if a pool still cannot hold it (scheduler keeps it queued)."""
        if self._mask[slot]:
            raise ServingError(f"slot {slot} already active")
        self._drain()
        fresh = {}
        try:
            for kind, pool in self.pools_by_kind.items():
                fresh[kind] = pool.alloc(int(blob["used"][kind].sum()))  # pairs-with: release (slot exit)
        except BaseException:
            for kind, got in fresh.items():  # all kinds or none
                self.pools_by_kind[kind].release(got)
            raise
        self._held_from[slot] = blob["held_from"]
        P = len(self.line_widths)
        with obs_context.span("engine.restore", slot=slot) as sp:
            for k, kind in enumerate(self.kinds):
                row = np.zeros((self.held_blocks[kind],), np.int32)
                row[blob["used"][kind]] = fresh[kind]
                self._bts[kind][slot] = 0
                self._bts[kind][slot, self._held_span(kind, slot)] = row
                self._set_kind_pools(kind, self._move(
                    kind, "scatter", self._jnp.asarray(row),
                    tuple(self._jnp.asarray(b)
                          for b in blob["pages"][k * P:(k + 1) * P]),
                    *self._kind_pools(kind)))
            if self._states:
                self._states = self._run(
                    "_put_state", self._put_state,
                    self._jnp.asarray(slot, self._jnp.int32),
                    tuple(self._jnp.asarray(b) for b in blob["state"]),
                    *self._states)
                sp.attrs["state_bytes"] = self.state_slot_bytes
            self._hand_over(slot, blob["pos"], blob["left"])
            # the host knows the slot's input token (a round's: and the
            # draft behind it, and the position) and the device does not
            self._tok[slot, 0] = blob["tok"]
            if self.drafts:
                self.next_draft[slot] = blob["draft"]
                self._join[slot] = (blob["tok"], blob["draft"], blob["pos"])
            else:
                self._join[slot] = blob["tok"]
            for i, owed in enumerate(blob["owed"]):
                if i == len(self._kept):
                    self._kept.append(self._no_tokens.copy())
                self._kept[i][slot] = owed
        self.pool.note_restore()

    # -- introspection --------------------------------------------------------
    @property
    def active_slots(self) -> int:
        return int(self._mask.sum())

    def state_stats(self) -> Optional[dict]:
        """The cache of the state a slot keeps, a kind of its own beside
        the pools' ``stats()``: a fixed cost a slot, not a cost a token.
        ``layers`` are the layers that keep one, of which
        ``attention_layers`` (said where there are any) also keep lines a
        token. ``None`` for a family that keeps none."""
        if not self._states:
            return None
        return {"layers": self.state_layers + self.slot_layers,
                **({"attention_layers": self.slot_layers}
                   if self.slot_layers else {}), "slots": self.slots,
                "slots_live": self.active_slots,
                "slot_bytes": self.state_slot_bytes,
                "bytes": self.state_slot_bytes * self.slots,
                "shapes": [list(s.shape[2:]) for s in self._states]}

    def memory_bytes(self) -> dict:
        """Serving-plane byte source (obs/memory.py ``track_serving``):
        the page pools and the state layers' arrays are the engine's
        resident buffers (``bytes``); page occupancy rides along so obs
        top can render utilization, not just capacity. ``state`` is the
        part of ``bytes`` that the slots' state takes (:meth:`state_stats`
        ; absent for a family with none)."""
        s = self.pool.stats()
        state = self.state_stats()
        return {**({} if state is None else {"state": state}),
                "name": self._mem_name, "kind": "kv_pool",
                "bytes": self.cache_bytes,
                "param_bytes": self.param_bytes,
                "slots": self.slots, "active_slots": self.active_slots,
                "pages_total": s["pages_total"],
                "pages_used": s["pages_used"],
                "pages_shared": s["pages_shared"],
                "page_bytes": self.page_bytes,
                "line_widths": list(self.line_widths),
                "token_bytes": self.token_bytes,
                "kinds": {kind: {"layers": self.kind_layers[kind],
                                 "pages_total": pool.pages,
                                 "pages_used": pool.used_pages,
                                 "page_bytes": pool.page_bytes,
                                 "bytes": (pool.pages + 1) * pool.page_bytes}
                          for kind, pool in self.pools_by_kind.items()}}

    def close(self) -> None:
        self._drain()
        for slot in range(self.slots):
            if self._mask[slot] or any(bt[slot].any()
                                       for bt in self._bts.values()):
                self.release(slot)
        for pool in self.pools_by_kind.values():
            pool.close()
