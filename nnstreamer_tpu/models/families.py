"""Model families as the paged serving engine sees them (ROADMAP D1).

``serving.lm_engine.PagedLMEngine`` owns slots, block tables, the page
pools and the programs' skeleton (write the new lines, attend over a
slot's lines, feed forward, head). What a *layer* is and what it
keeps per token comes from one object, the family, chosen by the type of
the entry's configuration (:func:`family_of`); no flag names a model.

A family says:

* ``layer_kinds`` — per layer ``"full"`` (a query sees every earlier
  position), ``"window"`` (the last ``window`` positions only) or
  ``"state"`` (no attention: a mixer that keeps a state a sequence), and
  ``window``, or ``None`` where no layer has one. The engine keeps a block
  table, a page allocator and pool arrays per attention kind present, and
  gives a window layer's pages back behind the window. ``gpt`` and
  ``deepseek_v3`` say "all full"; ``mellum`` (``models/mellum.py``) has
  full and window layers; ``jamba`` (``models/jamba.py``) full and state
  layers; ``ouro`` (``models/ouro.py``) says "all full"; ``exaone_moe``
  (``models/exaone_moe.py``) has full and window layers.
* ``passes`` — how many times a token runs the stack, with the same
  weights every time. ``gpt``, ``deepseek_v3``, ``mellum`` and ``jamba``
  say 1: the stack once, then ``head``; nothing closes their one pass and
  their programs hold no loop. ``ouro`` says its ``total_ut_steps``. Where
  ``passes > 1`` the engine keeps a cache line for every pass of every
  layer (pass ``t`` of the ``i``-th layer of a kind owns the rows of
  pass-layer ``t * layers of the kind + i`` and reads no other pass's),
  runs the stack inside ONE loop over ``t`` in each program, and asks the
  family what closes a pass:

  - ``open_passes(x) -> carry`` — before the first pass, for activations
    ``x (B, Q, D)``: whatever the family carries from pass to pass (``ouro``:
    the rows chosen so far, the exit rule's running sum ``c_t``, the
    running product of ``1 - lambda`` and who has left). The engine
    threads it through the loop and never looks into it;
  - ``close_pass(p, x, carry, t, live) -> (x', carry', counts)`` — after
    the last layer of pass ``t`` (0-based, a traced scalar): ``x'`` is
    what the next pass starts from (``ouro``: the final norm of ``x``),
    ``counts`` the int32 vector ``counters`` names, counted over the rows
    in ``live`` (``ouro``: how many left at this pass), added to what
    ``ffn`` counted. Under the ``jax.named_scope`` ``loop.exit``;
  - ``exit_rows(carry) -> (B, Q, D)`` — once the passes are done: the rows
    ``head`` reads (``ouro``: each row's hidden state at the pass where
    its ``c_t`` first reached ``early_exit_threshold``, the last pass's if
    none; the final norm is in them already, so its ``head`` is the
    output matrix alone).
* ``drafts`` — how many tokens a layer of the family drafts a pass. The
  five elder families say 0: their decode program is ``_step``, one token
  a slot. ``exaone_moe`` says 1 (its MTP layer, ``num_nextn_predict_layers``)
  and its decode program is the *round* (``serving/lm_engine.py``
  ``_round``): the engine carries ``[token, draft, position]`` a slot on
  the device, runs both tokens through the stack at ``position, position +
  1`` (two queries a slot in every attention layer, the second seeing the
  first's line), accepts the draft where the stack's best token after the
  first row is the draft, runs the drafting block on the committed rows
  and takes the next draft from the last of them; a prefill launch runs
  the drafting block too, shifted by one token, and leaves the first
  draft. The engine keeps ``drafts`` more cache layers of ``draft_kind``
  (``"full"``) behind the stack's for the drafting block's lines. What a
  family that drafts owes for it, beside ``project``, ``ffn`` and the two
  ``step_*`` finishes taking ``K`` rows a slot (``q (S, K, H, head_dim)``
  → ``(S, K * H, line)`` → ``(S, K, D)``):

  - ``mtp_input(p, x, toks) -> u`` — the stack's output ``x (B, Q, D)`` at
    some positions (before the output norm) and ``toks (B, Q)``, the
    tokens after them → the drafting block's input (``exaone_moe``:
    ``[RMSNorm(Emb(t)) ; RMSNorm(x)] W_eh``). Under ``mtp.embed``;
  - ``mtp_block(p) -> blk`` — the drafting block's parameters: one block
    of ``draft_kind`` that ``project``, the attention forms and ``ffn``
    take like any of ``blocks(p)``. The engine runs it under
    ``mtp.block`` (the family's own scopes nest inside);
  - ``mtp_head(p, x) -> logits`` — the block's output rows ``x (n, D)`` →
    their scores of the token two positions on (``exaone_moe``: its own
    output norm, the main model's head). Under ``mtp.head``.

  A family that drafts has one pass and no state layer, and
  ``serves_verify`` stays ``False``: a host-side draft beside its own is
  refused (``models/lm_serving.py``).
* ``state_lines`` — what a *slot* keeps in every state layer, whatever the
  sequence's length: one ``(shape, dtype)`` per array, a dtype of ``None``
  the cache's (``jamba``: the conv's last inputs ``(3 * 5120,)``, flat, and
  the scan state ``(16, 5120)`` float32, channels last); ``()`` for a
  family with no state layer. The engine keeps no pages for the kind: a
  device array ``(state layers, slots, *shape)`` per entry, donated through
  its programs beside the pools. Two functions, under the
  ``jax.named_scope``s ``ssm.in``, ``ssm.conv``, ``ssm.x``, ``ssm.scan``,
  ``ssm.out``:

  - ``mix_step(blk, x, states, layer, live) -> y, states'`` — one token a
    slot: ``x (S, D)``; ``states`` are the engine's whole arrays, because a
    step's update of 128 states is worth doing where they lie (a slice
    handed out and stored back is two more passes over it): the family
    advances rows ``[layer, s]`` for the slots in ``live (S,)`` and stores
    for every other slot what it read, bit for bit;
  - ``mix_chunk(blk, x, n_valid, state) -> y, state'`` — one slot's
    launch: ``x (C, D)`` whose first ``n_valid`` rows are real, ``state``
    that slot's rows (the engine has zeroed them where the launch starts a
    sequence, and stores what comes back); a padded row moves no part of
    the state.

  Both return what the residual adds. ``project``, ``attend``,
  ``step_queries`` and ``step_output`` below are an attention layer's.
* ``attention_scopes`` — per layer kind, the ``jax.named_scope`` the engine
  puts around a layer's projection, cache write, read and attention.
* ``cache_lines`` — one width per pool: the values a token keeps in a
  layer. The GPT block keeps keys and values, two pools of
  ``heads * head_dim``; the latent-attention block keeps one line that
  every head reads as keys and as values (``models/deepseek_v3.py``).
* ``stored(params) -> params`` — how the family keeps its parameters for
  serving. The engine calls it once, where it keeps the tree, and its
  programs read what comes back (``engine.params``); what a configuration's
  parameters look like from outside (``init_params``, a reference's
  ``program_params``, a checkpoint) does not change. ``gpt``,
  ``deepseek_v3``, ``mellum`` and ``jamba`` return the tree they were
  given, the same object. ``ouro`` returns it with every layer's ``wq`` and
  ``wk`` transposed, ``(heads * head_dim, hidden)``, which its ``project``
  contracts over their second axis: re-laid on the device once at build,
  the caller's arrays neither donated nor deleted. The rule: a family
  re-lays only what a compiled module showed copied through HBM on every
  call (``tools/serving_programs_ops.py`` prints the count: 96 ``copy
  bf16[2048,2048]`` in each of ``ouro_2.6b``'s two programs, 0.8 GB
  written and read again a call, PERF.md section 6, PR 45), never on a
  guess at the compiler's layout assignment: the same compiler prefetches
  the same kind of matrix into fast memory in ``mellum`` and ``kanana``,
  and that is the matrix's one read.
* ``vocab`` — the vocabulary served: ids ``0 .. vocab - 1``, what a request's
  tokens are checked against and what ``head`` scores. A family may hold a
  slice of a larger one (``exaone_moe``: ``vocab_held (first, count)``, the
  rows of the embedding and the columns of the head that this chip holds
  of a vocabulary divided over chips, as ``experts_held`` is its share of
  the routed experts): then ``vocab`` is ``count``, the ids are the
  slice's own, logits, argmax and the served ids are over it, and nothing
  stands in for the absent rows.
* ``embed(p, toks, pos)`` — tokens at positions → float32 activations.
* ``blocks(p)`` — the per-layer parameter groups, in order.
* ``project(blk, x, pos, kind)`` — from the input ``x (B, Q, D)`` of a
  layer of ``kind``: the query side and the lines to write, one per pool,
  each ``(B, Q, width)``.
* ``chunk_heads``, ``chunk_precision``, ``chunk_output(blk, o)`` — a
  prefill launch's context is
  one slot's, so its lines are split by key head there (and only there):
  ``chunk_heads`` is ``(KV, G)``, how many heads' keys lie side by side in
  a line (``1`` where every head reads the whole line) and how many query
  heads read each; ``chunk_precision`` names the ``jax.lax.Precision`` of
  the launch's two attention products (``"highest"``: float32 over the
  bfloat16 pool, nothing lowered; ``None``: jax's default, what the
  ``gpt`` family's launch has always had and its configurations state).
  The engine hands
  ``ops.paged_attention.chunk_line_attention`` the launch's queries grouped
  so, it walks the blocks the slot holds, and the family finishes the
  ``(1, C, KV * G, line width / KV)`` float32 result (output projection
  applied: what the residual adds).
* ``attend_verify(blk, q, ctxs, visible)`` — where ``serves_verify``: ``K``
  queries a slot over the gathered lines ``ctxs`` (one ``(S, ctx, width)``
  per pool), ``visible (S, K, ctx)``, output projection applied.
* ``step_queries(q)``, ``attention_scale``, ``step_output(blk, o)``,
  ``step_by_head(queries)`` — the decode step gathers nothing: every
  family's step is ``H`` queries a slot over one shared line a token, so
  the engine hands ``ops.paged_attention.paged_line_attention`` the step's
  queries and the score scale, and the family finishes the float32 result
  (output projection applied). The queries come in one of two operand
  forms, and the result goes back in the same one:

  - whole lines, ``(S, K * H, width)``: row ``r * H + n`` is head ``n`` of
    the slot's ``r``-th query over the whole line, zeros outside its key
    head's block (the block-diagonal query) or the latent family's
    absorbed query as it is; the family takes its own part of each
    ``(S, K * H, width)`` result row;
  - head-wide, ``(S, KV * K * G, head_dim)``: the queries as ``project``
    made them, the rows of one key head together in the order ``(KV, K,
    G)``; the kernel contracts each key head's rows with that head's
    ``head_dim`` values of a line alone and gives ``(S, KV * K * G,
    head_dim)`` back.

  Which form is the kernel's rule, a function of the call's shapes
  (``ops.paged_attention.contracts_by_head``): a family whose lines hold
  several key heads asks it in ``step_by_head(queries)`` and lays its rows
  out so (``exaone_moe``); the others answer ``False`` and hand whole
  lines. The engine writes the answer on its ``engine.step.prepare`` span
  (``attn_by_head``: the round's kernel calls that contract by head).
* ``ffn(blk, x, live)`` — norm and feed-forward of ``x (B, Q, D)`` →
  ``(y, counts)``; ``live (B, Q)`` marks the rows that are real, and
  ``counts`` is ``None`` or the int32 vector ``counters`` names.
* ``head(p, x)`` — final norm and output head of rows ``x (n, D)``.

Three more things are a family's to say, and :class:`PlainStack` holds the
answers of a family that has nothing to say (``gpt``, ``deepseek_v3``,
``mellum``, ``jamba``, ``ouro`` and ``exaone_moe`` all keep it, and their
programs are the ones they had before these existed). The skeleton has one
path: it calls ``project_slot`` and ``ffn_carry`` for every family, and
:class:`PlainStack` makes them of the ``project`` and ``ffn`` above.

* ``slot_lines`` — what a *slot* keeps in every *attention* layer beside
  the lines a token keeps there: one ``(shape, dtype)`` per array, flat, as
  ``state_lines`` are; ``()`` where an attention layer is a function of the
  launch's own rows. ``zaya`` (``models/zaya.py``) says one float32 line of
  ``2 * 1280 + 128``: its projection mixes each row with the rows before it
  (two causal convolutions of width 2 and a value taken from the token
  before), so a step needs the last packed row, the last row between the
  convolutions and the last shifted value. The engine keeps one device
  array ``(attention layers, slots, *shape)`` per entry behind the state
  layers' arrays, donated through ``_step`` and ``_prefill_chunk`` beside
  the pools, and hands a layer its batch entries' rows:

  - ``project_slot(blk, x, pos, kind, state, rows) -> (q, lines, state')``
    — ``state`` one ``(B, *shape)`` array per entry of ``slot_lines``, what
    each batch entry's sequence left (zeros where it starts: the engine's
    to say), ``rows (B,)`` how many of the ``Q`` rows of each entry are
    real (a step: 1 for a slot in the step, 0 for any other; a launch: its
    ``n_valid``). ``state'`` is the state after the entry's last real row,
    and with no real row the state as it was read, bit for bit: a padded
    row moves nothing. The default is ``project`` with ``state`` (``()``)
    handed back.

  Prefix sharing is refused for such a family (a hit would need the
  slot's rows as they were at the prefix's last token), as are several
  passes and a drafting block; ``preempt`` carries the rows with the
  pages.
* ``open_stack(p, x) -> carry`` and ``ffn_carry(blk, x, live, carry) ->
  (y, counts, carry')`` — one opaque value that goes down the stack inside
  a token, from each layer's feed-forward to the next's: the family opens
  it before layer 0 for activations ``x (B, Q, D)``, the engine threads it
  and never looks into it (a drafting block is a stack of one layer: its
  carry is opened anew). ``zaya``: the router's activations ``(B, Q,
  router_hidden_size)`` after the layer's own addition (exponential depth
  averaging; ``None`` before layer 0, where a first pipeline stage has
  nothing to receive). The default opens ``None`` and is ``ffn`` with the
  carry handed on.
* ``merge(blk, x, y, part) -> x'`` — what the skeleton does with the
  residual stream ``x`` and a part's output ``y`` (``part``: ``"attention"``,
  ``"state"`` or ``"ffn"``). The default is ``x + y``; ``zaya`` scales and
  shifts both with learned per-channel vectors (``scale_residual_merge``),
  under the ``jax.named_scope`` ``merge``.
"""
from __future__ import annotations

from .transformer import TransformerConfig, _rmsnorm


class PlainStack:
    """The answers of a family whose attention layers are functions of the
    launch's own rows, whose layers hand each other the residual stream
    alone and whose parts are added to it: every family but ``zaya``."""

    slot_lines = ()                # no attention layer keeps a state a slot

    def project_slot(self, blk, x, pos, kind, state, rows):
        return (*self.project(blk, x, pos, kind), state)

    def open_stack(self, p, x):
        return None                # nothing but x goes down the stack

    def ffn_carry(self, blk, x, live, carry):
        return (*self.ffn(blk, x, live), carry)

    def merge(self, blk, x, y, part):
        return x + y               # the residual adds a part's output


class GroupedQueryLines(PlainStack):
    """What the families whose attention layers keep keys and values of
    ``num_key_value_heads`` heads side by side share (``mellum``,
    ``jamba``): two lines of ``kv_heads * head_dim`` a token, the step's
    block-diagonal query over whole lines, and a launch's lines split by
    key head. ``self.cfg`` has ``num_attention_heads``,
    ``num_key_value_heads``, ``head_dim`` and ``line_width``; the family's
    ``project`` makes the queries ``(B, Q, H, head_dim)`` (and rotates
    them, if it has positions)."""

    chunk_precision = "highest"    # f32 queries over a bf16 pool
    passes = 1                     # the stack once a token
    drafts = 0                     # no layer of it drafts a token

    def stored(self, params):
        return params              # served as they come

    def step_by_head(self, queries: int) -> bool:
        return False               # the step's queries span whole lines

    @property
    def cache_lines(self) -> tuple:
        return (self.cfg.line_width, self.cfg.line_width)

    @property
    def attention_scale(self) -> float:
        return self.cfg.head_dim ** -0.5

    def _own(self):
        import jax.numpy as jnp

        # line element j belongs to key head j // head_dim, which query
        # heads n with n // group == that head read
        cfg = self.cfg
        group = cfg.num_attention_heads // cfg.num_key_value_heads
        return (jnp.arange(cfg.num_attention_heads)[:, None] // group
                == jnp.arange(cfg.line_width)[None, :] // cfg.head_dim)

    def step_queries(self, q):
        import jax.numpy as jnp

        # whole lines against a block-diagonal query: row n holds head n's
        # query in the block of its key head and zeros elsewhere (the
        # gpt family's form, with ``group`` rows a block)
        tiled = jnp.tile(q[:, 0], (1, 1, self.cfg.num_key_value_heads))
        return jnp.where(self._own()[None], tiled, 0.0)

    def step_output(self, blk, o):
        import jax.numpy as jnp

        # row n of o is head n's weights over every key head's values: the
        # block of its own key head is the attention output
        cfg = self.cfg
        S = o.shape[0]
        o = jnp.where(self._own()[None], o, 0.0).reshape(
            S, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim).sum(axis=2)
        return o.reshape(S, 1, -1) @ blk["wo"]

    @property
    def chunk_heads(self) -> tuple:
        cfg = self.cfg
        return (cfg.num_key_value_heads,
                cfg.num_attention_heads // cfg.num_key_value_heads)

    def chunk_output(self, blk, o):
        return o.reshape(*o.shape[:2], -1) @ blk["wo"]


class GPTFamily(PlainStack):
    """The repo's GPT-2-shaped block (``models/transformer.py``): learned
    positions, one fused ``wqkv``, full multi-head attention over keys and
    values, a ReLU MLP (or the trainer's switch layer), a tied head."""

    name = "gpt"
    attention_scopes = {"full": "attention"}
    window = None          # every layer sees the whole context
    passes = 1             # the stack once a token
    drafts = 0             # no layer of it drafts a token
    counters = ()          # nothing an expert layer would count
    state_lines = ()       # no layer keeps a state a sequence
    serves_verify = True   # speculative verification (``_verify``)
    chunk_precision = None  # a launch's products at jax's default, as ever

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg
        self.vocab = cfg.vocab
        self.layers = cfg.layers
        # the position table is a weight: the serving limit cannot pass it
        self.max_positions = cfg.max_seq
        self.layer_kinds = ("full",) * cfg.layers

    @property
    def cache_lines(self) -> tuple:
        width = self.cfg.heads * self.cfg.head_dim
        return (width, width)

    def init_params(self, seed: int):
        from .transformer import init_params

        return init_params(self.cfg, seed=seed)

    def stored(self, params):
        return params              # served as they come

    def step_by_head(self, queries: int) -> bool:
        return False               # the step's queries span whole lines

    def with_positions(self, positions: int) -> "GPTFamily":
        from dataclasses import replace

        return GPTFamily(replace(self.cfg, max_seq=positions))

    def embed(self, p, toks, pos):
        import jax.numpy as jnp

        return (p["embed"][toks] + p["pos"][pos]).astype(jnp.float32)

    def blocks(self, p):
        return p["blocks"]

    def project(self, blk, x, pos, kind="full"):
        import jax.numpy as jnp

        q, k, v = jnp.split(_rmsnorm(x, blk["ln1"]) @ blk["wqkv"], 3, axis=-1)
        return q, (k, v)

    @property
    def attention_scale(self) -> float:
        return self.cfg.head_dim ** -0.5

    @property
    def chunk_heads(self) -> tuple:
        return (self.cfg.heads, 1)

    def chunk_output(self, blk, o):
        return o.reshape(*o.shape[:2], -1) @ blk["wo"]

    def _own(self):
        import jax.numpy as jnp

        # line element j belongs to head j // head_dim
        cfg = self.cfg
        return (jnp.arange(cfg.heads)[:, None]
                == jnp.arange(cfg.heads * cfg.head_dim)[None, :]
                // cfg.head_dim)

    def step_queries(self, q):
        import jax.numpy as jnp

        # whole lines against a block-diagonal query (row h holds head h's
        # query and zeros) instead of lines re-tiled by head: 1.29 ms a
        # layer against 6.09 on a v5e for the gathered form (PR 25), and
        # the shape the latent family's step has by construction. The
        # zeros add nothing; the scores are the per-head float32 ones
        return jnp.where(self._own()[None], q[:, 0, None, :], 0.0)

    def step_output(self, blk, o):
        import jax.numpy as jnp

        # row h of o is head h's weights over every head's values: its
        # own block is the attention output
        S = o.shape[0]
        o = jnp.where(self._own()[None], o, 0.0).sum(axis=1)
        return o.reshape(S, 1, self.cfg.dim) @ blk["wo"]

    def attend_verify(self, blk, q, ctxs, visible):
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        ck, cv = ctxs
        H, Dh = cfg.heads, cfg.head_dim
        S, K = q.shape[0], q.shape[1]
        ctx = ck.shape[1]
        ck = ck.reshape(S, ctx, H, Dh)
        cv = cv.reshape(S, ctx, H, Dh)
        q = q.reshape(S, K, H, Dh)
        # broadcast-multiply-reduce instead of batched matmul:
        # XLA CPU lowers (S*H) tiny K x ctx GEMMs to per-batch
        # library calls whose fixed cost dwarfs the math; the
        # explicit reduce fuses into one loop (~30% off the
        # whole program at K=4). Scores are (S, K, ctx, H): the
        # context's own index order, so nothing is transposed
        att = ((q[:, :, None] * ck[:, None]).sum(-1)
               / jnp.sqrt(cfg.head_dim))
        att = jnp.where(visible[..., None], att, -1e30)
        att = jax.nn.softmax(att, axis=2)
        o = (att[..., None] * cv[:, None]).sum(2)   # (S, K, H, Dh)
        return o.reshape(S, K, cfg.dim) @ blk["wo"]

    def ffn(self, blk, x, live):
        from .decoding import _ffn

        return _ffn(blk, _rmsnorm(x, blk["ln2"]), None, self.cfg), None

    def head(self, p, x):
        return _rmsnorm(x, p["out_norm"]) @ p["embed"].T


def kept_state(family) -> str:
    """What a slot keeps beside its pages, in the words the refusals use:
    by where it lives, in state layers or in the attention layers; ``""``
    for a family that keeps none."""
    return " and ".join(
        what for what, lines in (
            ("its state layers' state", family.state_lines),
            ("the state a slot keeps in its attention layers",
             family.slot_lines)) if lines)


def _families() -> tuple:
    """``(configuration type, family)`` for every family there is: the one
    table :func:`family_of` dispatches on."""
    from .deepseek_v3 import DeepseekV3Config, DeepseekV3Family
    from .exaone_moe import ExaoneMoeConfig, ExaoneMoeFamily
    from .jamba import JambaConfig, JambaFamily
    from .mellum import MellumConfig, MellumFamily
    from .ouro import OuroConfig, OuroFamily
    from .zaya import ZayaConfig, ZayaFamily

    return ((TransformerConfig, GPTFamily),
            (DeepseekV3Config, DeepseekV3Family),
            (MellumConfig, MellumFamily),
            (JambaConfig, JambaFamily),
            (OuroConfig, OuroFamily),
            (ExaoneMoeConfig, ExaoneMoeFamily),
            (ZayaConfig, ZayaFamily))


def family_of(cfg):
    """The family of a configuration, by its type."""
    table = _families()
    for config_type, family in table:
        if isinstance(cfg, config_type):
            return family(cfg)
    raise TypeError(
        f"no model family serves a configuration of type "
        f"{type(cfg).__name__} (have "
        f"{', '.join(t.__name__ for t, _ in table)})")
