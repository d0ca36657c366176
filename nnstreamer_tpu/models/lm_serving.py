"""Shard-aware LM serving entries: autoregressive generation as a
``tensor_filter`` stage.

The reference has no generative path at all (SURVEY.md §5.7); this is
TPU-native capability beyond parity, and — paired with the jax backend's
``custom=mesh:DxT`` 2-D mesh — it puts the tensor-parallel decoding stack
(``models/decoding.py``) behind the PRODUCT surface: a launch line like

    appsrc ! tensor_filter framework=jax
        model=nnstreamer_tpu.models.lm_serving:tiny custom=mesh:2x4
    ! tensor_sink

serves batched greedy generation with the params sharded megatron-style
over ``tp`` (param_pspecs), the KV cache sharded per ``cache_pspecs``,
and the batch sharded over ``dp`` — all chips over ICI, zero topology
plumbing in the pipeline description.

Entry protocol (jax backend, backends/jax_backend.py _load_model):
  * ``make()``             — single-device build.
  * ``make_sharded(mesh)`` — build against the filter's device mesh; used
    automatically when ``custom=mesh:...`` is set. On a dp-only mesh the
    params stay replicated (jit constants) and only the batch shards; a
    2-D ``(dp, tp)`` mesh additionally shards params + cache over ``tp``.

The filter contract: input ``(B, P) int32`` prompt tokens → output
``(B, P + steps) int32`` (prompt echoed, ``steps`` greedy continuations).
``steps`` comes from the entry (env ``NNS_LM_STEPS`` overrides).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional

from .transformer import TransformerConfig


def _steps(default: int) -> int:
    raw = os.environ.get("NNS_LM_STEPS", str(default))
    try:
        steps = int(raw)
    except ValueError:
        raise ValueError(f"NNS_LM_STEPS={raw!r} is not an integer")
    if steps < 1:
        raise ValueError(f"NNS_LM_STEPS={steps} must be >= 1")
    return steps


@dataclass(frozen=True)
class _LMServingEntry:
    cfg: TransformerConfig
    default_steps: int = 8
    seed: int = 0
    # serving-efficiency knobs (models/decoding.py rationale): weights AND
    # KV cache in this dtype (activations stay f32); cache sized to the
    # actual serving length instead of cfg.max_seq. None/0 = train config.
    serve_dtype: Optional[str] = None
    cache_len: int = 0

    @property
    def _family(self):
        """The model family of this entry's configuration, by its type
        (models/families.py)."""
        from .families import family_of

        return family_of(self.cfg)

    @property
    def _cfg_serve(self):
        if self.cache_len:
            fam = self._family
            if self.cache_len > fam.max_positions:
                raise ValueError(
                    f"cache_len {self.cache_len} exceeds max_seq "
                    f"{fam.max_positions}")
            return fam.with_positions(self.cache_len).cfg
        return self.cfg

    def _shard_params(self, mesh):
        """Init params and, when ``mesh`` carries a real tp axis, place
        them per the megatron PartitionSpecs. Returns ``(params,
        use_tp)`` — the one definition both the whole-sequence and
        streaming builds rely on (divergence here would break their
        token-exactness)."""
        import jax

        from .transformer import param_pspecs

        params = self._family.init_params(self.seed)
        if self.serve_dtype:
            import jax.numpy as jnp

            dt = jnp.dtype(self.serve_dtype)
            params = jax.tree_util.tree_map(
                lambda a: a.astype(dt) if a.dtype == jnp.float32 else a,
                params)
        use_tp = (mesh is not None and "tp" in mesh.axis_names
                  and mesh.shape["tp"] > 1)
        if use_tp:
            self._gpt_only("tensor-parallel params")
            if self.cfg.heads % mesh.shape["tp"] != 0:
                raise ValueError(
                    f"lm_serving: heads={self.cfg.heads} not divisible by "
                    f"mesh tp={mesh.shape['tp']}")
            from jax.sharding import NamedSharding, PartitionSpec as P

            shardings = jax.tree_util.tree_map(
                lambda spec: NamedSharding(mesh, spec),
                param_pspecs(self.cfg),
                is_leaf=lambda x: isinstance(x, P))
            params = jax.device_put(params, shardings)
        return params, use_tp

    def _gpt_only(self, what: str) -> None:
        if not isinstance(self.cfg, TransformerConfig):
            raise NotImplementedError(
                f"lm_serving: {what} serves the gpt family only; the "
                f"{self._family.name} family is served by "
                "make_continuous()")

    def _build(self, mesh=None):
        from .decoding import make_generate

        self._gpt_only("the whole-sequence generate path")
        params, use_tp = self._shard_params(mesh)
        # dp-only / single-device: params replicate as jit constants; the
        # backend's dp batch sharding alone parallelizes the batch
        gen = make_generate(self.cfg, mesh=mesh if use_tp else None,
                            cache_len=self.cache_len)
        steps = _steps(self.default_steps)

        def serve(tokens):
            return (gen(params, tokens, steps),)

        return serve

    def make(self):
        return self._build(mesh=None)

    def make_sharded(self, mesh):
        return self._build(mesh=mesh)

    def make_streaming(self, mesh=None, temperature: float = 0.0):
        """Per-token generation for the ``tensor_generate`` element:
        returns ``stream(tokens (B, P), steps, rng=None) -> yields (B,)
        int32`` — prefill once, then one jitted ``decode_step`` per
        yielded token. A host loop (not ``lax.scan``) is the point: each
        token leaves the device as it is picked, so downstream elements
        render/forward incrementally instead of waiting out the whole
        scan. ``temperature`` 0 = greedy (deterministic); > 0 =
        categorical sampling (``rng``: int seed or jax key; per-step keys
        are folded from it, and continuation turns fold in the session
        position so multi-turn sampling never reuses a key)."""
        import functools

        import jax
        import jax.numpy as jnp

        from .decoding import (
            cache_pspecs,
            decode_step,
            init_cache,
            prefill,
            prefill_continue,
        )

        self._gpt_only("the streaming generate path")
        cfg = self._cfg_serve
        params, use_tp = self._shard_params(mesh)
        step_mesh = mesh if use_tp else None

        # the cache is the dominant HBM consumer: pin it to its specs
        # restricted to the axes THIS mesh actually has (dp-only meshes
        # batch-shard it; (dp, tp) meshes also head-shard it) — GSPMD
        # propagation alone could leave it replicated
        constrain = lambda c: c  # noqa: E731
        batch_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            axes = set(mesh.axis_names)

            def _restrict(spec):
                return P(*(a if a in axes else None for a in spec))

            cache_sh = [
                {k: NamedSharding(mesh, _restrict(s)) for k, s in layer.items()}
                for layer in cache_pspecs(cfg)]

            def constrain(cache):  # noqa: F811
                return jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, cache, cache_sh)

            if "dp" in axes:
                batch_sharding = NamedSharding(mesh, P("dp"))

        _dummy_key = jax.random.PRNGKey(0)

        def _pick(logits, key):
            if temperature > 0.0:
                return jax.random.categorical(
                    key, logits / temperature, axis=-1).astype(jnp.int32)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        @jax.jit
        def _prefill(params, tokens, key):
            cache = constrain(init_cache(cfg, tokens.shape[0],
                                         dtype=params["embed"].dtype))
            logits, cache, pos = prefill(cfg, params, tokens, cache,
                                         step_mesh)
            return _pick(logits, key), pos, constrain(cache)

        # donate the cache: each step writes one position in place —
        # without donation every token holds two full caches in HBM
        @functools.partial(jax.jit, donate_argnums=(3,))
        def _step(params, token, pos, cache, key):
            logits, cache = decode_step(cfg, params, token, pos, cache,
                                        step_mesh)
            return _pick(logits, key), pos + 1, constrain(cache)

        # multi-turn ingestion: one compiled call per turn (a decode_step
        # loop would pay P sequential dispatches); cache donated likewise
        @functools.partial(jax.jit, donate_argnums=(2,))
        def _ingest(params, feed, cache, start, key):
            logits, cache, pos = prefill_continue(cfg, params, feed, cache,
                                                  start, step_mesh)
            return _pick(logits, key), pos, constrain(cache)

        def _shard_tokens(tokens):
            if batch_sharding is not None \
                    and tokens.shape[0] % mesh.shape["dp"] == 0:
                return jax.device_put(tokens, batch_sharding)
            return tokens

        def stream(tokens, steps, _session=None, rng=None):
            """Yield ``steps`` tokens for ``tokens`` (B, P). With
            ``_session`` (a _StreamSession), the KV cache CONTINUES from
            the previous turn: the new prompt is ingested in one chunked
            prefill, then generation resumes — multi-turn serving
            without re-prefilling history."""
            if steps < 1:
                raise ValueError(f"steps={steps} must be >= 1")
            state = _session.state if _session is not None else None
            if temperature > 0.0:
                import numpy as _np

                # int-like seeds (incl. numpy scalars) become keys;
                # anything else is assumed to BE a key already
                base_key = (jax.random.PRNGKey(int(rng or 0))
                            if isinstance(rng, (int, _np.integer,
                                                type(None)))
                            else rng)
                if state is not None:
                    # a continuation turn must never reuse turn-1's keys
                    base_key = jax.random.fold_in(base_key, int(state[1]))
                keys = jax.random.split(base_key, steps)
            else:
                # greedy ignores keys (_pick's temperature branch is
                # static) — skip per-call key derivation on the hot path
                keys = [_dummy_key] * steps
            if state is None:
                if tokens.shape[1] + steps > cfg.max_seq:
                    raise ValueError(
                        f"prompt ({tokens.shape[1]}) + steps ({steps}) "
                        f"exceeds max_seq {cfg.max_seq}")
                token, pos, cache = _prefill(params, _shard_tokens(tokens),
                                             keys[0])
            else:
                pending, pos, cache = state
                if tokens.shape[0] != pending.shape[0]:
                    raise ValueError(
                        f"conversation batch changed: session has "
                        f"batch {pending.shape[0]}, new prompt has "
                        f"{tokens.shape[0]} (reset() to start over)")
                if int(pos) + tokens.shape[1] + steps > cfg.max_seq:
                    raise ValueError(
                        f"conversation at pos {int(pos)} + prompt "
                        f"({tokens.shape[1]}) + steps ({steps}) exceeds "
                        f"max_seq {cfg.max_seq}")
                tokens = _shard_tokens(tokens)
                # teacher-forced ingestion, ONE compiled call. The
                # previous turn's FINAL sample is still pending (its K/V
                # was never written — generation stopped at its
                # prediction), so it leads the chunk; the chunk's last
                # prediction opens generation. Cache states end up
                # identical to a from-scratch prefill over
                # history+prompt (asserted in test_generate).
                feed = jnp.concatenate([pending[:, None], tokens], axis=1)
                token, pos, cache = _ingest(params, feed, cache, pos,
                                            keys[0])
            # persist state after EVERY step, not just at exhaustion: the
            # cache is donated into each _step, so an abandoned generator
            # must leave the session holding the LIVE cache, never a
            # donated-away one
            if _session is not None:
                _session.state = (token, pos, cache)
            yield token
            for i in range(steps - 1):
                token, pos, cache = _step(params, token, pos, cache,
                                          keys[i + 1])
                if _session is not None:
                    _session.state = (token, pos, cache)
                yield token

        return stream

    def make_continuous(self, slots: int = 4, paged: bool = True,
                        draft=None, spec_k: int = 4, **engine_kw):
        """Continuous-batching decode state for the serving layer: a
        fixed-``slots`` :class:`~...serving.PagedLMEngine` where sequences
        join/retire independently between decode steps
        (``serving.DecodeScheduler`` drives it; ``engine_kw``: page_size /
        pages / chunk / share_prefixes / max_positions, docs/serving.md).
        ``chunk`` is the least a prefill launch ingests: the engine widens
        it to the chip's ridge where it knows the chip (256 tokens on a
        v5e in bfloat16, ``serving.lm_engine.prefill_width``) and takes it
        as given on the CPU; ``engine.chunk`` is the width in use.
        Params honor the entry's serve knobs (serve_dtype, cache_len); the
        model family comes from the type of the entry's configuration
        (models/families.py).

        ``draft`` wraps it in :class:`~...serving.SpeculativeLMEngine`:
        pass a draft object (``NgramDraft()``), a draft
        ``_LMServingEntry`` (becomes a ``ModelDraft`` over its own
        params), or the string ``"ngram"``; ``spec_k`` is the draft burst
        length verified per target call.

        ``paged`` is inert: True is its only value (ROADMAP D12)."""
        if not paged:
            raise ValueError(
                "make_continuous(paged=False): the dense slot engine was "
                "removed; the paged engine is the only one")
        from ..serving.lm_engine import PagedLMEngine

        fam = self._family
        from .families import kept_state

        stateful = kept_state(fam)
        if draft is not None and stateful:
            raise NotImplementedError(
                f"lm_serving: speculative verification (_verify) does not "
                f"serve the {fam.name} family yet (a rejected draft would "
                f"have to roll {stateful} back to the accepted token, and "
                f"no snapshot of it is kept); build it without draft=")
        if draft is not None and fam.drafts:
            raise NotImplementedError(
                f"lm_serving: the {fam.name} family drafts on the device "
                f"(its own layer, verified by the paged engine's round); a "
                f"host-side draft beside it is not served: build it without "
                f"draft=")
        if draft is not None and not fam.serves_verify:
            raise NotImplementedError(
                f"lm_serving: speculative verification (_verify: one "
                f"full-kind block table, one pass, keys and values gathered "
                f"a head) does not serve the {fam.name} family; build it "
                f"without draft=")
        from ..obs import context as obs_context

        # start-up's spans (docs/observability.md): the weights in the
        # serving type, then the engine: what its constructor cost the host
        # (the pools' allocations are dispatched and not waited for) and
        # what it built
        with obs_context.span("setup.params") as sp:
            params, _ = self._shard_params(None)
        with obs_context.span("setup.engine", slots=slots) as built:
            eng = PagedLMEngine(self._cfg_serve, params, slots=slots,
                                **engine_kw)
            built.attrs.update(
                page_size=eng.page_size, chunk=eng.chunk,
                pool_bytes={kind: of["bytes"] for kind, of
                            in eng.memory_bytes()["kinds"].items()},
                state_bytes=eng.state_slot_bytes * slots,
                relaid_matrices=eng.relaid["matrices"],
                relaid_bytes=eng.relaid["bytes"])
        sp.attrs["bytes"] = eng.param_bytes
        if draft is None:
            return eng
        from ..serving.speculative import (
            ModelDraft,
            NgramDraft,
            SpeculativeLMEngine,
        )

        if isinstance(draft, str):
            if draft != "ngram":
                raise ValueError(f"unknown draft spec {draft!r}")
            draft = NgramDraft()
        elif isinstance(draft, _LMServingEntry):
            dcfg = draft._cfg_serve
            if dcfg.vocab != self._cfg_serve.vocab:
                raise ValueError(
                    f"draft vocab {dcfg.vocab} != target vocab "
                    f"{self._cfg_serve.vocab}: speculative verify "
                    "compares token ids, the vocabularies must match")
            dparams, _ = draft._shard_params(None)
            draft = ModelDraft(dcfg, dparams)
        return SpeculativeLMEngine(eng, draft, k=spec_k)

    def make_session(self, mesh=None, temperature: float = 0.0):
        """Stateful multi-turn serving: ``session.generate(tokens, steps)``
        yields like the stream form but the KV cache persists across
        calls (turn 2's prompt is ingested at the current position, not
        re-prefilled). ``session.reset()`` starts a new conversation."""
        return _StreamSession(self.make_streaming(mesh, temperature))


class _StreamSession:
    def __init__(self, stream):
        self._stream = stream
        self.state = None  # (last_token, pos, cache) after each turn

    def generate(self, tokens, steps: int, rng=None):
        return self._stream(tokens, steps, _session=self, rng=rng)

    def reset(self) -> None:
        self.state = None

    @property
    def position(self):
        """Sequence position after the last turn (0 = fresh session)."""
        return int(self.state[1]) if self.state is not None else 0


# test-size entry: heads=4 supports tp in {1,2,4}; max_seq bounds P+steps
tiny = _LMServingEntry(
    TransformerConfig(vocab=64, dim=32, heads=4, layers=2, max_seq=64))

# draft companion to ``tiny`` for speculative decode (same vocab — verify
# compares token ids; half the width, one layer: cheap proposals)
tiny_draft = _LMServingEntry(
    TransformerConfig(vocab=64, dim=16, heads=2, layers=1, max_seq=64))

# bench-size entry (~raises to a realistic serving shape on a real chip)
base = _LMServingEntry(
    TransformerConfig(vocab=32000, dim=1024, heads=16, layers=12,
                      max_seq=2048),
    default_steps=64)
