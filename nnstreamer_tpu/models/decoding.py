"""Autoregressive KV-cache decoding for the transformer LM.

The training side (``models/transformer.py``) runs full sequences; this is
the inference side: a prefill pass that fills a per-layer K/V cache, a
single-token decode step that attends against the cache, and a
``lax.scan`` generation loop — all jittable with static shapes (the cache
is allocated at ``max_seq`` and written with ``dynamic_update_slice``,
positions masked by index, per XLA's no-dynamic-shapes rule).

For DENSE configs cached decode is exact: it picks the same greedy tokens
as re-running the full forward each step (asserted in test_decoding.py).
For MoE configs it is not bit-identical to a full-sequence rerun: switch
routing capacity is per-call (``C = ceil(T/E·cf)``), so a decode step
routing B tokens can overflow/passthrough differently than a forward over
B·S — inherent to capacity-based MoE serving, not a cache artifact.

Sharding: the cache is (B, H, max_seq, Dh) per layer, sharded
``P("dp", "tp", None, None)`` — batch over data parallel, heads over
tensor parallel, matching the training-side head sharding so decode reuses
the same weight layout with zero resharding. (Sequence stays unsharded in
decode: each step reads the whole cache; context-parallel decode would
psum partial attention over ``sp`` — noted as the scaling extension.)

No reference analog: the reference has no generative/LLM path at all
(SURVEY.md §5.7); this is TPU-native capability beyond parity.
"""
from __future__ import annotations

from .transformer import TransformerConfig, _rmsnorm


def init_cache(cfg: TransformerConfig, batch: int, dtype=None):
    """Zeroed K/V cache: list of {"k","v"} (B, H, max_seq, head_dim).

    ``dtype`` defaults to float32; serving passes the params' dtype so a
    bfloat16-weight model also halves its per-step cache HBM reads."""
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    shape = (batch, cfg.heads, cfg.max_seq, cfg.head_dim)
    return [
        {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        for _ in range(cfg.layers)
    ]


def cache_pspecs(cfg: TransformerConfig, context_parallel: bool = False):
    """Cache PartitionSpecs; with ``context_parallel`` the sequence axis
    shards over ``sp`` (each chip holds max_seq/sp cache positions)."""
    from jax.sharding import PartitionSpec as P

    seq_axis = "sp" if context_parallel else None
    return [{"k": P("dp", "tp", seq_axis, None),
             "v": P("dp", "tp", seq_axis, None)}
            for _ in range(cfg.layers)]


def make_sp_cache_attention(cfg: TransformerConfig, mesh):
    """Context-parallel cached attention: the KV cache's sequence axis is
    sharded over ``sp``; each shard scores its local cache slice and the
    partial online-softmax statistics combine with ``pmax``/``psum`` —
    the decode-side counterpart of the training ring attention
    (parallel/context.py). Cache memory per chip drops by the sp factor,
    which is what lets max_seq exceed one chip's HBM.

    Returns ``attn(q, k_new, v_new, ck, cv, pos) -> (o, ck, cv)`` with
    q/k_new/v_new (B, H, 1, Dh), cache (B, H, max_seq, Dh) [sp-sharded],
    pos scalar int32.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    if "sp" not in dict(mesh.shape):
        raise ValueError(
            "context-parallel decoding needs an 'sp' axis in the mesh "
            f"(got axes {list(dict(mesh.shape))})")
    sp = dict(mesh.shape)["sp"]
    if cfg.max_seq % sp:
        raise ValueError(
            f"max_seq {cfg.max_seq} must divide by the sp axis size {sp}")
    local = cfg.max_seq // sp
    scale = jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))

    def shard_fn(q, k_new, v_new, ck, cv, pos):
        # ck/cv here are the LOCAL (B, H, local, Dh) slices
        start = jax.lax.axis_index("sp") * local
        lp = pos - start
        in_range = (lp >= 0) & (lp < local)
        lpc = jnp.clip(lp, 0, local - 1)
        ck = jnp.where(in_range,
                       jax.lax.dynamic_update_slice(ck, k_new, (0, 0, lpc, 0)),
                       ck)
        cv = jnp.where(in_range,
                       jax.lax.dynamic_update_slice(cv, v_new, (0, 0, lpc, 0)),
                       cv)
        scores = (q @ ck.transpose(0, 1, 3, 2)) / scale   # (B,H,1,local)
        visible = (start + jnp.arange(local)) <= pos
        scores = jnp.where(visible[None, None, None, :], scores, -jnp.inf)
        m = jnp.max(scores, axis=-1)                      # (B,H,1) local max
        gm = jax.lax.pmax(m, "sp")                        # global max
        # exp(-inf - gm) == 0: fully-masked shards contribute nothing
        p = jnp.exp(scores - gm[..., None])
        p = jnp.where(visible[None, None, None, :], p, 0.0)
        denom = jax.lax.psum(jnp.sum(p, axis=-1), "sp")   # (B,H,1)
        num = jax.lax.psum(p @ cv, "sp")                  # (B,H,1,Dh)
        return num / denom[..., None], ck, cv

    qspec = P("dp", "tp", None, None)
    cspec = P("dp", "tp", "sp", None)
    return shard_map(
        shard_fn, mesh=mesh,
        in_specs=(qspec, qspec, qspec, cspec, cspec, P()),
        out_specs=(qspec, cspec, cspec),
    )


def _split_heads(cfg: TransformerConfig, t):
    B, S = t.shape[0], t.shape[1]
    return t.reshape(B, S, cfg.heads, cfg.head_dim).transpose(0, 2, 1, 3)


def _ffn(blk, h, mesh, cfg: TransformerConfig):
    import jax

    if "moe" in blk:
        from ..parallel.moe import moe_ffn

        y, _aux = moe_ffn(blk["moe"], h, mesh, ep_axis="tp",
                          capacity_factor=cfg.moe_capacity_factor,
                          return_aux=True)
        return y
    return jax.nn.relu(h @ blk["w1"]) @ blk["w2"]


def prefill(cfg: TransformerConfig, params, tokens, cache, mesh=None,
            context_parallel: bool = False):
    """Run the prompt (B, S) through the model, filling cache[:, :, :S].

    Returns (logits_last (B, V), cache, next_pos). Attention inside the
    prompt is causal, identical math to the training ``forward``. With
    ``context_parallel`` the prompt's activations/K/V are sequence-sharded
    over ``sp`` and attention runs through the ring schedule
    (parallel/context.py) — the prompt never materializes unsharded, so
    long prompts scale with the sp factor just like the cache does.
    """
    import jax
    import jax.numpy as jnp

    ctx_attn = None
    constrain = lambda x, *spec: x  # noqa: E731
    if context_parallel:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.context import make_context_attention

        ctx_attn = make_context_attention(mesh, impl="ring")

        def constrain(x, *spec):  # noqa: F811
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*spec)))

    B, S = tokens.shape
    S_real = S
    if ctx_attn is not None:
        # ring attention shards the sequence over sp: pad the prompt to a
        # multiple. Pad K/V slots sit at positions >= S_real, which causal
        # masking hides from every real token and which the decode loop
        # overwrites (position p is written before it first becomes
        # visible), so the padding never leaks into results.
        sp = dict(mesh.shape)["sp"]
        pad = (-S) % sp
        if S + pad > cfg.max_seq:
            raise ValueError(
                f"prompt ({S}) padded to the sp multiple ({S + pad}) "
                f"exceeds max_seq {cfg.max_seq}")
        if pad:
            tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
            S = S + pad
    x = (params["embed"][tokens]
         + params["pos"][:S][None, :, :]).astype(jnp.float32)
    x = constrain(x, "dp", "sp", None)
    mask = None if ctx_attn is not None else jnp.tril(jnp.ones((S, S), bool))
    for li, blk in enumerate(params["blocks"]):
        h = _rmsnorm(x, blk["ln1"])
        q, k, v = jnp.split(h @ blk["wqkv"], 3, axis=-1)
        q, k, v = (_split_heads(cfg, t) for t in (q, k, v))  # (B,H,S,Dh)
        if ctx_attn is not None:
            k = constrain(k, "dp", "tp", "sp", None)
            v = constrain(v, "dp", "tp", "sp", None)
        cache[li] = {
            "k": jax.lax.dynamic_update_slice(
                cache[li]["k"], k.astype(cache[li]["k"].dtype), (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache[li]["v"], v.astype(cache[li]["v"].dtype), (0, 0, 0, 0)),
        }
        if ctx_attn is not None:
            o = ctx_attn(q, k, v)
            o = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.dim)
        else:
            att = (q @ k.transpose(0, 1, 3, 2)) / jnp.sqrt(cfg.head_dim)
            att = jnp.where(mask[None, None], att, -1e30)
            att = jax.nn.softmax(att, axis=-1)
            o = (att @ v).transpose(0, 2, 1, 3).reshape(B, S, cfg.dim)
        x = x + o @ blk["wo"]
        x = x + _ffn(blk, _rmsnorm(x, blk["ln2"]), mesh, cfg)
        x = constrain(x, "dp", "sp", None)
    x = _rmsnorm(x[:, S_real - 1], params["out_norm"])  # last REAL position
    return x @ params["embed"].T, cache, jnp.asarray(S_real, jnp.int32)


def prefill_continue(cfg: TransformerConfig, params, tokens, cache, start,
                     mesh=None):
    """Chunked prefill: ingest ``tokens`` (B, P) at positions
    ``start..start+P-1``, attending causally over the EXISTING cache
    prefix plus the chunk itself — the multi-turn ingestion primitive
    (one compiled call per conversation turn where a decode_step loop
    would pay P sequential dispatches). ``start`` is a traced scalar;
    P is static. Returns (logits_last (B, V), cache, start + P).

    Equivalence contract: after this call the cache holds exactly the
    states a from-scratch :func:`prefill` over history+chunk would
    produce (asserted via the conversation oracle in test_generate).
    """
    import jax
    import jax.numpy as jnp

    B, P = tokens.shape
    x = (params["embed"][tokens]
         + jax.lax.dynamic_slice_in_dim(params["pos"], start, P, 0)
         ).astype(jnp.float32)
    positions = jnp.arange(cfg.max_seq)
    q_pos = start + jnp.arange(P)
    visible = (positions[None, None, None, :]
               <= q_pos[None, None, :, None])          # (1,1,P,max_seq)
    for li, blk in enumerate(params["blocks"]):
        h = _rmsnorm(x, blk["ln1"])
        q, k, v = jnp.split(h @ blk["wqkv"], 3, axis=-1)
        q, k, v = (_split_heads(cfg, t) for t in (q, k, v))  # (B,H,P,Dh)
        ck = jax.lax.dynamic_update_slice(
            cache[li]["k"], k.astype(cache[li]["k"].dtype), (0, 0, start, 0))
        cv = jax.lax.dynamic_update_slice(
            cache[li]["v"], v.astype(cache[li]["v"].dtype), (0, 0, start, 0))
        cache[li] = {"k": ck, "v": cv}
        att = (q @ ck.transpose(0, 1, 3, 2)) / jnp.sqrt(cfg.head_dim)
        att = jnp.where(visible, att, -1e30)           # (B,H,P,max_seq)
        att = jax.nn.softmax(att, axis=-1)
        o = (att @ cv).transpose(0, 2, 1, 3).reshape(B, P, cfg.dim)
        x = x + o @ blk["wo"]
        x = x + _ffn(blk, _rmsnorm(x, blk["ln2"]), mesh, cfg)
    x = _rmsnorm(x[:, -1], params["out_norm"])
    return x @ params["embed"].T, cache, start + P


def decode_step(cfg: TransformerConfig, params, token, pos, cache, mesh=None,
                sp_attn=None):
    """One token (B,) at position ``pos`` (scalar int32) → (logits (B, V),
    cache). Attends against cache[:, :, :pos+1]; positions > pos are
    masked by index so the fixed-size cache stays jit-static. With
    ``sp_attn`` (from :func:`make_sp_cache_attention`) the cache stays
    sequence-sharded and attention combines per-shard partials."""
    import jax
    import jax.numpy as jnp

    B = token.shape[0]
    x = (params["embed"][token] + jax.lax.dynamic_index_in_dim(
        params["pos"], pos, axis=0, keepdims=False)
         ).astype(jnp.float32)  # (B, D)
    x = x[:, None, :]                                # (B, 1, D)
    positions = jnp.arange(cfg.max_seq)
    visible = (positions <= pos)[None, None, None, :]  # (1,1,1,max_seq)
    for li, blk in enumerate(params["blocks"]):
        h = _rmsnorm(x, blk["ln1"])
        q, k, v = jnp.split(h @ blk["wqkv"], 3, axis=-1)
        q, k, v = (_split_heads(cfg, t) for t in (q, k, v))  # (B,H,1,Dh)
        k = k.astype(cache[li]["k"].dtype)
        v = v.astype(cache[li]["v"].dtype)
        if sp_attn is not None:
            o, ck, cv = sp_attn(q, k, v, cache[li]["k"], cache[li]["v"], pos)
            cache[li] = {"k": ck, "v": cv}
            o = o.transpose(0, 2, 1, 3).reshape(B, 1, cfg.dim)
        else:
            ck = jax.lax.dynamic_update_slice(cache[li]["k"], k, (0, 0, pos, 0))
            cv = jax.lax.dynamic_update_slice(cache[li]["v"], v, (0, 0, pos, 0))
            cache[li] = {"k": ck, "v": cv}
            if cfg.decode_attn not in ("xla", "pallas"):
                raise ValueError(
                    f"unknown decode_attn {cfg.decode_attn!r} "
                    "(expected 'xla' or 'pallas')")
            if cfg.decode_attn == "pallas" and mesh is None:
                # single-pass online-softmax kernel over the valid prefix
                # (ops/pallas_decode.py); sharded decode keeps the dense
                # path — GSPMD partitions it, a pallas_call would not
                import math

                from ..ops.pallas_decode import cached_decode_attention
                from ..utils.hw_accel import pallas_interpret

                o = cached_decode_attention(
                    q, ck, cv, pos,
                    block_k=math.gcd(cfg.max_seq, 128),
                    interpret=pallas_interpret(jax.devices()[0].platform))
                o = o.transpose(0, 2, 1, 3).reshape(B, 1, cfg.dim)
            else:
                att = (q @ ck.transpose(0, 1, 3, 2)) / jnp.sqrt(cfg.head_dim)
                att = jnp.where(visible, att, -1e30)  # (B,H,1,max_seq)
                att = jax.nn.softmax(att, axis=-1)
                o = (att @ cv).transpose(0, 2, 1, 3).reshape(B, 1, cfg.dim)
        x = x + o @ blk["wo"]
        x = x + _ffn(blk, _rmsnorm(x, blk["ln2"]), mesh, cfg)
    x = _rmsnorm(x[:, 0], params["out_norm"])
    return x @ params["embed"].T, cache


def make_generate(cfg: TransformerConfig, mesh=None,
                  temperature: float = 0.0, context_parallel: bool = False,
                  cache_len: int = 0):
    """Build ``generate(params, prompt (B, S), steps, [rng]) -> (B, S+steps)``
    — jitted prefill + ``lax.scan`` over decode_step. ``temperature`` 0 =
    greedy (deterministic); >0 = categorical sampling (pass ``rng``).

    ``steps`` is static (bakes the scan length). With ``mesh``, params keep
    their training PartitionSpecs and the cache shards per
    :func:`cache_pspecs`; XLA inserts the tp all-reduces per step. With
    ``context_parallel`` the cache sequence axis additionally shards over
    ``sp`` and attention runs via :func:`make_sp_cache_attention`.

    ``cache_len`` right-sizes the serving cache: every decode step reads
    the WHOLE cache (masked), so a model trained at max_seq=2048 serving
    prompt+steps=640 would pay 3.2× the attention HBM traffic it needs.
    Pass the actual serving length (≤ cfg.max_seq) and the cache, masks
    and scan are built at that size; position embeddings still come from
    the full table. 0 = cfg.max_seq.

    The cache (and its HBM read per step) follows the params dtype: cast
    params to bfloat16 for serving and the K/V cache stores bfloat16
    too, halving decode bandwidth; activations stay float32 throughout.
    """
    import functools
    from dataclasses import replace

    import jax
    import jax.numpy as jnp

    if cache_len:
        if cache_len > cfg.max_seq:
            raise ValueError(
                f"cache_len {cache_len} exceeds the model's max_seq "
                f"{cfg.max_seq} (position table size)")
        cfg = replace(cfg, max_seq=cache_len)

    sp_attn = None
    if context_parallel:
        if mesh is None:
            raise ValueError("context_parallel decoding needs a mesh")
        sp_attn = make_sp_cache_attention(cfg, mesh)

    def _constrain_cache(cache):
        if mesh is None:
            return cache
        from jax.sharding import NamedSharding

        shardings = [
            {k: NamedSharding(mesh, s) for k, s in layer.items()}
            for layer in cache_pspecs(cfg, context_parallel)
        ]
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, cache, shardings)

    @functools.partial(jax.jit, static_argnums=(2,))
    def generate(params, prompt, steps, rng=None):
        B, S = prompt.shape
        if S + steps > cfg.max_seq:
            raise ValueError(
                f"prompt ({S}) + steps ({steps}) exceeds max_seq {cfg.max_seq}")
        cache = _constrain_cache(
            init_cache(cfg, B, dtype=params["embed"].dtype))
        logits, cache, pos = prefill(cfg, params, prompt, cache, mesh,
                                     context_parallel=context_parallel)
        if rng is None:
            rng = jax.random.PRNGKey(0)

        def pick(logits, key):
            if temperature > 0.0:
                return jax.random.categorical(
                    key, logits / temperature, axis=-1).astype(jnp.int32)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        first = pick(logits, rng)

        def body(carry, key):
            token, pos, cache = carry
            logits, cache = decode_step(cfg, params, token, pos, cache, mesh,
                                        sp_attn=sp_attn)
            cache = _constrain_cache(cache)
            nxt = pick(logits, key)
            return (nxt, pos + 1, cache), nxt

        keys = jax.random.split(jax.random.fold_in(rng, 1), steps - 1)
        _, rest = jax.lax.scan(
            body, (first, pos, cache), keys, length=steps - 1)
        generated = jnp.concatenate([first[:, None], rest.T], axis=1)
        return jnp.concatenate([prompt, generated], axis=1)

    return generate
