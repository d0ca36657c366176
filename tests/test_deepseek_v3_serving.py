"""The DeepSeek-V3 family (latent attention, dropless experts) through the
paged serving engine, against the benchmark's plain reference
(``benchmark/references/deepseek_v3_lm.py``: expanded attention, a loop
over all experts, float32 at ``highest``). CPU, small sizes, seeded
weights; logits are compared, never sampled tokens.

Tolerances. Everything here is float32 on the CPU, so program and reference
differ only by the order of float32 sums (absorbed against expanded
attention, grouped against looped experts, chunks against a whole
sequence): logits of size 0.1-1 agree to a few 1e-6, and the limits below
(2e-5 on logits and layer outputs, 1e-4 on the gap of a served token under
the reference's best) leave a factor of ten above what is seen and lie twenty
times under what bfloat16 cache lines and bfloat16 expert inputs move a
logit by (4.5e-4 at these sizes: ``test_a_bfloat16_cache_would_fail``).
"""
import os
import sys

import numpy as np
import pytest
from engine_util import launch, spy_launches, step_now

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.lib.weights import seed_key  # noqa: E402
from benchmark.references import deepseek_v3_lm as ref  # noqa: E402
from nnstreamer_tpu.models.deepseek_v3 import (  # noqa: E402
    DeepseekV3Config,
    DeepseekV3Family,
)
from nnstreamer_tpu.models.lm_serving import _LMServingEntry  # noqa: E402
from nnstreamer_tpu.parallel import moe_dropless  # noqa: E402
from nnstreamer_tpu.serving import DecodeScheduler, PagedLMEngine  # noqa: E402

SIZES = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=3,
    num_attention_heads=4, intermediate_size=64, moe_intermediate_size=16,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    first_k_dense_replace=1, kv_lora_rank=16, qk_rope_head_dim=8,
    qk_nope_head_dim=16, v_head_dim=16, rms_norm_eps=1e-6, rope_theta=1e6,
    routed_scaling_factor=2.448, norm_topk_prob=True,
    max_position_embeddings=64)
LOGIT_TOL = 2e-5
GAP_TOL = 1e-4


def _model(seed=5, dtype=jnp.float32, **over):
    conf = {**SIZES, **over}
    cfg = DeepseekV3Config.from_published(conf)
    sz = ref.sizes(conf)
    key = seed_key(seed)
    return cfg, sz, key, ref.program_params(key, sz, dtype)


def _entry(cfg, params):
    class Seeded(_LMServingEntry):
        def _shard_params(self, mesh):
            return params, False

    return Seeded(cfg)


def _reference_logits(key, sz, prompt, served, width=48):
    """Teacher-forced reference logits at the rows that produced each
    served token: (len(served), V)."""
    n = len(served)
    tokens = np.zeros((1, width), np.int32)
    tokens[0, :prompt.size] = prompt
    tokens[0, prompt.size:prompt.size + n - 1] = served[:-1]
    rows = (prompt.size - 1 + np.arange(n))[None].astype(np.int32)
    with jax.default_matmul_precision("highest"):
        return ref.logits_for(key, sz, tokens, rows)["none"][0]


# -- (a) the served path against the reference's full forward -----------------

def test_chunked_prefill_then_decode_matches_the_reference_forward():
    cfg, sz, key, params = _model()
    eng = _entry(cfg, params).make_continuous(
        slots=3, page_size=4, chunk=8, pages=48)
    assert isinstance(eng, PagedLMEngine) and eng.family.name == "deepseek_v3"
    chunk_scores = spy_launches(eng)
    sched = DecodeScheduler(eng, name="dsv3-a")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, n).astype(np.int32)
               for n in (19, 7, 12, 9)]  # 19 = chunks of 8, 8 and 3
    try:
        reqs = [sched.submit(p, steps=10) for p in prompts]
        outs = [np.asarray(r.result(timeout=300)[0]) for r in reqs]
    finally:
        sched.close()
    assert eng.pool.used_pages == 0, "every page released at close"
    for prompt, served in zip(prompts, outs):
        exact = _reference_logits(key, sz, prompt, served)
        gap = exact.max(-1) - np.take_along_axis(
            exact, served[:, None], 1)[:, 0]
        assert gap.max() <= GAP_TOL, "a served token is not the reference's"
    # the launches of the first prompt, and its last row's scores (alone in the
    # lane first: its chunks are the first three calls)
    prompt = prompts[0]
    full = ref.logits_for(
        key, sz, np.pad(prompt, (0, 48 - prompt.size))[None],
        np.arange(prompt.size, dtype=np.int32)[None])["none"][0]
    seen = 0
    for start, n_valid, scores in chunk_scores[:3]:
        assert start == seen
        seen += n_valid
        if seen < prompt.size:
            assert scores is None, "only a prompt's last launch runs the head"
        else:
            np.testing.assert_allclose(scores, full[seen - 1],
                                       atol=LOGIT_TOL, rtol=0)
    assert seen == prompt.size
    # the expert layers counted what they did, in both programs
    slots = eng.family.expert_slots
    assert slots == 2 * 8
    for call in ("step", "chunk"):
        c = eng.layer_counts[call]
        assert c["moe_assignments"] > 0
        assert 0 < c["moe_experts_touched"] <= c["moe_assignments"]
        assert c["moe_max_load"] >= 2  # one for each of two expert layers
    # 10 tokens a request, the first from prefill: 9 steps' worth of rows,
    # two experts a token, two expert layers
    assert eng.layer_counts["step"]["moe_assignments"] == 4 * 9 * 2 * 2
    assert eng.layer_counts["chunk"]["moe_assignments"] == \
        sum(p.size for p in prompts) * 2 * 2


def test_served_through_the_experts_kernel_matches_the_reference(monkeypatch):
    """Both programs with the expert layers in the form a TPU runs
    (``ops/moe_grouped.py``'s kernel, interpreted here), at widths that
    tile by 128 lanes: the served tokens are the reference's."""
    import functools

    from nnstreamer_tpu.ops import moe_grouped

    rows, real = [], moe_grouped.kernel_grouped_experts

    def kernel(h, *args, **kw):
        rows.append(h.shape[0])
        return real(h, *args, **kw)

    monkeypatch.setattr(moe_grouped, "kernel_grouped_experts", kernel)
    monkeypatch.setattr(moe_grouped, "grouped_experts", functools.partial(
        moe_grouped.tpu_grouped_experts, interpret=True))
    cfg, sz, key, params = _model(hidden_size=128, moe_intermediate_size=128)
    eng = _entry(cfg, params).make_continuous(
        slots=3, page_size=4, chunk=8, pages=48)
    sched = DecodeScheduler(eng, name="dsv3-kernel")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (19, 7, 12)]
    try:
        reqs = [sched.submit(p, steps=8) for p in prompts]
        outs = [np.asarray(r.result(timeout=300)[0]) for r in reqs]
    finally:
        sched.close()
    # traced once a program and expert layer: a step of 3 rows, a launch of 8
    assert sorted(rows) == [3, 3, 8, 8]
    for prompt, served in zip(prompts, outs):
        exact = _reference_logits(key, sz, prompt, served)
        gap = exact.max(-1) - np.take_along_axis(
            exact, served[:, None], 1)[:, 0]
        assert gap.max() <= GAP_TOL, "a served token is not the reference's"
    assert eng.layer_counts["step"]["moe_assignments"] == 3 * 7 * 2 * 2


def test_a_bfloat16_cache_would_fail():
    # the same weights (exact in bfloat16) served with bfloat16 cache lines
    # and products: the scores of the launch's last row leave the reference
    # by far more than LOGIT_TOL, which is what makes the limits above a test
    cfg, sz, key, params = _model(dtype=jnp.bfloat16)
    eng = PagedLMEngine(cfg, params, slots=1, page_size=4, chunk=24, pages=16)
    assert eng._pools[0].dtype == jnp.bfloat16
    launches = spy_launches(eng)
    prompt = np.random.default_rng(3).integers(0, 96, 21).astype(np.int32)
    eng._ensure_writable(0, 0, 21)
    launch(eng, np.pad(prompt, (0, 3)), 0, 21)
    scores = np.asarray(launches[0][2], np.float32)
    full = ref.logits_for(
        key, sz, np.pad(prompt, (0, 27))[None],
        np.arange(21, dtype=np.int32)[None])["none"][0]
    assert np.abs(scores - full[20]).max() > 10 * LOGIT_TOL


# -- (b) absorbed against expanded attention, one layer ------------------------

def test_absorbed_attention_equals_expanded_on_one_layer():
    cfg, sz, key, params = _model()
    fam = DeepseekV3Family(cfg)
    blk = params["blocks"][1]
    S = 23
    x = jax.random.normal(jax.random.PRNGKey(1), (S, 32), jnp.float32)
    pos = jnp.arange(S)[None]
    q, (line,) = fam.project(blk, x[None], pos)
    assert q.shape == (1, S, 4, 128) and line.shape == (1, S, 128)
    assert not np.asarray(line[..., 24:]).any(), "the stored line's padding"
    # a launch's view: the walk over a pool of one page of S lines
    from nnstreamer_tpu.ops.paged_attention import chunk_line_attention

    o = chunk_line_attention(
        q[0].reshape(S, *fam.chunk_heads, -1), line, line,
        jnp.zeros((1,), jnp.int32), jnp.int32(0), jnp.int32(S),
        fam.attention_scale, S)
    got = fam.chunk_output(blk, o.reshape(1, S, 4, -1))[0]
    with jax.default_matmul_precision("highest"):
        want = ref.attention(x, blk, sz)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=LOGIT_TOL, rtol=0)
    # the step's view of the same thing: the last position alone, by the
    # step's own path (its queries over whole lines, the op's plain form
    # over a pool of one page of S lines, the family's finish)
    from nnstreamer_tpu.ops.paged_attention import plain_line_attention

    o = plain_line_attention(
        fam.step_queries(q[:, -1:]), line, line, jnp.zeros((1, 1), jnp.int32),
        jnp.asarray([S], jnp.int32), fam.attention_scale)
    got1 = fam.step_output(blk, o)[0, 0]
    np.testing.assert_allclose(np.asarray(got1), np.asarray(want[-1]),
                               atol=LOGIT_TOL, rtol=0)


# -- (c) the dropless layer against the reference's loop over experts ----------

def _layer_both(blk, h, sz, cfg, live=None, bias=None):
    bias = blk["router_bias"] if bias is None else bias
    experts, weights = moe_dropless.route(
        blk["router"], bias, h, cfg.num_experts_per_tok,
        cfg.routed_scaling_factor)
    e = blk["experts"]
    y, counts = moe_dropless.experts_ffn(
        e["w_gate"], e["w_up"], e["w_down"], h, experts, weights, live=live)
    with jax.default_matmul_precision("highest"):
        combine = ref.combine_weights(h, blk["router"], bias, sz)
        want = ref.experts_sum(h, combine, e)
    return y, counts, want, experts, weights


@pytest.mark.parametrize("tokens", [1, 5, 64])
def test_dropless_experts_equal_the_loop_over_all_experts(tokens):
    cfg, sz, key, params = _model()
    blk = params["blocks"][2]
    h = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, 32))
    y, counts, want, experts, _ = _layer_both(blk, h, sz, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               atol=LOGIT_TOL, rtol=0)
    touched, assigned, largest, held = (int(c) for c in counts)
    assert held == 8
    assert assigned == tokens * 2, "no token is dropped at any load"
    loads = np.bincount(np.asarray(experts).ravel(), minlength=8)
    assert touched == int((loads > 0).sum()) and largest == int(loads.max())


def test_one_expert_may_get_every_token():
    cfg, sz, key, params = _model()
    blk = params["blocks"][1]
    h = jax.random.normal(jax.random.PRNGKey(9), (40, 32))
    bias = blk["router_bias"].at[3].set(10.0)  # expert 3 wins every choice
    y, counts, want, experts, _ = _layer_both(blk, h, sz, cfg, bias=bias)
    assert (np.asarray(experts) == 3).any(axis=1).all()
    assert int(counts[2]) == 40 and int(counts[1]) == 80
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               atol=LOGIT_TOL, rtol=0)


def test_the_bias_changes_the_choice_and_not_the_weights():
    cfg, sz, key, params = _model()
    blk = params["blocks"][1]
    h = jax.random.normal(jax.random.PRNGKey(4), (64, 32))
    zero = jnp.zeros_like(blk["router_bias"])
    pushed = zero.at[5].set(0.3)
    e0, w0 = moe_dropless.route(blk["router"], zero, h, 2, 2.448)
    e1, w1 = moe_dropless.route(blk["router"], pushed, h, 2, 2.448)
    e0, e1, w1 = np.asarray(e0), np.asarray(e1), np.asarray(w1)
    assert (np.sort(e0, 1) != np.sort(e1, 1)).any(), "the bias chose others"
    assert ((e1 == 5).sum() > (e0 == 5).sum())
    # the weights are the chosen scores renormalised: the bias is not in them
    s = np.asarray(jax.nn.sigmoid(jnp.dot(
        h, blk["router"], precision=jax.lax.Precision.HIGHEST)))
    chosen = np.take_along_axis(s, e1, 1)
    want = chosen / (chosen.sum(1, keepdims=True) + 1e-20) * 2.448
    np.testing.assert_allclose(w1, want, atol=1e-6, rtol=0)
    np.testing.assert_allclose(w1.sum(1), 2.448, atol=1e-5)


def test_rows_that_are_not_live_reach_no_expert():
    cfg, sz, key, params = _model()
    blk = params["blocks"][1]
    h = jax.random.normal(jax.random.PRNGKey(6), (12, 32))
    live = jnp.arange(12) < 7
    y, counts, want, experts, _ = _layer_both(blk, h, sz, cfg, live=live)
    assert int(counts[1]) == 7 * 2
    np.testing.assert_allclose(np.asarray(y[:7]), np.asarray(want[:7]),
                               atol=LOGIT_TOL, rtol=0)
    assert not np.asarray(y[7:]).any()


def test_the_shares_of_the_experts_add_up_to_the_whole_layer():
    # a chip that holds experts 0-3 and one that holds 4-7 each compute
    # their experts' part; with the shared experts counted once the parts
    # add up to the uncut layer (model-configs guide, section 4)
    cfg, sz, key, params = _model()
    blk = params["blocks"][1]
    h = jax.random.normal(jax.random.PRNGKey(8), (30, 32))
    experts, weights = moe_dropless.route(
        blk["router"], blk["router_bias"], h, 2, 2.448)
    e = blk["experts"]
    parts, assigned = [], 0
    for first in (0, 4):
        held = {k: v[first:first + 4] for k, v in e.items()}
        y, counts = moe_dropless.experts_ffn(
            held["w_gate"], held["w_up"], held["w_down"], h, experts,
            weights, first_expert=first)
        parts.append(y)
        assigned += int(counts[1])
    assert assigned == 30 * 2
    with jax.default_matmul_precision("highest"):
        combine = ref.combine_weights(h, blk["router"], blk["router_bias"],
                                      sz)
        want = ref.experts_sum(h, combine, e)
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                               np.asarray(want), atol=LOGIT_TOL, rtol=0)


# -- (d) the one-pool geometry: sharing, preemption, release -------------------

def _engine(**kw):
    cfg, sz, key, params = _model()
    kw = {"slots": 2, "page_size": 4, "chunk": 8, "pages": 24, **kw}
    return cfg, PagedLMEngine(cfg, params, **kw)


def _pool_host(eng):
    L = eng.family.layers
    (pool,) = eng._pools
    return np.array(pool, np.float32).reshape(L, -1, eng.page_size,
                                              pool.shape[-1])


def test_the_pool_is_one_line_wide_and_its_bytes_follow():
    cfg, eng = _engine()
    # 16 latent + 8 rotary values, stored padded to one lane row of 128
    assert cfg.line_width == 24 and eng.line_widths == (128,)
    (pool,) = eng._pools
    assert pool.shape == (3 * (24 + 1), 4, 128)
    assert eng.token_bytes == 3 * 128 * 4
    assert eng.page_bytes == 4 * 3 * 128 * 4
    assert eng.cache_bytes == pool.nbytes
    assert eng.projected_page_bytes(9, 4) == 4 * eng.page_bytes
    stats = eng.pool.stats()
    assert stats["line_widths"] == [128]
    assert stats["token_bytes"] == eng.token_bytes
    assert stats["bytes_total"] == 24 * eng.page_bytes
    mem = eng.memory_bytes()
    assert mem["page_bytes"] == eng.page_bytes and mem["bytes"] == pool.nbytes
    eng.close()


def test_a_shared_prefix_is_mapped_and_copied_on_write():
    cfg, eng = _engine()
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, 96, 12).astype(np.int32)  # three full pages
    a = [eng.admit(0, prompt, 6)]
    shared_before = eng.pool.stats()["prefix_hits_total"]
    b = [eng.admit(1, prompt.copy(), 6)]
    assert eng.pool.stats()["prefix_hits_total"] == shared_before + 1
    assert eng.pool.stats()["cow_copies_total"] >= 1, \
        "recomputing the last prompt position writes into a shared page"
    for _ in range(5):
        tok = step_now(eng)
        a.append(int(tok[0]))
        b.append(int(tok[1]))
    assert a == b, "the sharer decodes what the owner decodes"
    # and both are what a fresh engine serves alone
    _, alone = _engine(share_prefixes=False)
    c = [alone.admit(0, prompt, 6)] + [int(step_now(alone)[0]) for _ in range(5)]
    assert a == c
    eng.release(0)
    eng.release(1)
    eng.pool.clear_prefixes()
    assert eng.pool.used_pages == 0
    alone.close()


@pytest.mark.parametrize("prompt_len,steps_before", [(8, 0), (9, 2), (13, 3)])
def test_preempt_and_restore_are_byte_exact_on_one_pool(prompt_len,
                                                        steps_before):
    cfg, eng = _engine(share_prefixes=False)
    rng = np.random.default_rng(53)
    prompt = rng.integers(0, 96, prompt_len).astype(np.int32)
    out = [eng.admit(0, prompt, 10)]
    for _ in range(steps_before):
        out.append(int(step_now(eng)[0]))
    held = [int(p) for p in eng._bt[0] if p]
    want = _pool_host(eng)[:, held]
    blob = eng.preempt(0)
    (pages,) = blob["pages"]
    assert pages.shape == (3, eng.blocks_per_slot, 4, 128)
    assert eng.pool.used_pages == 0, "preemption frees the victim's pages"
    eng.admit(1, rng.integers(0, 96, 10).astype(np.int32), 4)
    eng.restore(0, blob)
    fresh = [int(p) for p in eng._bt[0] if p]
    assert len(fresh) == len(held)
    np.testing.assert_array_equal(_pool_host(eng)[:, fresh], want)
    while len(out) < 10:
        out.append(int(step_now(eng)[0]))
    _, alone = _engine(share_prefixes=False)
    straight = [alone.admit(0, prompt, 10)]
    straight += [int(step_now(alone)[0]) for _ in range(9)]
    assert out == straight, "a paused request resumes where it stopped"
    eng.release(0)
    eng.release(1)
    assert eng.pool.used_pages == 0
    alone.close()


def test_the_serving_limit_is_the_engines_not_a_tables():
    cfg, sz, key, params = _model()
    eng = PagedLMEngine(cfg, params, slots=1, page_size=4, chunk=8, pages=8,
                        max_positions=32)
    assert eng.max_seq == 32 and eng.blocks_per_slot == 8
    assert "pos" not in params, "no position table: rotary positions"
    with pytest.raises(ValueError, match="exceeds"):
        eng.validate(np.zeros(30, np.int32), 3)
    with pytest.raises(ValueError, match="max_positions"):
        PagedLMEngine(cfg, params, slots=1, page_size=4, max_positions=128)
    eng.close()


def test_speculative_verify_refuses_the_family_by_name():
    cfg, sz, key, params = _model()
    with pytest.raises(NotImplementedError, match="deepseek_v3"):
        _entry(cfg, params).make_continuous(draft="ngram")
    with pytest.raises(NotImplementedError, match="deepseek_v3"):
        _entry(cfg, params).make()
    with pytest.raises(NotImplementedError):
        DeepseekV3Config(q_lora_rank=8)


def test_the_schedulers_metrics_sum_what_the_expert_layers_counted():
    from nnstreamer_tpu.obs import context as obs_context
    from nnstreamer_tpu.obs import metrics as obs_metrics

    cfg, sz, key, params = _model()
    eng = _entry(cfg, params).make_continuous(
        slots=2, page_size=4, chunk=8, pages=32)
    sched = DecodeScheduler(eng, name="dsv3-metrics")
    try:
        prompt = np.arange(1, 12, dtype=np.int32)
        sched.submit(prompt, steps=5).result(timeout=300)
        text = obs_metrics.render()
    finally:
        sched.close()
    snap = sched.metrics_snapshot()  # after the loop's last pass was counted
    both = {k: eng.layer_counts["step"][k] + eng.layer_counts["chunk"][k]
            for k in eng.layer_counts["step"]}
    for name in ("moe_experts_touched", "moe_expert_slots",
                 "moe_assignments", "moe_max_load"):
        assert snap[name] == both[name] > 0
        assert f"nns_serving_{name}_total" in text
    # 11 prompt tokens in two chunks, then 4 decode steps of one sequence
    assert snap["moe_assignments"] == (11 + 4) * 2 * 2
    assert snap["moe_expert_slots"] == (2 + 4) * eng.family.expert_slots
    assert snap["kv_pool"]["line_widths"] == [128]
    assert snap["kv_pool"]["token_bytes"] == 3 * 128 * 4
    pulls = [s for s in obs_context.finished_spans()
             if s.name == "engine.step.pull" and "moe_assignments" in s.attrs]
    assert pulls and pulls[-1].attrs["moe_assignments"] == 2 * 2
