"""The EXAONE-MoE family (grouped-query attention with q/k norms, window
layers that rotate beside full ones that do not, a dense block before
dropless sigmoid top-k expert layers with a shared expert, a chip's share of
the experts and of the vocabulary, an MTP layer that drafts) through the
paged serving engine and its round, against the benchmark's plain reference
(``benchmark/references/exaone_moe_lm.py``: a full forward with no cache and
the MTP forward on its output, float32 at ``highest``). CPU, small sizes,
seeded weights; logits are compared, never sampled tokens.

Sizes: a window of 12 positions under a limit of 96, five layers (dense +
window, window, full, window: the leading dense layer and one period), so
that a prompt of 37 crosses the window inside prefill (chunks of 8) and one
of 7 crosses it while decoding.

Tolerances. Everything here is float32 on the CPU, so program and reference
differ only by the order of float32 sums: logits of size 0.1-1 agree to a
few 1e-6; the limits (2e-5 on logits, 1e-4 on the gap of a served token or
a draft under the reference's best) leave a factor of ten above what is
seen. Streams of one engine built twice (the draft on and off) are compared
token for token: both are the same float32 program over the same pools up
to the second row, which changes no sum of the first.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.lib.weights import seed_key  # noqa: E402
from benchmark.references import exaone_moe_lm as ref  # noqa: E402
from nnstreamer_tpu.models.exaone_moe import (  # noqa: E402
    ExaoneMoeConfig,
    ExaoneMoeFamily,
)
from nnstreamer_tpu.models.families import family_of  # noqa: E402
from nnstreamer_tpu.models.lm_serving import _LMServingEntry  # noqa: E402
from nnstreamer_tpu.parallel import moe_dropless  # noqa: E402
from nnstreamer_tpu.serving import DecodeScheduler, PagedLMEngine  # noqa: E402

WINDOW, LIMIT = 12, 96
SIZES = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=64, moe_intermediate_size=16, num_experts=8,
    num_experts_per_tok=2, num_shared_experts=1, norm_topk_prob=True,
    routed_scaling_factor=2.5, scoring_func="sigmoid", n_group=1,
    topk_group=1, first_k_dense_replace=1,
    layer_types=["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 7, sliding_window=WINDOW,
    rms_norm_eps=1e-5, max_position_embeddings=LIMIT,
    rope_parameters={"rope_type": "default", "rope_theta": 10000.0},
    num_nextn_predict_layers=1, mtp_layer_types=["full_attention"])
LOGIT_TOL = 2e-5
GAP_TOL = 1e-4
ENGINE = dict(slots=3, page_size=4, chunk=8, share_prefixes=False,
              pages={"full": 72, "window": 24})
PROMPTS = (37, 7, 12)


def _model(seed=5, dtype=jnp.float32, **over):
    conf = {**SIZES, **over}
    cfg = ExaoneMoeConfig.from_published(ref.model_config(conf))
    sz = ref.sizes(conf)
    key = seed_key(seed)
    return cfg, sz, key, ref.program_params(key, sz, dtype)


def _prompts(seed, vocab=96, lengths=PROMPTS):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


def _force(eng, drafts):
    """Put ``drafts (slots,)`` in place of the drafts the next round would
    verify: in the carry on the device and, for a slot that joins with
    that round, in the row the host hands it."""
    drafts = np.asarray(drafts, np.int32)
    eng._tok_dev = eng._tok_dev.at[:, 1].set(jnp.asarray(drafts))
    joins = eng._join[:, 0] >= 0
    eng._join[joins, 1] = drafts[joins]


def _run(cfg, params, prompts, steps, draft=True, force=None, ahead=False,
         **over):
    """The streams of ``prompts`` through one engine, ``steps`` tokens
    each. ``force(slot, m) -> draft`` overrides the carry's draft before
    every round (``m``: the tokens the slot has so far); ``ahead`` keeps
    a round in flight as the scheduler does."""
    eng = PagedLMEngine(cfg, params, **{**ENGINE, **over}, draft=draft)
    outs = [[eng.admit(s, p, steps)] for s, p in enumerate(prompts)]
    for _ in range(2 * steps):
        if all(len(o) >= steps for o in outs):
            break
        if not draft:
            eng.step()
            got = eng.collect()
            for s, o in enumerate(outs):
                if got[s] >= 0:
                    o.append(int(got[s]))
            continue
        if force is not None:
            _force(eng, [force(s, len(o)) for s, o in enumerate(outs)])
        bursts = eng.step_tokens()
        if not ahead:
            bursts = [[int(t) for t in row if t >= 0]
                      for row in eng.collect()]
        for o, burst in zip(outs, bursts):
            o.extend(burst)
    account = (eng.spec_rounds, eng.spec_proposed, eng.spec_accepted,
               eng.spec_emitted)
    eng.close()
    assert all(pool.used_pages == 0 for pool in eng.pools_by_kind.values())
    return [o[:steps] for o in outs], account


# -- the family ----------------------------------------------------------------

def test_the_family_is_chosen_by_the_configurations_type_and_says_it_drafts():
    cfg, _, _, _ = _model()
    fam = family_of(cfg)
    assert isinstance(fam, ExaoneMoeFamily) and fam.name == "exaone_moe"
    assert fam.layer_kinds == ("window", "window", "window", "full", "window")
    assert fam.window == WINDOW and fam.cache_lines == (32, 32)
    assert fam.counters == moe_dropless.COUNTERS and not fam.serves_verify
    assert fam.drafts == 1 and fam.draft_kind == "full" and fam.passes == 1
    assert fam.expert_slots == 4 * 8
    with pytest.raises(TypeError, match="ExaoneMoeConfig"):
        family_of(object())


def test_the_five_elder_families_draft_nothing():
    from nnstreamer_tpu.models.families import _families

    elders = [f for _, f in _families() if f is not ExaoneMoeFamily]
    assert len(elders) >= 5 and all(f.drafts == 0 for f in elders)


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", True), ("attention_bias", True),
    ("hidden_act", "gelu"), ("scoring_func", "softmax"), ("n_group", 2),
    ("mlp_layer_types", ["dense"] + ["shared"] * 4),
    ("layer_types", ["chunked_attention"] * 5),
    ("rope_parameters", {"rope_type": "yarn", "rope_theta": 1e6}),
    ("num_key_value_heads", 3), ("num_nextn_predict_layers", 2),
    ("mtp_layer_types", ["sliding_attention"]),
    ("experts_held", [4, 8]), ("vocab_held", [90, 12]),
])
def test_a_key_the_block_does_not_implement_is_refused_by_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        ExaoneMoeConfig.from_published({**SIZES, key: value})


def test_the_published_lists_are_cut_to_the_layers_held():
    cfg = ExaoneMoeConfig.from_published({**SIZES, "num_hidden_layers": 2})
    fam = family_of(cfg)
    assert fam.layer_kinds == ("window", "window")
    assert cfg.is_dense(0) and not cfg.is_dense(1)
    assert len(fam.init_params(0)["blocks"]) == 2


def test_a_stack_of_full_layers_has_no_window_and_no_mtp_is_no_draft():
    cfg = ExaoneMoeConfig.from_published({
        **SIZES, "num_hidden_layers": 1, "layer_types": ["full_attention"],
        "num_nextn_predict_layers": 0})
    fam = family_of(cfg)
    assert fam.window is None and fam.drafts == 0
    assert "mtp" not in fam.init_params(0)


def test_a_full_layer_rotates_nothing_and_a_window_layer_does():
    cfg, _, _, params = _model()
    fam = family_of(cfg)
    blk = params["blocks"][3]
    x = jax.random.normal(jax.random.key(1), (1, 2, 32), jnp.float32)
    here, there = jnp.asarray([[3, 4]]), jnp.asarray([[30, 31]])
    q0, (k0, _) = fam.project(blk, x, here, "full")
    q1, (k1, _) = fam.project(blk, x, there, "full")
    assert (q0 == q1).all() and (k0 == k1).all()
    q0, (k0, _) = fam.project(blk, x, here, "window")
    q1, (k1, _) = fam.project(blk, x, there, "window")
    assert float(jnp.abs(q0 - q1).max()) > 1e-3
    assert float(jnp.abs(k0 - k1).max()) > 1e-3


def test_queries_and_keys_are_normed_a_head_with_the_learned_gain():
    cfg, _, _, params = _model()
    fam = family_of(cfg)
    blk = dict(params["blocks"][3])
    x = jax.random.normal(jax.random.key(2), (1, 3, 32), jnp.float32)
    pos = jnp.asarray([[0, 1, 2]])
    q, (k, _) = fam.project(blk, x, pos, "full")
    # unit mean square a head, whatever the projection's scale (eps 1e-5
    # beside mean squares of 1e-2 takes a thousandth off)
    assert np.allclose(np.mean(np.square(q), -1), 1.0, atol=5e-3)
    assert np.allclose(np.mean(np.square(k.reshape(1, 3, 2, 16)), -1), 1.0,
                       atol=5e-3)
    blk["q_norm"], blk["k_norm"] = blk["q_norm"] * 2.0, blk["k_norm"] * 3.0
    q2, (k2, _) = fam.project(blk, x, pos, "full")
    assert np.allclose(q2, 2.0 * q, atol=1e-6)
    assert np.allclose(k2, 3.0 * k, atol=1e-6)


# -- the shares add up ---------------------------------------------------------

def test_the_eight_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Eight chips, two of sixteen experts each: the parts of the result
    that the shares give, the shared expert counted once, are the uncut
    layer of the reference."""
    whole = {**SIZES, "num_experts": 16}
    cfg, sz, key, params = _model(**whole)
    blk = params["blocks"][1]
    x = jax.random.normal(jax.random.key(3), (2, 5, 32), jnp.float32)
    live = jnp.ones((2, 5), bool)
    with jax.default_matmul_precision("highest"):
        h = ref._rms(x.reshape(10, 32), blk["ln2"], sz.eps)
        want = ref.feed_forward(h, blk, sz)
        shared = moe_dropless.shared_ffn(
            blk["shared"]["w_gate"], blk["shared"]["w_up"],
            blk["shared"]["w_down"], h)
        total, served = jnp.zeros_like(want), 0
        for i in range(8):
            fam = family_of(ExaoneMoeConfig.from_published(
                {**whole, "experts_held": [2 * i, 2]}))
            part = {**blk, "experts": {
                name: w[2 * i:2 * i + 2]
                for name, w in blk["experts"].items()}}
            y, counts = fam.ffn(part, x, live)
            total = total + y.reshape(10, 32) - shared
            served += int(counts[1])
            assert int(counts[3]) == 2
        total = total + shared
    assert served == 10 * cfg.num_experts_per_tok  # every assignment once
    assert float(jnp.abs(total - want).max()) < LOGIT_TOL
    # and the layer that holds every expert is the sum
    y, _ = family_of(cfg).ffn(blk, x, live)
    assert float(jnp.abs(y.reshape(10, 32) - want).max()) < LOGIT_TOL


def test_the_eight_vocabulary_slices_side_by_side_are_the_whole_head():
    cfg, _, _, params = _model()
    x = jax.random.normal(jax.random.key(4), (6, 32), jnp.float32)
    toks = jnp.asarray([[0, 11, 12, 95]])
    whole = family_of(cfg)
    want = whole.head(params, x)
    parts, rows = [], []
    for i in range(8):
        share = ExaoneMoeConfig.from_published(
            {**SIZES, "vocab_held": [12 * i, 12]})
        fam = family_of(share)
        assert fam.vocab == share.vocab == 12
        p = {**params, "embed": params["embed"][12 * i:12 * i + 12],
             "head": params["head"][:, 12 * i:12 * i + 12]}
        assert fam.init_params(0)["embed"].shape == (12, 32)
        assert fam.init_params(0)["head"].shape == (32, 12)
        parts.append(fam.head(p, x))
        # the slice's id t is the whole table's row first + t
        local = jnp.clip(toks - 12 * i, 0, 11)
        rows.append(jnp.where(((toks >= 12 * i) & (toks < 12 * i + 12))
                              [..., None], fam.embed(p, local, None), 0.0))
    # a product's columns, summed in the slice's own order
    assert float(jnp.abs(jnp.concatenate(parts, axis=-1) - want).max()) < 1e-6
    assert (sum(rows) == whole.embed(params, toks, None)).all()


# -- prefill, then rounds, against the reference -------------------------------

def _spied(eng, store):
    """``eng`` with its family's two heads noting their scores."""
    fam = eng.family
    for name in ("head", "mtp_head"):
        def spy(p, x, inner=getattr(fam, name), name=name):
            out = inner(p, x)
            jax.debug.callback(
                lambda a, name=name: store.append((name, np.asarray(a))), out,
                ordered=True)
            return out

        setattr(fam, name, spy)
    return eng


def test_prefill_then_rounds_through_the_pools_agree_with_the_full_forward():
    """Main head and MTP head both: every score the launches and the rounds
    made, against the reference's at the same row."""
    cfg, sz, key, params = _model()
    steps, store = 14, []
    eng = _spied(PagedLMEngine(cfg, params, **ENGINE), store)
    prompts = _prompts(1)
    served = [[eng.admit(s, p, steps)] for s, p in enumerate(prompts)]
    launches = len(store)
    rounds = []
    for _ in range(steps - 1):
        pos = eng._pos.copy()
        eng.step_tokens()
        got = eng.collect()
        jax.effects_barrier()
        rounds.append((pos, got.copy()))
        for s in range(3):
            served[s] += [int(t) for t in got[s] if t >= 0]
    eng.close()
    # the reference, teacher-forced on what was served
    tokens = np.zeros((3, LIMIT), np.int32)
    for s, p in enumerate(prompts):
        tokens[s, :p.size] = p
        tokens[s, p.size:p.size + len(served[s])] = served[s]
    every = np.tile(np.arange(LIMIT, dtype=np.int32), (3, 1))
    main, mtp = ref.both_logits_for(key, sz, tokens, every, every)
    main, mtp = main["none"], mtp["none"]
    # the launches: one (head, mtp_head) pair a prompt's last launch... and
    # every launch runs both on its last row
    seen = [s for s in store[:launches]]
    assert {n for n, _ in seen} == {"head", "mtp_head"}
    worst = 0.0
    for s, p in enumerate(prompts):
        # the first token and the first draft came from row p - 1
        assert served[s][0] == int(main[s, p.size - 1].argmax())
    # the rounds: (S * 2, V) main scores, then (S, V) MTP scores, in order
    at = launches
    for pos, got in rounds:
        (n0, a), (n1, b) = store[at], store[at + 1]
        at += 2
        assert (n0, n1) == ("head", "mtp_head")
        a = a.reshape(3, 2, -1)
        for s in range(3):
            n = int((got[s] >= 0).sum())
            if not n:  # its request was done: the slot sat the round out
                continue
            worst = max(worst, float(np.abs(a[s, 0] - main[s, pos[s]]).max()))
            if n == 2:  # the second row stood on the accepted draft
                worst = max(worst, float(
                    np.abs(a[s, 1] - main[s, pos[s] + 1]).max()))
            # the next draft: the MTP's scores at the last committed row
            worst = max(worst, float(
                np.abs(b[s] - mtp[s, pos[s] + n - 1]).max()))
    assert at == len(store) and worst < LOGIT_TOL, worst


def test_served_tokens_and_drafts_lie_at_the_references_best():
    cfg, sz, key, params = _model(seed=9)
    prompts = _prompts(2)
    eng = PagedLMEngine(cfg, params, **ENGINE)
    steps = 20
    served = [[eng.admit(s, p, steps)] for s, p in enumerate(prompts)]
    drafts = [[(1, int(eng.next_draft[s]))] for s in range(3)]
    while any(len(o) < steps for o in served):
        eng.step_tokens()
        got = eng.collect()
        for s in range(3):
            served[s] += [int(t) for t in got[s] if t >= 0]
            drafts[s].append((len(served[s]), int(eng.next_draft[s])))
    eng.close()
    tokens = np.zeros((3, LIMIT), np.int32)
    for s, p in enumerate(prompts):
        tokens[s, :p.size] = p
        tokens[s, p.size:p.size + len(served[s])] = served[s]
    every = np.tile(np.arange(LIMIT, dtype=np.int32), (3, 1))
    main, mtp = ref.both_logits_for(key, sz, tokens, every, every)
    for s, p in enumerate(prompts):
        for m, tok in enumerate(served[s]):
            row = main["none"][s, p.size - 1 + m]
            assert row.max() - row[tok] < GAP_TOL
        for m, d in drafts[s]:
            if m < len(served[s]):
                row = mtp["none"][s, p.size + m - 2]
                assert row.max() - row[d] < GAP_TOL


# -- the stream is the draft-off engine's --------------------------------------

def _forcing(kind, truth, seed, vocab=96):
    rng = np.random.default_rng([seed, 77])
    coin = rng.random((3, 200)) < 0.5

    def right(s, m):
        return truth[s][min(m, len(truth[s]) - 1)]

    def wrong(s, m):
        return (right(s, m) + 1) % vocab

    def mixed(s, m):
        return right(s, m) if coin[s, m] else wrong(s, m)

    return {"right": right, "wrong": wrong, "mixed": mixed, "own": None}[kind]


@pytest.mark.parametrize("kind", ["own", "right", "wrong", "mixed"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_stream_is_the_draft_off_engines_token_for_token(seed, kind):
    cfg, _, _, params = _model(seed=seed)
    prompts, steps = _prompts(seed), 24
    truth, _ = _run(cfg, params, prompts, steps, draft=False)
    got, (rounds, proposed, accepted, emitted) = _run(
        cfg, params, prompts, steps, force=_forcing(kind, truth, seed))
    assert got == truth
    assert emitted == proposed + accepted
    if kind == "right":  # every round but a request's last yields two
        assert accepted >= proposed - 3 and rounds <= steps // 2 + 1
    if kind == "wrong":
        assert accepted == 0 and emitted == proposed
    if kind == "mixed":
        assert 0 < accepted < proposed


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_a_round_in_flight_changes_no_token(seed):
    # the scheduler's order: the next round is dispatched before the last
    # one's answer is read, from the carry on the device
    cfg, _, _, params = _model(seed=seed, vocab_size=8)
    prompts, steps = _prompts(seed, vocab=8), 30
    truth, _ = _run(cfg, params, prompts, steps, draft=False)
    got, account = _run(cfg, params, prompts, steps, ahead=True)
    assert got == truth and account[1] > 0
    if seed == 1:  # at a vocabulary of 8 some of its drafts hold
        assert account[2] > 0


def test_the_draft_off_engine_keeps_no_mtp_line_and_runs_the_step():
    cfg, _, _, params = _model()
    on = PagedLMEngine(cfg, params, **ENGINE)
    off = PagedLMEngine(cfg, params, **ENGINE, draft=False)
    assert (on.drafts, off.drafts) == (1, 0)
    assert on.kind_layers == {"full": 2, "window": 4}
    assert off.kind_layers == {"full": 1, "window": 4}
    assert off.step_tokens is None and callable(on.step_tokens)
    with pytest.raises(TypeError, match="step_tokens"):
        on.step()
    on.close(), off.close()


# -- pages ---------------------------------------------------------------------

def test_window_pages_go_back_behind_the_committed_position_only():
    """After a rejected draft the slot stands one position on, not two: the
    window kind's first held block follows the committed position, and
    never a page that a later query still sees."""
    cfg, _, _, params = _model()
    prompts, steps = _prompts(4), 40
    truth, _ = _run(cfg, params, prompts, steps, draft=False)
    wrong = _forcing("wrong", truth, 4)
    eng = PagedLMEngine(cfg, params, **ENGINE)
    outs = [[eng.admit(s, p, steps)] for s, p in enumerate(prompts)]
    pg = ENGINE["page_size"]
    for _ in range(steps - 1):
        _force(eng, [wrong(s, len(o)) for s, o in enumerate(outs)])
        before = eng._pos.copy()
        eng.step_tokens()
        got = eng.collect()
        for s, o in enumerate(outs):
            o += [int(t) for t in got[s] if t >= 0]
            # every draft was rejected: one position a round
            assert eng._pos[s] == before[s] + 1
            row = eng._bts["window"][s]
            first_seen = max(int(before[s]) - WINDOW + 1, 0) // pg
            assert not row[:first_seen].any()  # given back behind it
            # every page from the first position the round's queries saw
            # to the two positions it wrote is held
            assert row[first_seen:(int(before[s]) + 1) // pg + 1].all()
            assert int((row != 0).sum()) <= -(-(WINDOW + 2) // pg) + 1
    assert [o[:steps] for o in outs] == truth
    assert eng.window_pages_released > 0
    eng.close()


def test_preempt_and_restore_in_the_middle_of_a_sequence():
    cfg, _, _, params = _model(seed=6)
    prompts, steps = _prompts(6), 26
    truth, _ = _run(cfg, params, prompts, steps, draft=False)
    eng = PagedLMEngine(cfg, params, **ENGINE)
    outs = [[eng.admit(s, p, steps)] for s, p in enumerate(prompts)]
    for i in range(2 * steps):
        if all(len(o) >= steps for o in outs):
            break
        if i == 5:
            # with a round in flight: its tokens come home first and are
            # owed to the slot when it is back
            blob = eng.preempt(1)
            assert blob["pos"] == prompts[1].size + len(outs[1]) - 1 + int(
                (blob["owed"] >= 0).sum())
            assert not eng._bts["full"][1].any()
            assert not eng._bts["window"][1].any()
        if i == 8:
            eng.restore(1, blob)
        for o, burst in zip(outs, eng.step_tokens()):
            o.extend(burst)
    eng.close()
    assert [o[:steps] for o in outs] == truth


# -- the served path -----------------------------------------------------------

def test_make_continuous_returns_the_paged_engine_and_the_scheduler_drives_it():
    cfg, sz, key, params = _model(seed=8, vocab_size=8)

    class Seeded(_LMServingEntry):
        def _shard_params(self, mesh):
            return params, False

    eng = Seeded(cfg).make_continuous(**ENGINE)
    assert type(eng) is PagedLMEngine and eng.drafts == 1
    with pytest.raises(NotImplementedError, match="drafts on the device"):
        Seeded(cfg).make_continuous(**ENGINE, draft="ngram")
    prompts = _prompts(8, vocab=8, lengths=(37, 7, 12, 9, 21))
    truth, _ = _run(cfg, params, prompts[:3], 18, draft=False)
    sched = DecodeScheduler(eng, name="exaone-test")
    try:
        reqs = [sched.submit(p, steps=18) for p in prompts]
        outs = [r.result(timeout=300)[0] for r in reqs]
        snap = sched.metrics_snapshot()
    finally:
        sched.close()
    assert [list(map(int, o)) for o in outs[:3]] == truth
    assert all(len(o) == 18 for o in outs)
    # a pass yielded 1 or 2 tokens a slot, and the engine says how often 2
    assert snap["spec_rounds"] == eng.spec_rounds > 0
    assert snap["spec_accepted"] == eng.spec_accepted > 0
    assert snap["spec_acceptance_rate"] == pytest.approx(
        eng.spec_accepted / eng.spec_proposed)
    assert all(pool.used_pages == 0 for pool in eng.pools_by_kind.values())


def test_the_round_writes_its_account_on_the_prepare_span():
    from nnstreamer_tpu.obs import context as ctx

    cfg, _, _, params = _model(seed=8, vocab_size=8)
    ctx.reset()
    _, (rounds, proposed, accepted, emitted) = _run(
        cfg, params, _prompts(8, vocab=8), 16)
    spans = [s for s in ctx.finished_spans()
             if s.name == "engine.step.prepare" and s.attrs.get("rounds")]
    ctx.reset()
    assert len(spans) == rounds
    assert sum(s.attrs["proposed"] for s in spans) == proposed
    assert sum(s.attrs["accepted"] for s in spans) == accepted
    assert sum(s.attrs["emitted"] for s in spans) == emitted
    assert all(s.attrs["rows"] == 2 * s.attrs["live"] for s in spans)
    assert all("pages_fetched_full" in s.attrs
               and "pages_read_window" in s.attrs for s in spans)


def test_the_expert_counters_sum_the_stacks_layers_and_the_mtp_block():
    cfg, _, _, params = _model()
    eng = PagedLMEngine(cfg, params, **ENGINE)
    eng.admit(0, _prompts(1)[0], 6)
    before = dict(eng.layer_counts["step"])
    eng.step_tokens()
    got = eng.collect()
    after = eng.layer_counts["step"]
    n = int((got[0] >= 0).sum())
    # four sparse layers see both rows, the MTP block the committed ones
    assert after["moe_expert_slots"] - before["moe_expert_slots"] == 5 * 8
    assert after["moe_assignments"] - before["moe_assignments"] == \
        (4 * 2 + n) * cfg.num_experts_per_tok
    # a launch counts its stack's layers and its MTP block too
    assert eng.layer_counts["chunk"]["moe_expert_slots"] == 5 * 5 * 8
    eng.close()


# -- the step's operand forms (PR 48) --------------------------------------------

def _by_head_always(monkeypatch):
    """The kernel's rule answering "by head" at any shape: the rehearsal
    sizes (4 heads over 2 key heads of 16) then take the head-wide form,
    which the plain form serves at any width."""
    from nnstreamer_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "contracts_by_head", lambda *shape: True)


@pytest.mark.parametrize("draft", [True, False], ids=["round", "step"])
@pytest.mark.parametrize("seed", [1, 2])
def test_head_wide_rows_emit_the_tokens_the_block_diagonal_query_emitted(
        seed, draft, monkeypatch):
    cfg, _, _, params = _model(seed=seed)
    prompts, steps = _prompts(seed), 20
    assert not family_of(cfg).step_by_head(2)
    want, account = _run(cfg, params, prompts, steps, draft=draft)
    _by_head_always(monkeypatch)
    assert family_of(cfg).step_by_head(2)
    got, again = _run(cfg, params, prompts, steps, draft=draft)
    assert got == want and again == account


def test_head_wide_rows_go_in_by_key_head_and_come_back_by_query(
        monkeypatch):
    cfg, _, _, params = _model()
    fam = family_of(cfg)
    H, KV, Dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    q = jnp.arange(3 * 2 * H * Dh, dtype=jnp.float32).reshape(3, 2, H, Dh)
    wide = fam.step_queries(q)
    assert wide.shape == (3, 2 * H, KV * Dh)
    _by_head_always(monkeypatch)
    rows = fam.step_queries(q)
    assert rows.shape == (3, 2 * H, Dh)
    # row (g, r, n): query r's head g * G + n, as project made it
    G = H // KV
    for g, r, n in [(0, 0, 0), (0, 1, 1), (1, 0, 1), (1, 1, 0)]:
        assert (rows[:, (g * 2 + r) * G + n] == q[:, r, g * G + n]).all()
    # and what the kernel gives for those rows goes back in (K, H) order
    # through the output projection: the same function of the heads as the
    # whole-line form's own blocks
    blk = params["blocks"][0]
    back = fam.step_output(blk, rows)
    monkeypatch.undo()
    np.testing.assert_allclose(
        np.asarray(back), np.asarray(fam.step_output(blk, wide)),
        rtol=1e-6, atol=1e-6)


def test_the_prepare_span_says_how_many_calls_contract_by_head(monkeypatch):
    from nnstreamer_tpu.obs import context as ctx

    cfg, _, _, params = _model(seed=8, vocab_size=8)

    def spans(**engine):
        ctx.reset()
        _run(cfg, params, _prompts(8, vocab=8), 6, **engine)
        got = [s.attrs["attn_by_head"] for s in ctx.finished_spans()
               if s.name == "engine.step.prepare"]
        ctx.reset()
        assert got
        return set(got)

    # the rehearsal's 24 stacked rows ride through a tile in one pass
    assert spans() == {0}
    _by_head_always(monkeypatch)
    # five layers and the MTP block a round; the stack alone a step
    assert spans() == {6}
    assert spans(draft=False) == {5}


def test_a_round_through_the_kernel_by_head_emits_the_plain_forms_tokens(
        monkeypatch):
    """32 heads over 4 key heads of 128: a round stacks 192 rows, 48 a key
    head, and the rule says by head with nothing steered. The plain form
    and the kernel (interpreted) then get the same head-wide rows."""
    from nnstreamer_tpu.ops import paged_attention as pa

    cfg, _, _, params = _model(seed=3, num_attention_heads=32,
                               num_key_value_heads=4, head_dim=128)
    assert family_of(cfg).step_by_head(2)
    assert not family_of(cfg).step_by_head(1)   # 96 rows: one pass
    prompts, steps = _prompts(3, lengths=(21, 5)), 6
    want, account = _run(cfg, params, prompts, steps)
    calls = []

    def through_the_kernel(q, kpool, *rest, **kw):
        calls.append((q.shape, kpool.shape))
        return pa.kernel_line_attention(q, kpool, *rest, **kw,
                                        pages_per_block=2, interpret=True)

    monkeypatch.setattr(pa, "paged_line_attention", through_the_kernel)
    got, again = _run(cfg, params, prompts, steps)
    assert got == want and again == account
    # one call a layer and one for the MTP block, every one head-wide
    assert len(calls) == 6
    assert {c[0][1:] for c in calls} == {(2 * 32, 128)}
    assert {c[1][2] for c in calls} == {4 * 128}
