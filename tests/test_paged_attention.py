"""The decode step's attention over the pool's rows (ops/paged_attention.py).

* the Pallas kernel, interpreted on the CPU, against the op's plain form
  (gather, mask, softmax): two pools of one width and one pool as both,
  lengths at every edge of a page and of a block, a shuffled block table;
* the same with a first visible position a slot (a window layer's step):
  starts at every edge of a page and of a block, table entries below the
  start naming the null page; a start of 0 is the result without one, bit
  for bit in the plain form;
* at a block of 32 pages of 16 (the size at which a slot's last block has
  its shorter products): windows over three blocks, starts and lengths at
  every edge of a page, of the shorter products and of a block, live slots
  between empty ones, jamba's shape; every pool row a slot does not see
  filled with NaN and Inf; and the rule ``pages_fetched`` counts by against
  what the kernel reads and against the engine's ``pages_read``;
* an engine step through the kernel emits the tokens the plain form's step
  emits, for both model families;
* what ``GPTFamily._attend_step`` used to be held to: the family's step
  path (block-diagonal queries over whole lines, the plain form, each
  head's own block) is per-head attention;
* the counters that say how far the step follows what is visible, on the
  ``engine.step.prepare`` span and summed in the scheduler's metrics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from engine_util import step_now

from nnstreamer_tpu.ops import paged_attention as pa
from nnstreamer_tpu.serving import DecodeScheduler, PagedLMEngine

PG, NB, PAGES, LAYERS = 8, 6, 40, 2
MAX_SEQ = PG * NB
LENGTHS = {
    "empty": [0, 0, 0],
    "one": [1, 1, 1],
    "page_less_one": [PG - 1, 2 * PG - 1, 0],
    "page": [PG, 2 * PG, 3 * PG],
    "page_plus_one": [PG + 1, 2 * PG + 1, 1],
    "all_of_max_seq": [MAX_SEQ, MAX_SEQ, MAX_SEQ],
    "mixed": [0, MAX_SEQ, 2 * PG + 3],
}


def _pool(rng, width):
    return jnp.asarray(rng.standard_normal((LAYERS * (PAGES + 1), PG, width)),
                       jnp.bfloat16)


@pytest.mark.parametrize("lengths", LENGTHS, ids=list(LENGTHS))
@pytest.mark.parametrize("pools", ["keys_and_values", "one_pool_as_both"])
def test_kernel_matches_the_plain_form(pools, lengths):
    rng = np.random.default_rng(28)
    S, H, W = 3, 4, 48
    kpool = _pool(rng, W)
    vpool = kpool if pools == "one_pool_as_both" else _pool(rng, W)
    # a shuffled table: no slot's pages are neighbours, and layer 1's rows
    bt = np.stack([rng.permutation(PAGES)[:NB] + 1 for _ in range(S)])
    rows = jnp.asarray((PAGES + 1) + bt, jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, H, W)), jnp.float32)
    seen = jnp.asarray(LENGTHS[lengths], jnp.int32)

    want = pa.plain_line_attention(q, kpool, vpool, rows, seen, 0.25)
    # two pages a block: three blocks a full slot, tails of both parities
    got = pa.kernel_line_attention(q, kpool, vpool, rows, seen, 0.25,
                                   pages_per_block=2, interpret=True)
    assert got.shape == (S, H, W) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=0)
    empty = np.asarray(seen) == 0
    assert not np.asarray(got)[empty].any(), "an empty slot reads nothing"
    # the block size the kernel derives for itself (here: the whole table)
    derived = pa.kernel_line_attention(q, kpool, vpool, rows, seen, 0.25,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(derived), np.asarray(want),
                               atol=2e-6, rtol=0)


# (lengths, starts): the head of the first block masked, blocks below it
# not read, at every edge of a page (8) and of a block of two pages (16)
WINDOWS = {
    "start_0": ([MAX_SEQ, 2 * PG + 3, 5], [0, 0, 0]),
    "inside_first_page": ([MAX_SEQ, 2 * PG + 3, 5], [3, 1, 4]),
    "at_a_page": ([MAX_SEQ, 3 * PG, 2 * PG], [PG, 2 * PG, PG]),
    "at_a_block": ([MAX_SEQ, 4 * PG + 1, 2 * PG + 1],
                   [2 * PG, 4 * PG, 2 * PG]),
    "block_less_one": ([MAX_SEQ, 4 * PG, 0], [2 * PG - 1, 4 * PG - 1, 0]),
    "last_position_only": ([MAX_SEQ, 2 * PG + 3, 1],
                           [MAX_SEQ - 1, 2 * PG + 2, 0]),
    "a_window_of_ten": ([MAX_SEQ, 2 * PG + 3, 7],
                        [MAX_SEQ - 10, 2 * PG + 3 - 10, 0]),
    "an_empty_slot_between": ([MAX_SEQ, 0, 3 * PG + 2],
                              [MAX_SEQ - 10, 0, 2 * PG + 2]),
}


@pytest.mark.parametrize("case", WINDOWS, ids=list(WINDOWS))
@pytest.mark.parametrize("pools", ["keys_and_values", "one_pool_as_both"])
def test_kernel_matches_the_plain_form_from_a_first_visible_position(
        pools, case):
    rng = np.random.default_rng(31)
    S, H, W = 3, 4, 48
    kpool = _pool(rng, W)
    vpool = kpool if pools == "one_pool_as_both" else _pool(rng, W)
    lengths, starts = (np.asarray(x) for x in WINDOWS[case])
    bt = np.stack([rng.permutation(PAGES)[:NB] + 1 for _ in range(S)])
    # the pages behind the window were given back: their entries are 0
    bt[np.arange(NB)[None, :] < (starts // PG)[:, None]] = 0
    rows = jnp.asarray((PAGES + 1) + bt, jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, H, W)), jnp.float32)
    seen, first = jnp.asarray(lengths, jnp.int32), jnp.asarray(starts,
                                                               jnp.int32)

    want = pa.plain_line_attention(q, kpool, vpool, rows, seen, 0.25, first)
    # the oracle's own meaning, spelled out for one slot: softmax over
    # positions start..length-1 and no others
    s0 = 0
    k = np.asarray(pa.gathered_lines(kpool, rows), np.float32)[s0]
    v = np.asarray(pa.gathered_lines(vpool, rows), np.float32)[s0]
    lo, hi = int(starts[s0]), int(lengths[s0])
    sc = (np.asarray(q)[s0] @ k[lo:hi].T) * 0.25
    w = np.exp(sc - sc.max(-1, keepdims=True))
    np.testing.assert_allclose(
        np.asarray(want)[s0], (w / w.sum(-1, keepdims=True)) @ v[lo:hi],
        atol=2e-5, rtol=0)
    for blocks in (2, None):  # two pages a block; the derived block size
        got = pa.kernel_line_attention(q, kpool, vpool, rows, seen, 0.25,
                                       first, pages_per_block=blocks,
                                       interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=0)
        assert not np.asarray(got)[lengths == 0].any()


def test_a_start_of_zero_is_the_plain_form_without_one_bit_for_bit():
    rng = np.random.default_rng(32)
    S, H, W = 3, 4, 48
    kpool, vpool = _pool(rng, W), _pool(rng, W)
    bt = np.stack([rng.permutation(PAGES)[:NB] + 1 for _ in range(S)])
    rows = jnp.asarray(bt, jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, H, W)), jnp.float32)
    seen = jnp.asarray([MAX_SEQ, 2 * PG + 3, 0], jnp.int32)
    without = pa.plain_line_attention(q, kpool, vpool, rows, seen, 0.25)
    zeros = pa.plain_line_attention(q, kpool, vpool, rows, seen, 0.25,
                                    jnp.zeros((S,), jnp.int32))
    np.testing.assert_array_equal(np.asarray(zeros), np.asarray(without))
    kernel = [np.asarray(pa.kernel_line_attention(
        q, kpool, vpool, rows, seen, 0.25, first, pages_per_block=2,
        interpret=True)) for first in (None, jnp.zeros((S,), jnp.int32))]
    np.testing.assert_array_equal(kernel[0], kernel[1])


# a block of 32 pages of 16 positions: a slot's last block is contracted
# over 8 pages (128 positions) when it holds no more than that
BPG, BPB, BNB = 16, 32, 80
B, Q, P = BPB * BPG, BPB // 4 * BPG, BPG  # a block, its quarter, a page
EDGES = {
    # (lengths, starts); None: from the first position on
    "a_window_over_three_blocks": ([BNB * P, 1100, 1031],
                                   [BNB * P - 1024, 76, 7]),
    "a_window_of_two_whole_blocks": ([1024 + B, 1024, 1024 + 3 * P],
                                     [B, 0, 3 * P]),
    "starts_at_a_page": ([1200, 1200, 1200], [10 * P - 1, 10 * P, 10 * P + 1]),
    "starts_at_a_quarter": ([1200, 1200, 1200], [Q - 1, Q, Q + 1]),
    "starts_at_a_block": ([BNB * P] * 3, [B - 1, B, B + 1]),
    "ends_at_a_page": ([20 * P - 1, 20 * P, 20 * P + 1], None),
    "ends_at_a_quarter": ([Q - 1, Q, Q + 1], None),
    "ends_at_a_block": ([B - 1, B, B + 1], None),
    "ends_at_two_blocks": ([2 * B - 1, 2 * B, 2 * B + 1], None),
    "a_window_that_ends_at_a_block": ([1024 + B - 1, 1024 + B, 1024 + B + 1],
                                      [B - 1, B, B + 1]),
    "a_quarter_past_a_block": ([B + Q - 1, B + Q, B + Q + 1], None),
    "a_window_a_quarter_past_a_block": ([700 + B + Q, 700 + B + Q + P, 900],
                                        [700, 700 + 1, 900 - B - Q + 1]),
    "one_page_one_quarter_one_block": ([P, Q, B], None),
    "live_slots_between_empty_ones": ([0, 700, 0, 0, Q + 2, 0], None),
    "windows_between_empty_slots": ([0, 1100, 0, 1279, 0],
                                    [0, 76, 0, 255, 0]),
}


def _disjoint(rng, S, NB, width, pools, page=BPG):
    """Pools and a table in which no two slots share a row."""
    rows = 1 + S * NB
    made = [jnp.asarray(rng.standard_normal((rows, page, width)),
                        jnp.bfloat16) for _ in range(pools)]
    bt = 1 + rng.permutation(S * NB).reshape(S, NB).astype(np.int32)
    return made[0], made[-1], bt


@pytest.mark.parametrize("case", EDGES, ids=list(EDGES))
@pytest.mark.parametrize("pools", [2, 1], ids=["keys_and_values",
                                               "one_pool_as_both"])
def test_kernel_matches_the_plain_form_at_the_edges_of_a_real_block(
        pools, case):
    rng = np.random.default_rng(37)
    lengths, starts = EDGES[case]
    S, H, W = len(lengths), 4, 48
    kpool, vpool, bt = _disjoint(rng, S, BNB, W, pools)
    q = jnp.asarray(rng.standard_normal((S, H, W)), jnp.float32)
    seen = jnp.asarray(lengths, jnp.int32)
    first = None if starts is None else jnp.asarray(starts, jnp.int32)
    want = pa.plain_line_attention(q, kpool, vpool, jnp.asarray(bt), seen,
                                   0.25, first)
    got = pa.kernel_line_attention(q, kpool, vpool, jnp.asarray(bt), seen,
                                   0.25, first, pages_per_block=BPB,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=0)
    assert not np.asarray(got)[np.asarray(lengths) == 0].any()


def _poisoned(pool, bt, lengths, starts, page):
    """``pool`` with every row that ``bt`` names outside a slot's visible
    pages (before its start's, past its length's) and the null row filled
    with NaN and Inf by turns."""
    first, count = pa.visible_pages(np.asarray(lengths), np.asarray(starts),
                                    page)
    at = np.arange(bt.shape[1])[None, :]
    unseen = (at < first[:, None]) | (at >= (first + count)[:, None])
    rows = np.concatenate([[0], bt[unseen]])
    bad = np.where(np.arange(len(rows)) % 2, np.nan, np.inf)
    return pool.at[rows].set(jnp.asarray(bad, pool.dtype)[:, None, None])


POISONED = {
    "tails_of_every_kind": ([B + 3, Q - 5, 1], [0, 0, 0]),
    "heads_and_tails_of_windows": ([BNB * P - 9, 1100, 1031],
                                   [BNB * P - 9 - 1024, 76, 7]),
    "a_window_inside_one_page": ([700, 3 * P, 0], [700 - 5, 2 * P + 1, 0]),
    "whole_blocks_and_an_empty_slot": ([2 * B, 0, B], [0, 0, 0]),
}


@pytest.mark.parametrize("case", POISONED, ids=list(POISONED))
@pytest.mark.parametrize("pools", [2, 1], ids=["keys_and_values",
                                               "one_pool_as_both"])
def test_rows_a_slot_does_not_see_never_reach_its_output(pools, case):
    # a weight of 0 times NaN is NaN: a page that is not visible must not
    # be multiplied, whatever lies in it or in the buffer it was not copied
    # to (the interpreter hands the kernel buffers full of NaN: a first
    # call on fresh buffers every time)
    rng = np.random.default_rng(38)
    lengths, starts = POISONED[case]
    S, H, W = len(lengths), 4, 48
    kpool, vpool, bt = _disjoint(rng, S, BNB, W, pools)
    q = jnp.asarray(rng.standard_normal((S, H, W)), jnp.float32)
    seen, first = jnp.asarray(lengths, jnp.int32), jnp.asarray(starts,
                                                               jnp.int32)
    want = pa.plain_line_attention(q, kpool, vpool, jnp.asarray(bt), seen,
                                   0.25, first)
    kbad = _poisoned(kpool, bt, lengths, starts, BPG)
    vbad = kbad if pools == 1 else _poisoned(vpool, bt, lengths, starts, BPG)
    for blocks in (BPB, 4):
        got = np.asarray(pa.kernel_line_attention(
            q, kbad, vbad, jnp.asarray(bt), seen, 0.25, first,
            pages_per_block=blocks, interpret=True))
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, np.asarray(want), atol=1e-5, rtol=0)


def test_the_kernel_reads_every_page_the_rule_names():
    # the other half of the rule: a page ``visible_pages`` counts is read
    # (each holds a visible position, so a NaN there reaches the output)
    rng = np.random.default_rng(39)
    lengths, starts = [1100, B + 1, 5], [76, 0, 0]
    S, H, W = 3, 4, 48
    kpool, _, bt = _disjoint(rng, S, BNB, W, 1)
    q = jnp.asarray(rng.standard_normal((S, H, W)), jnp.float32)
    first, count = pa.visible_pages(np.asarray(lengths), np.asarray(starts),
                                    BPG)
    assert list(first) == [4, 0, 0] and list(count) == [65, 33, 1]
    for slot in range(S):
        for at in {int(first[slot]), int(first[slot] + count[slot]) - 1}:
            bad = kpool.at[bt[slot, at]].set(jnp.nan)
            got = np.asarray(pa.kernel_line_attention(
                q, bad, bad, jnp.asarray(bt), jnp.asarray(lengths), 0.25,
                jnp.asarray(starts), pages_per_block=BPB, interpret=True))
            assert np.isnan(got[slot]).all(), (slot, at)
            assert np.isfinite(np.delete(got, slot, axis=0)).all()


def test_kernel_matches_the_plain_form_at_jambas_shape():
    # pages of 64 positions, 20 queries a slot (padded to 32 rows) over one
    # 128-wide line, keys and values in a pool each, the block size derived
    rng = np.random.default_rng(40)
    lengths = [1000, 24 * 64, 0, 77, 6 * 64 + 1]
    S, H, W, NB = len(lengths), 20, 128, 24
    kpool, vpool, bt = _disjoint(rng, S, NB, W, 2, page=64)
    q = jnp.asarray(rng.standard_normal((S, H, W)), jnp.float32)
    seen = jnp.asarray(lengths, jnp.int32)
    want = pa.plain_line_attention(q, kpool, vpool, jnp.asarray(bt), seen,
                                   0.09)
    got = pa.kernel_line_attention(q, kpool, vpool, jnp.asarray(bt), seen,
                                   0.09, interpret=True)
    assert got.shape == (S, H, W)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=0)


@pytest.mark.parametrize("page", [4, 16, 64])
@pytest.mark.parametrize("window", [None, 10, 1024])
def test_pages_fetched_is_the_pages_read_by_kind(page, window):
    # the engine's ``pages_read`` of a layer's kind (lm_engine._dispatch)
    # against the kernel's rule: within a page a slot (they are equal)
    rng = np.random.default_rng(41)
    seen = rng.integers(1, 6000, 64)
    seen[::7] = rng.integers(1, 6000 // page, len(seen[::7])) * page
    starts = (np.zeros_like(seen) if window is None
              else np.maximum(seen - window, 0))
    read = int((-(-seen // page)).sum()) - int((starts // page).sum())
    fetched = pa.pages_fetched(seen, starts, page)
    assert abs(fetched - read) <= len(seen)
    assert fetched == read
    # an empty slot among them fetches nothing
    seen[3] = starts[3] = 0
    first, count = pa.visible_pages(seen, starts, page)
    assert count[3] == 0 and pa.pages_fetched(seen, starts, page) == \
        int(count.sum())


def _gpt():
    from nnstreamer_tpu.models.lm_serving import tiny
    from nnstreamer_tpu.models.transformer import init_params

    return tiny.cfg, init_params(tiny.cfg, seed=0)


def _latent():
    from nnstreamer_tpu.models.deepseek_v3 import (
        DeepseekV3Config,
        init_params,
    )

    cfg = DeepseekV3Config(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64, moe_intermediate_size=16,
        n_routed_experts=8, num_experts_per_tok=2, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        max_position_embeddings=64)
    return cfg, init_params(cfg, seed=0)


def _serve(cfg, params):
    """Two prompts of unlike lengths decoded side by side, slot 1 of 3 left
    empty: every token both slots emit."""
    eng = PagedLMEngine(cfg, params, slots=3, page_size=4, chunk=8, pages=24)
    rng = np.random.default_rng(5)
    out = {0: [eng.admit(0, rng.integers(1, 60, 21).astype(np.int32), 9)],
           2: [eng.admit(2, rng.integers(1, 60, 3).astype(np.int32), 9)]}
    for _ in range(8):
        tok = step_now(eng)
        for s in out:
            out[s].append(int(tok[s]))
    eng.close()
    return out


@pytest.mark.parametrize("family", [_gpt, _latent], ids=["gpt", "latent"])
def test_a_step_through_the_kernel_emits_the_plain_forms_tokens(family,
                                                                monkeypatch):
    cfg, params = family()
    want = _serve(cfg, params)
    calls = []

    def through_the_kernel(*args):
        calls.append(args)
        return pa.kernel_line_attention(*args, pages_per_block=2,
                                        interpret=True)

    # steered here, in the test: the engine takes the op as it stands in
    # the module when the engine is built
    monkeypatch.setattr(pa, "paged_line_attention", through_the_kernel)
    got = _serve(cfg, params)
    assert len(calls) == 2, "one call a layer, traced once"
    assert got == want


def test_gpt_step_path_is_per_head_attention():
    from nnstreamer_tpu.models.families import GPTFamily

    cfg, params = _gpt()
    fam = GPTFamily(cfg)
    blk = params["blocks"][0]
    H, Dh = cfg.heads, cfg.head_dim
    rng = np.random.default_rng(7)
    S, seen = 3, np.asarray([5, 16, 0], np.int32)
    q = jnp.asarray(rng.standard_normal((S, 1, cfg.dim)), jnp.float32)
    kpool = jnp.asarray(rng.standard_normal((4, 8, cfg.dim)), jnp.float32)
    vpool = jnp.asarray(rng.standard_normal((4, 8, cfg.dim)), jnp.float32)
    rows = jnp.asarray([[1, 3], [2, 0], [0, 0]], jnp.int32)

    queries = fam.step_queries(q)
    assert queries.shape == (S, H, cfg.dim)
    o = pa.plain_line_attention(queries, kpool, vpool, rows,
                                jnp.asarray(seen), fam.attention_scale)
    got = np.asarray(fam.step_output(blk, o))

    for s in range(S):
        n = int(seen[s])
        if not n:
            assert not got[s].any()
            continue
        k = np.asarray(kpool)[np.asarray(rows[s])].reshape(-1, H, Dh)[:n]
        v = np.asarray(vpool)[np.asarray(rows[s])].reshape(-1, H, Dh)[:n]
        att = np.einsum("hd,chd->hc", np.asarray(q[s, 0]).reshape(H, Dh),
                        k) / np.sqrt(Dh)
        att = np.exp(att - att.max(-1, keepdims=True))
        att /= att.sum(-1, keepdims=True)
        want = np.einsum("hc,chd->hd", att, v).reshape(cfg.dim) \
            @ np.asarray(blk["wo"], np.float32)
        np.testing.assert_allclose(got[s, 0], want, atol=2e-5, rtol=0)


def test_pages_per_block_follows_the_lines_bytes(monkeypatch):
    seen = {}

    def call(*args, pages_per_block, **kw):
        seen["pages"] = pages_per_block

    monkeypatch.setattr(pa, "_call", call)
    rows, q = jnp.zeros((16, 128), jnp.int32), None
    for width, pages in ((2048, 16), (640, 64), (512, 64), (256, 128)):
        pool = jax.ShapeDtypeStruct((10, 16, width), jnp.bfloat16)
        pa.kernel_line_attention(q, pool, pool, rows, None, 1.0)
        assert seen["pages"] == pages, width


def test_step_counts_the_pages_it_reads_against_the_padding():
    from nnstreamer_tpu.obs import context as obs_context
    from nnstreamer_tpu.obs import metrics as obs_metrics

    cfg, params = _gpt()
    eng = PagedLMEngine(cfg, params, slots=3, page_size=4, chunk=8, pages=24)
    sched = DecodeScheduler(eng)
    try:
        prompt = np.arange(1, 11, dtype=np.int32)  # 10 tokens: 3 pages
        sched.submit(prompt, steps=4).result(timeout=300)
        text = obs_metrics.render()
    finally:
        sched.close()
    steps = [s for s in obs_context.finished_spans()
             if s.name == "engine.step.prepare"][-3:]
    # the first token comes from the prompt's last chunk; three steps see
    # 11, 12 and 13 positions of one live slot of three
    assert [s.attrs["pages_read"] for s in steps] == [3, 3, 4]
    # the kernel copies the pages that hold a visible position, and no more
    assert [s.attrs["pages_fetched"] for s in steps] == [3, 3, 4]
    assert {s.attrs["pages_padded"] for s in steps} == {3 * (64 // 4)}
    snap = sched.metrics_snapshot()
    assert snap["attn_pages_read"] == eng.attn_pages["attn_pages_read"] == 10
    assert snap["attn_pages_fetched"] == \
        eng.attn_pages["attn_pages_fetched"] == 10
    assert snap["attn_pages_padded"] == 3 * 48
    assert "nns_serving_attn_pages_read_total" in text
    assert "nns_serving_attn_pages_fetched_total" in text
    assert "nns_serving_attn_pages_padded_total" in text
