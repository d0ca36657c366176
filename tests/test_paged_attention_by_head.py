"""The step's attention with head-wide operands (``ops/paged_attention.py``,
PR 48): where a family's lines hold several key heads side by side, the
kernel may contract each key head's query rows with that head's part of a
line alone, ``(S, KV * K * G, head_dim)`` in and out, the rows of one key
head together in the order ``(KV, K, G)``.

* the plain form with head-wide operands against the plain form with the
  block-diagonal query of the same heads over whole lines: the same sums
  without the zeros (a few 1e-7);
* the kernel, interpreted on the CPU, with head-wide operands against that
  oracle: ``H = 16`` heads over ``KV = 4`` key heads of 128, one query a
  slot and two, full layers and windows, an empty slot, lengths on the
  edges of a page, of a block's shorter products and of a block, rows a
  key head that are no whole tile (padded apart);
* the rule that chooses the form, at the shapes of the six configurations
  the benchmark runs.
"""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from nnstreamer_tpu.ops import paged_attention as pa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

# the two operand forms of the same heads, as the stand-alone tool lays
# them out (the family's own layout is held in tests/test_exaone_moe_serving)
from paged_attention_forms import by_heads, operands  # noqa: E402

H, KV, DH = 16, 4, 128
W = KV * DH
PG, PB, NB = 16, 32, 80
B, Q = PB * PG, PB // 4 * PG     # a block's positions, its shorter products'
SCALE = DH ** -0.5
CASES = {
    # the first query's lengths a slot; the window (None: a full layer)
    "edges_of_a_page_a_quarter_a_block": ([PG, Q - 1, Q, 0, B - 1, B], None),
    "a_short_last_block_behind_whole_ones": ([B + Q - 1, 2 * B + 1, 0,
                                              2 * B + Q], None),
    "one_position_and_the_whole_table": ([1, NB * PG - 1, 0], None),
    "a_window_of_130": ([129, 130, 131, 0, B, B + Q + 3, 1100], 130),
    "a_window_over_two_blocks": ([B - 1, 1031, 0, NB * PG - 1], B + 5),
}


def _wide(q):
    """``q (S, K, H, DH)`` → the block-diagonal rows over whole lines,
    ``(S, K * H, W)``: row ``r * H + n`` holds head ``n``'s query in the
    block of its key head."""
    return operands(q, KV, False)


def _own(o, K):
    """Of every whole-line result row its own key head's block:
    ``(S, K * H, W)`` → ``(S, K, H, DH)``."""
    return by_heads(o, K, KV, False)


def _by_head(q):
    """``q (S, K, H, DH)`` → head-wide rows ``(S, KV * K * G, DH)``."""
    return operands(q, KV, True)


def _back(o, K):
    return by_heads(o, K, KV, True)


def _case(name, K, seed=48, heads=H):
    lengths, window = CASES[name]
    rng = np.random.default_rng(seed)
    S = len(lengths)
    rows = 1 + S * NB
    kpool, vpool = (jnp.asarray(rng.standard_normal((rows, PG, W)),
                                jnp.bfloat16) for _ in range(2))
    table = jnp.asarray(1 + rng.permutation(S * NB).reshape(S, NB),
                        jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, K, heads, DH)), jnp.float32)
    lengths = jnp.asarray(lengths, jnp.int32)
    starts = None
    if window is not None:
        starts = jnp.maximum(
            lengths[:, None] + jnp.arange(K)[None, :] - window, 0)
        starts = starts[:, 0] if K == 1 else starts
    return q, kpool, vpool, table, lengths, starts


@pytest.mark.parametrize("K", [1, 2])
@pytest.mark.parametrize("name", CASES, ids=list(CASES))
def test_head_wide_rows_are_the_block_diagonal_querys_sums(name, K):
    q, kpool, vpool, table, lengths, starts = _case(name, K)
    wide = _own(pa.plain_line_attention(
        _wide(q), kpool, vpool, table, lengths, SCALE, starts, K), K)
    narrow = _back(pa.plain_line_attention(
        _by_head(q), kpool, vpool, table, lengths, SCALE, starts, K), K)
    assert narrow.shape == wide.shape
    assert float(jnp.abs(narrow - wide).max()) < 1e-6   # outputs of size 1
    assert not np.asarray(narrow)[np.asarray(lengths) == 0].any()


@pytest.mark.parametrize("K", [1, 2])
@pytest.mark.parametrize("name", CASES, ids=list(CASES))
def test_the_kernel_by_head_is_the_plain_form(name, K):
    # K = 1: 4 rows a key head, K = 2: 8; both padded apart to a tile's 16
    q, kpool, vpool, table, lengths, starts = _case(name, K)
    want = _own(pa.plain_line_attention(
        _wide(q), kpool, vpool, table, lengths, SCALE, starts, K), K)
    got = pa.kernel_line_attention(
        _by_head(q), kpool, vpool, table, lengths, SCALE, starts, queries=K,
        pages_per_block=PB, interpret=True)
    assert got.shape == (len(lengths), K * H, DH)
    got = np.asarray(_back(got, K))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want), atol=5e-6, rtol=0)
    assert not got[np.asarray(lengths) == 0].any()


@pytest.mark.parametrize("pages_per_block", [1, 4])
def test_the_kernel_by_head_at_whole_tiles_of_rows_and_small_blocks(
        pages_per_block):
    # 16 rows a key head (K = 2, 8 query heads each), as the cell has
    # them: nothing is padded; blocks of one size only
    q, kpool, vpool, table, lengths, starts = _case(
        "a_window_of_130", 2, seed=49, heads=32)
    want = _own(pa.plain_line_attention(
        _wide(q), kpool, vpool, table, lengths, SCALE, starts, 2), 2)
    got = _back(pa.kernel_line_attention(
        _by_head(q), kpool, vpool, table, lengths, SCALE, starts,
        queries=2, pages_per_block=pages_per_block, interpret=True), 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-6,
                               rtol=0)


def test_one_pool_as_keys_and_values_by_head():
    q, kpool, _, table, lengths, _ = _case(
        "edges_of_a_page_a_quarter_a_block", 2, seed=50)
    want = _own(pa.plain_line_attention(
        _wide(q), kpool, kpool, table, lengths, SCALE, None, 2), 2)
    got = _back(pa.kernel_line_attention(
        _by_head(q), kpool, kpool, table, lengths, SCALE, queries=2,
        pages_per_block=PB, interpret=True), 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-6,
                               rtol=0)


# (queries a slot, heads, key heads, head width) of a step or round of the
# six configurations BENCHMARK.json lists, and what the rule says
SHAPES = {
    "kexaone_round": ((2, 64, 8, 128), True),     # 384 rows, 48 a head
    "kexaone_step": ((1, 64, 8, 128), True),      # 192 rows, 24 a head
    "opt_1.3b": ((1, 32, 32, 64), False),         # 96 rows, 3 a head
    "kanana_latent": ((1, 32, 1, 640), False),    # one line every head reads
    "mellum": ((1, 32, 4, 128), False),           # 96 rows: one pass
    "jamba": ((1, 20, 1, 128), False),            # one key head
    "ouro": ((1, 16, 16, 128), False),            # 48 rows, 3 a head
}


@pytest.mark.parametrize("name", SHAPES, ids=list(SHAPES))
def test_the_rule_by_the_configurations_shapes(name):
    (K, heads, key_heads, width), by_head = SHAPES[name]
    assert pa.contracts_by_head(3 * K * heads, key_heads, width) is by_head


def test_the_rule_wants_whole_lanes_and_more_rows_than_a_pass():
    assert pa.contracts_by_head(384, 8, 128)
    assert not pa.contracts_by_head(384, 8, 64)    # half a lane tile a head
    assert not pa.contracts_by_head(128, 8, 128)   # one pass of a tile
    assert not pa.contracts_by_head(384, 32, 128)  # 12 rows a head
    assert not pa.contracts_by_head(384, 1, 1024)  # nothing to take apart


def _configurations():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entries = json.load(fh)["configs"]
    return {e["name"]: e["file"] for e in entries}


@pytest.mark.parametrize("name", sorted(_configurations()))
def test_only_the_round_of_many_heads_hands_head_wide_rows(name):
    """Every configuration the benchmark lists, at its published widths:
    the family of K-EXAONE's 64 heads over 8 key heads of 128 asks the rule
    and hands head-wide rows, a round's two queries or a step's one; the
    five others hand whole lines."""
    import json

    sys.path.insert(0, ROOT)   # the tool's own imports of ``benchmark``
    from serving_programs_ops import _model

    from nnstreamer_tpu.models.families import family_of

    with open(os.path.join(ROOT, _configurations()[name])) as fh:
        fam = family_of(_model(json.load(fh)))
    by_head = name.startswith("kexaone")
    assert fam.step_by_head(1 + fam.drafts) is by_head
    assert fam.step_by_head(1) is by_head
