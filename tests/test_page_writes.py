"""A prefill launch writes its lines a page at a time
(``serving.lm_engine.write_pages``, since PR 44).

The oracle is the row form the launch had before, kept here and nowhere in
the package's launch: one update a line, a line past ``n_valid`` sent to the
layer's null page (:func:`row_form`). Against it:

* the op alone: pages of 8, 16 and 64 positions, a row offset that is a
  constant and one that is traced (the ``ouro`` family's, inside its loop
  over the passes), against plain numpy;
* the engine's launches, family by family (``gpt``'s keys and values, the
  latent line, grouped-query lines in full and in window layers, jamba's
  pages of 64 positions beside its state layers, ``ouro``'s four passes):
  one launch from pools full of noise, at the limit's first and last
  positions and a block in, with every row real, a page and a row, one row,
  whole pages: the token the launch makes of its last real row and every
  line of every pool outside the null pages, bit for bit;
* what a page keeps: the positions of a launch's last page past ``n_valid``
  hold what they held;
* a registered prefix that covers a whole prompt of whole pages: the launch
  starts on the last page's edge and the tokens are the unshared run's;
* a width the page does not divide: the row form, and it serves;
* the span's ``lines_rows`` / ``lines_updates``, by hand.
"""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from engine_util import launch, step_now
from nnstreamer_tpu.obs import context as obs_context
from nnstreamer_tpu.ops import paged_attention
from nnstreamer_tpu.serving import lm_engine
from nnstreamer_tpu.serving.lm_engine import PagedLMEngine, write_pages


def row_form(pool, row0, pages, own, lines):
    """The oracle, with ``write_pages``' arguments: what a launch did until
    PR 44. One update a line, at ``(row0 + its page, its offset)``; a line
    that is not the launch's goes to the layer's null page."""
    n, pg, width = lines.shape
    dest = jnp.where(own[..., 0], pages[:, None], 0).reshape(-1)
    return pool.at[row0 + dest, jnp.tile(jnp.arange(pg), n)].set(
        lines.reshape(n * pg, width).astype(pool.dtype))


# -- the op alone --------------------------------------------------------------

@pytest.mark.parametrize("traced_row0", [False, True])
@pytest.mark.parametrize("n_valid", [64, 17, 1, 32, 0])
@pytest.mark.parametrize("pg, width", [(8, 16), (16, 128), (64, 8)])
def test_pages_land_where_the_rows_did(pg, width, n_valid, traced_row0):
    n = -(-64 // pg)                 # a launch of 64 rows, or one page
    C, R = n * pg, 3 * n + 1
    rng = np.random.default_rng(pg * 1000 + n_valid)
    pool = rng.normal(size=(2 * R, pg, width)).astype(np.float32)
    lines = rng.normal(size=(C, width)).astype(np.float32)
    table = rng.permutation(np.arange(1, R))[:n].astype(np.int32)
    valid = np.arange(C) < n_valid
    pages = np.where(valid[::pg], table, 0).astype(np.int32)
    own = valid.reshape(n, pg, 1)
    args = (jnp.asarray(pool, jnp.bfloat16),
            jnp.int32(R) if traced_row0 else R, jnp.asarray(pages),
            jnp.asarray(own), jnp.asarray(lines.reshape(n, pg, width)))
    static = () if traced_row0 else (1,)
    got = np.asarray(jax.jit(write_pages, static_argnums=static)(*args),
                     np.float32)
    rows = np.asarray(jax.jit(row_form, static_argnums=static)(*args),
                      np.float32)
    want = np.asarray(jnp.asarray(pool, jnp.bfloat16), np.float32)
    was = want.copy()
    for r in np.flatnonzero(valid):
        want[R + table[r // pg], r % pg] = np.asarray(
            jnp.asarray(lines[r], jnp.bfloat16), np.float32)
    np.testing.assert_array_equal(got, want)   # the null page kept whole too
    rows[R], was[R] = want[R], want[R]         # the row form's sink
    np.testing.assert_array_equal(rows, want)
    assert (got[:R] == was[:R]).all(), "another layer's rows were written"


# -- the engine's launches, family by family -----------------------------------

LIMIT = 128


def _gpt():
    from nnstreamer_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(vocab=61, dim=32, heads=4, layers=2, mlp_mult=2,
                            max_seq=LIMIT)
    return cfg, init_params(cfg, seed=3), {}


def _latent():
    from nnstreamer_tpu.models.deepseek_v3 import (
        DeepseekV3Config,
        init_params,
    )

    cfg = DeepseekV3Config(
        vocab_size=61, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        moe_intermediate_size=16, n_routed_experts=4, num_experts_per_tok=2,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, first_k_dense_replace=1,
        max_position_embeddings=LIMIT)
    return cfg, init_params(cfg, seed=3), {}


def _mellum():
    from nnstreamer_tpu.models.mellum import MellumConfig, init_params

    cfg = MellumConfig(
        vocab_size=61, hidden_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        moe_intermediate_size=16, num_experts=4, num_experts_per_tok=2,
        layer_types=("sliding_attention", "full_attention",
                     "sliding_attention"),
        sliding_window=20, max_position_embeddings=LIMIT)
    return cfg, init_params(cfg, seed=3), {"share_prefixes": False}


def _jamba():
    from nnstreamer_tpu.models.jamba import JambaConfig, init_params

    cfg = JambaConfig(
        vocab_size=61, hidden_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=1,
        intermediate_size=64, attn_layer_period=3, attn_layer_offset=1,
        mamba_d_state=4, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4,
        max_position_embeddings=4 * LIMIT)   # eight pages of 64
    return cfg, init_params(cfg, seed=3), {"share_prefixes": False}


def _ouro():
    from test_ouro_serving import _model

    cfg, _, _, params = _model(max_position_embeddings=LIMIT)
    return cfg, params, {"share_prefixes": False}


#: family: its model, the page, the launch's width
FAMILIES = {"gpt": (_gpt, 8, 32), "latent": (_latent, 8, 32),
            "mellum": (_mellum, 8, 32), "jamba64": (_jamba, 64, 128),
            "ouro": (_ouro, 8, 32)}
#: where a launch starts, in launches' widths; how many of its rows are
#: real, in pages and rows
STARTS = {"at_0": 0.0, "a_block_in": 0.5, "the_limits_last": None}
ROWS = {"every_row": (None, 0), "a_page_and_a_row": (1, 1), "one_row": (0, 1),
        "whole_pages": (2, 0)}


def _engine(family):
    model, pg, C = FAMILIES[family]
    cfg, params, more = model()
    # room for a slot that holds the whole limit and a prompt beside it
    pages = {"full": 40, "window": 40} if family == "mellum" else 40
    return PagedLMEngine(cfg, params, slots=2, page_size=pg, chunk=C,
                         pages=pages, **more)


@pytest.fixture(scope="module")
def engines():
    """``engines(family)`` -> ``(paged, rows)``: an engine whose launches
    write pages and one built while ``lm_engine.write_pages`` is the oracle,
    once a family (an engine's programs compile for seconds, on every
    core); a block of the launch's walk is two pages. Slot 0 holds a page
    for every position of the limit, in no order, the same in both."""
    built = {}

    def get(family):
        if family not in built:
            pg, C = FAMILIES[family][1:]
            old = (lm_engine.write_pages, paged_attention.SCORE_BYTES)
            paged_attention.SCORE_BYTES = 4 * 2 * pg * 4 * C
            try:
                pair = []
                for form in (write_pages, row_form):
                    lm_engine.write_pages = form
                    pair.append(_engine(family))
            finally:
                (lm_engine.write_pages, paged_attention.SCORE_BYTES) = old
            order = np.random.default_rng(7).permutation(
                pair[0].blocks_per_slot)
            for eng in pair:
                assert eng.chunk_pages == C // pg
                assert eng.chunk_block_pages == 2
                eng._ensure_writable(0, 0, eng.max_seq)
                for bt in eng._bts.values():
                    bt[0] = bt[0][order]
            built[family] = tuple(pair)
        return built[family]

    yield get
    for pair in built.values():
        for eng in pair:
            eng.close()


def _noise(eng, seed):
    """Every pool full of noise (what a launch does not write shows), the
    state layers' arrays zero."""
    rng = np.random.default_rng(seed)
    eng._pools = tuple(jnp.asarray(rng.normal(size=p.shape), p.dtype)
                       for p in eng._pools)
    eng._states = tuple(jnp.zeros_like(s) for s in eng._states)
    return [np.array(p) for p in eng._pools]


def _launch(eng, tokens, start, n_valid):
    """One launch by hand, as ``prefill_tick`` calls a prompt's last:
    ``(the token it made, pools)``."""
    padded = np.zeros((eng.chunk,), np.int32)
    padded[:n_valid] = tokens[:n_valid]
    (token,) = launch(eng, padded, start, n_valid)
    return int(token), [np.array(p) for p in eng._pools]


def _outside_the_null_pages(eng, pools):
    """The pools with every layer's null page zeroed: a line that is not the
    launch's goes there in the row form, and says nothing."""
    at = 0
    for kind in eng.kinds:
        rows = eng.pools_by_kind[kind].pages + 1
        for _ in eng.line_widths:
            pools[at][::rows] = 0
            at += 1
    return pools


@pytest.mark.parametrize("rows", list(ROWS))
@pytest.mark.parametrize("start", list(STARTS))
@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_launch_by_pages_equals_the_launch_by_rows(family, start, rows,
                                                     engines):
    pg, C = FAMILIES[family][1:]
    paged, by_rows = engines(family)
    start = paged.max_seq - C if STARTS[start] is None \
        else int(STARTS[start] * C)
    whole, more = ROWS[rows]
    n_valid = C if whole is None else whole * pg + more
    assert start % pg == 0 and 0 < n_valid <= C
    seed = zlib.crc32(f"{family} {start} {n_valid}".encode())
    tokens = np.random.default_rng(seed).integers(0, 61, C)
    runs = []
    for eng in (paged, by_rows):
        _noise(eng, seed)
        token, pools = _launch(eng, tokens, start, n_valid)
        runs.append((token, _outside_the_null_pages(eng, pools)))
    (got, got_pools), (want, want_pools) = runs
    assert got == want and 0 <= got < eng.family.vocab
    for a, b in zip(got_pools, want_pools):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("family", ["gpt", "jamba64", "ouro"])
def test_a_page_keeps_what_it_held_past_the_launchs_rows(family, engines):
    """A prompt's last page: the positions at and past ``start + n_valid``
    are the slot's, and a decode step writes them before anything reads
    them. The launch leaves them as they were, and the null page whole."""
    pg, C = FAMILIES[family][1:]
    paged, _ = engines(family)
    start, n_valid = C, pg + 3
    before = _noise(paged, 11)
    _, after = _launch(paged, np.arange(C) % 61, start, n_valid)
    kind = paged.kinds[0]
    R = paged.pools_by_kind[kind].pages + 1
    table = paged._bts[kind][0]
    first, last = table[start // pg], table[start // pg + 1]
    for was, now in zip(before, after):
        for layer in range(paged.kind_layers[kind]):
            at = layer * R
            assert (now[at + first] != was[at + first]).any(-1).all()
            assert (now[at + last, :3] != was[at + last, :3]).any(-1).all()
            np.testing.assert_array_equal(now[at + last, 3:],
                                          was[at + last, 3:])
            np.testing.assert_array_equal(now[at], was[at])  # the null page
            # the launch's third and fourth pages are past its rows
            for page in table[start // pg + 2:(start + C) // pg]:
                np.testing.assert_array_equal(now[at + page], was[at + page])


# -- the launch's start --------------------------------------------------------

def _loud_gpt():
    """The small ``gpt`` model with weights eight times larger: its greedy
    streams differ from token to token and from prompt to prompt (at the
    seeded size every stream repeats one token, whatever it read)."""
    cfg, params, _ = _gpt()
    return cfg, jax.tree_util.tree_map(lambda a: a * 8, params)


def _serve(eng, slot, prompt, steps, beside=None):
    """A prompt's first token and ``steps`` more of ``slot``; ``beside``,
    ``(another live slot, a list)``: its tokens of the same steps, added to
    the list."""
    eng.admit_start(slot, prompt, 8)
    done = []
    while not done:
        done = eng.prefill_tick()
    out = [done[0][1]]
    for _ in range(steps):
        tokens = step_now(eng)
        out.append(int(tokens[slot]))
        if beside is not None:
            beside[1].append(int(tokens[beside[0]]))
    return out


@pytest.mark.parametrize("pages_covered", [1, 2, 5])
def test_a_prefix_that_covers_a_whole_prompt_starts_on_a_pages_edge(
        pages_covered):
    """A registered prefix covers the whole of a prompt of whole pages: the
    launch that recomputes its last token starts on the last page's edge
    (at most a page recomputed, into a copy of the shared page), and the
    tokens are those of a run that shares nothing."""
    cfg, params = _loud_gpt()
    prompt = np.random.default_rng(pages_covered).integers(
        0, 61, 8 * pages_covered).astype(np.int32)
    eng = PagedLMEngine(cfg, params, slots=2, page_size=8, chunk=32)
    alone = PagedLMEngine(cfg, params, slots=2, page_size=8, chunk=32,
                          share_prefixes=False)
    try:
        want = _serve(alone, 0, prompt, 6)
        assert len(set(want)) > 3
        first = _serve(eng, 0, prompt, 3)
        obs_context.reset()
        hits, cows, later = eng.pool.prefix_hits, eng.pool.cow_copies, []
        assert _serve(eng, 1, prompt, 3, beside=(0, later)) == want[:4]
        # the first stream went on over its own pages meanwhile
        assert first + later == want
        assert eng.pool.prefix_hits == hits + 1
        assert eng.pool.cow_copies == cows + 1   # the shared last page
        (launch,) = [s for s in obs_context.finished_spans()
                     if s.name == "engine.chunk.prepare"]
        assert launch.attrs["start"] == 8 * (pages_covered - 1)
        assert launch.attrs["n_valid"] == 8
    finally:
        obs_context.reset()
        eng.close()
        alone.close()


def test_a_prefix_that_covers_part_of_a_prompt_starts_where_it_ends():
    cfg, params, _ = _gpt()
    rng = np.random.default_rng(2)
    head = rng.integers(0, 61, 16).astype(np.int32)
    eng = PagedLMEngine(cfg, params, slots=2, page_size=8, chunk=32)
    try:
        _serve(eng, 0, np.concatenate([head, head[:3]]), 1)
        eng.admit_start(1, np.concatenate([head, head[:5]]), 1)
        assert eng._pending[1]["next"] == 16
    finally:
        eng.close()


# -- a width the page does not divide ------------------------------------------

def test_a_width_the_page_does_not_divide_writes_rows_and_serves():
    cfg, params = _loud_gpt()
    prompt = np.random.default_rng(5).integers(0, 61, 45).astype(np.int32)
    served = {}
    obs_context.reset()
    try:
        for chunk in (12, 16):
            eng = PagedLMEngine(cfg, params, slots=1, page_size=8,
                                chunk=chunk, share_prefixes=False)
            assert eng.chunk_pages == (None if chunk % 8 else chunk // 8)
            served[chunk] = _serve(eng, 0, prompt, 4)
            eng.close()
        assert served[12] == served[16] and len(set(served[12])) > 2
        by_width = {}
        for s in obs_context.finished_spans():
            if s.name == "engine.chunk.prepare":
                by_width.setdefault(s.attrs["width"], []).append(
                    (s.attrs["lines_rows"], s.attrs["lines_updates"]))
        # two layers, keys and values: four writes a launch
        assert by_width[12] == [(48, 48), (48, 48), (48, 48), (36, 36)]
        assert by_width[16] == [(64, 8), (64, 8), (52, 8)]
    finally:
        obs_context.reset()


# -- the counter ---------------------------------------------------------------

@pytest.mark.parametrize("family, writes", [
    ("gpt", 2 * 2), ("latent", 2), ("mellum", 3 * 2), ("ouro", 4 * 4 * 2)])
def test_every_launch_says_its_lines_and_its_updates(family, writes, engines):
    """Three launches of a 77-token prompt over pages of 8: 32, 32 and 13
    rows in 4, 4 and 2 pages, every pool of every pass-layer."""
    paged, _ = engines(family)
    assert paged.pass_layers * len(paged.line_widths) == writes
    assert paged.chunk_lines(32) == (32 * writes, 4 * writes)
    assert paged.chunk_lines(13) == (13 * writes, 2 * writes)
    assert paged.chunk_lines(1) == (writes, writes)
    obs_context.reset()
    try:
        paged.admit_start(1, np.arange(77, dtype=np.int32) % 61, 1)
        while not paged.prefill_tick():
            pass
        spans = [s.attrs for s in obs_context.finished_spans()
                 if s.name == "engine.chunk.prepare"]
        assert [(a["start"], a["n_valid"]) for a in spans] \
            == [(0, 32), (32, 32), (64, 13)]
        assert [a["lines_rows"] for a in spans] \
            == [32 * writes, 32 * writes, 13 * writes]
        assert [a["lines_updates"] for a in spans] \
            == [4 * writes, 4 * writes, 2 * writes]
    finally:
        obs_context.reset()
        paged.release(1)


def test_jambas_pages_of_64_take_two_updates_a_write(engines):
    paged, _ = engines("jamba64")
    # one attention layer of three, keys and values
    assert paged.chunk_lines(128) == (2 * 128, 2 * 2)
    assert paged.chunk_lines(65) == (2 * 65, 2 * 2)
    assert paged.chunk_lines(64) == (2 * 64, 2 * 1)
