"""A prefill launch attends over the blocks its slot holds
(``ops.paged_attention.chunk_line_attention``, since PR 42).

The oracle is the gathered form the launch had before: every block the
serving limit allows taken out of the pool, the whole context scored under a
mask, one softmax (:func:`gathered_chunk_attention`, kept here and nowhere in
the package). Against it:

* the op alone, for the four shapes of line the families have (``gpt``: a
  key head a query head, keys and values; latent: one line every head reads
  as both; grouped-query lines in a full and in a window layer), at the
  launches that have an edge: the first of a prompt, one that starts on and
  one off a block's edge, one with padded rows, the one that ends at the
  serving limit, a window that begins mid-page;
* the engine's launches, family by family: the scores a prompt's last
  launch makes its token from and the lines every launch left in the
  pools, through ``prefill_tick`` as the scheduler drives it, and a launch
  over prefix pages another request wrote;
* the walk's rule (``chunk_walk``) on hand-made launches, and the engine's
  ``ctx_read`` / ``ctx_padded`` by it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from engine_util import spy_launches, step_now
from nnstreamer_tpu.ops import paged_attention
from nnstreamer_tpu.ops.paged_attention import (
    chunk_block_pages,
    chunk_line_attention,
    chunk_walk,
    gathered_lines,
)
from nnstreamer_tpu.serving.lm_engine import PagedLMEngine

TOL = 2e-5


def gathered_chunk_attention(q, kpool, vpool, rows, start, n_valid, scale,
                             span, *, precision=None, pages_per_block=None):
    """The oracle: the launch's queries over a gathered copy of the slot's
    whole block table, one masked softmax, in float32 (the CPU multiplies
    in float32 whatever ``precision`` says). A padded row sees what the last
    real row sees (the walk's contract; before PR 42 it saw the stale lines
    up to its own position, and its output was dropped as it is now)."""
    C = q.shape[0]
    k = gathered_lines(kpool, rows[None])[0]
    v = gathered_lines(vpool, rows[None])[0]
    KV = q.shape[1]
    k = k.reshape(k.shape[0], KV, -1).astype(jnp.float32)
    v = v.reshape(v.shape[0], KV, -1).astype(jnp.float32)
    seen = jnp.minimum(start + jnp.arange(C), start + n_valid - 1)
    back = seen[:, None] - jnp.arange(k.shape[0])[None, :]
    visible = (back >= 0) & (back < span)
    att = jnp.einsum("qkgd,tkd->kgqt", q, k,
                     precision=jax.lax.Precision.HIGHEST) * scale
    att = jax.nn.softmax(jnp.where(visible[None, None], att, -1e30), axis=-1)
    return jnp.einsum("kgqt,tkd->qkgd", att, v,
                      precision=jax.lax.Precision.HIGHEST)


# -- the op alone --------------------------------------------------------------

PG, NB, C = 8, 16, 24            # a limit of 128 positions, launches of 24
#: kind of line: key heads, query heads a key head, key width a head, value
#: width a head (None: one pool read as both), how far back a layer sees
LINES = {
    "gpt": (4, 1, 16, 16, PG * NB),
    "latent": (1, 4, 48, None, PG * NB),
    "gqa_full": (2, 3, 16, 16, PG * NB),
    "gqa_window": (2, 3, 16, 16, 20),
}
#: (start, n_valid): with two pages a block a block is 16 positions
LAUNCHES = {
    "first_of_a_prompt": (0, 24),
    "on_a_blocks_edge": (48, 24),
    "off_a_blocks_edge": (24, 24),
    "off_a_pages_edge": (21, 24),
    "padded_rows": (40, 5),
    "one_row": (0, 1),
    "ends_at_the_limit": (104, 24),
    "padded_past_the_limit": (120, 7),
    "window_begins_mid_page": (45, 24),   # 45 - 20 + 1 = 26, page 3's third
}


def _pool(rng, rows, width):
    # every line a slot does not see holds large values: were one let in,
    # or weighed by anything but an exact 0, the result would show it
    return rng.normal(size=(rows, PG, width)) * 1e3


@pytest.mark.parametrize("launch", list(LAUNCHES))
@pytest.mark.parametrize("lines", list(LINES))
@pytest.mark.parametrize("pages_per_block", [1, 2, 16])
def test_the_walk_equals_the_gathered_form(lines, launch, pages_per_block):
    KV, G, Dk, Dv, span = LINES[lines]
    start, n_valid = LAUNCHES[launch]
    rng = np.random.default_rng(hash((lines, launch)) % 2**32)
    R = 3 * NB + 1
    pools = [_pool(rng, R, KV * Dk)]
    if Dv is not None:
        pools.append(_pool(rng, R, KV * Dv))
    # a table of distinct pool rows in no order; the lines the launch sees
    # are written small, as a model's are
    rows = rng.permutation(np.arange(1, R))[:NB].astype(np.int32)
    seen = np.arange(max(start - span + 1, 0), start + n_valid)
    for pool in pools:
        pool[rows[seen // PG], seen % PG] = rng.normal(
            size=(seen.size, pool.shape[2]))
    kpool = jnp.asarray(pools[0], jnp.bfloat16)
    vpool = kpool if Dv is None else jnp.asarray(pools[1], jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(C, KV, G, Dk)), jnp.float32)
    args = (q, kpool, vpool, jnp.asarray(rows), jnp.int32(start),
            jnp.int32(n_valid), Dk ** -0.5, span)
    got = jax.jit(chunk_line_attention, static_argnums=(6, 7),
                  static_argnames="pages_per_block")(
        *args, pages_per_block=pages_per_block)
    want = gathered_chunk_attention(*args)
    assert got.shape == want.shape == (C, KV, G, Dv or Dk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=TOL, rtol=TOL)


# -- the rule ------------------------------------------------------------------

@pytest.mark.parametrize("start, n_valid, span, page, per, first, blocks", [
    (0, 256, 2048, 16, 16, 0, 1),        # a prompt's first launch: a block
    (0, 1, 2048, 16, 16, 0, 1),
    (256, 256, 2048, 16, 16, 0, 2),
    (255, 2, 2048, 16, 16, 0, 2),        # one row over the block's edge
    (1792, 256, 2048, 16, 16, 0, 8),     # the launch that ends at the limit
    (1792, 255, 2048, 16, 16, 0, 8),
    (512, 100, 2048, 16, 4, 0, 10),      # 612 positions, 39 pages, fours
    (2048, 128, 1024, 16, 64, 64, 2),    # a window: from position 1025 on
    (1100, 128, 1024, 16, 64, 4, 2),     # begins mid-page: 77 = 4 * 16 + 13
    (100, 128, 1024, 16, 64, 0, 1),      # nothing behind the window yet
    (4096, 64, 6144, 64, 4, 0, 17),
])
def test_the_walks_rule(start, n_valid, span, page, per, first, blocks):
    assert chunk_walk(start, n_valid, span, page, per) == (first, blocks)
    got = jax.jit(chunk_walk, static_argnums=(2, 3, 4))(
        jnp.int32(start), jnp.int32(n_valid), span, page, per)
    assert tuple(map(int, got)) == (first, blocks), \
        "the device's bounds are the host's"


@pytest.mark.parametrize("query_rows, page, blocks, want", [
    (32 * 256, 16, 128, 16),     # opt_1.3b: 256 positions a block
    (32 * 256, 16, 192, 16),     # kanana2: the same heads
    (32 * 256, 16, 768, 16),     # mellum2
    (20 * 256, 64, 96, 4),       # jamba2: 20 heads, pages of 64
    (4 * 32, 16, 8, 8),          # a small preset: the whole table
    (1 << 24, 16, 128, 1),       # never under a page
])
def test_a_blocks_pages_follow_the_launchs_scores(query_rows, page, blocks,
                                                  want):
    assert chunk_block_pages(query_rows, page, blocks) == want


# -- the engine's launches, family by family -----------------------------------

def _gpt():
    from nnstreamer_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(vocab=61, dim=32, heads=4, layers=2, mlp_mult=2,
                            max_seq=PG * NB)
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
        max_position_embeddings=PG * NB)
    return cfg, init_params(cfg, seed=3), {}


def _mellum():
    from nnstreamer_tpu.models.mellum import MellumConfig, init_params

    cfg = MellumConfig(
        vocab_size=61, hidden_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        moe_intermediate_size=16, num_experts=4, num_experts_per_tok=2,
        layer_types=("sliding_attention", "full_attention",
                     "sliding_attention"),
        sliding_window=20, max_position_embeddings=PG * NB)
    return cfg, init_params(cfg, seed=3), {"share_prefixes": False}


def _jamba():
    from nnstreamer_tpu.models.jamba import JambaConfig, init_params

    cfg = JambaConfig(
        vocab_size=61, hidden_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=1,
        intermediate_size=64, attn_layer_period=3, attn_layer_offset=1,
        mamba_d_state=4, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4,
        max_position_embeddings=PG * NB)
    return cfg, init_params(cfg, seed=3), {"share_prefixes": False}


FAMILIES = {"gpt": _gpt, "latent": _latent, "mellum": _mellum,
            "jamba": _jamba}
#: prompt lengths: launches of 24 at 0, 24, 48, ... with a block of 16
#: positions: on and off a block's edge, a last launch with padded rows, one
#: row, and the prompt whose last launch runs past the serving limit
PROMPTS = {"one_launch": 24, "padded_rows": 13, "one_row": 1,
           "three_launches": 72, "off_the_edge": 53, "to_the_limit": 127}


def _engine(family):
    """An engine of ``family`` (whose launches attend by whatever form
    ``paged_attention.chunk_line_attention`` is while it is built) and,
    of every launch it runs, ``(start, n_valid, the scores of its last row
    where it made a token)``."""
    cfg, params, more = FAMILIES[family]()
    eng = PagedLMEngine(cfg, params, slots=2, page_size=PG, chunk=C, **more)
    assert eng.chunk_block_pages == 2
    launches = spy_launches(eng)
    return eng, launches


@pytest.fixture(scope="module")
def engines():
    """``engines(family)`` -> ``[(engine, launches)]`` by the walk and by the
    gathered form, built once a family (an engine's programs compile for
    seconds, on every core) with a block of the walk two pages (the derived
    size would hold a small preset's whole table); ``launches`` starts
    empty, and a test gives back the slots and prefixes it took."""
    built = {}

    def get(family):
        if family not in built:
            old = (paged_attention.chunk_line_attention,
                   paged_attention.SCORE_BYTES)
            # four heads' rows, two pages
            paged_attention.SCORE_BYTES = 4 * 2 * PG * 4 * C
            try:
                pair = []
                for form in (chunk_line_attention, gathered_chunk_attention):
                    paged_attention.chunk_line_attention = form
                    pair.append(_engine(family))
            finally:
                (paged_attention.chunk_line_attention,
                 paged_attention.SCORE_BYTES) = old
            built[family] = pair
        for _, launches in built[family]:
            del launches[:]
        return built[family]

    yield get
    for pair in built.values():
        for eng, _ in pair:
            eng.close()


def _give_back(eng, *slots):
    for slot in slots:
        eng.release(slot)
    eng.pool.clear_prefixes()
    assert all(pool.used_pages == 0 for pool in eng.pools_by_kind.values())


def _lines_written(eng):
    """Every pool with its layers' null pages zeroed: a padded row's line
    goes there, and says nothing."""
    out = []
    for kind in eng.kinds:
        rows = eng.pools_by_kind[kind].pages + 1
        for pool in eng._kind_pools(kind):
            pool = np.array(pool, np.float32)
            pool[::rows] = 0
            out.append(pool)
    return out


def _prefill(eng, slot, prompt, steps=1):
    eng.admit_start(slot, prompt, steps)
    done = []
    while not done:
        done = eng.prefill_tick()
    return done[0][1]


@pytest.mark.parametrize("prompt", list(PROMPTS))
@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_prompts_launches_equal_the_gathered_forms(family, prompt, engines):
    n = PROMPTS[prompt]
    tokens = np.random.default_rng(n).integers(0, 61, n).astype(np.int32)
    runs = []
    for eng, launches in engines(family):
        first = _prefill(eng, 0, tokens)
        # two steps on: the step reads what the launches wrote
        eng._left[0] = 2
        after = [int(step_now(eng)[0]) for _ in range(2)]
        runs.append((list(launches), first, after, _lines_written(eng)))
        _give_back(eng, 0)
    (got, *toks), (want, *want_toks) = (r[:3] for r in runs)
    assert [g[:2] for g in got] == [w[:2] for w in want]
    assert len(got) == -(-n // C)
    assert [a is None for _, _, a in got] == [True] * (len(got) - 1) + [False]
    for (start, n_valid, a), (_, _, b) in zip(got[-1:], want[-1:]):
        np.testing.assert_allclose(a, b, atol=TOL, rtol=TOL,
                                   err_msg=f"launch at {start}")
    assert toks == want_toks
    for a, b in zip(runs[0][3], runs[1][3]):
        np.testing.assert_allclose(a, b, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("family", ["gpt", "latent"])
@pytest.mark.parametrize("shared", [16, 40])
def test_a_launch_over_another_requests_prefix_pages(family, shared,
                                                     engines):
    """The second prompt's first launch starts at ``shared`` (two and five
    pages: on a block's edge and off it), over pages the first request
    wrote and the registry handed on: read through its own table."""
    rng = np.random.default_rng(shared)
    head = rng.integers(0, 61, shared).astype(np.int32)
    one = np.concatenate([head, rng.integers(0, 61, 5).astype(np.int32)])
    two = np.concatenate([head, rng.integers(0, 61, 30).astype(np.int32)])
    runs = []
    for eng, launches in engines(family):
        hits = eng.pool.prefix_hits
        _prefill(eng, 0, one)
        del launches[:]
        first = _prefill(eng, 1, two)
        assert eng.pool.prefix_hits == hits + 1
        assert [x[:2] for x in launches] == [(shared, 24), (shared + 24, 6)]
        assert set(eng._bt[1, :shared // PG]) == set(eng._bt[0, :shared // PG])
        runs.append((list(launches), first))
        _give_back(eng, 0, 1)
    (got, first), (want, want_first) = runs
    assert [a is None for _, _, a in got] == [True] * (len(got) - 1) + [False]
    for (start, n_valid, a), (_, _, b) in zip(got[-1:], want[-1:]):
        np.testing.assert_allclose(a, b, atol=TOL, rtol=TOL,
                                   err_msg=f"launch at {start}")
    assert first == want_first


# -- the counter ---------------------------------------------------------------

@pytest.mark.parametrize("family, start, n_valid, read, padded", [
    # two layers of 128 positions; blocks of 16 from position 0
    ("gpt", 0, 24, 2 * 32, 2 * 128),
    ("gpt", 24, 24, 2 * 48, 2 * 128),
    ("gpt", 96, 5, 2 * 112, 2 * 128),
    ("latent", 48, 24, 2 * 80, 2 * 128),
    # a full layer and two window layers (20 back; 7 held blocks of 8): at
    # 48 the window's first page is (48 - 19) // 8 = 3, six pages to 71
    ("mellum", 0, 24, 32 + 2 * 32, 128 + 2 * 56),
    ("mellum", 48, 24, 80 + 2 * 48, 128 + 2 * 56),
    # one attention layer of three
    ("jamba", 24, 24, 48, 128),
])
def test_ctx_read_is_the_walks_own_count(family, start, n_valid, read, padded,
                                         engines):
    (eng, _), _ = engines(family)
    assert eng.chunk_ctx(start, n_valid) == (read, padded)


def test_every_launch_says_what_its_attention_read(engines):
    from nnstreamer_tpu.obs import context as obs_context

    (eng, launches), _ = engines("gpt")
    obs_context.reset()
    _prefill(eng, 0, np.arange(53, dtype=np.int32) % 61)
    spans = [s for s in obs_context.finished_spans()
             if s.name == "engine.chunk.prepare"]
    assert [(s.attrs["start"], s.attrs["n_valid"]) for s in spans] \
        == [x[:2] for x in launches] == [(0, 24), (24, 24), (48, 5)]
    assert [s.attrs["ctx_read"] for s in spans] == [64, 96, 128]
    assert {s.attrs["ctx_padded"] for s in spans} == {256}
    _give_back(eng, 0)
