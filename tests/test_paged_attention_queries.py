"""``paged_line_attention`` with several queries a slot (``queries=K``, the
verify round's form, PR 47): ``K = 2`` against ``K = 1`` called twice, once
a query at its own length and start, in the plain form and in the kernel
through the Pallas interpreter. Float32 over a bfloat16 pool: the plain
form's two calls are the same sums (exact), the kernel's online softmax
sums in another order (a few 1e-7)."""
import numpy as np
import pytest

import jax.numpy as jnp

from nnstreamer_tpu.ops import paged_attention as pa

S, H, W, PG, NB = 5, 4, 32, 4, 8
POS = (0, 3, 7, 18, 30)          # page edges, a window's edge, mid-page
LIVE = (True, True, True, False, True)


def _case(seed, window):
    rng = np.random.default_rng(seed)
    rows = 1 + S * NB
    kpool = jnp.asarray(rng.normal(size=(rows, PG, W)), jnp.bfloat16)
    vpool = jnp.asarray(rng.normal(size=(rows, PG, W)), jnp.bfloat16)
    table = jnp.asarray(1 + rng.permutation(S * NB).reshape(S, NB),
                        jnp.int32)
    q = jnp.asarray(rng.normal(size=(S, 2, H, W)), jnp.float32)
    lengths = jnp.where(jnp.asarray(LIVE), jnp.asarray(POS) + 1, 0)

    def starts(r):
        return None if window is None else jnp.maximum(
            lengths + r - window, 0)

    one = [pa.plain_line_attention(
        q[:, r], kpool, vpool, table,
        jnp.where(jnp.asarray(LIVE), lengths + r, 0), 0.3, starts(r))
        for r in range(2)]
    both = None if window is None else jnp.stack([starts(0), starts(1)], 1)
    return q.reshape(S, 2 * H, W), kpool, vpool, table, lengths, both, one


@pytest.mark.parametrize("window", [None, 6, 1])
def test_two_queries_a_slot_are_one_query_called_twice(window):
    q, kpool, vpool, table, lengths, starts, one = _case(0, window)
    two = pa.plain_line_attention(q, kpool, vpool, table, lengths, 0.3,
                                  starts, 2).reshape(S, 2, H, W)
    for r in range(2):
        assert float(jnp.abs(two[:, r] - one[r]).max()) < 1e-6
    # the second query sees the first's successor: where it matters the
    # two rows differ, and an empty slot answers zeros
    assert (two[3] == 0).all()


@pytest.mark.parametrize("pages_per_block", [1, 2, 8])
@pytest.mark.parametrize("window", [None, 6])
def test_the_kernel_with_two_queries_is_the_plain_form(window,
                                                       pages_per_block):
    q, kpool, vpool, table, lengths, starts, one = _case(1, window)
    got = pa.kernel_line_attention(
        q, kpool, vpool, table, lengths, 0.3, starts, queries=2,
        pages_per_block=pages_per_block, interpret=True).reshape(S, 2, H, W)
    for r in range(2):
        assert float(jnp.abs(got[:, r] - one[r]).max()) < 5e-6
    assert not np.isnan(np.asarray(got)).any()


def test_one_query_a_slot_is_the_step_it_was():
    q, kpool, vpool, table, lengths, _, one = _case(2, None)
    first = q.reshape(S, 2, H, W)[:, 0]
    assert (pa.plain_line_attention(first, kpool, vpool, table, lengths, 0.3,
                                    None, 1) == one[0]).all()
    got = pa.kernel_line_attention(first, kpool, vpool, table, lengths, 0.3,
                                   pages_per_block=2, interpret=True)
    assert float(jnp.abs(got - one[0]).max()) < 5e-6


def test_the_walk_ends_at_the_last_querys_page():
    # what the host counts as fetched (``pages_fetched``) for a round: from
    # the first query's first page to the second query's last
    lengths = np.asarray([4, 5, 16, 0])       # the first query's
    last = np.where(lengths > 0, lengths + 1, 0)
    starts = np.asarray([0, 0, 10, 0])
    assert pa.pages_fetched(last, starts, 4) == 2 + 2 + (5 - 2) + 0
    assert pa.pages_fetched(lengths, starts, 4) == 1 + 2 + (4 - 2) + 0
