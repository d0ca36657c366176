"""The routed experts' products over a launch's rows (ops/moe_grouped.py).

* the Pallas kernel, interpreted on the CPU, against the op's plain form
  (sort, three ``ragged_dot``, undo the sort) through
  ``moe_dropless.experts_ffn``: both benchmark cells' group shapes at
  reduced widths, and the edges of a routing — an expert no row reached,
  an expert every row chose, rows that are not live, no live row at all,
  a share of the experts held from ``first_expert`` on (two shares add up
  to the whole), fewer rows than a tile row holds — at every tiling of the
  hidden columns; ``counts`` equal exactly;
* the rule that picks the form from the call's static shapes, and the tile
  it derives;
* the stand-alone timing tool's path at a CPU size.

What the compiled step holds on a TPU is beside the other compiles for a
described chip: ``tests/test_kv_paged.py::TestExpertsStreamOnTpu`` (one
file loads the TPU's compiler).
"""
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nnstreamer_tpu.ops import moe_grouped as mg
from nnstreamer_tpu.parallel import moe_dropless

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
D, F = 256, 384  # three tiles of 128 hidden columns, or one of 384


def _stacks(E, dtype=jnp.bfloat16, seed=32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple((jax.random.normal(k, s) * 0.05).astype(dtype)
                 for k, s in zip(keys, ((E, D, F), (E, D, F), (E, F, D))))


def _both(stacks, h, experts, weights, live=None, first_expert=0, **tiling):
    """``experts_ffn`` through the plain form and through the kernel."""
    want = moe_dropless.experts_ffn(*stacks, h, experts, weights, live=live,
                                    first_expert=first_expert)
    kernel = functools.partial(mg.kernel_grouped_experts, interpret=True,
                               **tiling)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(mg, "grouped_experts", kernel)
        got = moe_dropless.experts_ffn(*stacks, h, experts, weights,
                                       live=live, first_expert=first_expert)
    return want, got


def _close(want, got, rel=5e-4):
    (y0, c0), (y1, c1) = want, got
    assert y1.shape == y0.shape and y1.dtype == jnp.float32
    # float32 sums in another order: of the result's own scale 1e-6, and
    # where a hidden value lies at the edge of two bfloat16 neighbours the
    # two orders round it apart (one part in 256 of one of F terms)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               atol=rel * float(jnp.abs(y0).max()) + 1e-9,
                               rtol=0)
    assert np.array_equal(np.asarray(c0), np.asarray(c1))


def _routed(T, k, E, seed=0, never=(), always=()):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    logits = rng.standard_normal((T, E))
    logits[:, list(never)] = -30.0
    logits[:, list(always)] = 3.0
    weights, experts = jax.lax.top_k(
        jax.nn.softmax(jnp.asarray(logits, jnp.float32)), k)
    return h, experts.astype(jnp.int32), weights


TILINGS = {"whole_d2": dict(tile_f=F, depth=2),
           "f128_d2": dict(tile_f=128, depth=2),
           "f128_d3": dict(tile_f=128, depth=3),
           "derived": {}}


@pytest.mark.parametrize("tiling", TILINGS, ids=list(TILINGS))
@pytest.mark.parametrize("T,k,E", [(32, 8, 64), (32, 6, 128)],
                         ids=["mellum_step", "kanana_step"])
def test_kernel_matches_the_plain_form_at_the_cells_group_shapes(T, k, E,
                                                                 tiling):
    want, got = _both(_stacks(E), *_routed(T, k, E), **TILINGS[tiling])
    _close(want, got)
    touched, assignments, largest, held = np.asarray(got[1])
    assert (assignments, held) == (T * k, E)
    assert touched < E or T * k > E, "192 assignments cannot reach 128"
    assert largest <= T, "an expert holds at most one assignment a row"


def _edge(name):
    """``(stacks, h, experts, weights, live, first_expert)`` of an edge."""
    T, k, E = 32, 4, 16
    h, experts, weights = _routed(
        T, k, E, seed=3,
        never=(0, 5, 15) if name == "an_expert_no_row_reached" else (),
        always=(2,) if name == "an_expert_every_row_chose" else ())
    live, first = None, 0
    if name == "rows_that_are_not_live":
        live = jnp.arange(T) % 3 != 1
        h = h.at[1].set(jnp.inf)  # what a dead row holds reaches no sum
    elif name == "no_live_row":
        live = jnp.zeros((T,), bool)
    elif name == "fewer_rows_than_a_tile_row":
        h, experts, weights = h[:5], experts[:5], weights[:5]
    elif name == "one_row":
        h, experts, weights = h[:1], experts[:1], weights[:1]
    return _stacks(E), h, experts, weights, live, first


EDGES = ["an_expert_no_row_reached", "an_expert_every_row_chose",
         "rows_that_are_not_live", "no_live_row",
         "fewer_rows_than_a_tile_row", "one_row"]


@pytest.mark.parametrize("tiling", ["whole_d2", "f128_d3"])
@pytest.mark.parametrize("edge", EDGES)
def test_kernel_matches_the_plain_form_at_the_edges_of_a_routing(edge,
                                                                 tiling):
    stacks, h, experts, weights, live, first = _edge(edge)
    want, got = _both(stacks, h, experts, weights, live, first,
                      **TILINGS[tiling])
    _close(want, got)
    y, counts = got
    if edge == "no_live_row":
        assert not np.asarray(y).any() and not np.asarray(counts)[:3].any()
    if edge == "rows_that_are_not_live":
        assert not np.asarray(y)[1::3].any(), "a dead row's result is zero"
        assert np.isfinite(np.asarray(y)).all()
        assert counts[1] == 4 * int(np.asarray(live).sum())
    if edge == "an_expert_every_row_chose":
        assert counts[2] == h.shape[0]
    if edge == "an_expert_no_row_reached":
        assert counts[0] <= 13


@pytest.mark.parametrize("tiling", ["whole_d2", "f128_d2"])
def test_two_shares_of_the_experts_add_up_to_the_whole(tiling):
    T, k, E = 32, 4, 16
    stacks = _stacks(E)
    h, experts, weights = _routed(T, k, E, seed=4)
    whole, _ = _both(stacks, h, experts, weights)
    parts = []
    for first in (0, E // 2):
        share = tuple(w[first:first + E // 2] for w in stacks)
        want, got = _both(share, h, experts, weights, None, first,
                          **TILINGS[tiling])
        _close(want, got)
        assert got[1][3] == E // 2
        parts.append(got)
    np.testing.assert_allclose(np.asarray(parts[0][0] + parts[1][0]),
                               np.asarray(whole[0]), atol=1e-4, rtol=0)
    both = np.asarray(parts[0][1]) + np.asarray(parts[1][1])
    assert both[0] == whole[1][0] and both[1] == whole[1][1] == T * k


def test_float32_stacks_take_the_same_kernel():
    # the CPU suites' parameter trees are float32: the operands then are
    # (and nothing is rounded on the way: float32 sums' distance alone)
    want, got = _both(_stacks(16, jnp.float32), *_routed(8, 2, 16))
    _close(want, got, rel=1e-5)


@pytest.mark.parametrize("T,Dm,Fm,itemsize,tile", [
    (32, 2304, 896, 2, 896),     # mellum's step: two whole experts, 24.8 MB
    (32, 2048, 768, 2, 768),     # kanana's step
    (256, 2304, 896, 2, 896),    # both cells' launch streams as well
    (256, 2048, 768, 2, 768),
    (512, 2304, 896, 2, None),   # a wider launch: the grouped product
    (32, 2304, 896, 4, 128),     # float32 experts: a tile of one lane
    (32, 4096, 2048, 2, 512),    # experts four times the size: four tiles
    (32, 32, 16, 4, None),       # the CPU suites' widths do not tile
    (32, 2304, 900, 2, None),
])
def test_the_form_follows_the_calls_static_shapes(T, Dm, Fm, itemsize, tile):
    dtype = {2: jnp.bfloat16, 4: jnp.float32}[itemsize]
    assert mg.streams(T, Dm, Fm, dtype) == (tile is not None)
    if T <= mg.MAX_ROWS:
        assert mg.tile_columns(Dm, Fm, itemsize) == tile
        if tile:
            assert 2 * 3 * Dm * tile * itemsize <= mg.BUFFER_BYTES


def test_on_the_cpu_the_op_takes_its_plain_form(monkeypatch):
    ran = []
    monkeypatch.setattr(mg, "kernel_grouped_experts",
                        lambda *a, **k: ran.append(1))
    y, counts = moe_dropless.experts_ffn(*_stacks(16), *_routed(8, 2, 16))
    assert not ran and y.shape == (8, D)
    # what a TPU would run: the kernel at these shapes, not past MAX_ROWS
    monkeypatch.undo()
    calls = []
    real = mg.kernel_grouped_experts
    monkeypatch.setattr(mg, "kernel_grouped_experts",
                        lambda *a, **k: calls.append(a[0].shape) or real(
                            *a, **k))
    tpu = functools.partial(mg.tpu_grouped_experts, interpret=True)
    monkeypatch.setattr(mg, "grouped_experts", tpu)
    for T in (8, mg.MAX_ROWS + 16):
        moe_dropless.experts_ffn(*_stacks(16), *_routed(T, 2, 16))
    assert calls == [(8, D)]


def test_the_timing_tool_runs_its_path_at_a_cpu_size():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "moe_grouped_forms.py"),
         "--rehearse", "kernel_fwhole", "ragged_halves"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(line) for line in out.stdout.splitlines()]
    assert [r["form"] for r in rows] == ["ragged", "ragged_halves",
                                         "kernel_fwhole_d2"]
    for r in rows:
        assert "error" not in r and r["max_diff"] < 1e-4
        assert "ms_per_layer" not in r, "a CPU's time is no device number"
