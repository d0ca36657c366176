"""Pallas kernels via interpret mode on CPU (no TPU in CI)."""
import numpy as np
import pytest

import jax.numpy as jnp

from nnstreamer_tpu.ops.pallas_attention import (
    dense_attention,
    flash_attention,
)
from nnstreamer_tpu.ops.pallas_decode import dense_cached_decode


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S,bq,bk", [(64, 32, 32), (64, 64, 16)])
def test_flash_matches_dense(causal, S, bq, bk):
    rng = np.random.default_rng(0)
    shape = (2, 2, S, 16)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
               for _ in range(3))
    want = dense_attention(q, k, v, causal)
    got = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_flash_rejects_ragged_seq():
    q = jnp.zeros((1, 1, 100, 16), jnp.float32)
    with pytest.raises(ValueError):
        flash_attention(q, q, q, block_q=64, block_k=64, interpret=True)


# -- cached-decode attention (ops/pallas_decode.py) --------------------------

@pytest.mark.parametrize("pos", [0, 1, 31, 32, 63])
@pytest.mark.parametrize("block_k", [16, 32, 64])
def test_cached_decode_matches_dense(pos, block_k):
    from nnstreamer_tpu.ops.pallas_decode import cached_decode_attention

    rng = np.random.default_rng(1)
    B, H, T, D = 2, 3, 64, 16
    q = jnp.asarray(rng.standard_normal((B, H, 1, D)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
    want = dense_cached_decode(q, ck, cv, pos)
    got = cached_decode_attention(q, ck, cv, pos, block_k=block_k,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_cached_decode_rejects_ragged_cache():
    from nnstreamer_tpu.ops.pallas_decode import cached_decode_attention

    q = jnp.zeros((1, 1, 1, 16), jnp.float32)
    c = jnp.zeros((1, 1, 100, 16), jnp.float32)
    with pytest.raises(ValueError):
        cached_decode_attention(q, c, c, 0, block_k=64, interpret=True)


def test_generate_token_exact_with_pallas_decode():
    """cfg.decode_attn='pallas' must pick the same greedy tokens as the
    XLA oracle path through the full generate loop."""
    from nnstreamer_tpu.models.decoding import make_generate
    from nnstreamer_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    base = dict(vocab=64, dim=32, heads=4, layers=2, max_seq=64)
    cfg_x = TransformerConfig(**base)
    cfg_p = TransformerConfig(**base, decode_attn="pallas")
    params = init_params(cfg_x)
    prompt = jnp.asarray(
        np.random.default_rng(9).integers(0, 64, (2, 7)), jnp.int32)
    out_x = np.asarray(make_generate(cfg_x)(params, prompt, 8))
    out_p = np.asarray(make_generate(cfg_p)(params, prompt, 8))
    np.testing.assert_array_equal(out_x, out_p)
