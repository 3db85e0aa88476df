"""The latent paged-attention kernel (ops/mla_attention.py) in the Pallas
interpreter against its gathered oracle, and against the GQA kernel run as
MQA with the pool passed as K and as V: decode, verify-shaped and chunk rows,
every slot at its own depth, dead blocks, key tiles of one to four blocks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistpackage_tpu.ops.mla_attention import (
    mla_gather_attention, mla_paged_attention)
from torchdistpackage_tpu.ops.paged_attention import paged_decode_attention

L, NB, BS, W, DC, H = 3, 23, 16, 40, 32, 4


@pytest.fixture(scope="module")
def pool():
    """A pool of random rows, [L, nb, 1, W, bs]; block 0 (the NULL block)
    and a never-used block hold NaN: nothing may read them."""
    p = jax.random.normal(jax.random.PRNGKey(0), (L, NB, 1, W, BS))
    return p.at[:, 0].set(jnp.nan).at[:, NB - 1].set(jnp.nan)


def _tables(B, mb, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(
        1 + rng.permutation(NB - 2)[:B * mb].reshape(B, mb), jnp.int32)


@pytest.mark.parametrize("s_in,offsets,kw", [
    (1, [0, 15, 16, 63], {}),
    (1, [5, 40, 31, 17], {"fetch_width": 4, "group": 2}),
    (1, [5, 40, 31, 17], {"fetch_width": 2, "group": 1}),
    (3, [0, 13, 30, 61], {"fetch_width": 4, "group": 4}),
    (16, [0, 16, 32, 48], {"fetch_width": 2, "group": 2}),
    (16, [0, 16, 32, 48], {"fetch_width": 3, "group": 1, "row_tile": 32}),
    (8, [3, 0, 41, 24], {"fetch_width": 4, "row_tile": 16}),
], ids=["decode", "decode_fw4_g2", "decode_fw2_g1", "verify", "chunk",
        "chunk_tiled", "chunk_unaligned"])
def test_kernel_equals_the_gathered_oracle(pool, s_in, offsets, kw):
    B, mb = 4, 4
    tables, offs = _tables(B, mb), jnp.asarray(offsets, jnp.int32)
    # a slot's table names only the blocks it has: the rest is the NULL block
    live = (np.asarray(offsets)[:, None] + s_in + BS - 1) // BS
    tables = jnp.where(np.arange(mb)[None, :] < live, tables, 0)
    q = jax.random.normal(jax.random.PRNGKey(s_in), (B, H, s_in, W))
    with jax.default_matmul_precision("highest"):
        want = mla_gather_attention(
            q, jnp.nan_to_num(pool), tables, offs, latent=DC, sm_scale=0.3,
            layer=1)
        got = mla_paged_attention(q, pool, tables, offs, latent=DC,
                                  sm_scale=0.3, layer=1, **kw)
    assert got.shape == (B, H, s_in, DC) and not np.isnan(got).any()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_one_layers_pool_and_a_scalar_offset(pool):
    tables = _tables(2, 3, seed=1)
    q = jax.random.normal(jax.random.PRNGKey(9), (2, H, 1, W))
    one = jnp.nan_to_num(pool[2])
    with jax.default_matmul_precision("highest"):
        got = mla_paged_attention(q, one, tables, 37, latent=DC,
                                  sm_scale=0.2, group=2, fetch_width=2)
        want = mla_gather_attention(q, one, tables, 37, latent=DC,
                                    sm_scale=0.2)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="latent pool"):
        mla_paged_attention(q, one.swapaxes(-1, -2), tables, 37, latent=DC,
                            sm_scale=0.2)


def test_the_gqa_kernel_as_mqa_reads_the_pool_twice_for_the_same_numbers(pool):
    """The baseline the kernel replaces: K = V = the rows, one KV head of
    width W; its output's first ``latent`` columns are the latent kernel's."""
    B, mb = 3, 4
    tables, offs = _tables(B, mb, seed=2), jnp.asarray([9, 33, 62], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(4), (B, H, 1, W))
    rows = jnp.nan_to_num(pool).swapaxes(-1, -2)        # [L, nb, 1, bs, W]
    with jax.default_matmul_precision("highest"):
        mqa = paged_decode_attention(q, rows, rows, tables, offs, layer=0,
                                     sm_scale=0.25, fetch_width=2)
        got = mla_paged_attention(q, pool, tables, offs, latent=DC,
                                  sm_scale=0.25, layer=0)
    np.testing.assert_allclose(got, mqa[..., :DC], rtol=2e-5, atol=2e-5)
