"""Golden tests for the attention ops: Pallas flash attention (interpret mode
on the CPU sim — same kernel code as TPU) and ring/Ulysses context
parallelism vs the plain softmax reference.  Forward AND gradient parity, per
the reference's test discipline (SURVEY.md §4)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from torchdistpackage_tpu.dist import tpc
from torchdistpackage_tpu.ops import (
    flash_attention,
    mha_reference,
    ring_attention,
    ulysses_attention,
)

B, H, S, D = 2, 4, 64, 16


def _qkv(key, s=S):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (B, H, s, D)
    return (
        jax.random.normal(kq, shape),
        jax.random.normal(kk, shape),
        jax.random.normal(kv, shape),
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(1))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=16, block_k=16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4,
            err_msg=f"d{name} mismatch",
        )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("kv_heads", [1, 2])
def test_flash_gqa_matches_reference(causal, kv_heads):
    """Grouped-query attention (kv_heads < q heads; 1 = MQA): the kernel's
    kv BlockSpecs index b//G instead of materializing repeated KV — outputs
    AND grads (dk/dv in the kv heads' own shape, group-summed) must match
    the broadcast reference."""
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(kq, (B, H, S, D))
    k = jax.random.normal(kk, (B, kv_heads, S, D))
    v = jax.random.normal(kv_, (B, kv_heads, S, D))

    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
            ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        assert a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4,
            err_msg=f"d{name} mismatch (kv_heads={kv_heads})",
        )

    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k[:, :1].repeat(3, 1), v[:, :1].repeat(3, 1))


@pytest.mark.parametrize("impl", ["ring", "ring-einsum", "ulysses"])
def test_context_parallel_gqa_matches_serial(devices8, impl):
    """GQA through the CP ops: ring serves shared KV via the per-hop flash
    kernel's index maps, the einsum (debug) path broadcasts upfront, and
    Ulysses all_to_alls each tensor by ITS OWN head count (kv_heads % cp
    required) — all must match the serial GQA reference."""
    cp = 2  # kv_heads=2 must divide the context axis for ulysses
    tpc.setup_process_groups([("data", 2), ("context", cp)],
                             devices=devices8[:4])
    mesh = tpc.get_view()
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(kq, (B, H, S, D))
    k = jax.random.normal(kk, (B, 2, S, D))
    v = jax.random.normal(kv_, (B, 2, S, D))
    ref = mha_reference(q, k, v, causal=True)

    def f(q, k, v):
        if impl == "ring":
            return ring_attention(q, k, v, axis="context", causal=True)
        if impl == "ring-einsum":
            return ring_attention(q, k, v, axis="context", causal=True,
                                  use_flash=False)
        return ulysses_attention(q, k, v, axis="context", causal=True)

    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    sm = shard_map(
        f, mesh=mesh,
        in_specs=(P(None, None, "context"),) * 3,
        out_specs=P(None, None, "context"),
    )
    out = jax.jit(sm)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _cp_mesh(devices8, cp=4):
    tpc.setup_process_groups([("data", 2), ("context", cp)], devices=devices8)
    return tpc.get_view()


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
def test_context_parallel_matches_serial(devices8, impl, causal):
    mesh = _cp_mesh(devices8)
    q, k, v = _qkv(jax.random.PRNGKey(2))
    ref = mha_reference(q, k, v, causal=causal)

    fn = ring_attention if impl == "ring" else ulysses_attention
    seq_spec = P(None, None, "context", None)

    sharded = shard_map(
        functools.partial(fn, axis="context", causal=causal),
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec),
        out_specs=seq_spec,
    )
    out = jax.jit(sharded)(
        *(jax.device_put(x, NamedSharding(mesh, seq_spec)) for x in (q, k, v))
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_context_parallel_grads_match_serial(devices8, impl):
    mesh = _cp_mesh(devices8)
    q, k, v = _qkv(jax.random.PRNGKey(3))
    fn = ring_attention if impl == "ring" else ulysses_attention
    seq_spec = P(None, None, "context", None)

    def loss_cp(q, k, v):
        out = shard_map(
            functools.partial(fn, axis="context", causal=True),
            mesh=mesh,
            in_specs=(seq_spec, seq_spec, seq_spec),
            out_specs=seq_spec,
        )(q, k, v)
        return jnp.sum(out**2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    gc = jax.jit(jax.grad(loss_cp, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gc, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4,
            err_msg=f"d{name} mismatch ({impl})",
        )


def test_transformer_flash_matches_naive():
    """attn_impl='flash' is a drop-in for the naive score-matrix path."""
    from torchdistpackage_tpu.parallel.tensor_parallel import (
        TransformerConfig,
        init_transformer_params,
        transformer_forward,
    )

    cfg_n = TransformerConfig(dim=32, nheads=4, nlayers=2, attn_impl="naive")
    cfg_f = TransformerConfig(dim=32, nheads=4, nlayers=2, attn_impl="flash")
    params = init_transformer_params(jax.random.PRNGKey(0), cfg_n)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32))
    out_n = transformer_forward(params, x, cfg_n)
    out_f = transformer_forward(params, x, cfg_f)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_n), rtol=2e-5, atol=2e-5)

    gn = jax.grad(lambda p: jnp.mean(transformer_forward(p, x, cfg_n) ** 2))(params)
    gf = jax.grad(lambda p: jnp.mean(transformer_forward(p, x, cfg_f) ** 2))(params)
    for (pth, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(gn)[0],
        jax.tree_util.tree_flatten_with_path(gf)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-5,
            err_msg=f"grad mismatch at {jax.tree_util.keystr(pth)}",
        )


def test_ring_flash_long_seq_8k(devices8):
    """Long-context: 8k global tokens, 8-way CP ring with the Pallas flash
    kernel per hop.  Cross-checked against the einsum-ring (use_flash=False)
    golden path — the serial reference would materialize an 8k x 8k score
    matrix, exactly what both ring paths avoid."""
    tpc.setup_process_groups([("context", 8)], devices=devices8)
    mesh = tpc.get_view()
    S_global = 8192
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, S_global, 64), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, S_global, 64), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 1, S_global, 64), jnp.float32)
    seq_spec = P(None, None, "context", None)

    def run(use_flash):
        return jax.jit(
            shard_map(
                functools.partial(
                    ring_attention, axis="context", causal=True, use_flash=use_flash
                ),
                mesh=mesh,
                in_specs=(seq_spec,) * 3,
                out_specs=seq_spec,
            )
        )(*(jax.device_put(x, NamedSharding(mesh, seq_spec)) for x in (q, k, v)))

    out_flash = run(True)
    out_einsum = run(False)
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_einsum), rtol=2e-5, atol=2e-5
    )


def test_ring_attention_long_seq_memory_shape(devices8):
    """Liveness at a longer sequence: 8-way CP over 2048 tokens, bf16."""
    tpc.setup_process_groups([("context", 8)], devices=devices8)
    mesh = tpc.get_view()
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 2048, 32), dtype=jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 2048, 32), dtype=jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 2048, 32), dtype=jnp.bfloat16)
    seq_spec = P(None, None, "context", None)
    out = jax.jit(
        shard_map(
            functools.partial(ring_attention, axis="context", causal=True),
            mesh=mesh,
            in_specs=(seq_spec,) * 3,
            out_specs=seq_spec,
        )
    )(*(jax.device_put(x, NamedSharding(mesh, seq_spec)) for x in (q, k, v)))
    assert out.shape == (1, 2, 2048, 32)
    assert out.dtype == jnp.bfloat16
    assert np.all(np.isfinite(np.asarray(out, dtype=np.float32)))


def test_zigzag_ring_matches_serial(devices8):
    """Zigzag (load-balanced causal) ring attention: permute inputs to the
    zigzag layout, run the ring, unpermute — must equal serial causal
    attention on the natural order (flash and einsum paths)."""
    from torchdistpackage_tpu.ops.ring_attention import (
        ring_attention,
        zigzag_permute,
        zigzag_unpermute,
    )

    cp = 4
    tpc.setup_process_groups([("context", cp)], devices=devices8[:cp])
    mesh = tpc.get_view()
    B, H, S, D = 2, 4, 64, 16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, H, S, D), jnp.float32)
    k = jax.random.normal(kk, (B, H, S, D), jnp.float32)
    v = jax.random.normal(kv, (B, H, S, D), jnp.float32)
    golden = mha_reference(q, k, v, causal=True)

    qz = zigzag_permute(q, cp, seq_dim=2)
    kz = zigzag_permute(k, cp, seq_dim=2)
    vz = zigzag_permute(v, cp, seq_dim=2)

    for use_flash in (True, False):
        ring = jax.jit(
            shard_map(
                lambda q, k, v: ring_attention(
                    q, k, v, axis="context", causal=True,
                    use_flash=use_flash, layout="zigzag",
                    block_q=8, block_k=8,
                ),
                mesh=mesh,
                in_specs=(P(None, None, "context"),) * 3,
                out_specs=P(None, None, "context"),
            )
        )
        out = zigzag_unpermute(ring(qz, kz, vz), cp, seq_dim=2)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(golden), rtol=2e-5, atol=2e-5,
            err_msg=f"zigzag use_flash={use_flash}",
        )


def test_zigzag_permute_roundtrip():
    from torchdistpackage_tpu.ops.ring_attention import (
        zigzag_permute,
        zigzag_unpermute,
        zigzag_positions,
    )

    x = jnp.arange(32)[None]  # [1, 32]
    z = zigzag_permute(x, 4, seq_dim=1)
    # shard 0 of 4 owns chunks 0 and 7 -> tokens 0-3 and 28-31
    np.testing.assert_array_equal(np.asarray(z[0, :8]), [0, 1, 2, 3, 28, 29, 30, 31])
    np.testing.assert_array_equal(np.asarray(zigzag_unpermute(z, 4, seq_dim=1)), np.asarray(x))
    pos, (lo, hi) = zigzag_positions(0, 8, 4)
    np.testing.assert_array_equal(np.asarray(pos), [0, 1, 2, 3, 28, 29, 30, 31])


def test_flash_sliding_window_matches_reference():
    """Sliding-window flash (Mistral semantics: key in (q-window, q]) must
    equal the masked reference for fwd AND all grads, across windows
    smaller than / equal to / larger than a KV block, GQA included, and
    the out-of-window KV block range must actually be SKIPPED (the
    O(S*window) compute claim)."""
    import numpy as np

    from torchdistpackage_tpu.ops.flash_attention import (
        flash_attention,
        mha_reference,
    )

    B, H, S, D = 2, 4, 256, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, D)) for kk in ks)
    kg, vg = k[:, ::2], v[:, ::2]  # GQA: 2 kv heads

    for W in (1, 17, 64, 100, 256, 300):
        ref = mha_reference(q, k, v, causal=True, window=W)
        out = flash_attention(q, k, v, causal=True, window=W,
                              block_q=64, block_k=128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=f"W={W}")
        gr = jax.grad(lambda *a: jnp.sum(
            mha_reference(*a, causal=True, window=W) ** 2), argnums=(0, 1, 2)
        )(q, k, v)
        gf = jax.grad(lambda *a: jnp.sum(
            flash_attention(*a, causal=True, window=W, block_q=64,
                            block_k=128) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=3e-4, err_msg=f"W={W}")

    # GQA + window
    ref = mha_reference(q, kg, vg, causal=True, window=48)
    out = flash_attention(q, kg, vg, causal=True, window=48,
                          block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    # window requires causal; bad window rejected
    import pytest

    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=0)


def test_sliding_window_core_attention_and_cfg_guards():
    import numpy as np
    import pytest

    from torchdistpackage_tpu.parallel.tensor_parallel import TransformerConfig
    from torchdistpackage_tpu.parallel.tensor_parallel.layers import (
        core_attention,
    )
    from torchdistpackage_tpu.ops.flash_attention import mha_reference

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 64, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 64, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 64, 16))
    for impl in ("naive", "flash"):
        cfg = TransformerConfig(dim=32, nheads=2, attn_impl=impl,
                                sliding_window=16)
        out = core_attention(q, k, v, cfg)
        ref = mha_reference(q, k, v, causal=True, window=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=impl)
    with pytest.raises(NotImplementedError, match="context-parallel"):
        TransformerConfig(dim=32, nheads=2, attn_impl="ring",
                          context_axis="context", sliding_window=16)
    with pytest.raises(ValueError, match="causal"):
        TransformerConfig(dim=32, nheads=2, causal=False, sliding_window=16)
