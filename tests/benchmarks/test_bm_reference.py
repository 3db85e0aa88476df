"""The plain reference against the program at toy size, in float32, for both
block types: GPT-2 style, and GQA + RoPE + SwiGLU + a window that binds."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bm_toy
from benchmarks import arch as A
from benchmarks.reference import model as M
from benchmarks.reference import train as T
from benchmarks.weights import make_weights

CONFIGS = {"gpt2": bm_toy.TOY_GPT2, "mistral": bm_toy.TOY_MISTRAL}
SEQ = 48  # the toy window is 24: it binds
OPT = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
       "weight_decay": 1e-2}


def setup(family):
    cfg = CONFIGS[family]
    fam = A.family_of(cfg)
    a = fam.arch(cfg, SEQ)
    pcfg = dataclasses.replace(fam.program_config(cfg, SEQ),
                               dtype=jnp.float32, attn_impl="naive")
    params = make_weights(a, 2**31 + 5, dtype=jnp.float32)
    # biases and norm offsets away from zero, so that they are compared too
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape) if x.ndim <= 2 else x
        for x, k in zip(leaves, keys)])
    rng = np.random.RandomState(0)
    toks = rng.randint(0, a.vocab, size=(2, SEQ + 1)).astype(np.int32)
    return a, pcfg, params, toks


@pytest.mark.parametrize("family", ["gpt2", "mistral"])
def test_forward_logits_match_the_program(family):
    from torchdistpackage_tpu.models import gpt_forward

    a, pcfg, params, toks = setup(family)
    with jax.default_matmul_precision("highest"):
        want = gpt_forward(params, toks[:, :-1], pcfg)
    for b in range(2):
        got = M.forward_logits(params, toks[b, :-1], a)
        # float32 against float32: only the order of sums differs
        np.testing.assert_allclose(got, want[b], atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("family", ["gpt2", "mistral"])
def test_loss_gradients_and_adamw_match_the_program(family):
    from torchdistpackage_tpu.models import gpt_loss

    a, pcfg, params, toks = setup(family)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: gpt_loss(p, batch, pcfg))(params)
    opt = optax.adamw(**OPT)
    updates, _ = opt.update(grads, opt.init(params), params)
    stepped = optax.apply_updates(params, updates)
    want_g = {k: float(v) for k, v in T.leaf_norms(grads).items()}
    want_u = {k: float(v) for k, v in T.delta_norms(stepped, params).items()}

    ref = T.TrainReference(jax.tree.map(jnp.copy, params), a, OPT)
    ref_loss = ref.step(batch["tokens"], batch["targets"])
    got_u = {k: float(v) for k, v in T.delta_norms(ref.params, params).items()}
    assert ref_loss == pytest.approx(float(loss), abs=1e-4)
    assert T.worst_gap(ref.grad_norms[0], want_g)["gap"] < 1e-3
    assert set(want_g) == set(ref.grad_norms[0])
    # GPT-2's key bias has no gradient (softmax ignores a constant added to
    # a row of scores): a part of its fused leaf, found by its reference
    # gradient, and the one leaf whose Adam update is rounding noise.  Under
    # RoPE the bias turns with the position, and has a gradient like any other
    dead = T.without_gradient(ref.grad_norms[0])
    assert dead == {"gpt2": ["blocks.attn.bqkv.k"], "mistral": []}[family]
    assert T.worst_gap(got_u, want_u, skip=dead)["gap"] < 1e-3
    if dead:
        assert T.worst_gap(got_u, want_u)["leaf"] == dead[0]


def test_the_window_and_the_causal_mask_bind():
    a, _, params, toks = setup("mistral")
    base = M.forward_logits(params, toks[0, :-1], a)
    # a token more than `window` back cannot move the last position ...
    far = toks[0, :-1].copy()
    far[0] = (far[0] + 1) % a.vocab
    moved = M.forward_logits(params, far, a)
    assert not np.allclose(moved[5], base[5])
    # (two layers reach 2 x (window - 1) back, not further)
    assert np.allclose(moved[-1], base[-1], atol=1e-5)
    # ... and no position sees a later token
    late = toks[0, :-1].copy()
    late[-1] = (late[-1] + 1) % a.vocab
    assert np.allclose(M.forward_logits(params, late, a)[:-1], base[:-1], atol=1e-5)


@pytest.mark.parametrize("family", ["gpt2", "mistral"])
def test_the_fp8_control_moves_the_logits(family):
    a, _, params, toks = setup(family)
    base = M.forward_logits(params, toks[0, :-1], a)
    err = float(jnp.abs(M.forward_logits(params, toks[0, :-1], a, "fp8") - base).max())
    assert 1e-2 < err < 1.0
    with pytest.raises(ValueError):
        M.forward_logits(params, toks[0, :-1], a, "int3")


def test_weights_repeat_from_a_seed_and_differ_across_seeds():
    a = A.family_of(bm_toy.TOY_GPT2).arch(bm_toy.TOY_GPT2, 32)
    w1, w2, w3 = (make_weights(a, s) for s in (2**31 + 9, 2**31 + 9, 2**31 + 10))
    assert w1["head"].dtype == jnp.bfloat16
    assert jax.tree.all(jax.tree.map(lambda x, y: bool((x == y).all()), w1, w2))
    assert not bool((w1["head"] == w3["head"]).all())
    assert sum(x.size for x in jax.tree.leaves(w1)) == a.num_params()
