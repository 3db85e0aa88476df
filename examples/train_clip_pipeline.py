"""End-to-end example: CLIP-style two-tower model on the 1F1B pipeline —
the non-linear stage graph the reference demonstrates with fwd_fn/bwd_fn
pairs (Intro.md:54-66), rebuilt for SPMD/XLA.

The two towers ride one static activation: ``first_fn`` embeds the image
patches into channel 0 and the text tokens into channel 1 of an
``[mbs, 2, S, D]`` tensor; ``stage_fn`` branches on :func:`stage_index`
(first half of the stages runs its transformer slab on the vision channel,
second half on the text channel — balanced FLOPs, uniform program, no
dynamic shapes); the last stage pools both channels and computes the
symmetric InfoNCE contrastive loss inside its 1F1B backward unit.

(When the towers genuinely need DIFFERENT widths per stage, use
``pipeline_parallel.make_heterogeneous_stage`` — the max-edge bus with
per-stage dispatch, ``examples/train_hetero_pipeline.py`` — instead of
this channel-stacking trick, which requires equal channel shapes.)

- real TPU chips:      python examples/train_clip_pipeline.py
- 8-device CPU sim:    TDP_CPU_SIM=8 python examples/train_clip_pipeline.py
"""

import os
import time

if os.environ.get("TDP_CPU_SIM"):
    # XLA_FLAGS handling is centralized in dist/overlap.py (test_repo_lint
    # bans direct writes); cpu_sim also pins the cpu platform, replacing
    # the old post-import jax.config.update dance.
    from torchdistpackage_tpu.dist.overlap import cpu_sim

    cpu_sim(os.environ["TDP_CPU_SIM"])

import jax

from jax.lax import axis_size

import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from torchdistpackage_tpu import setup_distributed, tpc
from torchdistpackage_tpu.parallel import DataParallel
from torchdistpackage_tpu.parallel.pipeline_parallel import (
    pipeline_1f1b,
    stage_index,
    stack_stage_params,
    stacked_param_specs,
)
from torchdistpackage_tpu.parallel.tensor_parallel import (
    TransformerConfig,
    block_forward,
    init_block_params,
)

SMOKE = bool(os.environ.get("TDP_SMOKE"))

CFG = TransformerConfig(dim=64, nheads=4, nlayers=4, ffn_mult=2, causal=False)
S, PATCH = 16, 48  # shared tower sequence length; raw image patch dim
VOCAB = 256
M, MBS = 4, 4  # microbatches, per-shard microbatch size
STEPS = 2 if SMOKE else 20


def init_params(key):
    kb, kpi, kpt, kt = jax.random.split(key, 4)
    keys = jax.random.split(kb, CFG.nlayers)
    blocks = stack_stage_params([init_block_params(k, CFG) for k in keys])
    return {
        # blocks [0, L/2) = vision tower, [L/2, L) = text tower — one stacked
        # slab, pipe-sharded like any other stage params
        "blocks": blocks,
        "patch_proj": jax.random.normal(kpi, (PATCH, CFG.dim)) * 0.05,
        "tok_emb": jax.random.normal(kt, (VOCAB, CFG.dim)) * 0.05,
        "pos_emb": jax.random.normal(kpt, (S, CFG.dim)) * 0.02,
        "logit_scale": jnp.zeros(()),
    }


def param_specs(pipe_axis="pipe"):
    bspecs = jax.tree.map(lambda _: P(pipe_axis), init_params(jax.random.PRNGKey(0))["blocks"])
    return {
        "blocks": bspecs,
        "patch_proj": P(),
        "tok_emb": P(),
        "pos_emb": P(),
        "logit_scale": P(),
    }


def first_fn(params, mb):
    """Embed both modalities into one [mbs, 2, S, D] activation."""
    img = mb["patches"] @ params["patch_proj"] + params["pos_emb"]  # [mbs, S, D]
    txt = jnp.take(params["tok_emb"], mb["text"], axis=0) + params["pos_emb"]
    return jnp.stack([img, txt], axis=1)


def stage_fn(params, h):
    """First half of the stages advances the vision channel, second half the
    text channel — per-stage heterogeneity via a stage_index branch."""
    pp = axis_size("pipe")

    def run(channel, h):
        x = h[:, channel]

        def body(x, lp):
            return block_forward(lp, x, CFG), None

        x, _ = jax.lax.scan(body, x, params["blocks"])
        return h.at[:, channel].set(x)

    return jax.lax.cond(
        stage_index() < pp // 2,
        lambda h: run(0, h),
        lambda h: run(1, h),
        h,
    )


def last_fn(params, h, _tgt):
    """Pool both towers, L2-normalize, symmetric InfoNCE over the microbatch."""
    img = jnp.mean(h[:, 0], axis=1)
    txt = jnp.mean(h[:, 1], axis=1)
    img = img / (jnp.linalg.norm(img, axis=-1, keepdims=True) + 1e-6)
    txt = txt / (jnp.linalg.norm(txt, axis=-1, keepdims=True) + 1e-6)
    logits = img @ txt.T * jnp.exp(params["logit_scale"])
    labels = jnp.arange(logits.shape[0])
    li = optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
    lt = optax.softmax_cross_entropy_with_integer_labels(logits.T, labels).mean()
    return 0.5 * (li + lt)


def main():
    setup_distributed()
    n = jax.device_count()
    pp = 4 if n % 4 == 0 else 2
    dpn = n // pp
    tpc.setup_process_groups([("data", dpn), ("pipe", pp)])
    mesh = tpc.get_view()
    assert CFG.nlayers % pp == 0

    params = init_params(jax.random.PRNGKey(0))
    specs = param_specs()

    def vg_fn(p, batch):
        return pipeline_1f1b(
            p,
            batch,
            batch["text"][..., 0],  # targets unused; labels are positional
            first_fn=first_fn,
            stage_fn=stage_fn,
            last_fn=last_fn,
            num_microbatches=M,
        )

    opt = optax.adam(1e-3)
    dp = DataParallel(mesh=mesh)
    sharded = dp.broadcast_params(params, param_specs=specs)
    state = opt.init(sharded)
    step = dp.make_train_step(
        value_and_grad_fn=vg_fn,
        optimizer=opt,
        param_specs=specs,
        batch_spec={"patches": P(None, "data"), "text": P(None, "data")},
    )

    key = jax.random.PRNGKey(1)
    t0 = time.time()
    for i in range(STEPS):
        key, k1, k2 = jax.random.split(key, 3)
        batch = {
            "patches": jax.random.normal(k1, (M, MBS * dpn, S, PATCH)),
            "text": jax.random.randint(k2, (M, MBS * dpn, S), 0, VOCAB),
        }
        batch = jax.tree.map(
            lambda a: jax.device_put(a, NamedSharding(mesh, P(None, "data"))), batch
        )
        sharded, state, loss = step(sharded, state, batch)
        if i % 5 == 0 or i == STEPS - 1:
            print(f"step {i:3d}  contrastive loss {float(loss):.4f}")
    print(f"done: {STEPS} steps, pp={pp} dp={dpn}, {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
