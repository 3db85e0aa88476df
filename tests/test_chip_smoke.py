"""chip_smoke.py off the chip: it refuses the CPU before compiling anything,
its phases run at toy size on the simulator, and every Pallas kernel an
``auto`` picks on TPU lowers for TPU at the smoke's shapes."""

import importlib
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from torchdistpackage_tpu.models import GPTConfig

REPO = Path(__file__).resolve().parent.parent


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolves the module by name
    spec.loader.exec_module(mod)
    return mod


def test_refuses_the_cpu_before_compiling():
    res = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert res.returncode != 0
    lines = res.stdout.strip().splitlines()
    assert len(lines) == 1 and '"platform": "cpu"' in lines[0], res.stdout
    assert "not a TPU" in res.stderr
    assert '"ok"' not in res.stdout


def test_phases_at_toy_size_on_the_sim(devices8):
    cs = _smoke()
    cfg = cs.SmokeConfig(
        model=GPTConfig(vocab_size=128, dim=32, nheads=4, nlayers=2,
                        max_seq=64, ffn_mult=2, dtype=jnp.float32,
                        attn_impl="flash"),
        mesh=(("data", 4), ("tensor", 2)), batch_per_chip=1, train_steps=3,
        xent_chunk=32, num_slots=4, block_size=8, chunk=16,
        requests=((5, 3), (20, 4), (33, 2)))
    compiles = cs.CompileCounter()

    kernels = cs.kernels_phase(cfg)
    assert set(kernels) >= {"flash_o", "flash_dq", "flash_dk", "flash_dv",
                            "paged_decode", "paged_chunk", "mla_decode",
                            "mla_chunk"}

    serve = cs.serve_phase(cfg, compiles)
    assert serve["attn_impl"] == "gather"  # what 'auto' means off the TPU
    assert serve["generated_tokens"] == 9
    assert serve["geometry"]["mesh"] == {"data": 4, "tensor": 2}

    train = cs.train_phase(cfg, compiles)
    assert train["losses"][-1] < train["losses"][0]
    assert train["global_batch"] == 8
    # the interpreter leaves no Mosaic kernel, and the sim reports no memory:
    # main() requires both, so it cannot pass off the chip
    assert train["mosaic_calls"] == 0 and not train["memory"]["reported"]


# ------------------------------------------------------- TPU lowering


def _lower_for_tpu(monkeypatch, fn, *args):
    """Trace and lower for TPU from the CPU: Pallas' own TPU lowering runs
    (block shapes, ref indexing, memory spaces), Mosaic's compiler does not."""
    for name in ("flash_attention", "paged_attention"):
        mod = importlib.import_module(f"torchdistpackage_tpu.ops.{name}")
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    return text


V5E = "TPU v5 lite"
S = jax.ShapeDtypeStruct


def test_flash_lowers_for_tpu_at_smoke_shapes(monkeypatch):
    from torchdistpackage_tpu.ops.flash_attention import (
        flash_attention, tiles_for)

    bq, bk = tiles_for(V5E)
    q = S((4, 16, 2048, 128), jnp.bfloat16)

    def loss(q, k, v):
        o = flash_attention(q, k, v, block_q=bq, block_k=bk)
        return o.astype(jnp.float32).sum()

    _lower_for_tpu(monkeypatch, jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


@pytest.mark.parametrize("s_in", [1, 256])
@pytest.mark.parametrize("quantized", [False, True])
def test_paged_lowers_for_tpu_at_smoke_shapes(monkeypatch, s_in, quantized):
    from torchdistpackage_tpu.ops.paged_attention import (
        paged_decode_attention, paged_params_for)

    B, H, hd, bs, mb = 8, 16, 128, 128, 16
    pool = S((1 + B * mb, H, bs, hd), jnp.bfloat16)
    if quantized:  # the int8 pool 'auto' also sends to the kernel
        pool = (S(pool.shape, jnp.int8), S(pool.shape[:-1], jnp.float32))
    _lower_for_tpu(
        monkeypatch,
        lambda q, k, v, t, o: paged_decode_attention(
            q, k, v, t, o, **paged_params_for(V5E)),
        S((B, H, s_in, hd), jnp.bfloat16), pool, pool,
        S((B, mb), jnp.int32), S((B,), jnp.int32))


def test_tpu_autos_pick_paths_that_lower(monkeypatch):
    """What each ``auto`` resolves to on a TPU: the paged kernel (covered
    above, both pools) and the sorted MoE dispatch, which is plain jnp."""
    from torchdistpackage_tpu.ops import resolve_attn_impl
    from torchdistpackage_tpu.parallel.moe import resolve_moe_dispatch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_attn_impl("auto") == "pallas"
    assert resolve_moe_dispatch("auto") == "sorted"
