"""The KV pool is updated where it lies (PR 27).

- **The write** (``paged_write``): the blocks a call's rows fall in are
  read, overlaid and scattered back whole, at ``[layer, blk]`` of the
  stacked pool.  Against a row-by-row numpy write: every block but the NULL
  one bit-equal, every other layer untouched, for decode / verify / chunk
  shapes, aligned and unaligned offsets, an overshoot past the table, the
  int8 pair.
- **The programs**: every compiled program that takes the pool (both
  signatures of the step, the verify step, copy-on-write) aliases it to its
  output (``memory_analysis().alias_size_in_bytes >= pool_bytes``), and the
  step and verify programs' optimised HLO holds no ``copy`` /
  ``dynamic-slice`` / ``dynamic-update-slice`` whose result has the pool's
  or one layer's shape.  (The CPU backend's own copy-on-write program copies
  the pool between its gather and its scatter, and the Pallas interpreter
  brings loops of its own: those two are held to the alias bytes here and
  their HLO is read on the chip, PERF.md section 6, PR 27.)
- **The contract**: after a step the array that was ``eng.cache['k']`` is
  deleted; ``Telemetry.wrap_step`` keeps the donation and does not call
  again with a pool a failed call consumed.

One module-scope engine (GQA + sliding window, ``spec_k`` = 2, prefix
cache) holds every program.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistpackage_tpu.models import init_gpt_params, llama_config
from torchdistpackage_tpu.models.generate import _kv_quant
from torchdistpackage_tpu.obs import Telemetry
from torchdistpackage_tpu.serving import (
    Request,
    ServingEngine,
    gather_kv,
    paged_write,
)
from torchdistpackage_tpu.serving.paged_cache import pool_bytes

# ------------------------------------------------------------------ the write

L, NB, HKV, BS, HD, MB = 3, 12, 2, 4, 8, 4

#: (S_in, offsets of the two slots, int8 pool)
WRITE_CASES = {
    "decode": (1, (5, 14), False),
    "verify-crosses-block": (3, (3, 10), False),
    "chunk-aligned": (8, (0, 8), False),
    "chunk-unaligned": (8, (2, 7), False),
    "overshoot-past-table": (8, (12, 4), False),
    "decode-int8": (1, (6, 9), True),
    "chunk-unaligned-int8": (8, (1, 6), True),
}


def _rowwise(pool, val, offsets, tables, layer):
    """The write as a plain loop: position ``offset[b] + s`` goes to row
    ``pos % bs`` of the block the table names; one past the table's width
    goes nowhere (to the NULL block, which nobody reads)."""
    pool = [np.array(a) for a in pool]
    val = [np.asarray(a) for a in val]
    for b, off in enumerate(offsets):
        for s in range(val[0].shape[2]):
            col, row = divmod(off + s, BS)
            if col >= MB:
                continue
            for a, v in zip(pool, val):
                a[layer, tables[b, col], :, row] = v[b, :, s]
    return pool


@pytest.mark.parametrize("case", sorted(WRITE_CASES))
def test_paged_write_blocks_match_rowwise(case):
    s_in, offsets, int8 = WRITE_CASES[case]
    rs = np.random.RandomState(3)
    tables = rs.permutation(np.arange(1, NB))[:2 * MB].reshape(2, MB)
    val = jnp.asarray(rs.standard_normal((2, HKV, s_in, HD)), jnp.float32)
    if int8:
        pool = (jnp.asarray(rs.randint(-127, 128, (L, NB, HKV, BS, HD)),
                            jnp.int8),
                jnp.asarray(rs.uniform(1e-3, 1, (L, NB, HKV, BS)),
                            jnp.float32))
        vals = _kv_quant(val)
    else:
        pool = jnp.asarray(rs.standard_normal((L, NB, HKV, BS, HD)),
                           jnp.float32)
        vals = (val,)
    write = jax.jit(lambda c, li: paged_write(
        c, val, jnp.asarray(offsets), tables=jnp.asarray(tables), layer=li))
    def same(got, want):
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            g, w = np.asarray(g), np.asarray(w)
            if int8 and w.dtype == np.float32:
                # a scale computed inside another program than its
                # reference: the last bit of amax / 127 is the compiler's
                np.testing.assert_allclose(g, w, rtol=1e-6)
            else:
                np.testing.assert_array_equal(g, w)

    for layer in (0, 2):
        got = write(pool, jnp.int32(layer))
        want = _rowwise(jax.tree.leaves(pool), vals, offsets, tables, layer)
        same([g[:, 1:] for g in jax.tree.leaves(got)],
             [w[:, 1:] for w in want])
        # one layer's pool (layer=None) is the one-layer stack
        one = lambda c: jax.tree.map(lambda a: a[layer], c)
        alone = paged_write(one(pool), val, jnp.asarray(offsets),
                            tables=jnp.asarray(tables))
        same(jax.tree.map(lambda a: a[1:], one(got)),
             jax.tree.map(lambda a: a[1:], alone))
        # and the oracle's gather reaches the layer by index
        same(gather_kv(got, jnp.asarray(tables), layer),
             gather_kv(one(got), jnp.asarray(tables)))


# --------------------------------------------------------------- the programs

CFG = llama_config(vocab_size=64, dim=32, nheads=4, nlayers=3, max_seq=48,
                   kv_heads=2, ffn_hidden=48, dtype=jnp.float32,
                   sliding_window=6)
SLOTS, K = 3, 2


@pytest.fixture(scope="module")
def bundle():
    params = init_gpt_params(jax.random.PRNGKey(0), CFG)
    eng = ServingEngine(params, CFG, num_slots=SLOTS, block_size=4, chunk=8,
                        spec_k=K, prefix_cache=True, attn_impl="gather")
    mb, W, C = eng.max_blocks, eng.prefill_width, eng.chunk
    i32 = lambda *s: jnp.zeros(s, jnp.int32)

    def rows(n):
        return ({"temperature": jnp.zeros((n,), jnp.float32),
                 "top_k": jnp.full((n,), CFG.vocab_size, jnp.int32),
                 "top_p": jnp.ones((n,), jnp.float32)},
                jnp.zeros((n, 2), jnp.uint32))

    def step_args(n, s):
        return (eng.params, eng.cache, i32(n, s), i32(n, mb), i32(n), i32(n),
                *rows(n))

    programs = {
        "step-decode": (eng._step_fn, step_args(SLOTS, 1)),
        "step-prefill": (eng._step_fn, step_args(W, C)),
        "verify": (eng.device_step.verify_fn(),
                   (eng.params, eng.cache, i32(SLOTS, K + 1), i32(SLOTS, mb),
                    i32(SLOTS), *rows(SLOTS))),
        "cow": (eng._cow_fn, (eng.cache, i32(SLOTS), i32(SLOTS))),
    }
    return {"eng": eng, "programs": programs}


def _pool_shapes(cache):
    """HLO spellings of the pool's and of one layer's shape, every leaf."""
    out = set()
    for leaf in jax.tree.leaves(cache):
        dt = {"float32": "f32", "bfloat16": "bf16", "int8": "s8"}[
            str(leaf.dtype)]
        dims = [str(d) for d in leaf.shape]
        for shape in (dims, dims[1:], ["1"] + dims[1:]):
            out.add(f"{dt}[{','.join(shape)}]")
    return out


@pytest.mark.parametrize("program",
                         ["step-decode", "step-prefill", "verify", "cow"])
def test_program_aliases_pool_and_never_copies_it(bundle, program):
    fn, args = bundle["programs"][program]
    eng = bundle["eng"]
    compiled = fn.lower(*args).compile()
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= pool_bytes(eng.cache))
    if program == "cow":
        return  # the CPU backend's own gather-then-scatter copy: docstring
    shapes = _pool_shapes(eng.cache)
    bad = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+\[[\d,]*\])\S* "
                     r"(copy|dynamic-slice|dynamic-update-slice)\(", line)
        if m and m.group(2) in shapes:
            bad.append(m.group(0))
    assert not bad, bad


# --------------------------------------------------------------- the contract


def test_step_consumes_the_pool_it_was_given(bundle):
    """Prefill calls, verify calls and a copy-on-write each leave the
    array that WAS the pool deleted: it went in donated, the engine holds
    what came back."""
    eng = bundle["eng"]
    prompt = list(range(1, 9))  # two full blocks: asked again, the whole
    #                             prompt is resident and its last block is
    #                             copied on write
    seen = set()

    def serve():
        rid = eng.submit(Request(prompt, 4))
        while eng.queue or eng.n_busy:
            before = eng.cache["k"]
            eng.step()
            rec = eng.tick_records[-1]
            seen.update(k for k in ("prefill_slots", "decode_slots")
                        if rec[k])
            assert before.is_deleted() and not eng.cache["k"].is_deleted()
        return eng.finished[rid]["tokens"]

    first, again = serve(), serve()
    assert seen == {"prefill_slots", "decode_slots"}
    assert eng.serving_summary()["prefix_cache"]["cow_copies"] >= 1
    np.testing.assert_array_equal(first, again)


def test_wrap_step_keeps_donation_and_never_recalls_consumed_args():
    step = jax.jit(lambda pool, x: (pool + x, x * 2), donate_argnums=(0,))
    tel = Telemetry()
    wrapped = tel.wrap_step(step)
    pool = jnp.zeros((4, 4))
    out, _ = wrapped(pool, jnp.ones((4, 4)))
    assert pool.is_deleted()  # the AOT executable kept the donation
    # an executable that fails AFTER it consumed its donated argument: the
    # wrapper must not fall back to a second call with the dead pool
    calls = []

    class Consuming:
        def __call__(self, pool, x):
            calls.append("aot")
            pool.delete()
            raise RuntimeError("device fault mid-call")

    for entry in tel._compiled.values():
        entry["compiled"] = Consuming()
    with pytest.raises(RuntimeError, match="device fault"):
        wrapped(out, jnp.ones((4, 4)))
    assert calls == ["aot"] and tel._aot_ok
    # one that rejects the call before touching anything still falls back

    class Rejecting:
        def __call__(self, pool, x):
            raise TypeError("sharding mismatch")

    for entry in tel._compiled.values():
        entry["compiled"] = Rejecting()
    fresh = jnp.zeros((4, 4))
    got, _ = wrapped(fresh, jnp.ones((4, 4)))
    np.testing.assert_array_equal(np.asarray(got), np.ones((4, 4)))
    assert not tel._aot_ok
