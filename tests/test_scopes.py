"""Scopes inside the compiled programs (utils/profiling.py ``SCOPES``,
``note_program``, ``op_scopes``): every heavy instruction of a toy dense,
MoE and hybrid engine's two programs and of the toy train step is credited
to a name of the closed vocabulary, the table is read from the executable
JAX already holds (no compile request, no device buffer kept), and the
scopes are metadata: the program without them is the same program."""

import contextlib
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from torchdistpackage_tpu.dist import tpc
from torchdistpackage_tpu.models import (
    GPTConfig, HybridConfig, gpt_loss, init_gpt_moe_params, init_gpt_params,
    init_hybrid_params)
from torchdistpackage_tpu.parallel import DataParallel
from torchdistpackage_tpu.serving import Request, ServingEngine
from torchdistpackage_tpu.utils import profiling as prof
from torchdistpackage_tpu.utils import spans

F32 = jnp.float32
TOKEN = re.compile(r"tdp:[\w.]+")
#: a scan's own handling of its stacked operands and results
SCAN_OWN = re.compile(r"while/body/dynamic_update_slice$")
#: the opcodes whose time a component must own
HEAVY = {"dot", "convolution", "custom-call", "scatter", "gather",
         "dynamic-update-slice", "sort"}

DENSE = GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=64,
                  ffn_mult=2, dtype=F32)
MOE = GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=64,
                ffn_mult=2, dtype=F32, moe_experts=4, moe_every=2,
                moe_top_k=2)
_H = dict(vocab_size=64, dim=32, max_seq=64, nheads=4, kv_heads=2, head_dim=8,
          dtype=F32, moe_experts=4, moe_held=(0, 4), moe_top_k=2, moe_ffn=16)
#: the hybrid family's eight kinds of layer between them
HYBRIDS = {
    "M*WCDE": HybridConfig(
        pattern="M*WCDE", mamba_heads=4, mamba_head_dim=8, ssm_state=8,
        ssm_chunk=4, window=8, dense_ffn=32, cca_rope=4, moe_latent=16,
        moe_shared_ffn=16, **_H),
    "LDLE": HybridConfig(
        pattern="LDLE", mla_latent=16, mla_nope=8, mla_rope=4, mla_v=8,
        dense_ffn=32, moe_score="mlp", moe_router_hidden=8, moe_act="swiglu",
        **_H),
    "SE": HybridConfig(
        pattern="SE", idx_heads=2, idx_dim=8, idx_topk=4, idx_rope=4,
        moe_score="softmax", **_H),
}

_requests = [0]
jax.monitoring.register_event_duration_secs_listener(
    lambda name, secs, **kw: _requests.__setitem__(
        0, _requests[0] + (name == "/jax/core/compile/backend_compile_duration")))


def _serve(params, cfg, slots):
    """One request through a fresh engine: the keys of its two programs as
    its dispatch spans name them."""
    spans.clear()
    eng = ServingEngine(params, cfg, num_slots=slots, block_size=8, chunk=8,
                        max_ctx=64)
    eng.submit(Request(tokens=list(range(1, 12)), max_new_tokens=3))
    eng.run_until_idle(max_ticks=30)
    named = {r[2].rpartition(".")[2]: r[5]["program"]
             for r in spans.snapshot()
             if r[2] in ("tdp:engine.prefill", "tdp:engine.decode")}
    assert named["decode"] == f"decode[{slots},1]"
    assert re.fullmatch(r"prefill\[\d+,8\]", named["prefill"])
    return sorted(named.values())


def _train():
    cfg = GPTConfig(vocab_size=64, dim=32, nheads=4, nlayers=2, max_seq=32,
                    ffn_mult=2, dtype=F32)
    tpc.reset()
    tpc.setup_process_groups([("data", 1)], devices=jax.devices()[:1])
    dp = DataParallel(mesh=tpc.get_view())
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-3)
    state = opt.init(params)
    step = dp.make_train_step(
        lambda p, b: gpt_loss(p, b, cfg, remat=True, xent_chunk=8), opt)
    batch = dp.shard_batch({"tokens": jnp.zeros((2, 32), jnp.int32),
                            "targets": jnp.ones((2, 32), jnp.int32)})
    # as benchmarks/runners/train.py makes its step ready
    step.lower(params, state, batch).compile()(params, state, batch)
    return ["train"]


def _programs(name):
    key = jax.random.PRNGKey(0)
    if name == "train":
        return _train()
    if name == "dense":
        return _serve(init_gpt_params(key, DENSE), DENSE, 3)
    if name == "moe":
        return _serve(init_gpt_moe_params(key, MOE), MOE, 5)
    cfg = HYBRIDS[name]
    return _serve(init_hybrid_params(key, cfg), cfg, 2)


@pytest.fixture(scope="module", params=["dense", "moe", *HYBRIDS, "train"])
def ran(request):
    """A toy program made ready and asked for its tables: ``{key: (table,
    compiled text)}``, the compile requests the asks raised, and the
    registry's entries."""
    keys = _programs(request.param)
    before = _requests[0]
    tables = {k: prof.op_scopes(k) for k in keys}
    asked = _requests[0] - before
    texts = {}
    for k in keys:
        jitted, args = prof._programs[k]
        texts[k] = jitted.lower(*args).compile().as_text()
    return {"tables": tables, "texts": texts, "asked": asked,
            "entries": [prof._programs[k] for k in keys]}


def _instructions(text):
    """(name, opcodes, op_name) of every instruction a trace can show: a
    fusion's opcodes are those of the computation it calls."""
    inside, comp = {}, None
    for line in text.splitlines():
        m = prof._INSTRUCTION.match(line)
        if m is None:
            head = prof._COMPUTATION.match(line)
            comp = inside.setdefault(head.group(1), set()) if head else comp
        elif comp is not None:
            comp.add(m.group(2))
    for line in text.splitlines():
        m = prof._INSTRUCTION.match(line)
        if m is None:
            continue
        name, opcode, rest = m.groups()
        calls = prof._APPLIES.search(rest)
        held = inside.get(calls.group(1), set()) if (
            opcode == "fusion" and calls) else set()
        op = prof._OP_NAME.search(rest)
        yield name, {opcode} | held, op.group(1) if op else ""


def test_both_signatures_have_a_table(ran):
    assert ran["tables"] and all(ran["tables"].values())
    for table in ran["tables"].values():
        assert any(TOKEN.search(op) for op in table.values())


def test_every_heavy_instruction_has_an_owner(ran):
    """A GEMM, a convolution, a kernel, a scatter, a gather, an update in
    place or a sort (alone or inside a fusion) is some component's: its
    ``op_name`` carries a ``tdp:`` scope, its own or (what the compiler
    rewrote without a name: some batched dots on the CPU, a ``ragged-dot``
    and the weights' prefetches on the TPU) that of what consumes its
    result.  One thing is nobody's: a ``lax.scan``'s own stacking of its
    results, traced right under the loop's body and consumed by no block
    half."""
    seen = inherited = 0
    for key, text in ran["texts"].items():
        table = ran["tables"][key]
        for name, opcodes, op_name in _instructions(text):
            if name in table and opcodes & HEAVY:
                seen += 1
                inherited += prof.OWNER in table[name]
                assert (TOKEN.search(table[name])
                        or SCAN_OWN.search(table[name])), (key, name, op_name)
                assert table[name].startswith(op_name)
    assert seen >= 8 and inherited < seen


def test_every_token_is_of_the_vocabulary(ran):
    found = {t for table in ran["tables"].values() for op in table.values()
             for t in TOKEN.findall(op)}
    assert found and found <= set(prof.SCOPES)


def test_the_ask_compiles_nothing_and_holds_no_buffer(ran):
    assert ran["asked"] == 0
    for jitted, args in ran["entries"]:
        leaves = jax.tree.leaves(args)
        assert leaves and not any(isinstance(x, jax.Array) for x in leaves)
        assert any(isinstance(x, jax.ShapeDtypeStruct) for x in leaves)


def test_the_table_leaves_out_what_a_fusion_holds(ran):
    for key, text in ran["texts"].items():
        fused = set(re.findall(r"fusion\(.*calls=%?([\w.\-]+)", text))
        inner, comp = set(), None
        for line in text.splitlines():
            head = prof._COMPUTATION.match(line)
            comp = head.group(1) if head else comp
            m = prof._INSTRUCTION.match(line)
            if m is not None and comp in fused:
                inner.add(m.group(1))
        assert fused and inner and not inner & set(ran["tables"][key])


HLO = """
HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %mul.9 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/tdp:ffn/mul"}
}

%region_0.2 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.3 = f32[] add(%a, %b), metadata={op_name="jit(step)/tdp:head/reduce_sum"}
}

%branch_1_fun.4 (arg: (f32[8])) -> f32[8] {
  %arg = (f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element((f32[8]{0}) %arg), index=0
  ROOT %ragged-dot-none.1 = f32[8]{0} custom-call(%gte.1), custom_call_target="RaggedDot", metadata={op_name="ragged-dot-none"}
}

%body.5 (carry: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {
  %carry = (s32[], f32[4,8]{1,0}) parameter(0)
  %w = f32[4,8]{1,0} get-tuple-element(%carry), index=1
  %slice.6 = f32[8]{0} fusion(%w), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/dynamic_slice"}
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%slice.6)
  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
  %fusion.7 = f32[8]{0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/tdp:mixer/dot_general"}
  %stack.8 = f32[4,8]{1,0} fusion(%w, %fusion.7), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/dynamic_update_slice"}
  ROOT %tuple.9 = (s32[], f32[4,8]{1,0}) tuple(%i, %stack.8)
}

ENTRY %main.10 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %while.11 = (s32[], f32[4,8]{1,0}) while(%init), condition=%cond.0, body=%body.5, metadata={op_name="jit(step)/while"}
  %gte.15 = f32[4,8]{1,0} get-tuple-element(%while.11), index=1
  %conditional.12 = f32[8]{0} conditional(%pred, %gte.15, %t1), branch_computations={%branch_0_fun.3, %branch_1_fun.4}, metadata={op_name="jit(step)/tdp:ffn/tdp:ffn/cond"}
  %reduce.13 = f32[] reduce(%conditional.12, %zero), dimensions={0}, to_apply=%region_0.2, metadata={op_name="jit(step)/tdp:head/reduce_sum"}
  ROOT %copy.14 = f32[8]{0} copy(%x)
}
"""


def test_what_the_compiler_made_is_credited_to_what_consumes_it():
    table = prof.parse_op_scopes(HLO)
    own = "jit(step)/while/body/tdp:mixer/dot_general"
    # inside a fusion or a reducer: no entry
    assert not {"mul.9", "p0", "add.3", "a"} & set(table)
    assert table["fusion.7"] == own
    # a scan's slice of its stacked operand and its prefetch: the user's
    assert table["slice.6"] == "jit(step)/while/body/dynamic_slice=>" + own
    assert table["copy-start.1"] == table["copy-done.1"] == "=>" + own
    # the stacking of the results is consumed by nothing with a scope, and
    # the loop that runs the body was traced under none (what consumes the
    # LOOP's result says nothing of its body)
    assert table["stack.8"] == "jit(step)/while/body/dynamic_update_slice"
    assert table["while.11"] == "jit(step)/while" + table["gte.15"]
    assert table["gte.15"] == "=>jit(step)/tdp:ffn/tdp:ffn/cond"
    # a branch's root has no user: the conditional that runs it owns it
    assert table["ragged-dot-none.1"] == (
        "ragged-dot-none=>jit(step)/tdp:ffn/tdp:ffn/cond")
    assert table["reduce.13"] == "jit(step)/tdp:head/reduce_sum"
    assert table["x"] == "x" and table["copy.14"] == ""


def test_a_key_nobody_noted_and_a_host_stub_give_an_empty_table():
    assert prof.op_scopes("decode[no such program]") == {}
    prof.note_program("stub[1,1]", lambda *a: a, (1,))
    assert prof.op_scopes("stub[1,1]") == {}


def _canonical(text):
    """A compiled program's computations and instructions alone (no table
    of source lines), the metadata taken out and every name replaced by its
    order of appearance (a computation and its parameters are named after
    the scope they were traced under)."""
    text = "\n".join(
        line for line in text.splitlines()
        if prof._INSTRUCTION.match(line) or prof._COMPUTATION.match(line))
    text = re.sub(r",? ?(metadata|frontend_attributes)=\{[^}]*\}", "", text)
    text = re.sub(r"[\w.\-]+: ", "", text)
    seen = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: seen.setdefault(m.group(0), f"%n{len(seen)}"),
                  text)


def test_scopes_are_metadata(monkeypatch):
    """The dense engine's two programs, compiled once as they are and once
    with ``jax.named_scope`` a no-op: the same instructions in the same
    order."""
    params = init_gpt_params(jax.random.PRNGKey(0), DENSE)

    def texts():
        out = []
        for key in _serve(params, DENSE, 3):
            jitted, args = prof._programs[key]
            out.append(jitted.lower(*args).compile().as_text())
        return out

    scoped = texts()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = texts()
    assert all("tdp:" in t for t in scoped)
    assert not any("tdp:" in t for t in bare)
    assert [_canonical(t) for t in scoped] == [_canonical(t) for t in bare]
