"""The arithmetic behind the fifteen metrics of PR 51
(benchmarks/layer_metrics/scope_ms.py and the metric files that call it):
device time inside the compiled programs by the innermost ``tdp:`` scope of
each operation's compiled ``op_name``, on synthetic event lists and a fake
table."""

import glob
import itertools
import os

import pytest

from benchmarks import harness
from benchmarks.layer_metrics import scope_ms as S
from torchdistpackage_tpu.utils import profiling as prof
from torchdistpackage_tpu.utils.profiling import spans

MS = 1e-3
T = "tdp:engine."
#: the metrics whose own reader calls this module
NEW = sorted(os.path.basename(p)[:-3] for p in glob.glob(os.path.join(
    os.path.dirname(S.__file__), "*.py")) if "scope_ms." in open(p).read())
BATCH = [m for m in NEW if m.endswith(".batch")]
TRAIN = [m for m in NEW if m.endswith(".train")]

#: a decode program's table and a prefill program's: instruction -> op_name
DECODE = {
    "fusion.1": "jit(step)/tdp:embed/gather",
    "fusion.2": "jit(step)/while/body/tdp:mixer/dot_general",
    "scatter.3": "jit(step)/while/body/tdp:mixer/tdp:mixer.kv_write/scatter",
    "paged_decode.4": "jit(step)/tdp:mixer/tdp:mixer.attend/pallas_call",
    "fusion.5": "jit(step)/tdp:ffn/tdp:ffn/tdp:ffn.route/top_k",
    "fusion.6": "jit(step)/tdp:ffn/tdp:ffn/tdp:ffn.experts/dot_general",
    "fusion.7": "jit(step)/tdp:ffn/tdp:ffn/tdp:ffn.combine/scatter-add",
    "fusion.8": "jit(step)/tdp:head/dot_general",
    "sort.9": "jit(step)/tdp:sample/cond/branch_1_fun/sort",
    "copy.10": "",
    "while.11": "jit(step)/while",
}
#: ms of each, one execution
DECODE_MS = {"fusion.1": .1, "fusion.2": 2., "scatter.3": .5,
             "paged_decode.4": 1.5, "fusion.5": .2, "fusion.6": 3.,
             "fusion.7": .3, "fusion.8": 1., "sort.9": .25, "copy.10": .15}
PREFILL = {
    "fusion.1": "jit(step)/tdp:state/gather",
    "fusion.2": "jit(step)/tdp:mixer/tdp:mixer/tdp:mixer.scan/while/body/dot",
    "fusion.3": "jit(step)/tdp:ffn/tdp:ffn/tdp:ffn.dispatch/sort",
    "fusion.4": "jit(step)/tdp:ffn/dot_general",
    "fusion.5": "jit(step)/tdp:state/scatter",
}
PREFILL_MS = {"fusion.1": .4, "fusion.2": 6., "fusion.3": .7, "fusion.4": 2.,
              "fusion.5": .6}
#: a train step's table: forward, recomputed and backward operations
STEP = {
    "f.1": "jit(s)/jvp()/checkpoint/tdp:ffn/dot_general",
    "f.2": "jit(s)/transpose(jvp())/checkpoint/rematted_computation/"
           "tdp:ffn/dot_general",
    "f.3": "jit(s)/transpose(jvp())/checkpoint/tdp:ffn/dot_general",
    "f.4": "jit(s)/transpose(jvp())/tdp:loss/checkpoint/"
           "rematted_computation/tdp:loss/dot_general",
    "f.5": "jit(s)/tdp:optimizer/mul",
    "f.6": "jit(s)/jvp()/while/body/dynamic_slice",
}
STEP_MS = {"f.1": 1., "f.2": 1.1, "f.3": 2., "f.4": .5, "f.5": .3, "f.6": .2}
WANT = {   # what the ten serving metrics read of two decodes and one prefill
    "decode_mixer_ms.batch": 4.0, "decode_kv_write_ms.batch": .5,
    "decode_ffn_ms.batch": 3.5, "decode_moe_glue_ms.batch": .5,
    "decode_head_ms.batch": 1.25, "prefill_mixer_ms.batch": 6.0,
    "prefill_kv_write_ms.batch": 1.0, "prefill_ffn_ms.batch": 2.7,
    "prefill_moe_glue_ms.batch": .7,
    "unscoped_share.batch": 100 * (2 * .15) / (2 * 9.0 + 9.7),
}


def _ops(table_ms, at, wrapper=None):
    """One execution's operations back to back from ``at`` (seconds), as
    the profiler names them; ``wrapper``: a ``while`` over all of them."""
    out, t = [], at
    for name, ms in table_ms.items():
        out.append((f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p)", t, ms * MS))
        t += ms * MS
    if wrapper:
        out.append((f"%{wrapper} = (f32[8]{{0}}) while((f32[8]{{0}}) %t)", at,
                    t - at))
    return out


# ---------------------------------------------------------- the arithmetic


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step)/tdp:mixer/tdp:mixer.attend/pallas_call", "tdp:mixer.attend"),
    ("jit(step)/while/body/closed_call/tdp:ffn/dot_general", "tdp:ffn"),
    ("jit(f)/tdp:ffn/tdp:ffn/tdp:ffn.experts/cond/branch_0_fun/dot",
     "tdp:ffn.experts"),
    ("jit(s)/transpose(jvp(tdp:mixer))/tdp:mixer.attend/mul",
     "tdp:mixer.attend"),
    ("jit(s)/transpose(jvp())/checkpoint/rematted_computation/tdp:ffn/dot",
     "tdp:ffn"),
    ("jit(step)/while/body/dynamic_slice", ""),
    ("", ""),
    # what the compiler made, credited to what consumes it (op_scopes)
    ("=>jit(step)/tdp:ffn/tdp:ffn.experts/dot_general", "tdp:ffn.experts"),
    ("jit(step)/while/body/dynamic_slice=>jit(step)/while/body/tdp:mixer/dot",
     "tdp:mixer"),
    ("ragged-dot-none=>jit(step)/tdp:ffn/tdp:ffn/cond", "tdp:ffn"),
])
def test_an_operation_belongs_to_its_innermost_scope(op_name, scope):
    assert S.scope_of(op_name) == scope


@pytest.mark.parametrize("scope, prefixes, held", [
    ("tdp:mixer.attend", ["tdp:mixer"], True),
    ("tdp:mixer", ["tdp:mixer"], True),
    ("tdp:mixer", ["tdp:mixer.kv_write", "tdp:state"], False),
    ("tdp:state", ["tdp:mixer.kv_write", "tdp:state"], True),
    ("tdp:ffn.experts", ["tdp:ffn.route", "tdp:ffn.combine"], False),
    ("tdp:ffnx", ["tdp:ffn"], False),
    ("", ["tdp:ffn"], False),
])
def test_a_prefix_holds_its_own_name_and_what_lies_under_it(
        scope, prefixes, held):
    assert S.under(scope, prefixes) is held


def test_the_instruction_of_an_event_is_its_name_without_the_line():
    assert S.instruction(
        "%fusion.237 = bf16[64,1,2048]{2,1,0} fusion(bf16[64]{0} %p)") \
        == "fusion.237"
    assert S.instruction("paged_decode.4") == "paged_decode.4"


def _rows():
    runs = [("decode", "decode[4,1]", _ops(DECODE_MS, 1.0, "while.11")),
            ("decode", "decode[4,1]", _ops(DECODE_MS, 1.1)),
            ("prefill", "prefill[1,8]", _ops(PREFILL_MS, 1.2))]
    return S.by_kind(runs, {"decode[4,1]": DECODE, "prefill[1,8]": PREFILL})


def test_a_metric_is_the_mean_over_the_executions_of_its_kind():
    rows = _rows()
    assert (rows["decode"]["n"], rows["prefill"]["n"]) == (2, 1)
    # the while is its body's time, not more
    assert rows["decode"]["total"] == pytest.approx(2 * 9.0 * MS)
    assert S.read_ms(rows, "decode", ["tdp:mixer"]) == pytest.approx(4.0)
    assert S.read_ms(rows, "prefill", ["tdp:mixer"]) == pytest.approx(6.0)
    assert S.read_ms(rows, "decode", ["tdp:embed"]) == pytest.approx(.1)
    assert S.read_ms(rows, "train", ["tdp:mixer"]) is None
    # the components and what no scope holds are the whole execution
    parts = sum(S.read_ms(rows, "decode", [p]) for p in (
        "tdp:embed", "tdp:mixer", "tdp:state", "tdp:ffn", "tdp:head",
        "tdp:sample"))
    bare = S.unscoped_percent(rows, ["decode"]) / 100 * 9.0
    assert parts + bare == pytest.approx(S.read_ms(rows, "decode"))
    assert S.read_ms(rows, "decode") == pytest.approx(9.0)


def test_direction_and_recompute_are_tokens_of_the_same_name():
    ms = {k: v for k, v in STEP_MS.items() if k != "f.6"}
    rows = S.by_kind([("train", "train", _ops(ms, 0.0))] * 2,
                     {"train": STEP})
    assert S.read_ms(rows, "train", ["tdp:ffn"]) == pytest.approx(4.1)
    assert S.read_ms(rows, "train", None, "rematted_computation") \
        == pytest.approx(1.6)
    assert S.read_ms(rows, "train", ["tdp:ffn"], r"transpose\(") \
        == pytest.approx(3.1)
    assert S.read_ms(rows, "train", ["tdp:loss", "tdp:head"]) \
        == pytest.approx(.5)
    assert S.unscoped_percent(rows, ["train"]) == pytest.approx(0.0)


def test_a_table_of_another_program_gives_no_number():
    runs = [("decode", "decode[4,1]", _ops(DECODE_MS, 1.0))]
    rows = S.by_kind(runs, {"decode[4,1]": PREFILL})   # names 5 of 10
    assert S.read_ms(rows, "decode", ["tdp:mixer"]) is None
    assert S.unscoped_percent(rows, ["decode"]) is None
    # under a hundredth of the time unnamed: still read, and it is nobody's
    near = dict(DECODE)
    del near["fusion.1"]    # 0.1 of 9.0 ms: over a hundredth
    assert S.read_ms(S.by_kind(runs, {"decode[4,1]": near}), "decode") is None
    slow = dict(DECODE_MS, **{"fusion.6": 30.})   # 0.1 of 36 ms
    rows = S.by_kind([("decode", "decode[4,1]", _ops(slow, 1.0))],
                     {"decode[4,1]": near})
    assert S.read_ms(rows, "decode", ["tdp:ffn"]) == pytest.approx(30.5)
    assert S.unscoped_percent(rows, ["decode"]) == pytest.approx(
        100 * (.15 + .1) / 36.0)
    # a program nobody noted
    assert S.read_ms(S.by_kind(runs, {}), "decode") is None


def test_operations_go_to_the_execution_they_start_in():
    runs = [(2.0, 2.5), (1.0, 1.5), (3.0, 3.5)]
    ops = [("a", 1.0, .1), ("b", 1.49, .1), ("c", 1.5, .1), ("d", 2.2, .1),
           ("e", 3.4, .3), ("f", 0.9, .2)]
    assert [[e[0] for e in evs] for evs in S.split(ops, runs)] == [
        ["d"], ["a", "b"], ["e"]]


# ------------------------------------------------- spans and executions


def _tick(b, call, prefill_calls=0, program=True):
    """One tick that opens at ``b`` seconds: ``prefill_calls`` prefill calls
    in one dispatch span, then the decode call, a fetch for each; the
    executions they made and their operations."""
    def at(name, s, e, **attrs):
        return (name, b + s * MS, b + e * MS, attrs)

    def named(key):
        return {"program": key} if program else {}

    host = [at(T + "tick", 0, 40), at(T + "build", 0, 1)]
    runs, ops, t = [], [], 2.0
    if prefill_calls:
        host.append(at(T + "prefill", 1, 1.5, call=call + prefill_calls - 1,
                       calls=prefill_calls, **named("prefill[1,8]")))
        for _ in range(prefill_calls):
            runs.append(("jit_step", b + t * MS, 9.8 * MS))
            ops += _ops(PREFILL_MS, b + t * MS)
            t += 10
        call += prefill_calls
    host.append(at(T + "decode", 1.5, 2, call=call, **named("decode[4,1]")))
    runs.append(("jit_step", b + t * MS, 9.2 * MS))
    ops += _ops(DECODE_MS, b + t * MS, "while.11")
    if prefill_calls:
        host.append(at(T + "fetch", 2, t + 0.5, call=call - 1))
    host.append(at(T + "fetch", t + 0.5, t + 10, call=call))
    return host, runs, ops


def _two_ticks(**kw):
    """A tick of one prefill call and the decode call, then a decode-only
    tick: two decode executions and one prefill execution."""
    h1, r1, o1 = _tick(1.0, 7, prefill_calls=1, **kw)
    h2, r2, o2 = _tick(1.05, 9, **kw)
    return h1 + h2, r1 + r2, o1 + o2


def test_an_execution_is_the_kind_and_program_of_its_call():
    host, runs, ops = _two_ticks()
    got = S.matched_executions(runs, ops, host)
    assert [(k, p, len(evs)) for k, p, evs in got] == [
        ("prefill", "prefill[1,8]", 5), ("decode", "decode[4,1]", 11),
        ("decode", "decode[4,1]", 11)]
    # a span of k calls covers k executions
    host, runs, ops = _tick(1.0, 7, prefill_calls=3)
    got = S.matched_executions(runs, ops, host)
    assert [k for k, _, _ in got] == ["prefill"] * 3 + ["decode"]


def test_no_program_attr_or_a_broken_order_gives_nothing():
    host, runs, ops = _two_ticks(program=False)   # the parent's spans
    assert S.matched_executions(runs, ops, host) is None
    host, runs, ops = _two_ticks()
    # the second tick's spans 30 ms early: its execution would start before
    # its dispatch span opens
    early = [(n, s - 30 * MS, e - 30 * MS, a) if s >= 1.05 else (n, s, e, a)
             for n, s, e, a in host]
    assert S.matched_executions(runs, ops, early) is None
    # more executions than calls
    assert S.matched_executions(runs + [("jit_cow", 1.2, MS)], ops,
                                host) is None


# ------------------------------------------------ through the metric files


def _ring_of(host, t_ring0=500.0):
    ids = itertools.count(1)
    out = []
    for t in [h for h in host if h[0] == T + "tick"]:
        tid = next(ids)
        out += [(next(ids), tid, n, s + t_ring0, e + t_ring0, a)
                for n, s, e, a in host
                if n != T + "tick" and t[1] <= s and e <= t[2]]
        out.append((tid, None, t[0], t[1] + t_ring0, t[2] + t_ring0, t[3]))
    return out


@pytest.fixture
def traced_run(monkeypatch):
    """Two ticks in the ring on ``perf_counter``, anchored to a wall clock,
    a trace whose clock started 0.75 s before the first tick, and the two
    programs' tables in the registry: what a traced run of a serving cell
    hands the readers."""
    host, runs, ops = _two_ticks()
    wall0 = 1_790_000_000 * 10**9
    spans.clear()
    kept = list(spans.anchors)
    spans.anchors.clear()
    spans.anchors.extend([(400.0, wall0 - 100 * 10**9),
                          (600.0, wall0 + 100 * 10**9)])
    spans.extend(_ring_of(host))
    monkeypatch.setattr(prof, "_op_scopes", {"decode[4,1]": DECODE,
                                             "prefill[1,8]": PREFILL})
    monkeypatch.setattr(S, "_last", (None, None))
    started = 0.25
    yield {"spans": {"engine_step": [0.0] * 2}, "values": {}, "costs": {},
           "peaks": {},
           "trace": {"window_s": 0.1, "busy_s": 0.03,
                     "events": {"/device:TPU:0": [
                         (n, s - started, d) for n, s, d in ops]},
                     "modules": [(n, s - started, d) for n, s, d in runs]}}
    spans.clear()
    spans.anchors.clear()
    spans.anchors.extend(kept)


@pytest.mark.parametrize("name", BATCH)
def test_a_traced_serving_run_reads_the_ten(traced_run, name):
    assert len(BATCH) == 10 and set(BATCH) == set(WANT)
    assert harness.read_layer_metric(name, traced_run) == pytest.approx(
        WANT[name], rel=1e-6)


@pytest.mark.parametrize("name", BATCH)
def test_nothing_to_read_leaves_a_serving_metric_out(traced_run, monkeypatch,
                                                     name):
    # an untraced run
    assert harness.read_layer_metric(
        name, {**traced_run, "trace": None}) is None
    # a parent: spans without ``program``
    recs = spans.snapshot()
    spans.clear()
    spans.extend([r[:5] + ({k: v for k, v in r[5].items()
                            if k != "program"},) for r in recs])
    assert harness.read_layer_metric(name, dict(traced_run)) is None
    spans.clear()
    spans.extend(recs)
    assert harness.read_layer_metric(name, dict(traced_run)) is not None
    # a parent: no registry in the package
    monkeypatch.delattr(prof, "op_scopes")
    assert harness.read_layer_metric(name, dict(traced_run)) is None


@pytest.mark.parametrize("name, want", [
    ("mlp_ms.train", 4.1), ("loss_head_ms.train", .5),
    ("optimizer_ms.train", .3), ("recompute_ms.train", 1.6),
    ("unscoped_share.train", 100 * .2 / 5.1),
])
def test_a_traced_train_run_reads_the_five(monkeypatch, name, want):
    assert set(TRAIN) == {
        "mlp_ms.train", "loss_head_ms.train", "optimizer_ms.train",
        "recompute_ms.train", "unscoped_share.train"}
    ms = STEP_MS
    monkeypatch.setattr(prof, "_op_scopes", {"train": STEP})
    monkeypatch.setattr(S, "_last", (None, None))
    steps = [("jit_step", 1.0 + .01 * k, 6 * MS) for k in range(3)]
    obs = {"spans": {"step": [.01] * 3}, "values": {}, "costs": {},
           "peaks": {},
           "trace": {"window_s": .05, "busy_s": .02,
                     "events": {"/device:TPU:0": [
                         e for _, s, _ in steps for e in _ops(ms, s)]
                         + _ops({"f.9": 1.}, 1.04)},
                     "modules": steps + [("jit_other", 1.04, 2 * MS)]}}
    assert harness.read_layer_metric(name, obs) == pytest.approx(want)
    assert harness.read_layer_metric(name, {**obs, "trace": None}) is None
