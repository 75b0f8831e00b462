"""``benchmark/trace_scopes.py``: the walk over a trace's own record of what
was compiled (on the two traces recorded on the v5e and on hand-built
bytes), the join to device events, and the eleven readers over it."""

import itertools
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from benchmark import harness, reduce_trace, trace_scopes

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(HERE, "fixture.xplane.pb")
FIXTURE_SCOPES = os.path.join(HERE, "fixture_scopes.xplane.pb")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
STEP_READERS = ["step_forward_share", "step_backward_share", "step_update_share", "step_scoped_share",
                "step_head_loss_share", "moe_row_movement_share_of_step"]
DECODE_READERS = ["decode_step_scoped_share", "decode_step_attend_share", "decode_step_state_share",
                  "decode_step_moe_share", "decode_step_head_sample_share"]


# -- protobuf by hand: what the walk has to read ---------------------------------

def vint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return vint(number << 3) + vint(value)
    value = value.encode() if isinstance(value, str) else value
    return vint(number << 3 | 2) + vint(len(value)) + value


_IDS = itertools.count(1000)


def instruction(name, opcode, op_name="", called=(), packed=True, own_id=None, operands=()):
    body = field(1, name) + field(2, opcode) + field(3, b"\x08\x01")  # a shape the walk passes over
    if op_name:
        body += field(7, field(1, "op_type") + field(2, op_name) + field(4, 12))
    if called and packed:
        body += field(38, b"".join(vint(c) for c in called))
    else:
        body += b"".join(field(38, c) for c in called)
    if operands:
        body += field(36, b"".join(vint(o) for o in operands))
    own_id = next(_IDS) if own_id is None else own_id
    return body + field(35, own_id) + vint(9 << 3 | 1) + b"\0" * 8  # and a fixed64 to skip


def hlo_proto(computations) -> bytes:
    """[(id, [instruction bytes])] -> a serialized HloProto."""
    module = field(1, "jit_step")
    for comp_id, instructions in computations:
        body = b"".join(field(2, i) for i in instructions)
        module += field(3, field(1, f"comp.{comp_id}") + body + field(5, comp_id))
    return field(1, module)


def xspace(programs: dict) -> bytes:
    """An XSpace whose ``/host:metadata`` plane holds the programs, between
    two planes the walk has to pass over."""
    entries = b""
    for n, (name, proto) in enumerate(programs.items()):
        meta = field(1, n + 1) + field(2, name) + field(5, field(1, 1) + field(6, proto))
        entries += field(4, field(1, n + 1) + field(2, meta))
    return (field(1, field(2, "/device:TPU:0") + field(4, field(1, 1) + field(2, field(2, "not here"))))
            + field(1, field(1, 3) + field(2, trace_scopes.METADATA_PLANE) + entries)
            + field(1, field(2, "/host:CPU")))


PROGRAM = "jit_step(123)"
HELD = {PROGRAM: trace_scopes.hlo_instructions(hlo_proto([
    (1, [instruction("tanh.1", "tanh", "jit(step)/jvp(M)/fam.attend/tanh"),
         instruction("mul.1", "multiply", "jit(step)/jvp(M)/fam.head/mul"),
         instruction("p.1", "parameter", "x")]),
    (2, [instruction("add.9", "add", "jit(step)/jvp(M)/fam.attend/add")]),
    (3, [instruction("copy.4", "copy"),
         instruction("gmm", "custom-call", "jit(step)/jvp(M)/moe.experts/gmm"),
         instruction("gmm.3", "custom-call", "jit(step)/transpose(jvp(M))/moe.experts/gmm"),
         instruction("while.2", "while", "jit(step)/jvp(M)/fam.attend/while", called=(2,)),
         instruction("inner.5", "dot", "jit(step)/jvp(M)/fam.attend/while/body/dot_general"),
         instruction("fusion.7", "fusion", "jit(step)/jvp(M)/fam.attend/tanh", called=(1,)),
         instruction("fusion.8", "fusion", "jit(step)/jvp(M)/fam.attend/add", called=(2,), packed=False),
         instruction("sub.2", "subtract", "jit(step)/train.optimizer/sub"),
         instruction("dot.6", "dot", "jit(step)/dot_general"),
         instruction("mixed.1", "fusion", "jit(step)/jvp(M)/fam.attend/add;jit(step)/jvp(M)/fam.head/mul")]),
]))}


def trace_of(events, executions=((0, 1000, PROGRAM),)):
    """A ``reduce_trace.Trace`` of one device: ``events`` [(start, end, text)]
    in ns, inside the given program executions."""
    return reduce_trace.Trace({"/device:TPU:0": {"ops": list(events), "modules": list(executions)}}, {})


def rows_of(tab):
    return {(r["pass"], r["scope"]): round(r["self_s"] * 1e9) for r in tab["rows"]}


# -- the walk ---------------------------------------------------------------------

def test_the_walk_finds_the_recorded_program_under_its_modules_name():
    """The trace recorded on the v5e: one program, named as its ``XLA
    Modules`` events are, its fusion under the product's ``op_name`` and the
    instructions of the computation it calls under their own."""
    held = trace_scopes.programs(FIXTURE)
    trace = reduce_trace.load(None, FIXTURE)
    names = {n for _, _, n in trace.devices[trace.first_plane()]["modules"]}
    assert set(held) == names == {"jit__lambda(8911407554505906894)"}
    instructions = held["jit__lambda(8911407554505906894)"]
    opcode, op_name, inside, _ = instructions["fusion"]
    assert (opcode, op_name) == ("fusion", "jit(<lambda>)/dot_general")
    assert inside == ["jit(<lambda>)/dot_general", "jit(<lambda>)/reduce_sum", "jit(<lambda>)/tanh"]
    assert instructions["tanh.3"][:2] == ("tanh", "jit(<lambda>)/tanh")
    assert instructions["reduce.1"][:2] == ("reduce", "jit(<lambda>)/reduce_sum")
    # a transfer the compiler scheduled has no name: its data goes to the product's fusion
    assert instructions["copy-start"] == ("copy-start", "", [], "jit(<lambda>)/dot_general")
    assert trace_scopes.programs(FIXTURE, wanted={"jit_other(1)"}) == {}


def test_the_walk_reads_hand_built_bytes(tmp_path):
    """An instruction with no metadata, called computations packed and not,
    a parameter's argument name left out of what a fusion holds, planes
    without programs passed over, only the wanted programs decoded."""
    path = tmp_path / "t.xplane.pb"
    proto = hlo_proto([(1, [instruction("p.1", "parameter", "x"), instruction("tanh.1", "tanh", "a/fam.attend/tanh")]),
                       (2, [instruction("copy.4", "copy"), instruction("f.1", "fusion", "a/fam.attend/tanh", (1,)),
                            instruction("f.2", "fusion", "a/fam.head/mul", (1, 1), packed=False)])])
    path.write_bytes(xspace({"jit_a(1)": proto, "jit_b(2)": proto}))
    held = trace_scopes.programs(str(path))
    assert set(held) == {"jit_a(1)", "jit_b(2)"}
    assert held["jit_a(1)"] == {
        "p.1": ("parameter", "x", [], ""), "tanh.1": ("tanh", "a/fam.attend/tanh", [], ""),
        "copy.4": ("copy", "", [], ""), "f.1": ("fusion", "a/fam.attend/tanh", ["a/fam.attend/tanh"], ""),
        "f.2": ("fusion", "a/fam.head/mul", ["a/fam.attend/tanh", "a/fam.attend/tanh"], "")}
    assert set(trace_scopes.programs(str(path), wanted={"jit_b(2)"})) == {"jit_b(2)"}
    with pytest.raises(ValueError, match="wire type"):
        list(trace_scopes._fields(memoryview(b"\x0b")))


@pytest.mark.parametrize("op_name, passed, path", [
    ("jit(step)/jvp(M)/fam.attend/Dense_0/dot_general", "forward", "M/fam.attend/Dense_0"),
    ("jit(step)/transpose(jvp(M))/fam.head/Dense_1/dot_general", "backward", "M/fam.head/Dense_1"),
    ("jit(step)/transpose(jvp(GPT))/jvp(GPT)/checkpoint/rematted_computation/block_7/Mlp_0/mul", "backward",
     "GPT/block_7/Mlp_0"),
    ("jit(step)/transpose(jvp(LM))/mla.mtp/mtp_0/jvp(LM)/mla.mtp/mtp_0/checkpoint/rematted_computation/block/"
     "moe.experts/mul", "backward", "LM/mla.mtp/mtp_0/moe.experts"),
    ("jit(step)/train.optimizer/sub", "update", "train.optimizer"),
    ("jit(step)/jvp(train.loss)/div", "forward", "train.loss"),
    ("jit(step)/transpose(jvp(train.loss))/mul", "backward", "train.loss"),
    ("jit(step)/while/body/train.accumulate/add", "update", "train.accumulate"),
    ("jit(step)/train.grad_norm/sqrt", "update", "train.grad_norm"),
    ("jit(chunk)/while/body/closed_call/GPT/block_0/CausalSelfAttention_0/serve.cache_write/scatter", "forward",
     "GPT/block_0/CausalSelfAttention_0/serve.cache_write"),
    ("jit(step)/jvp(GPT)/gpt.head/ln_f/transpose", "forward", "GPT/gpt.head"),
    ("jit(step)/dot_general", "forward", ""),
])
def test_pass_and_scope_path_from_an_op_names_form(op_name, passed, path):
    got = trace_scopes.scope_path(op_name)
    assert "/".join(got) == path and trace_scopes.pass_of(op_name, got) == passed


# -- the join ---------------------------------------------------------------------

def test_self_time_under_a_while_that_spans_its_body():
    """``while.2`` runs 0-600 and holds two body operations and a nested
    fusion with a child of its own; each level keeps what its children leave."""
    trace = trace_of([
        (0, 600, "%while.2 = (s32[]) while(...)"),
        (100, 200, "%inner.5 = f32[8] dot(...)"),
        (300, 500, "%fusion.7 = f32[8] fusion(...), calls=%comp.1"),
        (350, 400, "%tanh.1 = f32[8] tanh(...)"),
        (700, 800, "%sub.2 = f32[8] subtract(...)"),
    ])
    tab = trace_scopes.table(trace, HELD, trace.devices["/device:TPU:0"]["modules"])
    assert rows_of(tab) == {("forward", "M/fam.attend"): 300 + 100 + 150 + 50, ("update", "train.optimizer"): 100}
    assert round(tab["total_s"] * 1e9) == 700 and tab["executions"] == 1
    assert round(tab["passes"]["forward"] * 1e9) == 600 and round(tab["passes"]["update"] * 1e9) == 100
    assert [own for own, _ in trace_scopes.self_times([(0, 10, "a"), (2, 6, "b"), (4, 8, "c")])] == [4, 4, 4]


def test_what_is_booked_where():
    """``%gmm`` beside ``%gmm.3``, with and without the ``%``; no metadata ->
    ``unscoped`` by opcode; an ``op_name`` with no scope -> ``unscoped`` by
    opcode and primitive; an instruction or a program the record lacks ->
    ``unjoined``; an event outside every execution is not counted."""
    trace = trace_of([
        (0, 100, "%gmm = bf16[64,2048] custom-call(...)"),
        (100, 300, "gmm.3 = bf16[64,2048] custom-call(...)"),
        (300, 340, "%copy.4 = bf16[8] copy(...)"),
        (340, 400, "%dot.6 = f32[8] dot(...)"),
        (400, 410, "%nobody.1 = f32[8] add(...)"),
        (2000, 2100, "%gmm = bf16[64,2048] custom-call(...)"),
        (5000, 5030, "%gmm = bf16[64,2048] custom-call(...)"),
    ], executions=((0, 1000, PROGRAM), (4000, 6000, "jit_unknown(9)")))
    tab = trace_scopes.table(trace, HELD, trace.devices["/device:TPU:0"]["modules"])
    assert rows_of(tab) == {("forward", "M/moe.experts"): 100, ("backward", "M/moe.experts"): 200}
    assert {k: round(v * 1e9) for k, v in tab["unscoped"].items()} == {"dot (dot_general)": 60, "copy": 40}
    assert round(tab["unjoined_s"] * 1e9) == 10 + 30 and round(tab["total_s"] * 1e9) == 440
    # the heaviest instructions by the compiler's names, each beside where it was booked
    assert [(o["instruction"], o["pass"], o["scope"], o["events"]) for o in tab["ops"][:3]] == [
        ("gmm.3 bf16[64,2048]", "backward", "M/moe.experts", 1), ("gmm bf16[64,2048]", "forward", "M/moe.experts", 1),
        ("dot.6 f32[8]", "forward", "unscoped dot (dot_general)", 1)]
    assert tab["ops"][-1]["scope"] == "unjoined" and len(tab["ops"]) == 6
    assert trace_scopes.share(tab, lambda t: t["passes"]["forward"]) is None  # 40 of 440 unjoined: over 1%
    known = trace_scopes.table(trace, HELD, [(0, 1000, PROGRAM)])
    assert round(known["unjoined_s"] * 1e9) == 10 and known["programs"] == [PROGRAM]
    joined = dict(known, unjoined_s=0.0)
    assert trace_scopes.share(joined, lambda t: t["passes"]["backward"]) == pytest.approx(100 * 200 / 410)
    assert trace_scopes.share(joined, lambda t: 0.0) is None and trace_scopes.share(None, lambda t: 1.0) is None


def test_a_fusion_across_two_scopes_is_booked_by_its_own_name_and_counted_as_mixed():
    trace = trace_of([
        (0, 100, "%fusion.7 = f32[8] fusion(...)"),  # calls M/fam.attend and M/fam.head: they diverge
        (100, 150, "%fusion.8 = f32[8] fusion(...)"),  # calls M/fam.attend alone
        (150, 180, "%mixed.1 = f32[8] fusion(...)"),  # two op_names joined by ';'
    ])
    tab = trace_scopes.table(trace, HELD, trace.devices["/device:TPU:0"]["modules"])
    assert rows_of(tab) == {("forward", "M/fam.attend"): 180}
    assert round(tab["mixed_s"] * 1e9) == 100 + 30
    assert not trace_scopes.diverge(["GPT", "block_3"], ["GPT", "block_3", "Mlp_0"])
    assert trace_scopes.diverge(["GPT", "block_3", "Mlp_0"], ["GPT", "block_3", "LayerNorm_1"])


def test_an_instruction_with_no_name_is_booked_by_what_all_it_calls_share():
    """An ``async-done`` waits for the computation its pair started: its
    time belongs where that computation's instructions lie, as far as they
    agree; one that calls nothing named stays ``unscoped`` by opcode."""
    held = {PROGRAM: trace_scopes.hlo_instructions(hlo_proto([
        (1, [instruction("slice.1", "dynamic-slice", "jit(chunk)/while/body/M/block_2/moe.experts/dynamic_slice"),
             instruction("p.1", "parameter", "w")]),
        (2, [instruction("a.1", "add", "jit(chunk)/while/body/M/block_2/gdn.step/add"),
             instruction("m.1", "multiply", "jit(chunk)/while/body/M/block_2/gdn.conv/mul")]),
        (4, [instruction("c.1", "copy")]),
        (3, [instruction("async-done.1", "async-done", called=(1,)), instruction("fusion.2", "fusion", called=(2,)),
             instruction("fusion.3", "fusion", called=(4,)),
             # a wait the compiler made: its slice reaches ``Mlp_0``'s product through a bitcast
             instruction("async-done.9", "async-done", called=(4,), own_id=1), instruction("bitcast.1", "bitcast",
                                                                                           own_id=2, operands=(1,)),
             instruction("fusion.5", "fusion", "jit(chunk)/while/body/M/block_2/Mlp_0/dot_general", own_id=3,
                         operands=(2,)),
             # one whose data goes into the loop's carry, which no scope owns
             instruction("copy.7", "copy", own_id=5), instruction("tuple.1", "tuple", own_id=6, operands=(5,)),
             instruction("while.3", "while", "jit(chunk)/while", own_id=7, operands=(6,))]),
    ]))}
    assert held[PROGRAM]["async-done.9"] == ("async-done", "", [], "jit(chunk)/while/body/M/block_2/Mlp_0/dot_general")
    assert held[PROGRAM]["copy.7"][3] == "jit(chunk)/while" and held[PROGRAM]["fusion.5"][3] == ""
    trace = trace_of([(0, 50, "%async-done.1 = bf16[8] async-done(...)"), (50, 80, "%fusion.2 = f32[8] fusion(...)"),
                      (80, 90, "%fusion.3 = f32[8] fusion(...)"), (90, 160, "%async-done.9 = bf16[8] async-done(...)"),
                      (160, 165, "%copy.7 = bf16[8] copy(...)")])
    tab = trace_scopes.table(trace, held, trace.devices["/device:TPU:0"]["modules"])
    assert rows_of(tab) == {("forward", "M/block_2/moe.experts"): 50, ("forward", "M/block_2"): 30,
                            ("forward", "M/block_2/Mlp_0"): 70}
    assert {k: round(v * 1e9) for k, v in tab["unscoped"].items()} == {"fusion": 10, "copy": 5}
    assert round(tab["mixed_s"] * 1e9) == 30 and round(tab["by_consumer_s"] * 1e9) == 70


def test_a_decode_steps_rows_go_to_one_kind_each():
    kinds = {scope: trace_scopes.decode_part(scope) for scope in (
        "GPT/block_3/CausalSelfAttention_0", "GPT/block_3/CausalSelfAttention_0/serve.cache_write", "GPT/gpt.head",
        "serve.sample", "Qwen3NextLM/block_1/gdn.step", "GraniteHybridLM/block_0/mamba.conv",
        "Lfm2MoeLM/block_2/lfm2.conv_mixer/lfm2.conv", "Lfm2MoeLM/block_2/lfm2.attn/lfm2.attend",
        "SdarMoeLM/block_0/moe.experts", "OlmoHybridLM/block_3/olmo.attn/olmo.attend_decode",
        "OlmoHybridLM/block_3/olmo.attn", "SdarMoeLM/block_0/sdar.attn/sdar.attend_block/serve.cache_write",
        "OlmoHybridLM/block_3/olmo.mlp", "GPT/block_3/Mlp_0")}
    assert list(kinds.values()) == ["attend", "head_sample", "head_sample", "head_sample", "state", "state", "state",
                                    "attend", "moe", "attend", "attend", "head_sample", None, None]


# -- the trace recorded for it on the v5e ------------------------------------------------

def test_the_recorded_step_splits_into_its_passes_and_scopes():
    """``record_fixture_scopes.py``: three steps of a ``value_and_grad`` over
    a scan of two scopes, a loss and an update, as the chip ran them."""
    trace = reduce_trace.load(None, FIXTURE_SCOPES)
    name, durations = trace.main_module()
    assert name == "jit_step" and len(durations) == 3
    found = trace_scopes.executions_matching(trace, r"^jit_step\(")
    tab = trace_scopes.table(trace, trace_scopes.programs(FIXTURE_SCOPES, {n for _, _, n in found}), found)
    assert tab["executions"] == 3 and tab["unjoined_s"] == 0.0
    assert tab["total_s"] <= sum(durations) and tab["total_s"] > 0.9 * sum(durations)
    total = tab["total_s"]
    by_pass = {k: 100 * v / total for k, v in tab["passes"].items()}
    scoped = 100 * trace_scopes.scoped_seconds(tab) / total
    assert sum(by_pass.values()) + (100 - scoped) == pytest.approx(100.0, abs=1e-6)
    assert by_pass["backward"] > by_pass["forward"] > by_pass["update"] > 0
    scopes = {part for r in tab["rows"] for part in r["scope"].split("/")}
    # XLA fused the gate into the product's fusions: no row of its own, and the table says so
    assert scopes == {"fix.mix", "train.loss", "train.optimizer"} and 0.2 * total < tab["mixed_s"] < 0.5 * total
    assert set(tab["unscoped"]) >= {"fusion (dynamic_update_slice)", "copy-done", "while (while)"}
    assert 0 < tab["by_consumer_s"] < 0.02 * total  # a copy whose data goes to ``fix.mix``'s product
    assert RECORDED == {k: round(v, 2) for k, v in dict(by_pass, scoped=scoped).items()}


RECORDED = {"forward": 26.86, "backward": 57.93, "update": 1.09, "scoped": 85.88}  # my chip run, PR 61


# -- the readers ---------------------------------------------------------------------------

def run_of(tmp_path, monkeypatch, fixture, cell="gpt2s-train-save"):
    """A ``ctx`` as ``run.py`` makes it, its work directory holding a trace."""
    work = tmp_path / "work"
    os.makedirs(work / "trace" / "plugins" / "profile" / "x")
    shutil.copy(fixture, work / "trace" / "plugins" / "profile" / "x" / "host.xplane.pb")
    monkeypatch.setattr(harness, "KEEP", str(tmp_path / "keep"))
    run = types.SimpleNamespace(work=str(work), cell=cell, seed=7)
    trace = reduce_trace.load(str(work / "trace"))
    return types.SimpleNamespace(run=run, stamps={"cycles": [1]}, config={}, trace=trace)


def reader(name):
    from benchmark import run as bench_run

    return bench_run.load_reader("layer_metrics", name)


def test_the_step_readers_add_up_and_keep_the_table(tmp_path, monkeypatch):
    ctx = run_of(tmp_path, monkeypatch, FIXTURE_SCOPES)
    got = {name: reader(name).read(ctx) for name in STEP_READERS}
    assert got["moe_row_movement_share_of_step"] is None  # no such scope in this program
    assert (got["step_forward_share"] + got["step_backward_share"] + got["step_update_share"]
            + 100 - got["step_scoped_share"]) == pytest.approx(100.0, abs=1e-6)
    assert 0 < got["step_head_loss_share"] < got["step_forward_share"] + got["step_backward_share"]
    kept = tmp_path / "keep" / "gpt2s-train-save.seed7.trace1" / "device_scopes.json"
    tab = json.load(open(kept))
    assert tab["programs"][0].startswith("jit_step(") and tab["reader_s"] > 0
    assert "forward" in trace_scopes.render(tab, 2) and "train.optimizer" in trace_scopes.render(tab, 2)
    assert ctx._device_scopes["total_s"] == tab["total_s"]  # built once, read six times
    for name in DECODE_READERS:  # no chunk in this trace
        assert reader(name).read(ctx) is None


def test_a_program_the_record_lacks_gives_no_reading(tmp_path, monkeypatch):
    """The first fixture's program holds no scope: the forward pass is all
    there is, nothing is scoped, and a reader of a scope returns None; with
    its record taken away nothing joins and every reader returns None."""
    ctx = run_of(tmp_path, monkeypatch, FIXTURE)
    assert reader("step_forward_share").read(ctx) is None  # all unscoped: no forward row
    assert reader("step_scoped_share").read(ctx) is None
    assert reader("step_head_loss_share").read(ctx) is None
    ctx = run_of(tmp_path / "again", monkeypatch, FIXTURE)
    monkeypatch.setattr(trace_scopes, "programs", lambda path, wanted=None: {})
    for name in STEP_READERS:
        assert reader(name).read(ctx) is None
    ctx.trace = None
    for name in STEP_READERS + DECODE_READERS:
        assert reader(name).read(ctx) is None


def test_the_decode_table_falls_back_on_the_name_the_engine_jits_its_chunk_under(tmp_path, monkeypatch):
    ctx = run_of(tmp_path, monkeypatch, FIXTURE_SCOPES)
    ctx.stamps = {"healthz": {}}
    assert trace_scopes.decode_table(ctx) is None  # no ``jit_chunk`` here
    ctx = run_of(tmp_path / "named", monkeypatch, FIXTURE_SCOPES)
    ctx.stamps, ctx.config = {"healthz": {}}, {"trace_names": {"decode_chunk": r"^jit_step"}}
    tab = trace_scopes.decode_table(ctx)
    assert tab["executions"] == 3 and reader("decode_step_scoped_share").read(ctx) > 50


def test_the_eleven_readers_are_the_benchmarks_last_entries_each_with_its_cells():
    last = BENCH["per_layer"][-11:]
    assert [m["name"] for m in last] == STEP_READERS + DECODE_READERS
    cells = {w["name"]: w for w in BENCH["workloads"]}
    for m in last:
        assert m["source"] == "device_trace" and m["unit"] == "%" and m["workloads"]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        traffic = {json.load(open(os.path.join(ROOT, "benchmark", "traffic", cells[c]["traffic"] + ".json")))["driver"]
                   .startswith(("serve_closed", "model_serve_closed")) for c in m["workloads"]}
        assert traffic == {m["moves"] == "serve_tokens_per_s"}
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))


def test_the_operators_table_on_the_recorded_trace():
    got = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "trace_scopes.py"), FIXTURE_SCOPES,
                          "--depth", "1"], capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert got.returncode == 0, got.stderr[-2000:]
    head = got.stdout.splitlines()[0]
    assert head.startswith("jit_step(") and "3 executions" in head
    assert "backward" in got.stdout and "train.optimizer" in got.stdout and "unjoined_s" in got.stdout


# -- a CPU trace has no record of the programs: the line leaves the readers out ------------------

@pytest.fixture(scope="module")
def rehearsed():
    """``run(cell)``: the body of one traced CPU rehearsal's line a cell,
    made once for the readers that are asked about it."""
    lines = {}

    def run(cell):
        if cell not in lines:
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            got = subprocess.run([sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "3000000019",
                                  "--seconds", "4", "--trace", "1", "--rehearse"], cwd=ROOT, env=env,
                                 capture_output=True, text=True, timeout=600)
            assert got.returncode == 0, got.stderr[-2000:]
            lines[cell] = json.loads(got.stdout.strip().splitlines()[-1])["cpu_rehearsal"]
        return lines[cell]

    return run


@pytest.mark.parametrize("name", STEP_READERS + DECODE_READERS)
def test_a_cpu_rehearsal_leaves_the_reader_out_of_the_line(name, rehearsed):
    entry = {m["name"]: m for m in BENCH["per_layer"]}[name]
    cell = "mellum2-train-ep4share" if name in STEP_READERS else "lfm2-moe-serve-rollout-16"
    assert cell in entry["workloads"]
    body = rehearsed(cell)
    assert body["correct"] is True and body["failed"] == 0
    assert name not in body["metrics"] and "setup_programs" in body["metrics"]
