"""Which model part issued each instruction of a compiled program, and a
device trace reduced by part (ISSUE 38): `obs.programs.ProgramReport` over
hand-written text and over both serving programs of every family compiled
for the CPU (the described v5e's, with the kernels in, are in
`test_aot_compile.py`, beside its topology fixture), the reducer over
synthetic events, the engine's and the trainer's `program_report`, the
profiler's `by_part`, the new leaf spans of a scheduler step, and the
benchmark's reader through a rehearsal.
"""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from elephas_tpu import obs, to_simple_rdd
from elephas_tpu.api.compile import CompiledModel
from elephas_tpu.models import get_model
from elephas_tpu.obs import devprof
from elephas_tpu.obs.programs import (TOP, UNSCOPED, ProgramReport, load_reports,
                                      save_reports, scope_of, shape_bytes)
from elephas_tpu.serving import InferenceEngine

from conftest import assert_report_is_whole, make_blobs, rehearsal_engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a program of two layers written by hand: a weight's prefetch, a fusion of one
# scope and one of two, an asynchronous slice, a lone transpose, a loop whose
# body copies, and a sort whose comparator is no device event
TEXT = """HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[8,16]{1,0})->f32[8,16]{1,0}}

%fused_computation (p0: f32[8,16], p1: f32[16]) -> f32[8,16] {
  %p0 = f32[8,16]{1,0:T(8,128)} parameter(0)
  %p1 = f32[16]{0:T(128)} parameter(1)
  %b.1 = f32[8,16]{1,0:T(8,128)} broadcast(%p1), dimensions={1}, metadata={op_name="jit(step)/Model/Model._forward/blocks_0/mlp/norm/mul" stack_frame_id=4}
  ROOT %m.1 = f32[8,16]{1,0:T(8,128)} multiply(%p0, %b.1), metadata={op_name="jit(step)/Model/Model._forward/blocks_0/mlp/up/dot_general" stack_frame_id=5}
}

%fused_computation.1 (p0.1: f32[8,16]) -> f32[8,16] {
  %p0.1 = f32[8,16]{1,0:T(8,128)} parameter(0)
  ROOT %n.1 = f32[8,16]{1,0:T(8,128)} negate(%p0.1), metadata={op_name="jit(step)/Model/Model._forward/blocks_1/mlp/up/neg"}
}

%compare (a: f32[], b: f32[]) -> pred[] {
  %a = f32[] parameter(0), metadata={op_name="sort"}
  %b = f32[] parameter(1), metadata={op_name="sort"}
  ROOT %lt = pred[] compare(%a, %b), direction=LT, metadata={op_name="lt_to"}
}

%body (arg: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %arg = (s32[], f32[8,16]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x.1 = f32[8,16]{1,0} get-tuple-element(%arg), index=1
  %copy.3 = f32[8,16]{0,1} copy(%x.1), metadata={op_name="jit(step)/Model/while/body/blocks_1/attention/kv_write/jit(_take)/copy"}
  ROOT %t.1 = (s32[], f32[8,16]{1,0}) tuple(%i, %copy.3)
}

%cond (arg.1: (s32[], f32[8,16])) -> pred[] {
  %arg.1 = (s32[], f32[8,16]{1,0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%arg.1), index=0
  %c.1 = s32[] constant(4)
  ROOT %lt.1 = pred[] compare(%i.1, %c.1), direction=LT
}

ENTRY %main.9 (w: f32[8,16], s: f32[16]) -> f32[8,16] {
  %w = f32[8,16]{1,0:T(8,128)} parameter(0), metadata={op_name="params[\\'blocks_0\\'][\\'up\\'][\\'kernel\\']"}
  %s = f32[16]{0:T(128)} parameter(1), metadata={op_name="params[\\'final_norm\\'][\\'scale\\']"}
  %copy-start.1 = (f32[8,16]{1,0:T(8,128)S(1)}, f32[8,16]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%w), cross_program_prefetch_index=0
  %copy-done.1 = f32[8,16]{1,0:T(8,128)S(1)} copy-done(%copy-start.1)
  %fusion.1 = f32[8,16]{1,0:T(8,128)} fusion(%copy-done.1, %s), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/Model/Model._forward/blocks_0/mlp/up/dot_general" stack_frame_id=5}, backend_config={"flag_configs":[],"window_config":{"kernel_window_bounds":["8","1"]}}
  %fusion.2 = f32[8,16]{1,0:T(8,128)} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/Model/Model._forward/blocks_1/mlp/up/neg"}
  %slice-start.4 = ((f32[8,16]{1,0:T(8,128)}), f32[4,16]{1,0:T(4,128)}, s32[]{:S(2)}) slice-start(%fusion.2), slice={[0:4], [0:16]}, metadata={op_name="jit(step)/Model/Model._forward/blocks_1/attention/slice"}
  %slice-done.4 = f32[4,16]{1,0:T(4,128)} slice-done(%slice-start.4)
  %transpose.5 = bf16[16,8]{1,0:T(8,128)(2,1)} transpose(%fusion.2), dimensions={1,0}, metadata={op_name="jit(step)/Model/lm_head/transpose"}
  %sort.6 = f32[8,16]{1,0:T(8,128)} sort(%fusion.2), dimensions={1}, to_apply=%compare, metadata={op_name="jit(step)/sample/sort"}
  %zero = s32[] constant(0)
  %tuple.7 = (s32[], f32[8,16]{1,0}) tuple(%zero, %sort.6)
  %while.8 = (s32[], f32[8,16]{1,0}) while(%tuple.7), condition=%cond, body=%body, metadata={op_name="jit(step)/Model/while"}
  %select.9 = f32[8,16]{1,0} get-tuple-element(%while.8), index=1
  ROOT %add.10 = f32[8,16]{1,0} add(%select.9, %select.9), metadata={op_name="jit(step)/add"}
}
"""


def test_shapes_and_scopes_by_hand():
    assert shape_bytes("f32[4,32]{1,0:T(4,128)S(1)}") == 512
    assert shape_bytes("(bf16[2,3]{1,0}, pred[7]{0}, token[], u32[]{:S(2)})") == 12 + 7 + 4
    assert shape_bytes("f8e4m3fn[16]{0}") == 16
    assert scope_of("jit(f)/Model/blocks_3/attention/kv_write/dynamic_update_slice") == \
        "Model/blocks_3/attention/kv_write"
    assert scope_of("params[\\'Block_1\\'][\\'Dense_0\\'][\\'kernel\\']") == \
        "params/Block_1/Dense_0/kernel"
    # where the compiler merged instructions it lists their names: the first
    assert scope_of("jit(f)/a/kv_write/reshape;kv_write/transpose") == "a/kv_write"
    assert scope_of("jit(f)/select_n") == ""


def test_report_of_a_hand_written_program():
    report = ProgramReport.from_text(TEXT)
    assert report.program == "jit_step"
    ins = report.instructions
    # the entry and the loop's body and condition; no fused computation's
    # inside, no comparator's
    assert {"fusion.1", "copy.3", "lt.1", "while.8"} <= set(ins)
    assert not {"m.1", "b.1", "n.1", "lt", "a"} & set(ins)
    # layers collapse (two numbers of `blocks_` at one place), a method's frame
    # and a jit wrapper name no part, an argument is a path
    assert ins["fusion.2"].scope == "Model/Model._forward/blocks_1/mlp/up"
    assert ins["fusion.2"].part == "Model/blocks_*/mlp/up" and not ins["fusion.2"].mixed
    assert ins["copy.3"].part == "Model/blocks_*/attention/kv_write"
    assert ins["w"].part == "params/blocks_0/up/kernel"  # one number there: no layer
    assert ins["transpose.5"].part == "Model/lm_head" and ins["add.10"].part == TOP
    # a fusion of two scopes: the root's part, `mixed`, both kept
    assert ins["fusion.1"].mixed and ins["fusion.1"].part == "Model/blocks_*/mlp/up"
    assert ins["fusion.1"].parts == ["Model/blocks_*/mlp/norm", "Model/blocks_*/mlp/up"]
    # a pair: the `-done` carries its `-start`'s scope and operand; a `-start`
    # with no `op_name` has the scope of the argument it reads
    done = ins["copy-done.1"]
    assert done.start == "copy-start.1" and done.operand == "w"
    assert done.part == ins["copy-start.1"].part == "params/blocks_0/up/kernel"
    assert done.operand_shape.startswith("f32[8,16]")
    assert ins["slice-done.4"].part == "Model/blocks_*/attention"
    assert ins["slice-done.4"].operand == "fusion.2"
    # instructions with nothing of their own take what they read
    assert ins["select.9"].part == ins["while.8"].part == "Model"
    assert_report_is_whole(report)
    # copies: the prefetch 512, the slice 256, the transpose 16*8*2, the body's 512
    assert sorted(i.name for i in report.copies()) == [
        "copy-done.1", "copy.3", "slice-done.4", "transpose.5"]
    assert report.copy_bytes() == 512 + 256 + 256 + 512
    assert report.copies_by_part(2) == [["params/blocks_0/up/kernel", 512, 1],
                                        ["Model/blocks_*/attention/kv_write", 512, 1]]
    assert report.lookup("%fusion.2 = f32[8,16]{1,0} fusion(f32[8,16] %fusion.1)") \
        is ins["fusion.2"]
    # a name that coincides on another shape is another compile's instruction
    assert report.lookup("%fusion.2 = s32[4]{0:T(128)} fusion(s32[4] %fusion.1)") is None
    assert report.lookup("fusion.2") is ins["fusion.2"]


def test_an_asynchronous_pair_as_the_chips_runtime_prints_it():
    """On the chip `compiled.as_text()` spells the pair `async-start` /
    `async-done` around a computation that holds the `slice` (my chip run,
    PR 38); a compile for a described chip spells it `slice-start` /
    `slice-done`. Both are the same pair: one row of copies, the same bytes;
    the wrapped computation's inside is kept (the chip may name an event by
    it) and counts no bytes of its own."""
    sugar = ("  %slice-start.4 = ((f32[8,16]{1,0:T(8,128)}), f32[4,16]{1,0:T(4,128)}, "
             "s32[]{:S(2)}) slice-start(%fusion.2), slice={[0:4], [0:16]}, "
             'metadata={op_name="jit(step)/Model/Model._forward/blocks_1/attention/slice"}\n'
             "  %slice-done.4 = f32[4,16]{1,0:T(4,128)} slice-done(%slice-start.4)\n")
    assert sugar in TEXT
    plain = TEXT.replace(sugar, (
        "  %slice-start.4 = ((f32[8,16]{1,0:T(8,128)}), f32[4,16]{1,0:T(4,128)}, "
        "s32[]{:S(2)}) async-start(%fusion.2), calls=%async_computation\n"
        "  %slice-done.4 = f32[4,16]{1,0:T(4,128)} async-done(%slice-start.4), "
        "calls=%async_computation\n")).replace("ENTRY %main.9", (
            "%async_computation (param_0: f32[8,16]) -> f32[4,16] {\n"
            "  %param_0 = f32[8,16]{1,0:T(8,128)} parameter(0)\n"
            "  ROOT %slice.2 = f32[4,16]{1,0:T(4,128)} slice(%param_0), slice={[0:4], [0:16]}, "
            'metadata={op_name="jit(step)/Model/Model._forward/blocks_1/attention/slice"}\n'
            "}\n\nENTRY %main.9"))
    report, chip = ProgramReport.from_text(TEXT), ProgramReport.from_text(plain)
    # what the wrapper holds may be an event's name, and moves nothing itself
    inner = chip.instructions["slice.2"]
    assert inner.inside == "slice-start.4" and not inner.moves_data
    assert inner.part == "Model/blocks_*/attention"
    done = chip.instructions["slice-done.4"]
    assert done.opcode == "async-done" and done.wraps == "slice" and done.moves_data
    assert done.kind == "slice-done" and done.part == "Model/blocks_*/attention"
    assert chip.copy_bytes() == report.copy_bytes()
    assert chip.copies_by_part() == report.copies_by_part()
    assert_report_is_whole(chip)


def test_a_fusion_nested_in_a_fusion_is_an_event_of_its_own():
    """The chip's trace names a fusion inside a fused computation (65 events a
    decode step of `doc-mix-32k`, unjoined before the report held them: my
    chip run, PR 38); it reads the outer one's parameters, so it is the outer
    one's part where it carries no `op_name`."""
    text = """HloModule jit_f, is_scheduled=true

%inner (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%p)
}

%outer (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  %fusion.7 = f32[8]{0} fusion(%q), kind=kLoop, calls=%inner
  ROOT %exp.1 = f32[8]{0} exponential(%fusion.7), metadata={op_name="jit(f)/M/layers_0/mlp/exp"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  ROOT %fusion.3 = f32[8]{0} fusion(%x), kind=kCustom, calls=%outer, metadata={op_name="jit(f)/M/layers_0/mlp/exp"}
}
"""
    report = ProgramReport.from_text(text)
    assert set(report.instructions) == {"x", "fusion.3", "fusion.7"}
    assert report.instructions["fusion.7"].part == report.instructions["fusion.3"].part \
        == "M/layers_0/mlp"
    assert_report_is_whole(report)


def test_reports_are_kept_as_files(tmp_path):
    report = ProgramReport.from_text(TEXT)
    path = str(tmp_path / "reports.json")
    save_reports(path, [report])
    (kept,) = load_reports(path)
    assert kept.to_dict() == report.to_dict() and kept.copy_bytes() == report.copy_bytes()
    text = tmp_path / "step.txt"
    text.write_text(TEXT)
    assert load_reports(str(text))[0].to_dict() == report.to_dict()


# -- both serving programs of every family, compiled for the CPU ---------------


@pytest.mark.parametrize("cell", ["gpt2-xl.chat-steady", "jamba2-3b.doc-batch-4k",
                                  "deepseek-v2.doc-batch-16k",
                                  "dots3-note-prev.doc-mix-32k"])
def test_report_of_both_serving_programs_on_the_cpu(cell):
    """At the cell's rehearsal sizes, through `InferenceEngine.program_report`
    on an engine that has not run: nothing counts as a trace."""
    engine = rehearsal_engine(cell)
    before = _retraces()
    for which, name in (("prefill", "jit__chunk_prefill_impl"),
                        ("decode", "jit__paged_decode_impl")):
        report = engine.program_report(which)
        assert report.program == name
        assert engine.program_report(which) is report  # built once, kept
        assert_report_is_whole(report, layers=2)
        assert "sample" in report.parts()
    stats = engine.stats()
    assert stats["prefill_traces"] == 0 and stats["decode_traces"] == 0
    assert _retraces() == before
    with pytest.raises(ValueError, match="program is one of"):
        engine.program_report("verify")


@pytest.mark.parametrize("cell", ["gpt2-xl.chat-steady", "dots3-note-prev.doc-mix-32k"])
def test_a_report_is_lowered_as_the_live_call_is(cell):
    """`program_args` says of each argument's placement what the live call's
    own argument says and no more (an array not committed to its device states
    none): the module a report is lowered from is the live call's byte for
    byte, so its compile is the live program's (a load from the compile
    cache) and its instruction names are the trace's. A sharding stated where
    the live call states none is another module, and the chip's compiler
    numbered `doc-mix-32k`'s decode program apart for it (my chip runs, PR 38)."""
    engine = rehearsal_engine(cell)
    seen = {}

    class Spy:
        def __init__(self, jitted, key):
            self.jitted, self.key = jitted, key

        def __call__(self, *args):
            seen[self.key] = args
            return self.jitted(*args)

    decode, prefill = engine._jit_decode, engine._jit_prefill
    engine._jit_decode, engine._jit_prefill = Spy(decode, "decode"), Spy(prefill, "prefill")
    for _ in range(2):  # the second request's steps chain from device tokens
        rid = engine.submit(list(range(1, 12)), max_new_tokens=5, stop_token=None)
        engine.result(rid, timeout_s=120)
    engine._jit_decode, engine._jit_prefill = decode, prefill
    engine._report_lowering.on = True  # lowering by hand is no retrace either
    try:
        for which, jitted in (("decode", decode), ("prefill", prefill)):
            assert jitted.lower(*seen[which]).as_text() == \
                jitted.lower(*engine.program_args(which)).as_text(), which
    finally:
        engine._report_lowering.on = False
    assert engine.stats()["decode_traces"] == engine.stats()["prefill_traces"] == 1


def _retraces() -> list:
    return [line for line in obs.default_registry().expose_text().splitlines()
            if line.startswith("retrace_total")]


@pytest.fixture(scope="module")
def compiled():
    return CompiledModel(
        get_model("transformer_lm", vocab_size=97, d_model=32, num_heads=4,
                  num_layers=2, max_seq_len=64),
        optimizer={"name": "adam", "learning_rate": 3e-3},
        loss="sparse_categorical_crossentropy", metrics=[],
        input_shape=(64,), input_dtype=jnp.int32, seed=0)


def test_program_report_is_no_retrace_and_moves_no_token(compiled):
    engine = InferenceEngine(compiled, max_slots=3, max_prompt_len=12, max_len=32,
                             kv_block_size=4, prefill_chunk=4)

    def serve():
        rid = engine.submit([5, 3, 9, 4, 1, 6, 2], max_new_tokens=6, stop_token=None)
        return engine.result(rid, timeout_s=120).tokens

    first = serve()
    stats, retraces = engine.stats(), _retraces()
    assert stats["prefill_traces"] == 1 and stats["decode_traces"] == 1
    decode, prefill = engine.program_report("decode"), engine.program_report("prefill")
    assert decode.copy_bytes() >= 0 and prefill.instructions and decode.instructions
    after = engine.stats()
    assert after["prefill_traces"] == 1 and after["decode_traces"] == 1
    assert _retraces() == retraces
    assert serve() == first
    after = engine.stats()
    assert after["prefill_traces"] == 1 and after["decode_traces"] == 1
    # the arguments a report is lowered from are the live engine's
    params, cache, table, *_ = engine.program_args("decode")
    assert table.shape == engine.pool.table.rows.shape
    assert jax.tree_util.tree_structure(cache) == jax.tree_util.tree_structure(
        engine.pool.cache)


def test_trainer_program_report_by_step_part():
    from elephas_tpu.engine.sync import SyncTrainer
    from elephas_tpu.parallel.mesh import build_mesh

    x, y = make_blobs(n=256)
    model = CompiledModel(get_model("mlp", features=(16,), num_classes=4),
                          optimizer={"name": "sgd", "learning_rate": 0.1},
                          loss="categorical_crossentropy", metrics=["acc"],
                          input_shape=(20,))
    trainer = SyncTrainer(model, build_mesh(num_data=1, devices=jax.devices()[:1]))
    with pytest.raises(ValueError, match="needs state, xs and ys"):
        trainer.program_report()  # no traced fit yet, and no shapes given
    dataset = to_simple_rdd(None, x, y, 1)
    trainer.fit(dataset, epochs=1, batch_size=64)
    assert trainer._epoch_shapes is None  # an untraced fit remembers nothing
    tracer = obs.enable_tracing(capacity=1 << 12, annotate_device=False)
    try:
        trainer.fit(dataset, epochs=1, batch_size=64)
    finally:
        obs.disable_tracing()
    assert tracer.events()
    report = trainer.program_report()
    assert report.program == "jit_epoch_fn"
    assert trainer.program_report() is report
    state, xs, ys = trainer._epoch_shapes
    assert trainer.program_report(state, xs, ys) is report  # the same shapes, given
    parts = set(report.parts())
    for scope in ("forward", "backward", "update"):
        assert any(scope in p.split("/") for p in parts), (scope, sorted(parts))
    assert_report_is_whole(report, layers=0)


# -- a device trace by part ----------------------------------------------------


def _events():
    """One chip: two calls of `jit_step`, one of a program with no report,
    an operation between calls."""
    modules = [(0.0, 1.0, "jit_step(123)"), (2.0, 3.0, "jit_step(123)"),
               (5.0, 5.5, "jit_other(9)")]
    shapes = {i.name: i.out_shape for i in ProgramReport.from_text(TEXT).instructions.values()}

    def hlo(name):  # as the trace prints an event: its instruction's own text
        return f"%{name} = {shapes.get(name, 'f32[8,16]{1,0}')} op(f32[8,16]{{1,0}} %x)"

    ops = [
        (0.00, 0.05, hlo("copy-start.1")), (0.05, 0.10, hlo("fusion.1")),
        (0.10, 0.30, hlo("copy-done.1")),
        (0.30, 0.90, hlo("while.8")),      # 0.6 s around 0.4 s of body
        (0.40, 0.60, hlo("copy.3")), (0.60, 0.80, hlo("copy.3")),
        (0.90, 1.00, hlo("fusion.99")),    # no such instruction
        (2.00, 2.10, hlo("fusion.1")), (2.10, 2.50, hlo("transpose.5")),
        (4.00, 4.25, hlo("fusion.1")),     # under no program call
        (5.00, 5.50, hlo("fusion.1")),     # a program without a report
    ]
    return [(modules, ops)]


def test_seconds_by_part_over_synthetic_events():
    table = devprof.seconds_by_part(_events(), [ProgramReport.from_text(TEXT)])
    step = table["programs"]["jit_step"]
    assert step["calls"] == 2 and step["device_s"] == pytest.approx(2.0)
    assert step["copy_bytes_per_call"] == 1536
    rows = {(r["part"], r["kind"]): r for r in table["rows"]}
    # the loop counts its self time: 0.6 s less the 0.4 s of its body
    assert rows[("Model", "while")]["seconds"] == pytest.approx(0.2)
    body = rows[("Model/blocks_*/attention/kv_write", "copy")]
    assert body["seconds"] == pytest.approx(0.4) and body["calls"] == 2
    assert body["bytes"] == 2 * 512 and not body["mixed"]
    up = rows[("Model/blocks_*/mlp/up", "fusion")]
    assert up["mixed"] and up["seconds"] == pytest.approx(0.15) and up["calls"] == 2
    # the pair: the `-done`'s wait, and from the `-start`'s begin to its end
    done = rows[("params/blocks_0/up/kernel", "copy-done")]
    assert done["seconds"] == pytest.approx(0.2) and done["bytes"] == 512
    assert done["in_flight_s"] == pytest.approx(0.3)
    assert rows[("Model/lm_head", "transpose")]["bytes"] == 256
    assert step["mixed_s"] == pytest.approx(0.15)
    assert step["unjoined_s"] == pytest.approx(0.1)
    assert step["joined_s"] == pytest.approx(0.05 + 0.05 + 0.2 + 0.2 + 0.4 + 0.1 + 0.4)
    assert table["unjoined"] == [
        {"program": "jit_step", "kind": "fusion", "seconds": pytest.approx(0.1), "calls": 1}]
    assert table["unreported_s"] == {devprof.NO_PROGRAM: pytest.approx(0.25),
                                     "jit_other": pytest.approx(0.5)}
    text = devprof.format_by_part(table)
    assert "jit_step: 2 calls" in text and "(unjoined: not in the report)" in text
    assert "fusion (mixed)" in text and "jit_other: 0.5000 s of operations, no report" in text


def test_the_recorded_chip_trace_reads_with_nothing_but_jax():
    """`benchmark/tests/data/small.xplane.pb`, a v5e's: its module and
    operation lines come back as events named as a report keys them."""
    read = devprof.read_device_events(
        os.path.join(REPO, "benchmark", "tests", "data", "small.xplane.pb"))
    (modules, ops), = read["chips"]
    assert {m[2].split("(")[0] for m in modules} >= {
        "jit__chunk_prefill_impl", "jit__paged_decode_impl"}
    assert len(ops) > 1000 and all(name.startswith("%") for _, _, name in ops[:50])
    assert read["mark_s"] is None  # the benchmark's capture, not the profiler's
    table = devprof.seconds_by_part(read["chips"], [])
    assert not table["rows"] and set(table["unreported_s"]) >= {"jit__paged_decode_impl"}


def test_profiler_answers_by_part(tmp_path, monkeypatch):
    calls = []
    (tmp_path / "plugins" / "profile" / "t0").mkdir(parents=True)
    (tmp_path / "plugins" / "profile" / "t0" / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(devprof, "read_device_events", lambda path: calls.append(path) or {
        "chips": _events(), "mark_s": 40.0})
    asked = []

    def reports():
        asked.append(1)
        return [ProgramReport.from_text(TEXT)]

    marks = []
    prof = devprof.DeviceProfiler(
        out_dir=str(tmp_path), starter=lambda d: None, stopper=lambda: None,
        marker=marks.append, reports=reports)
    started = prof.start()
    assert started["status"] == "started" and marks == [devprof.PROFILE_MARK]
    assert started["mark"]["name"] == devprof.PROFILE_MARK
    assert not asked  # nothing is built before the capture ends
    doc = prof.stop()
    assert doc["status"] == "stopped" and asked == [1]
    assert calls == [str(tmp_path / "plugins" / "profile" / "t0" / "host.xplane.pb")]
    by_part = doc["by_part"]
    assert by_part["programs"]["jit_step"]["calls"] == 2 and by_part["unjoined"]
    assert by_part["to_monotonic_s"] == pytest.approx(
        started["mark"]["monotonic_s"] - 40.0)
    json.dumps(doc)  # opsd answers it as JSON
    # a profiler beside nothing answers as before; one whose reports fail says so
    plain = devprof.DeviceProfiler(out_dir=str(tmp_path), starter=lambda d: None,
                                   stopper=lambda: None)
    plain.start()
    assert "by_part" not in plain.stop() and plain._marker is None

    def broken():
        raise RuntimeError("no shapes yet")

    failing = devprof.DeviceProfiler(out_dir=str(tmp_path), starter=lambda d: None,
                                     stopper=lambda: None, reports=broken)
    failing.start()
    assert "no shapes yet" in failing.stop()["by_part"]["error"]


def test_engine_mounts_a_profiler_that_knows_its_programs(compiled):
    engine = InferenceEngine(compiled, max_slots=3, max_prompt_len=12, max_len=32,
                             kv_block_size=4, prefill_chunk=4)
    ops = engine.mount_ops()
    try:
        reports = ops._get_profiler()._reports()
        assert [r.program for r in reports] == ["jit__chunk_prefill_impl",
                                                "jit__paged_decode_impl"]
    finally:
        engine.unmount_ops()


# -- the holes in a scheduler step's spans ---------------------------------------


def test_a_steps_children_and_its_record_cover_it(compiled):
    """Under a clock that moves a millisecond a read, what no child of a
    step covers, from its begin to the end of `step/record` (and of
    `step/publish`, where the step finished a request), is the clock reads
    between two spans and no phase; and `submit` lies on the caller's thread
    beside the request's instant."""
    from test_program_spans import PROMPTS, TickClock, _serve

    clock = TickClock()
    tracer = obs.Tracer(capacity=1 << 16, clock=clock, annotate_device=False)
    _serve(compiled, tracer, clock)
    events = tracer.events()
    steps = [e for e in events if e.name == "sched_step"]
    assert len(steps) > 5
    for step in steps:
        kids = [e for e in events if e.parent_id == step.span_id]
        assert "step/record" in {k.name for k in kids}
        covered = sum(k.duration_s for k in kids)
        end = max(k.end_s for k in kids)
        assert (end - step.begin_s) - covered <= 7e-3 + 1e-9, (step, kids)
    submits = [e for e in events if e.name == "submit"]
    spans = [e for e in submits if e.duration_s > 0]
    instants = [e for e in submits if e.duration_s == 0]
    assert len(spans) == len(instants) == len(PROMPTS)
    assert {e.track for e in instants} == {f"req:{i}" for i in range(len(PROMPTS))}
    assert all(not e.track.startswith("req:") for e in spans)
    assert sorted(e.args["req_id"] for e in spans) == list(range(len(PROMPTS)))


def test_untraced_steps_read_the_clock_as_before(compiled):
    """Tracing off: `submit` and `step` read the clock as often as they did
    before the spans of ISSUE 38 (counted on PR 37's tree: 2 reads a submit,
    the queue's among them, 7 a step that finds nothing to do)."""
    reads = []

    def clock():
        reads.append(1)
        return 100.0 + 1e-3 * len(reads)

    engine = InferenceEngine(compiled, max_slots=3, max_prompt_len=12, max_len=32,
                             kv_block_size=4, prefill_chunk=4, clock=clock,
                             tracer=obs.NULL_TRACER)
    del reads[:]
    engine.submit([1, 2, 3], max_new_tokens=2, stop_token=None)
    assert len(reads) == 2
    while engine.scheduler.has_work:
        engine.step()
    del reads[:]
    engine.step()
    assert len(reads) == 7


# -- the benchmark's reader ------------------------------------------------------


@pytest.mark.parametrize("cell,names", [
    ("gpt2-xl.chat-steady", ["decode_copy_mb_per_step.chat"]),
    ("gpt2-xl.doc-batch", ["chunk_copy_mb_per_call", "decode_copy_mb_per_step"]),
])
def test_the_benchmarks_reader_reads_the_reports(cell, names, capsys, monkeypatch):
    monkeypatch.setattr(sys, "path", list(sys.path))  # the script adds its own
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(REPO, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    try:
        code = run.main(["--workload", cell, "--rehearse", "--trace", "1",
                         "--seed", "3000000019"])
    finally:
        obs.disable_tracing()
    assert code == run.REHEARSAL_EXIT
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(names) <= set(line["notes"]["metrics_read"])
    copies = line["notes"]["needed"]["copies_by_part"]
    assert "jit__paged_decode_impl" in copies
    assert ("jit__chunk_prefill_impl" in copies) == (len(names) == 2)
    assert all(len(rows) <= 10 for rows in copies.values())
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in names:
        assert cell in listed[name]["workloads"] and listed[name]["unit"] == "MB"
        assert listed[name]["source"] == "program_counter"
