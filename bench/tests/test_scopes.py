"""Device time per dispatch scope: parsing the scope names, reading each
op's ``op_name`` from a recorded v5e trace, summing device time per scope
within the step program's executions, and the NT roofline's need."""

import os
from types import SimpleNamespace

import pytest

from bench import counts, harness, scopes, trace
from bench.tests import toy

RECORDED = os.path.join(os.path.dirname(__file__), "data", "tiny_v5e.xplane.pb")
NT = "dispatch.NT.4096x26752x4096.d4.XLA_NT"

# instruction -> op_name, as a TPU trace gives them: a fusion holding a
# product names its product's scope
OP_NAMES = {
    "convolution_add_fusion": f"jit(step_fn)/jvp({NT})/dot_general",
    "fusion.7": "jit(step_fn)/jvp(jit(relu))/max",
    "dot.4": "jit(step_fn)/transpose(jvp(dispatch.NN.8x8x8.d4.XLA_NN))/dot_general",
    "while.1": f"jit(step_fn)/while/body/jvp({NT})/dot_general",
}


def op_event(instr, opcode="fusion"):
    return f"%{instr} = f32[8,8]{{1,0}} {opcode}(f32[8,8]{{1,0}} %a), kind=kLoop"


def synthetic_trace():
    """Two executions of ``jit_step_fn`` (0-100 and 200-300 ns) and one of
    another program; ops inside and outside them."""
    ops = [(op_event("convolution_add_fusion"), 10, 40),
           (op_event("fusion.7"), 40, 50),
           (op_event("dot.4", "dot"), 50, 90),
           (op_event("while.1", "while"), 0, 100),  # a container: its body ops count
           (op_event("convolution_add_fusion"), 210, 250),
           (op_event("dot.4", "dot"), 250, 280),
           (op_event("copy.1", "copy"), 280, 290),
           (op_event("convolution_add_fusion"), 410, 450)]  # another program's
    modules = [("jit_step_fn(11)", 0, 100), ("jit_step_fn(11)", 200, 300),
               ("jit_other(12)", 400, 500)]
    return trace.Trace(window=(0, 600), chips={0: trace.ChipTrace(ops, modules)},
                       host_spans=[("bench.window", 0, 600)])


@pytest.mark.parametrize("op_name,want", [
    (f"jit(f)/jvp({NT})/dot_general", ("NT", "4096x26752x4096.d4", "XLA_NT")),
    (f"jit(f)/transpose(jvp({NT}))/dot_general", ("NT", "4096x26752x4096.d4", "XLA_NT")),
    ("jit(f)/jvp(dispatch.ATTN.3072x2048x64.g48.d2.UNFUSED_ATTN)/"
     "dispatch.BNT.3072x2048x64.g48.d4.XLA_BNT/dot_general", ("BNT", "3072x2048x64x48.d4", "XLA_BNT")),
    ("jit(f)/while/body/dispatch.NT.64x32x16.d2.PALLAS_TNN_FUSED@128x128x128/transpose",
     ("NT", "64x32x16.d2", "PALLAS_TNN_FUSED@128x128x128")),
    ("jit(f)/jvp()/add", None),
    ("", None),
])
def test_innermost_scope(op_name, want):
    s = scopes.innermost(op_name)
    assert (s if s is None else (s.op, s.key, s.arm)) == want


def test_device_time_per_scope_within_the_step_programs():
    t = synthetic_trace()
    runs = t.module_runs("jit_step_fn")
    times = scopes.scope_times(scopes.instruction_times(t, runs), OP_NAMES)
    by_op = {(s.op if s else None): v for s, v in times.items()}
    assert by_op == pytest.approx({"NT": 70e-9, "NN": 70e-9, None: 20e-9})
    assert sum(times.values()) == pytest.approx(
        t.op_time_s({"convolution_add_fusion", "fusion.7", "dot.4", "copy.1"}, runs))
    lines = scopes.table(times, len(runs), 100e-9)
    assert lines[-1].split()[:2] == ["-", "unscoped"] and lines[-1].endswith("10.00%")
    assert any("XLA_NT" in line and line.endswith("35.00%") for line in lines)


def test_op_names_come_from_the_recorded_trace():
    by_program = scopes.op_names_in_trace(RECORDED)
    assert by_program == {(9508296911920170931, "fusion"): "jit(<lambda>)/dot_general"}
    t = trace.load(RECORDED)
    assert scopes.step_op_names(t, RECORDED, "jit__lambda") == {"fusion": "jit(<lambda>)/dot_general"}
    assert scopes.step_op_names(t, RECORDED, "jit_other") == {}
    assert scopes.program_id("jit_step_fn(9677080685342000563)") == 9677080685342000563


def test_the_nt_need_is_each_layers_forward_product():
    c, w = toy.cell("fcn-4h.train-b4096")
    dims = [c["input_dim"], *c["hidden"], c["output_dim"]]
    nt = scopes.required_nt(c, w)
    assert nt == [counts.gemm(w["batch"], o, i, 4) for i, o in zip(dims, dims[1:])]
    assert all(g in harness.family(c).train_gemms(c, w) for g in nt)


def test_the_lm_nt_need_is_a_third_of_the_dense_gemms():
    c, w = toy.cell("smollm-135m.train-b16s2048")
    nt = scopes.required_nt(c, w)
    attn = [counts.causal_attention(c, w["batch"], w["seq"], 2, b) for b in (False, True)]
    dense = [g for g in harness.family(c).train_gemms(c, w) if g not in attn]
    assert len(nt) == 7 * c["n_layers"] + 1
    # each NT product's two gradients cost what it costs
    assert 3 * counts.total_flops(nt) == pytest.approx(counts.total_flops(dense))


def fake_train_run(tmp_path, t, kind="train"):
    return SimpleNamespace(counters={"kind": kind, "step_module": "jit_step_fn",
                                     "matmul_ops": set()},
                           trace_data=t, out_dir=str(tmp_path), notes=[],
                           peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
                           config=toy.fcn_config(), workload=toy.cell("fcn-4h.train-b4096")[1])


def test_the_nt_reader_returns_none_without_scopes(tmp_path):
    read = harness.metric_reader("nt_roofline.train")
    assert read(fake_train_run(tmp_path, None)) is None
    assert read(fake_train_run(tmp_path, synthetic_trace())) is None  # no trace file
    assert read(fake_train_run(tmp_path, synthetic_trace(), kind="serve_closed")) is None


def test_the_nt_reader_notes_a_trace_it_cannot_decode(tmp_path, monkeypatch):
    bad = tmp_path / "bad.xplane.pb"
    bad.write_bytes(b"\x0b\x01")  # a field of wire type 3, which XPlane never uses
    with pytest.raises(ValueError):
        scopes.op_names_in_trace(str(bad))
    monkeypatch.setattr(trace, "find_xplane", lambda d: str(bad))
    run = fake_train_run(tmp_path, synthetic_trace())
    assert harness.metric_reader("nt_roofline.train")(run) is None
    assert run.notes and run.notes[0].startswith("no op names read from")


def test_the_nt_reader_reads_scoped_ops(tmp_path, monkeypatch):
    (tmp_path / "trace").mkdir()
    monkeypatch.setattr(trace, "find_xplane", lambda d: RECORDED)
    monkeypatch.setattr(scopes, "op_names_in_trace", lambda path: {
        (11, i): n for i, n in OP_NAMES.items()})
    run = fake_train_run(tmp_path, synthetic_trace())
    read = harness.metric_reader("nt_roofline.train")
    value = read(run)
    need = counts.roofline_s(scopes.required_nt(run.config, run.workload), 197e12, 819e9)
    assert value == pytest.approx(100.0 * need * 2 / 70e-9)
    notes = list(run.notes)
    assert any("unscoped" in n for n in notes) and any("clock offset" in n for n in notes)
    assert read(run) == value and run.notes == notes  # notes are written once


def test_the_nt_reader_notes_a_step_without_scopes(tmp_path, monkeypatch):
    """An executable with no dispatch scope, as a compile cache filled by a
    program without scopes serves, reads None and says why."""
    monkeypatch.setattr(trace, "find_xplane", lambda d: RECORDED)
    monkeypatch.setattr(scopes, "op_names_in_trace", lambda path: {
        (11, "fusion.7"): "jit(step_fn)/jvp(jit(relu))/max"})
    run = fake_train_run(tmp_path, synthetic_trace())
    assert harness.metric_reader("nt_roofline.train")(run) is None
    assert run.notes == ["no op of jit_step_fn carries a dispatch scope: its executable "
                         "likely came from a compile cache filled by a program without scopes"]
