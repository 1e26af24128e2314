"""The reduction of device time to the program's named scopes and the
readers built on it, on records made by hand and on a short chunk tail
recorded on a TPU v5e."""
import gzip
import importlib.util
import json
import os
import re
import types

import pytest

from harness import scopes
from harness.trace import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e_stage2_scoped_trace.json.gz")
MS = 1_000_000
BODY = "jit(epochs_step)/while/body/closed_call/jit(gen_step)"


def reader(name):
    path = os.path.join(os.path.dirname(HERE), "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def hand_made():
    # a 10 ms window: a while loop (0-4 ms) holding a teacher fusion and
    # a student convolution; the teacher fusion again at 6-7 ms; the
    # loss kernel at 8-8.5 ms, a fusion of generator and teacher ops at
    # 8.5-9 ms; the trace names ops by HLO text, the program's HLO maps
    # them to op names
    dev = [["%while.1 = (s32[]) while(...)", 0, 4 * MS, {}],
           ["%fusion.2 = f32[5,8] fusion(...)", 0, 1 * MS, {}],
           ["%convolution.3 = f32[8,4] convolution(...)", 1 * MS, 2 * MS,
            {}],
           ["%fusion.2 = f32[5,8] fusion(...)", 6 * MS, 1 * MS, {}],
           ["%distill_kl_fwd.9 = f32[8,1] custom-call(...)", 8 * MS,
            MS // 2, {}],
           ["%fusion.5 = f32[8] fusion(...)", 8 * MS + MS // 2, MS // 2, {}]]
    host = [["window", 0, 10 * MS, "python"]]
    return Trace({"devices": {"/device:TPU:0": dev}, "host": host})


HLO = f"""HloModule jit_epochs_step
  %while.1 = (s32[]) while(%t), body=%b
  %fusion.2 = f32[5,8]{{1,0}} fusion(%p), kind=kLoop, metadata={{op_name="{BODY}/jvp(teacher)/resnet18/conv_general_dilated" stack_frame_id=3}}
  %convolution.3 = f32[8,4]{{1,0}} convolution(%a, %b), metadata={{op_name="{BODY}/transpose(jvp(student))/conv_general_dilated"}}
  %distill_kl_fwd.9 = (f32[8,1]{{1,0}}) custom-call(%t, %s), metadata={{op_name="{BODY}/jvp(loss)/distill_kl_fwd/pallas_call"}}
  ROOT %fusion.5 = f32[8]{{0}} fusion(%q), metadata={{op_name="{BODY}/generator/add;{BODY}/jvp(teacher)/cnn1/mul"}}
  %copy-start.7 = (f32[5,8]{{1,0}}, f32[5,8]{{1,0}}, u32[]) copy-start(f32[5,8]{{1,0}} %fusion.2)
  %copy-done.7 = f32[5,8]{{1,0}} copy-done((f32[5,8]{{1,0}}, f32[5,8]{{1,0}}, u32[]) %copy-start.7)
"""
NAMES = scopes.op_names(HLO)


@pytest.mark.parametrize("op_name,depth,want", [
    (f"{BODY}/transpose(jvp(teacher))/resnet18/conv", 1, "teacher"),
    (f"{BODY}/transpose(jvp(teacher))/resnet18/conv", 2, "teacher/resnet18"),
    (f"{BODY}/jvp(student)/dot_general", 2, "student/dot_general"),
    ("jit(loss)/jit(student_step)/add", 1, None),
    ("jit(epochs_step)/while/body/closed_call/jit(_normal)/add", 1, None),
    (f"{BODY}/generator/add;{BODY}/teacher/mul", 1, "generator"),
    ("", 1, None),
])
def test_scope_of(op_name, depth, want):
    assert scopes.scope_of(op_name, scopes.program_scopes(), depth) == want


def test_op_names_from_hlo_text():
    assert sorted(NAMES) == ["%convolution.3", "%copy-done.7",
                             "%copy-start.7", "%distill_kl_fwd.9",
                             "%fusion.2", "%fusion.5"]
    assert NAMES["%fusion.2"].endswith("resnet18/conv_general_dilated")
    # the compiler's copy of the teacher's output is the teacher's
    assert NAMES["%copy-done.7"] == NAMES["%fusion.2"]


def test_hand_made_scope_seconds_add_up_to_busy():
    t = hand_made()
    secs = scopes.scope_seconds(t, names=NAMES)
    assert secs == {None: pytest.approx(0.001),
                    "teacher": pytest.approx(0.002),
                    "student": pytest.approx(0.002),
                    "loss": pytest.approx(0.0005),
                    "generator": pytest.approx(0.0005)}
    assert sum(secs.values()) == pytest.approx(t.busy_s)
    groups = scopes.scope_seconds(t, depth=2, names=NAMES)
    assert groups["teacher/resnet18"] == pytest.approx(0.002)


def _run(trace, epochs=2):
    return types.SimpleNamespace(traffic={"driver": "stage2"}, trace=trace,
                                 window_units=epochs)


SCOPE_READERS = ("stage2_teacher_ms", "stage2_student_ms",
                 "stage2_generator_ms", "stage2_unscoped_share")


def test_readers_on_hand_made_trace(monkeypatch):
    monkeypatch.setattr(scopes, "program_op_names", lambda: NAMES)
    run = _run(hand_made())
    assert reader("stage2_teacher_ms")(run) == pytest.approx(1.0)
    assert reader("stage2_student_ms")(run) == pytest.approx(1.0)
    assert reader("stage2_generator_ms")(run) == pytest.approx(0.25)
    assert reader("stage2_unscoped_share")(run) == pytest.approx(
        100 * 0.001 / 0.006)


@pytest.mark.parametrize("missing", ["scopes", "program"])
def test_readers_are_silent_without_scopes(monkeypatch, missing):
    # a program without repro.obs, or one that kept no chunk program,
    # reads None, never 0
    monkeypatch.setattr(scopes, "program_op_names", lambda: NAMES)
    if missing == "scopes":
        monkeypatch.setattr(scopes, "program_scopes", lambda: None)
    else:
        monkeypatch.setattr(scopes, "program_op_names", lambda: None)
    for name in SCOPE_READERS:
        assert reader(name)(_run(hand_made())) is None


def test_setup_compile_s_reads_the_first_boundary_of_the_last_call():
    from repro import obs
    read = reader("setup_compile_s")
    run = _run(None)
    obs.reset()
    try:
        assert read(run) is None
        for compiled in (1.0, 5.0):
            obs._REC.compile_total = compiled
            with obs.span("dense.setup"):
                pass
            obs._REC.compile_total += 2.0
            with obs.span("dense.eval"):
                pass
            obs._REC.compile_total += 7.0
            with obs.span("dense.eval"):
                pass
        assert read(run) == pytest.approx(7.0)
    finally:
        obs.reset()


def recorded():
    # the last 120 ms of an r18x5.stage2 chunk on a TPU v5e, ending at
    # the chunk boundary, with the op names of its instructions from the
    # chunk program's compiled HLO
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    return Trace(rec), rec["op_names"]


def test_recorded_scope_time_adds_up_to_busy():
    t, names = recorded()
    secs = scopes.scope_seconds(t, names=names)
    assert set(secs) == {None, "teacher", "student", "generator", "loss"}
    assert sum(secs.values()) == pytest.approx(t.busy_s)
    assert max(secs, key=secs.get) == "teacher"
    assert secs[None] < 0.1 * t.busy_s


def test_recorded_patch_fusions_fall_in_the_teacher():
    from harness.trace import short_name
    t, names = recorded()
    patch = [n for n in t.op_seconds()
             if re.search(r"fusion f32\[5,128,34,32,(192|64)\]",
                          short_name(n))]
    assert len(patch) >= 8
    for n in patch:
        assert scopes.scope_of(names[n.split(" = ")[0]],
                               scopes.program_scopes(), 2) \
            == "teacher/resnet18"
