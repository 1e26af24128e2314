"""Collective time and its exposed part (harness.collectives), and the
client-sharded cell's readers, on events made by hand."""
import types

import pytest

from harness import collectives, scopes
from harness.trace import Trace
from test_scopes import reader

MS = 1_000_000


@pytest.mark.parametrize("text,want", [
    ("%all-reduce.5 = f32[128,10]{1,0} all-reduce(f32[128,10]{1,0} %x), "
     "replica_groups={{0,1,2,3}}", "all-reduce"),
    ("%ars.1 = (f32[8]{0}, f32[8]{0}) all-reduce-start(f32[8]{0} %a)",
     "all-reduce-start"),
    ("%fusion.3 = f32[8]{0:T(128)} fusion(f32[8]{0} %p), kind=kLoop",
     "fusion"),
    ("%while.1 = (s32[]{:T(128)}, f32[128]{0:T(128)}, f", None),
    ("all-reduce", None),
])
def test_opcode_of_hlo_text(text, want):
    assert collectives.opcode(text) == want


def test_a_collective_is_found_by_opcode_not_by_name():
    assert collectives.is_collective(
        "%psum.57 = f32[128,10]{1,0} all-reduce(f32[128,10]{1,0} %s)")
    assert collectives.is_collective(
        "%x.2 = f32[20,64]{1,0} all-gather(f32[5,64]{1,0} %s)")
    assert not collectives.is_collective(
        "%all-reduce-fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)")
    # a name the trace cut before its opcode: the program's text decides
    cut = "%cps.9 = (f32[128,32,32,64]{3,2,1,0}, f32[128,32,32,64]{3,2"
    assert not collectives.is_collective(cut)
    assert collectives.is_collective(
        cut, collectives.opcodes("  %cps.9 = (f32[1]) "
                                 "collective-permute-start(f32[1] %a)"))


def hand_made():
    # a 10 ms window on two chips. Chip 0: a while loop over the whole
    # window holding a fusion at 1-3 ms and an all-reduce at 2-4 ms
    # (overlapped for 1 ms) and an all-gather alone at 6-7 ms; chip 1:
    # the all-reduce alone at 2-4 ms and a fusion at 5-6 ms
    ar = "%psum.1 = f32[8,4]{1,0} all-reduce(f32[8,4]{1,0} %s)"
    ag = "%ag.2 = f32[20,4]{1,0} all-gather(f32[5,4]{1,0} %t)"
    fu = "%fusion.3 = f32[8,4]{1,0} fusion(f32[8,4]{1,0} %u)"
    wh = "%while.4 = (s32[]) while((s32[]) %t), body=%b"
    return Trace({"devices": {
        "/device:TPU:0": [[wh, 0, 10 * MS, {}], [fu, 1 * MS, 2 * MS, {}],
                          [ar, 2 * MS, 2 * MS, {}], [ag, 6 * MS, MS, {}]],
        "/device:TPU:1": [[ar, 2 * MS, 2 * MS, {}], [fu, 5 * MS, MS, {}]]},
        "host": [["window", 0, 10 * MS, "python"]]})


def test_collective_and_exposed_seconds_on_hand_made_events():
    total, exposed = collectives.collective_seconds(hand_made())
    # chip 0: 3 ms of collectives, 2 of them exposed; chip 1: 2 and 2
    assert total == pytest.approx(2.5e-3)
    assert exposed == pytest.approx(2.0e-3)


def test_collective_readers_per_epoch():
    from repro import obs
    obs.reset()     # no kept program: opcodes come from the op text
    run = types.SimpleNamespace(traffic={"driver": "stage2"},
                                window_units=2, trace=hand_made())
    assert reader("stage2_collective_ms")(run) == pytest.approx(1.25)
    assert reader("stage2_collective_exposed_ms")(run) == \
        pytest.approx(1.0)
    run.trace = Trace({"devices": {}, "host": [["window", 0, MS, "p"]]})
    assert reader("stage2_collective_ms")(run) is None


def test_teacher_sharded_share_reads_the_counters():
    from repro import obs
    run = types.SimpleNamespace(traffic={"driver": "stage2"})
    obs.reset()
    assert reader("teacher_sharded_share")(run) is None
    obs.count("ensemble.sharded_clients", 20)
    assert reader("teacher_sharded_share")(run) == 100.0
    obs.count("ensemble.replicated_clients", 5)
    assert reader("teacher_sharded_share")(run) == 80.0
    obs.reset()


BODY = "jit(epochs_step)/while/body/closed_call/while/body/closed_call"


@pytest.mark.parametrize("op_name,want", [
    (f"{BODY}/jit(gen_step)/jvp(teacher)/resnet18/shard_map/psum/psum",
     "teacher/resnet18"),
    (f"{BODY}/jit(gen_step)/transpose(jvp(teacher))/resnet18/shard_map/psum",
     "teacher/resnet18"),
    (f"{BODY}/jit(gen_step)/jvp(teacher)/resnet18/shard_map/all_gather/"
     "all_gather", "teacher/resnet18"),
    (f"{BODY}/jit(student_step)/transpose(jvp(loss))/shard_map/psum",
     "loss/shard_map"),
])
def test_the_sharded_teachers_collectives_carry_its_scope(op_name, want):
    """The op names of the collectives in the four-chip chunk as compiled
    for a described v5e:2x2: the logit and input-gradient psums and the
    BN statistics' all-gathers are the teacher's, the replicated KL
    pair's cotangent psum the loss's."""
    found = scopes.scope_of(op_name, scopes.program_scopes(), 2)
    assert found == want
