"""The client-sharded stage 2 of ``r18x20.stage2.4chip`` at a tiny size on
the CPU: the stage-2 driver's fused chunks with
``ensemble_shard_mode="clients"``, compared with the plain reference as
the cell's ``correct`` compares them, under the cell's committed limit;
and the bfloat16 control above it. Eight clients of one residual kind,
so the group shards on 1, 2, 4 or 8 devices: on one host device the
mesh is degenerate, and the CI job ``sharding-equivalence`` runs this
file under 8 forced host devices."""
import importlib

import jax

import calibrate
import run as bench_run
from test_scopes import reader
from tiny import PEAKS, TINY, limits, load

CELL = "r18x20.stage2.4chip"
CFG = dict(load("configs", "r18x20"),
           **dict(TINY, n_clients=8, client_kinds=["wrn16_1"] * 8,
                  global_kind="resnet18", loop_mode="fused"))


def test_sharded_program_reads_under_the_limit():
    from repro import obs
    obs.reset()
    traffic = load("traffic", "stage2")
    run = bench_run.Run({"name": "tiny." + CELL, "chips": 1}, CFG, traffic,
                        limits(CELL), seed=3, seconds=0.2, trace=False,
                        devices=jax.devices()[:1], peaks=PEAKS)
    importlib.import_module("harness." + traffic["driver"]).run(run)
    assert run.non_finite == 0
    assert run.correct, run.details["reference"]
    assert reader("teacher_sharded_share")(run) == 100.0
    setup = [s for s in obs.snapshot()["spans"] if s["name"] == "dense.setup"]
    assert setup[-1]["attrs"]["mesh"] == f"clients={len(jax.devices())},data=1"


def test_control_reads_above_the_limit():
    (_, gap), = calibrate.stage2_readings(CFG, {"max_chunks": 6}, 7,
                                          ["control"])
    failed = [name for name, lim in limits(CELL).items()
              if gap[name] > lim["limit"]]
    assert failed, gap
