"""A tiny cell on the CPU: the drivers' plumbing without the chip."""
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {"client_kinds": ["cnn1", "cnn1", "cnn2"], "n_clients": 3,
        "global_kind": "cnn1", "image_size": 8, "batch_size": 8,
        "synth_batch": 8, "nz": 8, "t_g": 2, "loop_chunk": 2,
        "train_per_class": 6}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def limits(cell):
    return load("limits", cell)


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def tiny_run(traffic_name, *, seed=3, seconds=0.2, limits=None,
             traffic=None, trace=False):
    import jax
    import importlib
    import run as bench_run
    cfg = dict(load("configs", "r18x5"), **TINY)
    tr = dict(load("traffic", traffic_name), **(traffic or {}))
    cell = {"name": "tiny." + traffic_name, "chips": 1}
    run = bench_run.Run(cell, cfg, tr, limits or {}, seed=seed,
                        seconds=seconds, trace=trace,
                        devices=jax.devices()[:1], peaks=PEAKS)
    importlib.import_module("harness." + tr["driver"]).run(run)
    return run
