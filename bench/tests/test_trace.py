"""The trace reduction, on records made by hand and on a small trace
recorded on a TPU v5e."""
import os

import pytest

from harness.trace import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e_stage2_trace.json.gz")
MS = 1_000_000


def hand_made():
    # a 10 ms window; core 0 runs a while loop (0-4 ms) holding two ops,
    # one op at 6-7 ms and a kernel at 8-8.5 ms; the host's eval_hook
    # covers 4-6 ms and a dispatch event 7-8 ms
    dev = [["while.1", 0, 4 * MS, {}],
           ["fusion.2", 0, 1 * MS, {}],
           ["convolution.3", 1 * MS, 2 * MS, {}],
           ["fusion.2", 6 * MS, 1 * MS, {}],
           ["custom-call.9", 8 * MS, MS // 2,
            {"long_name": "_kl_fwd_kernel"}]]
    host = [["window", 0, 10 * MS, "python"],
            ["eval_hook", 4 * MS, 2 * MS, "python"],
            ["PjitFunction(epochs_step)", 7 * MS, 1 * MS, "python"]]
    return Trace({"devices": {"/device:TPU:0": dev}, "host": host})


def test_busy_and_idle_share():
    t = hand_made()
    assert t.window_s == pytest.approx(0.010)
    # busy: 0-4, 6-7, 8-8.5 ms
    assert t.busy_s == pytest.approx(0.0055)
    assert t.idle_share == pytest.approx(0.45)


def test_self_time_of_enclosing_ops():
    ops = hand_made().op_seconds()
    assert ops["while.1"] == pytest.approx(0.001)
    assert ops["fusion.2"] == pytest.approx(0.002)
    assert ops["convolution.3"] == pytest.approx(0.002)


def test_kernel_time_by_long_name():
    assert hand_made().kernel(r"_kl_fwd_kernel") == (pytest.approx(5e-4), 1)
    assert hand_made().kernel(r"no_such_kernel") == (0.0, 0)


def test_gaps_named_by_host_spans():
    gaps = hand_made().idle_gaps()
    assert gaps[0] == ["eval_hook", pytest.approx(0.002)]
    names = [g[0] for g in gaps]
    assert "PjitFunction(epochs_step)" in names
    assert sum(g[1] for g in gaps) == pytest.approx(0.0045)


def test_breakdown_lists_at_most_ten():
    b = hand_made().breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def recorded():
    return Trace.from_json(RECORDED)


def test_recorded_busy_and_self_time_add_up():
    t = recorded()
    assert t.window_s == pytest.approx(0.120)
    assert 0.0 < t.busy_s < t.window_s
    assert sum(t.op_seconds().values()) == pytest.approx(t.busy_s)


def test_recorded_chunk_boundary_is_the_eval_hook():
    # the end of the window is the chunk boundary: ~60 ms with no
    # device op, while the host ran the benchmark's eval_fn hook
    name, seconds = recorded().idle_gaps()[0]
    assert name == "eval_hook"
    assert 0.03 < seconds < 0.1


def test_recorded_distill_kl_calls():
    import importlib.util
    import types
    path = os.path.join(os.path.dirname(HERE), "metrics",
                        "distill_kl_roofline.py")
    spec = importlib.util.spec_from_file_location("roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    t = recorded()
    run = types.SimpleNamespace(
        traffic={"driver": "stage2"}, trace=t,
        cfg={"synth_batch": 128, "num_classes": 10, "t_g": 30},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    seconds, calls = t.kernel("tpu_custom_call")
    assert calls == 2 and seconds > 0
    share = mod.read(run)
    assert 0.0 < share <= 100.0
    # with no such kernel on the path the metric is silent, not 0
    run.cfg = dict(run.cfg, num_classes=11)
    assert mod.read(run) is None
