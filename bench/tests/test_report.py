"""The result line of a run at a tiny size on the CPU: the keys the
contract names, the cell's metrics with their units, and the numbers
compared, last, each beside its limit."""
import json
import os

import pytest

from conftest import ROOT
from tiny import limits, tiny_run

CELLS = {"stage2": ("r18x5.stage2", {"max_chunks": 6})}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("traffic", sorted(CELLS))
@pytest.mark.parametrize("trace", [False, True])
def test_result_line(spec, traffic, trace, capsys):
    import run as bench_run
    cell, tr = CELLS[traffic]
    run = tiny_run(traffic, traffic=tr, limits=limits(cell),
                   trace=trace)
    result = bench_run.report(spec, cell, run, trace=trace)
    assert list(result)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(result)
    assert result["correct"] is True and result["attempted"] > 0
    want = bench_run.metrics_of(spec, cell, per_layer=trace)
    units = {m["name"]: m["unit"] for m in want}
    assert set(result["metrics"]) <= set(units)
    for name, m in result["metrics"].items():
        assert m["unit"] == units[name] and m["value"] > 0
    if trace:
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert result["device"]["window_s"] > 0
    else:
        assert set(result["metrics"]) == set(units)
    for c in result["compared"].values():
        assert set(c) == {"value", "limit"}
        assert c["value"] <= c["limit"]
    err = capsys.readouterr().err.strip().splitlines()
    assert all(line.startswith("compared: ")
               for line in err[-len(result["compared"]):])
    json.dumps(result)
