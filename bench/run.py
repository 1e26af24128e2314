#!/usr/bin/env python3
"""On-chip benchmark of the DENSE one-shot round.

    python3 bench/run.py --workload r18x5.stage2 --seed 7 --seconds 40 \
        --trace 0

Runs one cell of ``BENCHMARK.json`` on the TPU this process starts on
and prints, as the last line of stdout, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``compared``:
each number the correctness check compared, beside its limit.

Everything is found by name: the cell's configuration in the file
``BENCHMARK.json`` gives it, its traffic in ``traffic/<name>.json``
(whose ``driver`` names the module under ``harness/`` that runs it),
its limits in ``limits/<cell>.json``, and each per-layer metric in
``metrics/<name>.py``, a reader ``read(run) -> float | None``. A cell,
a traffic mix or a metric is added by adding files and entries.

With no TPU, or fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def find_cell(spec: dict, name: str):
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    limits_path = os.path.join(BENCH, "limits", name + ".json")
    limits = load_json(limits_path) if os.path.exists(limits_path) else {}
    return cell, cfg, traffic, limits


def metrics_of(spec: dict, cell_name: str, per_layer: bool) -> list:
    """The cell's metrics: end-to-end ones, or per-layer ones, each
    listed for the cell or listed for no cell in particular."""
    group = spec["per_layer"] if per_layer else spec["end_to_end"]
    return [m for m in group
            if cell_name in m.get("workloads", [cell_name])]


def load_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


class Run:
    """One run of one cell: what the driver measures and compares, and
    what the per-layer readers read."""

    def __init__(self, cell, cfg, traffic, limits, *, seed, seconds, trace,
                 devices, peaks):
        import jax
        from harness import common
        self.cell, self.cfg, self.traffic = cell, cfg, traffic
        self.limits, self.seed, self.seconds = limits, seed, seconds
        self.tracing, self.devices, self.peaks = trace, devices, peaks
        self.chips = cell["chips"]
        self.e2e, self.compared, self.details = {}, {}, {}
        self.attempted, self.failed, self.non_finite = 0, 0, None
        self.unit_rate, self.window_units = 0.0, 0
        self.counts: dict = {}
        self.setup_s = self.memory_peak = self.reference_s = None
        self.trace = None
        self.trace_dir = None
        self.compiles = common.CompileCounter()
        self._span = None
        self._jax = jax

    def start_window(self):
        """Called by the driver where set-up ends and the window begins.
        A traced run traces the window's first unit (a chunk, a job) and
        the boundary after it, and its window is that unit: a TPU trace
        holds a bounded number of events, so a longer span would lose
        its tail, and stopping the profiler takes seconds that would
        fall into the next unit's time."""
        self.setup_s = time.perf_counter() - T_START
        if self.tracing:
            jax = self._jax
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("window")
            self._span.__enter__()
        self.compiles.active = True

    def unit_done(self):
        """Called by the driver at each unit boundary of the window."""
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            self._jax.profiler.stop_trace()

    def end_window(self):
        self.compiles.active = False
        self.unit_done()

    def read_memory(self):
        from harness.device import memory_peak_bytes
        self.memory_peak = memory_peak_bytes(self.devices)

    def compare(self, name, value, detail=None):
        self.compared[name] = value
        if detail is not None:
            self.details[name] = detail

    @property
    def correct(self) -> bool:
        return bool(self.non_finite == 0 and self.compared and all(
            name in self.limits and value <= self.limits[name]["limit"]
            for name, value in self.compared.items()))


def report(spec: dict, cell_name: str, run: Run, *, trace: bool,
           trace_out: str = "") -> dict:
    """The result line of a finished run; the numbers compared, each
    beside its limit, also go to stderr as its last lines."""
    device = {"platform": run.devices[0].platform,
              "kind": run.devices[0].device_kind, "count": len(run.devices),
              "memory_peak_bytes": run.memory_peak}
    metrics, breakdown = {}, None
    if trace:
        from harness.trace import Trace
        run.trace = Trace.from_dir(run.trace_dir)
        if trace_out:
            run.trace.to_json(trace_out)
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = run.trace.breakdown()
        for m in metrics_of(spec, cell_name, per_layer=True):
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(run.e2e, setup_s=run.setup_s)
        for m in metrics_of(spec, cell_name, per_layer=False):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    print(f"bench: setup_s={run.setup_s} window units={run.window_units} "
          f"rate={run.unit_rate} compiles_in_window={run.compiles.count} "
          f"reference_s={run.reference_s} memory_peak_bytes="
          f"{run.memory_peak}", file=sys.stderr)
    for name, d in run.details.items():
        print(f"bench: {name} detail {json.dumps(d)}", file=sys.stderr)
    compared = {name: {"value": v,
                       "limit": run.limits.get(name, {}).get("limit")}
                for name, v in run.compared.items()}
    compared["non_finite"] = {"value": run.non_finite, "limit": 0}
    for name, c in compared.items():
        print(f"compared: {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out", default="",
                    help="also write the trace's event records (JSON, "
                         "gzipped if the name ends in .gz) to this path")
    args = ap.parse_args(argv)

    sys.path.insert(0, BENCH)
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg, traffic, limits = find_cell(spec, args.workload)
    from harness.device import NoChip, peaks, pin_tpu
    try:
        devices = pin_tpu(cell["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: the system under test is not at {src}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.configs.backend import resolve_exec_policy
    pol = resolve_exec_policy(None)
    print(f"bench: {args.workload} seed={args.seed} on {len(devices)} x "
          f"{devices[0].device_kind}; policy loop={pol.loop} "
          f"distill_kl={pol.distill_kl} client_loop={pol.client_loop}",
          file=sys.stderr, flush=True)

    run = Run(cell, cfg, traffic, limits, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace), devices=devices,
              peaks=peaks(devices[0].device_kind))
    driver = importlib.import_module("harness." + traffic["driver"])
    driver.run(run)

    result = report(spec, args.workload, run, trace=bool(args.trace),
                    trace_out=args.trace_out)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
