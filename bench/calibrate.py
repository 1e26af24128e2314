#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, made on the chip.

    python3 bench/calibrate.py --workload r18x5.stage2 --seeds 1,2,3 \
        --variants control,altered

Reads, on each seed and at the cell's own size, the numbers the cell
compares against the float32 reference, for each of:

  program          the program's first chunk, as a run makes it (the
                   lower end of each limit), without the window;
  program_exact    the same with every matmul at HIGHEST precision and
                   the plain jnp distill_kl in place of the Pallas pair
                   (a witness for where the program's gap comes from);
  nudged           the reference itself from a start one ulp away (how
                   far rounding alone carries over the first chunk);
  control          the reference computed in bfloat16, the precision
                   below the configuration's float32, in the program's
                   place;
  half_batch       the reference with half of every batch left out and
                   the means taken over the rest (a planted fault), in
                   the program's place;
  altered          the reference with the teacher's answer moved one
                   class over (a planted fault), in the program's place.

One process reads every seed, so set-up is paid once. The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def stage2_readings(cfg, traffic, seed, variants):
    """Yields (variant, the numbers compared) on one seed, each against
    the same run of the reference."""
    import dataclasses
    import jax
    from harness import stage2
    ref = None
    for variant in variants:
        t0 = time.perf_counter()
        st = stage2.prepare(cfg, traffic, seed)
        if ref is None:
            ref = stage2.reference(cfg, st)
        if variant == "program":
            got = stage2.drive(st, lambda: False)
        elif variant == "program_exact":
            st["scfg"] = dataclasses.replace(st["scfg"],
                                             distill_kl_mode="ref")
            with jax.default_matmul_precision("highest"):
                got = stage2.drive(st, lambda: False)
        else:
            got = stage2.reference(cfg, st, **VARIANTS[variant])
        gap = stage2.reference_gap(cfg, st, got, ref)
        yield variant, dict(gap, seconds=time.perf_counter() - t0)


VARIANTS = {"control": {"prec": "bf16"}, "half_batch": {"half_batch": True},
            "altered": {"altered": True}, "nudged": {"nudged": True}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--variants", required=True,
                    help="comma-separated, of: program, program_exact, "
                         + ", ".join(VARIANTS))
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    import run as bench_run
    from harness.device import NoChip, pin_tpu
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell, cfg, traffic, _ = bench_run.find_cell(spec, args.workload)
    try:
        pin_tpu(cell["chips"])
    except NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    variants = args.variants.split(",")
    unknown = set(variants) - {"program", "program_exact", *VARIANTS}
    if unknown:
        ap.error(f"unknown variants {sorted(unknown)}")
    for seed in (int(s) for s in args.seeds.split(",")):
        for variant, gap in stage2_readings(cfg, traffic, seed,
                                            variants):
            gap.update(workload=args.workload, variant=variant, seed=seed)
            print(json.dumps(gap), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
