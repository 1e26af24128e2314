"""Seconds of set-up spent tracing, lowering and compiling or loading
from the persistent cache: what ``repro.obs`` recorded from JAX's
compile events before the stage-2 call's first chunk boundary (its first
``dense.eval`` span), where the window starts."""


def read(run):
    if run.traffic["driver"] != "stage2":
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    spans = obs.snapshot()["spans"]
    setups = [s["start_ns"] for s in spans if s["name"] == "dense.setup"]
    evals = [s for s in spans if s["name"] == "dense.eval"
             and setups and s["start_ns"] > max(setups)]
    if not evals:
        return None
    return min(evals, key=lambda s: s["start_ns"])["compile_s_at_start"]
