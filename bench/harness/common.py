"""What every driver shares: seeds, the window's stopping rule, host
spans, the count of compiles inside the window, and the comparison of
parameter changes that decides ``correct``."""
from __future__ import annotations

import time
from dataclasses import dataclass, field, fields

import numpy as np

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# leaves whose reference gradient is under this share of the median
# leaf's move by round-off alone (a bias under softmax, a running stat)
GRAD_FLOOR = 1e-3


def seed_words(seed: int, n: int) -> list[int]:
    """``n`` independent 31-bit integers drawn from ``--seed`` (any whole
    number, however large)."""
    ss = np.random.SeedSequence(int(seed) % (1 << 64))
    return [int(w) & 0x7FFFFFFF for w in ss.generate_state(n)]


def program_config(cfg: dict, *, epochs: int):
    """The program's ``DenseExperimentConfig`` for a configuration file:
    its numbers as they stand, the stage-2 epochs that the traffic cuts
    as given."""
    from repro.configs.paper_cifar import DenseExperimentConfig
    names = {f.name for f in fields(DenseExperimentConfig)}
    kw = {k: (tuple(v) if isinstance(v, list) else v)
          for k, v in cfg.items() if k in names and k != "epochs"}
    return DenseExperimentConfig(**kw, epochs=epochs)


def span(name: str):
    """A host span on the profiler's clock, named for what the host is
    doing; the trace reduction names idle gaps by these."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclass
class Window:
    """The measured window: whole units (chunks, jobs) back to back.

    ``mark()`` is called at each unit boundary. The window starts at the
    first and closes at the boundary nearest ``seconds``: another unit
    is started while the window, with one more unit of the mean length
    so far, would end nearer to ``seconds`` than it ends now. At least
    one unit is measured."""
    seconds: float
    marks: list = field(default_factory=list)

    def mark(self) -> bool:
        """Record a boundary; True when the window goes on."""
        self.marks.append(time.perf_counter())
        n = len(self.marks) - 1
        if n == 0:
            return True
        elapsed = self.marks[-1] - self.marks[0]
        return elapsed + 0.5 * elapsed / n < self.seconds

    @property
    def units(self) -> int:
        return len(self.marks) - 1

    @property
    def seconds_measured(self) -> float:
        return self.marks[-1] - self.marks[0]


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache
    while ``active``: inside the window there should be none."""

    def __init__(self):
        import jax
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if self.active and event == BACKEND_COMPILE_EVENT:
            self.count += 1


def _leaves(tree):
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(p), np.asarray(v, np.float64))
            for p, v in flat]


def change_gap(start, program, reference, ref_grad) -> dict:
    """How far the program's parameter change departs from the
    reference's, by the worst leaf.

    Per leaf: the gap between the norm of the program's change and the
    norm of the reference's, over the larger of the reference's change
    norm of that leaf and of the median leaf. Leaves whose reference
    gradient is under ``GRAD_FLOOR`` of the median leaf's are left out:
    round-off alone moves them."""
    s, p, r, g = (_leaves(t) for t in (start, program, reference, ref_grad))
    gn = np.array([np.linalg.norm(v) for _, v in g])
    keep = gn >= GRAD_FLOOR * np.median(gn)
    dp = np.array([np.linalg.norm(b - a) for (_, a), (_, b) in zip(s, p)])
    dr = np.array([np.linalg.norm(b - a) for (_, a), (_, b) in zip(s, r)])
    if not np.all(np.isfinite(dp)):
        return {"gap": float("inf"), "leaf": "non-finite", "median": 0.0,
                "leaves": int(keep.sum())}
    denom = np.maximum(dr, np.median(dr[keep]))
    gaps = np.where(keep, np.abs(dp - dr) / np.maximum(denom, 1e-30), 0.0)
    worst = int(np.argmax(gaps))
    return {"gap": float(gaps[worst]), "leaf": s[worst][0],
            "median": float(np.median(gaps[keep])),
            "leaves": int(keep.sum())}


def logit_gap(start, program, reference) -> float:
    """How far the program's change of the student's function departs
    from the reference's: the norm of the gap between the two students'
    logits on the same probe images, over the norm of the reference's
    change of them. A change in the wrong direction reads as large as
    one of the wrong size."""
    change = np.linalg.norm(reference - start)
    return float(np.linalg.norm(program - reference) / max(change, 1e-30))


def loss_gap(program, reference) -> float:
    """The first epoch's losses (the last generator step's, the
    student's), the worst of the two gaps as a share of the
    reference's."""
    p, r = np.asarray(program)[0], np.asarray(reference)[0]
    return float(np.max(np.abs(p - r) / np.maximum(np.abs(r), 1e-30)))


def non_finite(tree) -> int:
    """Number of non-finite values in a tree of arrays."""
    import jax
    return int(sum(np.size(a) - np.count_nonzero(np.isfinite(a))
                   for a in map(np.asarray, jax.tree.leaves(tree))))
