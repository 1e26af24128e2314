"""A run at a tiny size on the CPU, with the program broken underneath
the timed path, must come out not correct under the cells' committed
limits; the same run unbroken must come out correct.

The TPU's execution profile is forced (fused chunk driver, fused
distill_kl in interpret mode), so the faults sit in the path the chip
runs. Faults: a step that returns its state unchanged; half of every
batch left out, the means taken over the rest; the teacher's answer
altered where it is produced, each row's logits moved one class over.
One chip has no exchange between chips to leave out.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tiny import limits, tiny_run

STAGE2 = {"max_chunks": 6}


@pytest.fixture(autouse=True)
def tpu_profile(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "tpu")
    monkeypatch.setenv("REPRO_INTERPRET", "1")


def _half(a):
    return a[: a.shape[0] // 2]


def stage2_unchanged(monkeypatch):
    from repro.core import dense
    real = dense.make_dense_steps

    def broken(*a, **k):
        out = list(real(*a, **k))
        step = out[6]

        def epochs_step(gen_p, g_state, stu_p, s_state, gparams, keys):
            keep = jax.tree.map(jnp.copy, (gen_p, g_state, stu_p, s_state))
            return (*keep, step(gen_p, g_state, stu_p, s_state, gparams,
                                keys)[4])
        out[6] = epochs_step
        return tuple(out)
    monkeypatch.setattr(dense, "make_dense_steps", broken)


def stage2_half_batch(monkeypatch):
    from repro.core import losses
    for name in ("ce_loss", "div_loss", "distill_loss"):
        real = getattr(losses, name)
        monkeypatch.setattr(losses, name,
                            lambda a, b, *r, _f=real, **k:
                            _f(_half(a), _half(b), *r, **k))


def stage2_teacher_altered(monkeypatch):
    from repro.core import dense
    real = dense.grouped_ensemble_logits

    def broken(*a, **k):
        out = real(*a, **k)
        avg = out[0] if isinstance(out, tuple) else out
        avg = jnp.roll(avg, 1, axis=-1)
        return (avg, out[1]) if isinstance(out, tuple) else avg
    monkeypatch.setattr(dense, "grouped_ensemble_logits", broken)


STAGE2_FAULTS = [stage2_unchanged, stage2_half_batch,
                 stage2_teacher_altered]


@pytest.mark.parametrize("cell", ["r18x5.stage2", "zoo5.stage2"])
def test_sound_run_is_correct(cell):
    run = tiny_run("stage2", traffic=STAGE2, limits=limits(cell))
    assert run.non_finite == 0
    assert run.correct, run.details


@pytest.mark.parametrize("cell", ["r18x5.stage2", "zoo5.stage2"])
@pytest.mark.parametrize("fault", STAGE2_FAULTS, ids=lambda f: f.__name__)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    run = tiny_run("stage2", traffic=STAGE2, limits=limits(cell))
    assert not run.correct, (fault.__name__, run.compared, run.details)
    assert np.isfinite(list(run.compared.values())).all()

