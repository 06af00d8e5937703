"""The one-chip training cell with its timed path broken underneath: each
fault the cell can have makes ``correct`` false."""
import jax
import jax.numpy as jnp
import pytest

from bench.tests.tiny import run_tiny

CELL = "water3d.train"


def state_unchanged(monkeypatch):
    from repro.training import optim

    monkeypatch.setattr(optim.Adam, "update",
                        lambda self, grads, state, params: (params, state))


def half_batch(monkeypatch):
    from repro.training import trainer

    def first_half_mean(values, sample_mask):
        return jax.tree.map(lambda v: jnp.mean(v[: v.shape[0] // 2]), values)

    monkeypatch.setattr(trainer, "_batch_mean", first_half_mean)


def answer_altered(monkeypatch):
    from repro.training import trainer

    real = trainer.combined_objective

    def altered(*args, **kw):
        loss, parts = real(*args, **kw)
        return loss * 1.01, parts

    monkeypatch.setattr(trainer, "combined_objective", altered)


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   answer_altered])
def test_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = run_tiny(CELL)
    assert res["correct"] is False, res["checks"]
