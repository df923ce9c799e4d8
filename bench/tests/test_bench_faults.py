"""A run with the timed path broken underneath comes out not correct:
once for each fault a training cell can have."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from repro.core import history, runtime

CELL = "arxiv-gcn-f32.train"


def unchanged_state(monkeypatch):
    """Each epoch computes its losses but returns the state it got."""
    epoch = runtime.train_epoch

    def broken(plan, state, e):
        _, metrics = epoch(plan, jax.tree_util.tree_map(jnp.copy, state), e)
        return state, metrics

    monkeypatch.setattr(runtime, "train_epoch", broken)


def half_batch(monkeypatch):
    """Every other node leaves the loss: the mean is over the rest."""
    build = runtime.build_plan

    def broken(*a, **kw):
        plan = build(*a, **kw)
        keep = jnp.arange(plan.train_mask.shape[0]) % 2 == 0
        plan.train_mask = plan.train_mask & keep
        return plan

    monkeypatch.setattr(runtime, "build_plan", broken)


def altered_push(monkeypatch):
    """The history rows a batch pushes are 10% off."""
    push = history.HistoryStore.push

    def broken(self, ell, idx, values, mask):
        return push(self, ell, idx, values * 1.1, mask)

    monkeypatch.setattr(history.HistoryStore, "push", broken)


@pytest.mark.parametrize("fault", [unchanged_state, half_batch,
                                   altered_push])
def test_fault_is_not_correct(tiny_bench, monkeypatch, fault):
    fault(monkeypatch)
    out = harness.run(CELL, 2**31 + 5, 0.2, False, time.perf_counter(),
                            bench=tiny_bench, chips_required=False)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
