"""Shared execution-config base for training and serving.

`GASConfig` (core/runtime.py) and `ServeConfig` (core/serve.py) used to
declare the same three knobs independently — the kernel `backend`, the
history-table `history_dtype`, and a staleness bound — with nothing but
convention keeping their semantics aligned. `HistoryExecConfig` is the
single declaration both inherit: one docstring, one default, one field
name per knob, so the training and serving surfaces cannot drift apart
on how a backend or history precision is selected.

All base fields are keyword-only (`kw_only=True`, so subclasses keep
their own positional fields — `GASConfig(num_parts)` stays valid) and
every subclass remains a frozen dataclass.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True, kw_only=True)
class HistoryExecConfig:
    """Knobs shared by every config that executes against a
    `HistoryStore`.

    `backend` — kernel backend for history I/O and aggregation; None
    auto-selects via `kernels.ops.resolve_backend` ($REPRO_KERNEL_BACKEND
    -> platform default). For serving, None additionally defers to the
    bound store's backend (`gnn.model.resolve_store`).

    `history_dtype` — history-table storage precision; None resolves via
    `history.resolve_history_dtype` ($REPRO_HISTORY_DTYPE -> "f32").
    Training creates the store at this precision; serving validates it
    against the bound store (the store's own dtype always wins at run
    time).

    `staleness_slo` — max acceptable history age (steps since a row was
    last pushed) of any row an execution may read. Training runs
    unbounded (None: GAS reads whatever the previous epoch left — the
    paper's Theorem 2 bounds the resulting error instead of preventing
    it). Serving overrides the default to 0 (refresh to exactness) and
    treats None as pure cache reads (never refresh).
    """
    backend: Optional[str] = None
    history_dtype: Optional[str] = None
    staleness_slo: Optional[int] = None

    def __post_init__(self):
        # fail at construction, not at first use: a typo'd dtype or
        # backend raises the canonical registry error immediately
        if self.history_dtype is not None:
            from .history import get_codec
            get_codec(self.history_dtype)
        if self.backend is not None:
            from repro.kernels.ops import BACKENDS
            if self.backend not in BACKENDS:
                raise ValueError(
                    f"backend must be one of {BACKENDS}, "
                    f"got {self.backend}")
