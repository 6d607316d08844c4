"""Fused-vs-generic error budgets: the one table that ``bench.py --verify``
and ``chip_smoke.py`` both assert.

A "flip" is a materially different Sharpe cell (relative error above
``FLIP_REL``): a knife-edge crossover the MXU's selection matmul resolved
the other way. Flip-rate caps carry ~4x headroom over the rates measured
on the chip (r4: every kernel <= 0.05%); a best-parameter flip changes
which combo a researcher would pick. See DESIGN.md "Fused-kernel error
budgets".
"""

from __future__ import annotations

import numpy as np

FLIP_REL = 1e-2
FLIP_BUDGET = {"pairs": 0.002}
FLIP_BUDGET_DEFAULT = 0.002
ARGMAX_BUDGET = {"pairs": 1}      # knife-edge band entries, ~1 in 50
ARGMAX_BUDGET_DEFAULT = 0


def compare_sharpe(name: str, ref, got) -> dict:
    """Compare ``(rows, P)`` Sharpe matrices of the reference and the
    fused path under ``name``'s budget. Returns the counts and
    ``over_budget``."""
    r = np.asarray(ref, np.float64)
    g = np.asarray(got, np.float64)
    if r.shape != g.shape:
        raise ValueError(f"{name}: reference shape {r.shape} != fused "
                         f"shape {g.shape}")
    rel = np.abs(g - r) / (np.abs(r) + 1e-6)
    # NaN on one side only fails both comparisons below and would vanish
    # from the report: count it as a flip.
    nan_mismatch = int((np.isnan(g) != np.isnan(r)).sum())
    rel = np.where(np.isnan(g) & np.isnan(r), 0.0, rel)
    flips = int((rel > FLIP_REL).sum()) + nan_mismatch
    argmax_flips = int((np.argmax(g, axis=1)
                        != np.argmax(r, axis=1)).sum())
    flip_cap = FLIP_BUDGET.get(name, FLIP_BUDGET_DEFAULT)
    argmax_cap = ARGMAX_BUDGET.get(name, ARGMAX_BUDGET_DEFAULT)
    return {
        "cells": int(rel.size),
        "max_rel_err_nonflip": float(rel[rel <= FLIP_REL].max())
        if (rel <= FLIP_REL).any() else None,
        "entry_flips": flips,
        "nan_mismatches": nan_mismatch,
        "flip_rate": flips / rel.size,
        "best_param_flips": argmax_flips,
        "n_tickers": int(r.shape[0]),
        "flip_cap": flip_cap,
        "argmax_cap": argmax_cap,
        "over_budget": bool(flips / rel.size > flip_cap
                            or argmax_flips > argmax_cap),
    }
