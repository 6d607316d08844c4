"""The comparison that decides ``correct``.

Each sampled answer (a job's result block after the wire: nine metrics
per grid cell) is held against the configuration's plain reference,
computed in float64 from the same prices. A cell agrees when every one
of its nine metrics lies within ``atol + rtol * |reference|``. The
tolerances sit far above float32 rounding of the metric arithmetic and
far below what one changed trading decision moves, so a cell disagrees
exactly when some decision (a crossover, a band entry) came out
differently. Float32 decides a knife-edge bar the other way now and
then; a wrong kernel, a dropped bar or a lower precision does it in
most cells. The one exception is the win test of ``hit_rate`` on a bar
whose return is within rounding of zero: it flips every cell of the job
at once, so ``hit_rate`` allows 2e-3: a win or two where 1,000 bars or
more are held, as in the SMA grids.

The numbers compared, each with its limit:

- ``missing``: jobs due in the window that never completed, or whose
  sampled result never arrived (limit 0);
- ``failed``: jobs the queue failed or re-queued, plus backend batch
  failures (limit 0);
- ``disagree_share``: the share of sampled cells that disagree (limit
  from the configuration, set from the program's and the control's
  readings).
"""

from __future__ import annotations

import numpy as np

# (atol, rtol) per metric, in the result block's row order.
TOLERANCES = (
    (2e-3, 1e-3),   # sharpe
    (2e-3, 1e-3),   # sortino
    (1e-4, 1e-3),   # max_drawdown
    (1e-4, 1e-3),   # total_return
    (1e-4, 1e-3),   # cagr
    (1e-4, 1e-3),   # volatility
    # hit_rate: a win or two. A held bar whose return lies within float32
    # rounding of zero is a win or not as the rounding falls, and moves
    # every cell long on that bar by 1/active (under 1e-3 with 1,000
    # active bars); a changed trade moves the turnover.
    (2e-3, 0.0),    # hit_rate
    (0.25, 0.0),    # n_trades (half-integers)
    (0.5, 0.0),     # turnover (integers)
)


def disagreeing_cells(ref: np.ndarray, got: np.ndarray) -> np.ndarray:
    """Boolean ``(P,)``: cells of ``(9, P)`` blocks where any metric lies
    outside its tolerance (a NaN on either side disagrees)."""
    ref = np.asarray(ref, np.float64)
    got = np.asarray(got, np.float64)
    if ref.shape != got.shape:
        raise ValueError(f"result shape {got.shape} != reference "
                         f"{ref.shape}")
    tol = np.asarray(TOLERANCES)
    bound = tol[:, :1] + tol[:, 1:] * np.abs(ref)
    with np.errstate(invalid="ignore"):
        ok = np.abs(got - ref) <= bound
    return ~ok.all(axis=0)


class Tally:
    """Accumulates the sampled comparison over a run."""

    def __init__(self):
        self.cells = 0
        self.disagree = 0
        self.jobs = 0
        self.worst_job_share = 0.0

    def add(self, ref: np.ndarray, got: np.ndarray | None) -> None:
        """Count one answer; a malformed one (None, or of another shape)
        disagrees in every cell."""
        if got is None or np.shape(got) != np.shape(ref):
            flags = np.ones(np.shape(ref)[-1], bool)
        else:
            flags = disagreeing_cells(ref, got)
        self.cells += flags.size
        self.disagree += int(flags.sum())
        self.jobs += 1
        self.worst_job_share = max(self.worst_job_share,
                                   float(flags.mean()))

    @property
    def share(self) -> float:
        return self.disagree / self.cells if self.cells else 1.0


def numbers(*, missing: int, failed: int, tally: Tally,
            limits: dict) -> dict:
    """``{name: {"value", "limit"}}`` for every number compared; a run is
    correct when each value is at or under its limit and some cells
    were compared."""
    return {
        "missing": {"value": int(missing), "limit": 0},
        "failed": {"value": int(failed), "limit": 0},
        "disagree_share": {"value": float(tally.share),
                           "limit": float(limits["disagree_share"])},
    }


def verdict(nums: dict, tally: Tally) -> bool:
    return tally.cells > 0 and all(
        v["value"] <= v["limit"] for v in nums.values())
