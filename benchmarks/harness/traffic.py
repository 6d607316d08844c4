"""The one traffic generator: jobs and their schedule, from a cell's
configuration, its traffic file and the seed.

A traffic file is data (``traffic/<name>.json``):

- ``"loop": "closed"``: one client keeps ``queued_sweeps`` whole sweeps
  (one job per instrument of the configuration, on the configuration's
  grid) pending, and enqueues the next whenever fewer wait. The window
  opens at the first completion after ``lead_in_s`` of steady load.
- ``"loop": "open"``: single-instrument jobs arrive as a Poisson stream
  at ``rate_per_s``, each on ``grid``, the instrument drawn by
  ``popularity`` (Zipf with exponent ``zipf_s`` over a seeded ranking
  of the universe). Every seed sends the same number of jobs in the
  lead-in and in the window (a Poisson stream given its count is
  uniform), in another order.

``sample_jobs`` (configuration, per loop) sets how many answers the
correctness check compares: instruments for a closed loop (every
instance of them in the window is compared), jobs due in the window for
an open loop.
"""

from __future__ import annotations

import numpy as np

from harness import universe


def parse_grid(spec: str) -> dict[str, np.ndarray]:
    """``"fast=5:25,slow=30:230:2"`` -> float32 axes (``start:stop[:step]``
    as ``arange``, or ``a;b;c``)."""
    axes = {}
    for part in spec.split(","):
        name, _, rng = part.partition("=")
        if ":" in rng:
            p = [float(v) for v in rng.split(":")]
            axes[name.strip()] = np.arange(p[0], p[1], p[2] if len(p) > 2
                                           else 1.0, dtype=np.float32)
        else:
            axes[name.strip()] = np.asarray(
                [float(v) for v in rng.split(";")], np.float32)
    return axes


class Workload:
    """Data and jobs of one cell and seed."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from distributed_backtesting_exploration_tpu.utils import data

        self.config = config
        self.traffic = traffic
        self.loop = traffic["loop"]
        self.strategy = config["strategy"]
        self.axes = parse_grid(traffic.get("grid") or config["grid"])
        self.combos = int(np.prod([a.size for a in self.axes.values()]))
        self.cost = float(config["cost"])
        self.ppy = int(config["periods_per_year"])
        close = universe.closes(config, seed)
        self.fields = universe.ohlcv(config, seed, close)
        self.blobs = [data.to_wire_bytes(data.OHLCV(**f))
                      for f in self.fields]
        self.instruments = universe.instruments(config, seed)
        self.bars = int(config["bars"])
        self.rng = universe.rng_for(seed, "traffic")

    def record(self, jid: str, inst: tuple):
        from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
            JobRecord)

        return JobRecord(
            id=jid, strategy=self.strategy, grid=self.axes, cost=self.cost,
            periods_per_year=self.ppy, ohlcv=self.blobs[inst[0]],
            ohlcv2=self.blobs[inst[1]] if len(inst) > 1 else None)

    def legs(self, inst: tuple) -> list[dict]:
        return [self.fields[i] for i in inst]

    def sample_count(self) -> int:
        return int(self.config["correct"]["sample_jobs"][self.loop])

    # -- closed loop -------------------------------------------------------

    def sweep(self, k: int, tag: str = "s") -> list:
        return [self.record(f"{tag}{k}.{i}", inst)
                for i, inst in enumerate(self.instruments)]

    def sampled_instruments(self) -> np.ndarray:
        n = len(self.instruments)
        return np.sort(self.rng.choice(n, min(self.sample_count(), n),
                                       replace=False))

    # -- open loop ---------------------------------------------------------

    def schedule(self, seconds: float):
        """``(offsets, instruments)``: due offsets in seconds from the
        window's start (negative in the lead-in) and each job's
        instrument index."""
        rate = float(self.traffic["rate_per_s"])
        lead = float(self.traffic["lead_in_s"])
        n_lead = int(round(rate * lead))
        n_win = int(round(rate * seconds))
        off = np.concatenate([
            np.sort(self.rng.uniform(-lead, 0.0, n_lead)),
            np.sort(self.rng.uniform(0.0, seconds, n_win))])
        pop = self.traffic["popularity"]
        n = len(self.instruments)
        ranks = np.arange(1, n + 1, dtype=np.float64)
        p = ranks ** -float(pop["zipf_s"])
        ranking = self.rng.permutation(n)
        picks = ranking[self.rng.choice(n, off.size, p=p / p.sum())]
        return off, picks

    def sampled_jobs(self, n_window: int, n_lead: int) -> np.ndarray:
        k = min(self.sample_count(), n_window)
        return n_lead + np.sort(self.rng.choice(n_window, k, replace=False))
