"""What a cell asks of the program under test before its set-up.

The harness builds a cell's universe and warms the program's shapes
before it measures; a program that cannot run the cell would spend that
set-up stalled instead of failing. A check here stops such a run at once
(exit status 1), from the one module the harness loads for a cell before
its set-up: the cell's reference (``reference/<strategy>.py``).
"""

from __future__ import annotations


def require_uncapped(strategy: str, bars: int) -> None:
    """Stop unless the program's router takes a ``bars``-bar history of
    ``strategy`` to its fused kernel on one chip, by the router's own cap
    test (``rpc.compute.bar_capped``). A program without that test caps
    every fused family at its one-block bar limit and sends a longer
    history to its generic path, which holds every (combo, bar) array of
    a job group in device memory: it stops too."""
    try:
        from distributed_backtesting_exploration_tpu.rpc.compute import (
            bar_capped)
    except ImportError as e:
        raise SystemExit(
            f"benchmark: the program has no router cap test "
            f"(rpc.compute.bar_capped: {e}), so it sends {bars}-bar "
            f"histories of {strategy} to its generic path, which cannot "
            f"sweep this cell in a run; stopping") from None
    if bar_capped(strategy, bars):
        raise SystemExit(
            f"benchmark: the program routes {bars}-bar histories of "
            f"{strategy} to its generic path, which cannot sweep this "
            f"cell in a run; stopping")
