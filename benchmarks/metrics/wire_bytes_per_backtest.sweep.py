"""Serialized proto bytes over the worker's RPCs in the window
(``dbx_worker_wire_bytes_total``, every method and direction), per
backtest completed in the window."""


def read(run):
    if not run.backtests:
        return None
    total = sum(v for k, v in run.counters.items()
                if k.startswith("dbx_worker_wire_bytes_total{"))
    return total / run.backtests if total > 0 else None
