"""Real multi-process jax.distributed bring-up (VERDICT r2 #8).

Two OS processes, each with 4 virtual CPU devices, form one 8-device JAX
slice through a loopback coordinator: ``multihost.initialize`` runs its
*distributed* path (not the single-process no-op), ``host_shard`` splits a
work list across the processes, and a ticker-sharded sweep runs over the
global mesh with each process verifying its addressable shard against a
locally-computed reference.
"""

import os
import socket
import subprocess
import sys

import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import os, sys
pid = int(sys.argv[1]); coord = sys.argv[2]
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[3])
import numpy as np
import jax, jax.numpy as jnp
# The children run on the CPU whatever the parent's environment says (see
# tests/conftest.py). multihost.initialize enables gloo CPU collectives
# itself when the platform is cpu.
jax.config.update("jax_platforms", "cpu")
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_backtesting_exploration_tpu.parallel import (
    multihost, sharding, sweep as sweep_mod)
from distributed_backtesting_exploration_tpu.models import base
from distributed_backtesting_exploration_tpu.utils import data as data_mod

n = multihost.initialize(coord, num_processes=2, process_id=pid)
assert n == 2, n
assert jax.process_count() == 2
assert jax.local_device_count() == 4 and jax.device_count() == 8

# host_shard: disjoint halves of an 8-item work list.
sl = multihost.host_shard(8)
assert (sl.start, sl.stop) == ((0, 4) if pid == 0 else (4, 8)), sl

# Tiny sweep sharded over the GLOBAL 8-device mesh: every process
# contributes its local ticker rows and verifies its addressable shard.
mesh = sharding.make_mesh()
assert mesh.devices.size == 8
axis = mesh.axis_names[0]
ohlcv_np = data_mod.synthetic_ohlcv(8, 64, seed=0)
row_sh = NamedSharding(mesh, P(axis, None))
rep_sh = NamedSharding(mesh, P())

def global_rows(x):
    return jax.make_array_from_process_local_data(row_sh, np.asarray(x)[sl])

def replicated(x):
    return jax.make_array_from_process_local_data(rep_sh, np.asarray(x))

panel = type(ohlcv_np)(*(global_rows(f) for f in ohlcv_np))
grid_np = sweep_mod.product_grid(
    fast=np.asarray([3.0, 5.0], np.float32),
    slow=np.asarray([10.0, 20.0], np.float32))
grid = {k: replicated(v) for k, v in grid_np.items()}
strategy = base.get_strategy("sma_crossover")
m = sharding.sharded_sweep(mesh, panel, strategy, grid, cost=1e-3)

# Local reference for this process's ticker rows.
local_panel = type(ohlcv_np)(*(jnp.asarray(np.asarray(f)[sl])
                               for f in ohlcv_np))
want = sweep_mod.jit_sweep(local_panel, strategy,
                           {k: jnp.asarray(v) for k, v in grid_np.items()},
                           cost=1e-3)
got_rows = sorted(
    (s.index[0].start or 0, np.asarray(s.data))
    for s in m.sharpe.addressable_shards)
got = np.concatenate([r for _, r in got_rows], axis=0)
np.testing.assert_allclose(got, np.asarray(want.sharpe), rtol=1e-5,
                           atol=1e-6)

# A worker process on a multi-host slice must advertise and mesh over its
# OWN chips only (it cannot device_put to another host's devices); the
# slice-wide scale-out axis is the dispatcher's job-level DP.
from distributed_backtesting_exploration_tpu.rpc import compute
backend = compute.JaxSweepBackend(use_fused=False, use_mesh=True)
assert backend.chips == 4, backend.chips
assert backend._mesh is not None and backend._mesh.devices.size == 4
assert all(d.process_index == jax.process_index()
           for d in backend._mesh.devices.flat)
print("MULTIHOST_OK", pid, flush=True)
"""


_SLICE_CHILD = r"""
import os, sys
pid = int(sys.argv[1]); coord = sys.argv[2]; dispatcher = sys.argv[4]
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[3])
import jax
jax.config.update("jax_platforms", "cpu")
from distributed_backtesting_exploration_tpu.parallel import multihost
from distributed_backtesting_exploration_tpu.rpc.slice_worker import (
    SliceWorker)

n = multihost.initialize(coord, num_processes=2, process_id=pid)
assert n == 2 and jax.device_count() == 8
w = SliceWorker(dispatcher, worker_id="slice-under-test",
                poll_interval_s=0.1, jobs_per_chip=1)
assert w.chips == 8
w.run(max_idle_polls=20)
print("SLICE_OK", pid, w.jobs_completed, flush=True)
print("SLICE_TS", pid, len(w._ts_fns), flush=True)
"""


@pytest.mark.slow   # 2-process jax.distributed slice: minutes of wall on
                    # CPU-only boxes (gloo collectives + fresh-jax children)
def test_slice_worker_drains_live_dispatcher(tmp_path):
    """VERDICT r3 #8 — the two proven halves joined: a 2-process
    jax.distributed worker (4+4 virtual devices, ONE 8-device mesh)
    serves a LIVE dispatcher as one logical worker. The slice drains the
    queue and every job's stored DBXM block matches the direct
    single-device sweep."""
    import numpy as np

    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from distributed_backtesting_exploration_tpu.models import base
    from distributed_backtesting_exploration_tpu.parallel import sweep
    from distributed_backtesting_exploration_tpu.rpc import wire
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        Dispatcher, DispatcherServer, JobQueue, PeerRegistry,
        synthetic_jobs)
    from distributed_backtesting_exploration_tpu.utils import data

    grid = {"fast": np.float32([3.0, 5.0]), "slow": np.float32([10.0, 20.0])}
    queue = JobQueue()
    recs = synthetic_jobs(6, 64, "sma_crossover", grid, cost=1e-3, seed=13)
    for rec in recs:
        queue.enqueue(rec)
    # A two-legged job the slice worker does NOT implement: it must be
    # completed empty with a loud error, not crash the slice or
    # requeue-loop forever.
    pair_rec = synthetic_jobs(
        1, 64, "pairs", {"lookback": np.float32([8.0]),
                         "z_entry": np.float32([1.0])}, seed=14)[0]
    queue.enqueue(pair_rec)
    # A long-context job (bars above the shrunk DBX_SLICE_LC_CAP, and NOT
    # divisible by the 8-chip mesh so the t_real pad contract is live):
    # the slice must shard its BAR axis over the global mesh instead of
    # replicating pad rows on every chip. Momentum keeps parity tight —
    # its signal compares raw closes, so positions are exact.
    lc_grid = {"lookback": np.float32([10.0, 20.0])}
    lc_rec = synthetic_jobs(1, 201, "momentum", lc_grid, cost=1e-3,
                            seed=15)[0]
    queue.enqueue(lc_rec)
    results = tmp_path / "results"
    disp = Dispatcher(queue, PeerRegistry(prune_window_s=120.0),
                      results_dir=str(results))
    srv = DispatcherServer(disp, bind="localhost:0").start()

    with socket.socket() as s:
        s.bind(("localhost", 0))
        coord = f"localhost:{s.getsockname()[1]}"
    script = tmp_path / "slice_child.py"
    script.write_text(_SLICE_CHILD)
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["DBX_SLICE_LC_CAP"] = "96"   # shrink the long-context trigger
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(pid), coord, _REPO_ROOT,
             f"localhost:{srv.port}"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        for pid in (0, 1)
    ]
    try:
        outs = [p.communicate(timeout=280) for p in procs]
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        srv.stop()
        pytest.fail("slice worker children timed out")
    srv.stop()
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"child failed:\n{err[-3000:]}"
    assert "SLICE_OK 0 8" in outs[0][0]   # 6 sweeps + empty pairs + lc
    assert "SLICE_OK 1" in outs[1][0]
    # The long-context job compiled a time-sharded program on BOTH
    # processes (the SPMD route ran slice-wide, not leader-only).
    assert "SLICE_TS 0 1" in outs[0][0]
    assert "SLICE_TS 1 1" in outs[1][0]
    assert queue.drained
    s = queue.stats()
    assert s["jobs_completed"] == 8 and s["jobs_failed"] == 0
    # The unsupported pairs job completed with an EMPTY block (which the
    # dispatcher does not persist — no stored result, but no requeue loop).
    assert not (results / f"{pair_rec.id}.dbxm").exists()

    # Per-job parity: each stored DBXM block equals the direct sweep.
    flat = sweep.product_grid(
        **{k: jnp.asarray(v) for k, v in grid.items()})
    strat = base.get_strategy("sma_crossover")
    for rec in recs:
        blob = (results / f"{rec.id}.dbxm").read_bytes()
        got = wire.metrics_from_bytes(blob)
        series = data.from_wire_bytes(rec.ohlcv)
        panel = type(series)(*(jnp.asarray(np.asarray(f))[None, :]
                               for f in series))
        want = sweep.jit_sweep(panel, strat, dict(flat), cost=1e-3)
        for name in want._fields:
            np.testing.assert_allclose(
                np.asarray(getattr(got, name)),
                np.asarray(getattr(want, name))[0],
                rtol=1e-4, atol=1e-5, err_msg=name)

    # Long-context job parity: the time-sharded slice result equals the
    # direct single-device sweep on the same series.
    lc_blob = (results / f"{lc_rec.id}.dbxm").read_bytes()
    lc_got = wire.metrics_from_bytes(lc_blob)
    lc_series = data.from_wire_bytes(lc_rec.ohlcv)
    lc_panel = type(lc_series)(*(jnp.asarray(np.asarray(f))[None, :]
                                 for f in lc_series))
    lc_want = sweep.jit_sweep(
        lc_panel, base.get_strategy("momentum"),
        dict(sweep.product_grid(
            **{k: jnp.asarray(v) for k, v in lc_grid.items()})),
        cost=1e-3)
    for name in lc_want._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(lc_got, name)),
            np.asarray(getattr(lc_want, name))[0],
            rtol=5e-4, atol=5e-5, err_msg=f"long-context/{name}")


@pytest.mark.slow   # 2-process jax.distributed slice (see above)
def test_two_process_distributed_sharded_sweep(tmp_path):
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    coord = f"localhost:{port}"
    script = tmp_path / "child.py"
    script.write_text(_CHILD)
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(pid), coord, _REPO_ROOT],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=280)
            outs.append((p.returncode, out, err))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("multihost children timed out")
    for rc, out, err in outs:
        assert rc == 0, f"child failed:\n{err[-3000:]}"
    assert "MULTIHOST_OK 0" in outs[0][1]
    assert "MULTIHOST_OK 1" in outs[1][1]
