"""Driver-contract tests for ``__graft_entry__``.

``dryrun_multichip`` once asserted on the ambient device count instead of
provisioning its own mesh, and failed in a one-device process. These tests
pin the fix: the inline path on the conftest's 8 virtual devices, and the
CPU re-exec path that a device-starved process takes.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft  # noqa: E402


def test_entry_compiles_and_runs():
    import jax

    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    assert np.isfinite(np.asarray(out.sharpe)).all()


def test_dryrun_inline_on_virtual_devices():
    # conftest provisions 8 CPU devices, so this takes the inline path
    # (4 <= 8). A 4-way mesh drives the identical body — every branch is
    # written against n_devices — at roughly half the SPMD-partitioning
    # compile wall of the 8-way flavor, which the slow-marked subprocess
    # twins and the driver itself still exercise. The long-context sweep
    # keeps the structurally hardest machine (two-legged pairs; ~15-30s of
    # CPU compile per family for one finiteness assert): the band/windowed
    # machine has a bit-exact sharded parity test in tier-1
    # (test_timeshard), and all eight families have served-path parity
    # tests in tier-1 (test_timeshard_wire).
    graft.dryrun_multichip(4, lc_families=("pairs",))


@pytest.mark.slow   # fresh-jax subprocess: minutes of wall on CPU-only boxes
def test_dryrun_subprocess_path():
    # Force the re-exec path regardless of ambient device count: the child
    # must self-provision its mesh from a bare environment.
    graft._dryrun_in_subprocess(2)


def test_dryrun_subprocess_propagates_failure(monkeypatch):
    real_run = subprocess.run

    def failing_run(*a, **kw):
        proc = real_run([sys.executable, "-c",
                         "import sys; sys.stderr.write('boom'); sys.exit(3)"],
                        capture_output=True, text=True)
        return proc

    monkeypatch.setattr(subprocess, "run", failing_run)
    with pytest.raises(RuntimeError, match="boom"):
        graft._dryrun_in_subprocess(2)


@pytest.mark.slow   # fresh-jax subprocess: minutes of wall on CPU-only boxes
def test_driver_style_import_and_call():
    # Replicate the driver exactly: fresh process, ambient (TPU or 1-device)
    # platform, direct import + call — no __main__ env setup.
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(4)"],
        cwd=root, env=env, capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-2000:]
