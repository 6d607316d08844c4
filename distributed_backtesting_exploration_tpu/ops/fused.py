"""Fused Pallas sweep kernel for SMA-crossover parameter grids.

The generic sweep path (``parallel.sweep``) lets XLA materialize every
``(ticker, param, T)`` intermediate in HBM — profiling on v5e shows the
sweep is bound by that traffic, spread evenly across indicator, PnL and
metric passes. This kernel keeps the entire working set of one
(ticker x 128-param) cell in VMEM and writes only the 9 metric scalars per
backtest back to HBM:

- **Distinct-window SMA table.** A (fast, slow) grid of P combos touches only
  ~``n_fast + n_slow`` distinct windows. The table ``(T, W)`` per ticker is
  built once with the standard O(T) cumsum kernels, then each lane *selects*
  its two rows inside the kernel with a one-hot matmul — turning a per-lane
  gather (slow on TPU) into an MXU contraction.
- **Time on sublanes, params on lanes.** Each cell works on ``(T_pad, 128)``
  f32 tiles; per-bar recurrences (equity cumsum, running peak for drawdown,
  the band machines' 3-state compose) run as a SINGLE sequential pass over
  T-blocks with carry state between blocks (O(T) work — see
  :func:`_equity_scan`), with the original full-T log-depth shift-op
  ladders kept as the ``"ladder"`` fallback substrate, entirely in VMEM.
- **Padding discipline.** Bars padded beyond ``T`` hold the last position and
  earn zero return, so every reduction matches the unpadded reference
  exactly; metric denominators use the static true ``T``.

Numerics match :func:`~..parallel.sweep.run_sweep` +
:func:`~.metrics.summary_metrics` to float32 tolerance (golden-tested).
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import rolling
from .metrics import Metrics

_LANES = 128
_METRIC_ROWS = 16   # 9 metric rows padded up to a legal f32 sublane tile
_EPS = 1e-12


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def interpret_default() -> bool:
    """Pallas interpret mode for the default backend: True on ``cpu`` (the
    test path), False on ``tpu``. Any other platform raises — the kernels
    are written for the TPU and a silent interpret-mode run on another
    accelerator would hide the device. Every wrapper's ``interpret=None``
    and the worker backend's ``use_fused``/``use_mesh`` auto defaults
    resolve through this one helper."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"fused Pallas kernels run compiled on 'tpu' or interpreted on "
        f"'cpu'; the default backend is {platform!r}")


# A 1024-lane block only qualifies while one (T_pad, lanes) f32 value
# stays under this budget — past it the sign kernels' live set (returns,
# sign, pos, equity, two ladder temps) presses v5e VMEM and Mosaic
# spills. 6 MiB admits the headline T_pad=1280 (5.2 MiB/array).
_WIDE_BLOCK_BYTES = 6 * 1024 * 1024

# Scoped-VMEM limit every pallas_call here compiles under, set in ONE
# place. Mosaic's default scoped limit is 16 MiB, which every family
# overflows at the router's bar cap (rpc.compute._FUSED_MAX_BARS = 8192:
# a (8192, 512) f32 tile alone is 16 MiB); v5e has 128 MiB of VMEM per
# core, and the headroom left below it is Mosaic's internal scratch.
# tests/test_tpu_compile.py compiles the cap for a described v5e.
# Ignored in interpret mode.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=100 * 1024 * 1024)
# The same limit for a cell that walks T in blocks (`_TBlock`): its T axis
# carries state from block to block, so it runs in order.
_T_BLOCKED_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=100 * 1024 * 1024,
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# ---------------------------------------------------------------------------
# Tuned-schedule consultation (tune/ round 11)
#
# Every substrate knob below resolves through the SAME four-step chain:
# explicit call arg > env knob > tuned schedule > hardcoded default. The
# tuned schedule is the autotuner's persisted winner for the group's
# (kernel family, shape-bucket) — activated HOST-side by the worker
# backend around one group submit (`tuned_schedule`, thread-local so
# concurrent submit threads cannot bleed schedules into each other), or
# process-wide for knobs that bind at construction time
# (`set_tuned_defaults`, e.g. the page pool's page size). Placing the
# schedule BELOW env keeps every existing test and operator override
# byte-identical: an env knob always beats a tuned schedule. Tuned values
# are validated like env values but NEVER raise — an invalid entry (a
# corrupt registry, a newer peer's schema) silently degrades to the
# hardcoded default, because tuning must never fail a job. All reads stay
# host-side resolve-helper territory (dbxlint trace-time-env): resolved
# values thread into the kernels as jit statics exactly like env knobs.
# ---------------------------------------------------------------------------

_TUNED_TLS = threading.local()
_TUNED_GLOBAL: dict = {}


def set_tuned_defaults(schedule: dict | None) -> None:
    """Install (or clear, with None) process-wide tuned substrate
    defaults — the construction-time consultation used for knobs that
    bind before any group is submitted (page pool sizing). Host-side."""
    _TUNED_GLOBAL.clear()
    if schedule:
        _TUNED_GLOBAL.update({str(k): str(v)
                              for k, v in schedule.items()})


@contextlib.contextmanager
def tuned_schedule(schedule: dict | None):
    """Activate a tuned substrate schedule for the calling thread. The
    worker backend wraps one group submit in this, so every resolver the
    wrappers call inside sees the group's tuned values (below env)."""
    prev = getattr(_TUNED_TLS, "schedule", None)
    _TUNED_TLS.schedule = dict(schedule) if schedule else None
    try:
        yield
    finally:
        _TUNED_TLS.schedule = prev


def _tuned_value(key: str):
    """The active tuned value for ``key`` (thread-local schedule first,
    then the process-wide defaults), or None."""
    sched = getattr(_TUNED_TLS, "schedule", None)
    if sched is not None and key in sched:
        return sched[key]
    return _TUNED_GLOBAL.get(key)


def tuned_schedule_active() -> dict:
    """The merged tuned schedule in effect on this thread (observability:
    the ``dbx_tuned_substrate_info`` surface reads this, never the
    registry directly, so it cannot report values the kernels did not
    serve)."""
    out = dict(_TUNED_GLOBAL)
    sched = getattr(_TUNED_TLS, "schedule", None)
    if sched:
        out.update(sched)
    return out


# The legal param-block widths (f32 lane multiples the kernels tile by).
# DBX_LANES_CAP must name one of these — an off-ladder value can satisfy
# no candidate, and the old fall-through then returned the FULL un-blocked
# P_pad: the opposite of a cap, blowing VMEM on headline sweeps (ADVICE.md).
_LANES_LADDER = (_LANES, 256, 512, 1024)


def resolve_lanes_cap() -> int:
    """Validated ``DBX_LANES_CAP`` override (0 = unset).

    Read ONCE per public sweep call, host-side, and threaded into the
    jitted kernels as the static ``lanes_env`` argument — part of the jit
    cache key, so changing it in-process recompiles at the new width
    instead of silently reusing the stale one (ADVICE.md; the in-process
    A/B measured nothing before this). Raises on values outside the
    {128, 256, 512, 1024} ladder rather than falling through to an
    unbounded block width.
    """
    raw = os.environ.get("DBX_LANES_CAP")
    if not raw:
        tuned = _tuned_value("lanes_cap")
        if tuned is not None:
            try:
                tv = int(tuned)
            except (TypeError, ValueError):
                tv = -1
            if tv == 0 or tv in _LANES_LADDER:
                return tv
        return 0   # invalid tuned value: degrade to unset, never raise
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(
            f"DBX_LANES_CAP={raw!r} is not an integer; expected one of "
            f"{_LANES_LADDER} (or 0/empty to disable)") from None
    if v == 0:
        return 0   # explicit disable, same as unset (the old sentinel)
    if v not in _LANES_LADDER:
        raise ValueError(
            f"DBX_LANES_CAP={v} is unusable: no kernel block ladder "
            f"candidate matches it (legal values: {_LANES_LADDER})")
    return v


def _widest_lanes(P_pad: int, cap: int, T_pad: int | None = None,
                  env_cap: int = 0) -> int:
    """Widest legal param-block width <= ``cap``: fewer, wider cells
    amortize per-cell fixed overhead (+16% measured at 512 on the SMA
    headline — bench.py roofline_stages). Sign kernels take 512; kernels
    holding a 3-state compose ladder cap at 256 (VMEM budget).

    1024 stays OFF the default ladder: the roofline stage twin (HBM-table
    SMA) measured +7% at 1024, but the SHIPPED inline kernels measured a
    wash-to-regression in the 3x interleaved on-chip A/B (median sma
    -0.6%, momentum -2.6%, obv -0.5%) — the scratch table build plus the
    wider live set spills what the stage twin keeps resident. ``env_cap``
    is the :func:`resolve_lanes_cap`-validated ``DBX_LANES_CAP`` override
    (replaces ``cap`` for sign-kernel-class calls, still VMEM-gated),
    passed in as a jit-static so the A/B recompiles per setting."""
    if env_cap and cap > 256:
        cap = env_cap
    for cand in (1024, 512, 256, _LANES):
        if cand > 512 and (T_pad is None
                           or T_pad * cand * 4 > _WIDE_BLOCK_BYTES):
            continue
        if cand <= cap and P_pad >= cand and P_pad % cand == 0:
            return cand
    return P_pad


def _const(a):
    """Concrete device array, safe to build *inside* a trace.

    The lru-cached grid setups convert their numpy tables once and reuse the
    device buffer across calls. When a sweep is first invoked under an outer
    trace (e.g. ``jit(shard_map(...))`` in the multi-chip worker backend), a
    plain ``jnp.asarray`` would produce a tracer and the cache would capture
    it — escaping the trace and poisoning every later call."""
    with jax.ensure_compile_time_eval():
        return jnp.asarray(a)


def _pad_last(close, T_pad: int):
    """Pad ``(N, T)`` closes to ``T_pad`` bars by repeating the final close.

    Load-bearing for the padding discipline shared by every kernel here: a
    repeated last close makes the pad bars' returns exactly zero, so held
    positions earn nothing and reductions over T_pad match T_real
    (see ``_metrics_pack``).
    """
    pad_t = T_pad - close.shape[1]
    if not pad_t:
        return close
    return jnp.concatenate(
        [close, jnp.repeat(close[:, -1:], pad_t, axis=1)], axis=1)


def _t_real_col(t_real, close):
    """Per-ticker real bar counts as an (N, 1) int32 column for the kernels'
    SMEM array, or None for uniform histories (the kernels then specialize
    on a static length — measured ~25% faster than the dynamic path on the
    headline sweep). Ragged callers pass the lengths from
    :func:`~..utils.data.pad_and_stack`."""
    if t_real is None:
        return None
    return jnp.asarray(t_real, jnp.int32).reshape(close.shape[0], 1)


def _real_mean(a, t_real, T: int):
    """Per-row mean over each row's REAL bars along the last axis — the
    centering constant the generic path computes on the unpadded series.
    Uniform groups (``t_real`` None) average the first ``T`` bars; ragged
    groups average the first ``t_real[i]`` bars of row ``i``, so the
    repeat-last pad of a short row never shifts its centering (it used
    to, and the rounding of every centered moment moved with it: ~half of
    a ragged pairs group's cells flipped against the reference on v5e)."""
    if t_real is None:
        return jnp.mean(a[..., :T], axis=-1, keepdims=True)
    tr = t_real.reshape((a.shape[0],) + (1,) * (a.ndim - 1))
    t_idx = jax.lax.broadcasted_iota(jnp.int32, a.shape, a.ndim - 1)
    total = jnp.sum(jnp.where(t_idx < tr, a, 0.0), axis=-1, keepdims=True)
    return total / tr.astype(a.dtype)


def _rets3(close_p):
    """Per-bar simple returns of padded closes, shaped ``(N, T_pad, 1)`` for
    a (1, T_pad, 1) kernel block (broadcasts over param lanes); ``r[0] = 0``."""
    prev = jnp.concatenate([close_p[:, :1], close_p[:, :-1]], axis=1)
    return (close_p / prev - 1.0)[..., None]


def _shift_t(x, s: int, fill: float):
    """``y[..., t] = x[..., t-s]`` along the last axis, ``fill`` for t < s
    (static shift: slice+concat copies, no gather). A shift at or beyond
    the axis length yields all-fill — the same answer the clipped-gather
    ``rolling._shifted`` gives, so windows larger than the (padded)
    history stay graceful instead of producing a wrapped negative slice."""
    T = x.shape[-1]
    if s == 0:
        return x
    if s >= T:
        return jnp.full_like(x, fill)
    pad = jnp.full(x.shape[:-1] + (s,), fill, x.dtype)
    return jnp.concatenate([pad, x[..., :T - s]], axis=-1)


def _prefix_rows(x):
    """``(N, T_pad)`` series -> its :func:`rolling.prefix_sum2` pair as
    ``(N, 2, T_pad)`` rows (hi, lo): what an in-kernel table builder takes
    to difference windows (:func:`_lagged_window_sum`)."""
    return jnp.stack(rolling.prefix_sum2(x), axis=1)


def _lagged_window_sum(pair, w: int, lane):
    """In-kernel trailing-``w`` sum of a ``(2, T_pad)`` prefix pair: rotate
    both rows and zero the wrapped lanes (``_shift_t``'s zero fill), then
    ``rolling.window_diff``. ``(1, T_pad)``."""
    hi, lo = pair[0:1, :], pair[1:2, :]
    if w >= hi.shape[-1]:
        return hi + lo

    def lag(row):
        return jnp.where(lane >= w, _rot_lanes(row, w), 0.0)
    return rolling.window_diff(hi, lo, lag(hi), lag(lo))


def _rot_lanes(x, w: int):
    """``y[..., t] = x[..., (t - w) mod T`` — static rotate along the lane
    (minor) axis, expressed as a two-slice concat Mosaic lowers to lane
    rotations. Used by the in-kernel table builders (callers mask the
    wrapped region before use)."""
    T = x.shape[-1]
    w = w % T
    if w == 0:
        return x
    return jnp.concatenate([x[..., T - w:], x[..., :T - w]], axis=-1)


def _shift_down(x, k: int, fill: float):
    """``y[t] = x[t-k]`` along axis 0 with ``fill`` for t < k (static k)."""
    pad = jnp.full((k,) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([pad, x[:-k]], axis=0)


def _cumsum0(x):
    """Inclusive prefix sum along axis 0 via a log-depth doubling ladder."""
    t = x.shape[0]
    shift = 1
    while shift < t:
        x = x + _shift_down(x, shift, 0.0)
        shift *= 2
    return x


def _cummax0(x):
    """Inclusive running max along axis 0 via the same doubling ladder."""
    t = x.shape[0]
    shift = 1
    while shift < t:
        x = jnp.maximum(x, _shift_down(x, shift, -jnp.inf))
        shift *= 2
    return x


# ---------------------------------------------------------------------------
# Single-pass carry-scan epilogue (the "scan" substrate)
#
# BENCH_r05's roofline_stages put 47.6% of the flagship SMA sweep in the
# shared metrics tail's two full-T shift ladders (equity cumsum + running-
# peak cummax: O(T log T) element-ops), and another ~55% of every band
# machine's tail in the 3-state compose ladder. All three recurrences are
# now evaluated as ONE sequential pass over T-blocks with carry state
# threaded between blocks — O(T log B) work for a fixed block B, i.e. O(T).
# The carries (cumulative return, running-max equity, band machine state)
# live in VMEM vregs across an unrolled static block loop; block bounds are
# compile-time constants, so every slice is a static sublane slice (the
# T-block analogue of the sequential-grid scratch the inline tables use,
# without re-tiling the signal stage). The ladder path survives verbatim as
# the "ladder" fallback substrate so parity and flip budgets verify
# substrate-vs-substrate (`DBX_EPILOGUE=ladder`, bench roofline A/B rows).
# ---------------------------------------------------------------------------

_EPILOGUE_DEFAULT = "scan"
_SCAN_BLOCK_DEFAULT = 8          # one f32 sublane tile per block step
_SCAN_MAX_BLOCKS = 256           # unroll bound: B doubles past this


def _epilogue_ok(epilogue: str) -> bool:
    if epilogue in ("ladder", "scan"):
        return True
    if isinstance(epilogue, str) and epilogue.startswith("scan:"):
        try:
            b = int(epilogue[5:])
        except ValueError:
            return False
        return b >= 8 and b % 8 == 0
    return False


def _resolve_epilogue(epilogue: str | None) -> str:
    """Shared epilogue-substrate knob: explicit arg > ``DBX_EPILOGUE`` >
    tuned schedule > ``"scan"``. ``"scan"`` (default) is the single-pass
    blocked carry scan; ``"scan:<B>"`` pins the T-block size to ``B``
    sublane rows (multiple of 8 — the tuning surface for the on-chip A/B
    and the autotuner's epilogue axis); ``"ladder"`` is the O(T log T)
    full-T shift-ladder fallback kept for substrate-vs-substrate
    verification. An invalid arg/env value raises (operator error); an
    invalid TUNED value silently degrades to the default (tuning must
    never fail a job)."""
    if epilogue is None:
        epilogue = os.environ.get("DBX_EPILOGUE")
        if epilogue is None:
            tuned = _tuned_value("epilogue")
            if tuned is not None and _epilogue_ok(tuned):
                return tuned
            epilogue = _EPILOGUE_DEFAULT
    if _epilogue_ok(epilogue):
        return epilogue
    raise ValueError(
        f"epilogue must be 'scan', 'scan:<B>' (B a positive multiple of 8) "
        f"or 'ladder', got {epilogue!r}")


def _scan_block(T_pad: int, epilogue: str) -> int:
    """Static T-block size for the carry scan. The default starts at one
    sublane tile (8 rows — the modeled sweet spot: per-row ladder work is
    4*log2(B), so smaller blocks do strictly less VPU work) and doubles
    until the unrolled block count fits ``_SCAN_MAX_BLOCKS`` (bounding
    Mosaic program size for long-context shapes)."""
    if epilogue.startswith("scan:"):
        return int(epilogue[5:])
    b = _SCAN_BLOCK_DEFAULT
    while -(-T_pad // b) > _SCAN_MAX_BLOCKS:
        b *= 2
    return b


def _spans(T_pad: int, block: int):
    """Static (start, stop) spans tiling the sublane axis by ``block``."""
    return [(s, min(s + block, T_pad)) for s in range(0, T_pad, block)]


def _interp_epilogue(epilogue: str, T_pad: int, interpret: bool) -> str:
    """Interpret mode (the CPU test path) re-blocks the default scan to
    ONE T-block: the long unrolled per-block op chain that is cheap for
    Mosaic is expensive for trace + XLA-CPU interpretation (measured ~8x
    golden-test wall at the default 8-row block vs ~1x single-block —
    a single block does the ladder's exact op count through the scan
    code path). Carry chains across block boundaries are exercised by
    the dedicated multi-block substrate tests (tests/test_z_epilogue.py),
    which pin ``"scan:<B>"`` explicitly; pinned values and ``"ladder"``
    pass through untouched. Block size only moves the f32 association
    rounding of the equity-path metrics."""
    if not interpret or epilogue != "scan":
        return epilogue
    return f"scan:{_round_up(T_pad, 8)}"


def _equity_scan(net, block: int, carry=None):
    """``(mdd, cum, peak)`` rows of ``equity = 1 + cumsum(net)`` in one
    sequential pass over T-blocks: the max drawdown, the cumulative net
    return (the final equity is ``1 + cum``) and the running-max equity.
    ``carry`` continues a scan an earlier stretch of the history left
    as those three ``(1, lanes)`` rows (a T-blocked launch,
    :class:`_TBlock`); None starts from ``0 / 0 / -inf``.

    Carries: the cumulative net return and the running-max equity, both
    ``(1, lanes)`` rows threaded between blocks. Per block the local
    cumsum/cummax ladders are log2(block)-deep instead of log2(T_pad) —
    total O(T log B) = O(T) for the static ``block``. Padding discipline
    (``net == 0`` for ``t >= tr``) makes masks unnecessary: equity and
    peak freeze at the last real bar, so pad rows' drawdown replays
    ``dd[tr-1]`` exactly and the final carry IS the total return. For a
    single block this is bit-identical to the ladder substrate
    (``x + 0.0 == x``); across blocks the summation tree differs by the
    usual f32 association rounding (~1 ULP class — positions, and hence
    every flip-sensitive comparison, are untouched). Since round 16
    this is no longer prose-only: dbxcert's association-boundary census
    counts every block-merge add and ladder step on the equity cone and
    pins the counts per substrate in ``numerics.contract.json`` — a
    re-blocking or reassociating edit here fails the drift gate with
    the introducing equation chain."""
    T_pad, lanes = net.shape
    if carry is None:
        carry = jnp.zeros((1, lanes), jnp.float32)
        peak_c = jnp.full((1, lanes), -jnp.inf, jnp.float32)
        mdd = jnp.zeros((1, lanes), jnp.float32)
    else:
        mdd, carry, peak_c = carry
    for s, e in _spans(T_pad, block):
        cs = _cumsum0(net[s:e])
        eq = (1.0 + carry) + cs
        peak = jnp.maximum(_cummax0(eq), peak_c)
        dd = (peak - eq) / jnp.maximum(peak, _EPS)
        mdd = jnp.maximum(mdd, jnp.max(dd, axis=0, keepdims=True))
        carry = carry + cs[e - s - 1:]
        peak_c = peak[e - s - 1:]
    return mdd, carry, peak_c


def _cumsum_last(x):
    """Inclusive prefix sum over the LAST axis as a Hillis–Steele
    shift-doubling ladder — the host-XLA twin of :func:`_cumsum0`.
    NOT ``jnp.cumsum``: its ``associative_scan`` lowering compiles a
    deeply recursive slice graph, and the blocked equity advance emits
    one prefix op PER BLOCK — hundreds of them at long-context shapes
    turned a tiny jit into a multi-minute XLA-CPU compile (the
    ``ema_ladder`` lesson, re-learned host-side)."""
    T = x.shape[-1]
    s = 1
    while s < T:
        pad = jnp.zeros(x.shape[:-1] + (s,), x.dtype)
        x = x + jnp.concatenate([pad, x[..., :-s]], axis=-1)
        s *= 2
    return x


def _cummax_last(x):
    """Inclusive running max over the LAST axis (shift ladder, see
    :func:`_cumsum_last`)."""
    T = x.shape[-1]
    s = 1
    while s < T:
        pad = jnp.full(x.shape[:-1] + (s,), -jnp.inf, x.dtype)
        x = jnp.maximum(x, jnp.concatenate([pad, x[..., :-s]], axis=-1))
        s *= 2
    return x


def _equity_advance(net, block: int, cum, peak, mdd):
    """Recurrent form of :func:`_equity_scan`, over the LAST axis.

    Advances the ``(cumulative net, running peak, max drawdown)`` carry
    across a ``(..., D)`` net-return slice in T-blocks of ``block`` bars
    — the exact carry threading `_equity_scan` uses between its blocks,
    exposed as a standalone step so a streaming append
    (``streaming.recurrent``) can continue a finished sweep's equity
    state in O(ΔT). Block boundaries are the only association
    difference vs a cold full-length scan (the PR-3 f32 budget);
    ``cum``/``peak``/``mdd`` initialize to ``0 / -inf / 0`` exactly as
    `_equity_scan` seeds them, so the scan form is literally one call
    covering the whole panel. This is a certified cone: every streaming
    family's build/append row in ``numerics.contract.json`` pins the
    census of the shift-ladder and block-merge adds emitted here (the
    structural reassociations dbxcert counts without any reduce
    primitive present)."""
    D = net.shape[-1]
    for s, e in _spans(D, block):
        cs = _cumsum_last(net[..., s:e])
        eq = (1.0 + cum)[..., None] + cs
        pk = jnp.maximum(_cummax_last(eq), peak[..., None])
        dd = (pk - eq) / jnp.maximum(pk, _EPS)
        mdd = jnp.maximum(mdd, jnp.max(dd, axis=-1))
        cum = cum + cs[..., -1]
        peak = pk[..., -1]
    return cum, peak, mdd


def _unpack_tr(refs, T_real):
    """Shared ragged-vs-uniform ref plumbing for all sweep kernels: with a
    static ``T_real`` the refs are just ``(out_ref,)``; in ragged mode an
    SMEM lengths array precedes it and this grid row's length is read out.
    Returns ``(tr, out_ref)``."""
    if T_real is None:
        tr_ref, out_ref = refs
        return tr_ref[pl.program_id(0), 0], out_ref
    (out_ref,) = refs
    return T_real, out_ref


def _tr_specs(T_real):
    """Extra in_specs for ragged mode (whole lengths array in SMEM)."""
    return [] if T_real is not None else [
        pl.BlockSpec(memory_space=pltpu.SMEM)]


def _tr_args(t_real, T_real):
    """Extra pallas operands for ragged mode."""
    return [] if T_real is not None else [t_real]


def _row_at(x, tr, t_idx, *, keepdims: bool):
    """Row ``tr - 1`` of a (T_pad, 128) tile. Static ``tr`` folds to a plain
    slice (zero runtime cost — the uniform-history fast path); a traced
    ``tr`` uses a one-hot masked sum, bit-identical to the slice (exactly
    one nonzero row) but one extra VPU pass."""
    if isinstance(tr, int):
        row = x[tr - 1:tr, :]
        return row if keepdims else row[0]
    return jnp.sum(jnp.where(t_idx == tr - 1, x, 0.0), axis=0,
                   keepdims=keepdims)


# ---------------------------------------------------------------------------
# T-blocked cells
#
# A history longer than one VMEM-sized block walks T as the innermost,
# sequential grid axis (`_band_machine_pallas`): each step reads one
# ``(1, T_blk, .)`` block of the returns and the z-table, and the rows of
# one ``(16, lanes)`` VMEM scratch carry what the next block needs — the
# band machine's last position (its latch state and the next block's
# ``pos_{t-1}``) and every running accumulator of the shared epilogue
# (`_metrics_pack`), the in-kernel twin of ``streaming.recurrent``'s
# append carry. The metric rows written at the last block are final.
# ---------------------------------------------------------------------------

# Carry rows of the T-blocked scratch.
_C_POS, _C_CUM, _C_PEAK, _C_MDD = 0, 1, 2, 3
_C_S1, _C_S2, _C_DOWN, _C_WINS, _C_ACTIVE, _C_TURN = 4, 5, 6, 7, 8, 9


class _TBlock(NamedTuple):
    """Where a T-blocked cell is in the history: ``carry`` is its
    ``(16, lanes)`` VMEM scratch ref (rows ``_C_*``), ``t0`` the first
    bar of this block."""
    carry: object
    t0: object


def _t_block_of(refs, T_blk: int):
    """Split a T-blocked cell's carry scratch off the end of its ``refs``
    and reset it at the history's first block: ``(refs, _TBlock)``."""
    *head, carry = refs
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _start():
        row = jax.lax.broadcasted_iota(jnp.int32, carry.shape, 0)
        carry[...] = jnp.where(row == _C_PEAK, -jnp.inf, 0.0)

    return tuple(head), _TBlock(carry, t * T_blk)


def _carried(tb, row: int, x):
    """``x``, a ``(lanes,)`` sum over this block's bars, plus what the
    earlier blocks summed in carry row ``row`` (stored back); ``x`` itself
    in a one-block cell (``tb`` None)."""
    if tb is None:
        return x
    total = tb.carry[row:row + 1, :] + x[None, :]
    tb.carry[row:row + 1, :] = total
    return total[0]


def _metrics_tail(pos, r, t_idx, tr, *, cost: float, ppy: int,
                  epilogue: str = _EPILOGUE_DEFAULT, tb=None):
    """Shared kernel tail: positions -> packed (16, 128) metric rows.

    ``pos`` is the per-lane position path over ``(T_pad, 128)`` (any signal
    kernel produces it); ``tr`` is this ticker's real bar count (an int32
    scalar — traced, so ragged groups work with one compiled kernel). Bars
    at ``t >= tr`` are overwritten to hold the final real position so every
    reduction over T_pad equals the unpadded reduction over tr (zero
    return, zero turnover in the pad). ``epilogue`` picks the equity/
    drawdown substrate (see `_equity_scan` / `_resolve_epilogue`).

    ``tb`` (a T-blocked cell, :class:`_TBlock`): ``pos`` and ``t_idx``
    cover one block; the bar before it and the held final position come
    from the carry, and the rows returned are final at the last block.
    """
    row_ok = t_idx < tr
    if tb is None:
        pos_last = _row_at(pos, tr, t_idx, keepdims=True)
        pos = jnp.where(row_ok, pos, pos_last)
        prev = _shift_down(pos, 1, 0.0)
    else:
        # The last real bar lies in this block or an earlier one, whose
        # last (held) position the carry keeps.
        held = tb.carry[_C_POS:_C_POS + 1, :]
        in_block = jnp.sum(jnp.where(t_idx == tr - 1, pos, 0.0), axis=0,
                           keepdims=True)
        pos = jnp.where(row_ok, pos,
                        jnp.where(tr - 1 < tb.t0, held, in_block))
        prev = jnp.concatenate([held, pos[:-1]], axis=0)
    net = prev * r - cost * jnp.abs(pos - prev)
    rows = _metrics_pack(pos, prev, net, row_ok, t_idx, tr, ppy=ppy,
                         epilogue=epilogue, tb=tb)
    if tb is not None:
        tb.carry[_C_POS:_C_POS + 1, :] = pos[-1:]
    return rows


def _metrics_pack(pos, prev, net, row_ok, t_idx, tr, *, ppy: int,
                  epilogue: str = _EPILOGUE_DEFAULT, tb=None):
    """Reduce per-bar ``net``/positions to the packed (16, 128) metric rows.

    Callers guarantee the padding discipline: ``pos`` holds its final real
    value for ``t >= tr`` and ``net`` is exactly zero there, so plain
    reductions over T_pad equal the unpadded reductions over tr. In a
    T-blocked cell (``tb``) every reduction continues the carried one.
    """
    n = jnp.asarray(tr, jnp.float32)
    s1 = _carried(tb, _C_S1, jnp.sum(net, axis=0))
    s2 = _carried(tb, _C_S2, jnp.sum(net * net, axis=0))
    mean = s1 / n
    var = jnp.maximum(s2 / n - mean * mean, 0.0)
    std = jnp.sqrt(var)
    ann = jnp.sqrt(jnp.float32(ppy))
    down = jnp.minimum(net, 0.0)
    dstd = jnp.sqrt(_carried(tb, _C_DOWN, jnp.sum(down * down, axis=0)) / n)

    if epilogue == "ladder" and tb is None:
        equity = 1.0 + _cumsum0(net)
        peak = _cummax0(equity)
        dd = (peak - equity) / jnp.maximum(peak, _EPS)
        mdd = jnp.max(jnp.where(row_ok, dd, 0.0), axis=0)
        eq_final = _row_at(equity, tr, t_idx, keepdims=False)
    else:
        # A T-blocked ladder cell scans its block as one ladder.
        block = (net.shape[0] if epilogue == "ladder"
                 else _scan_block(net.shape[0], epilogue))
        rows = (_C_MDD, _C_CUM, _C_PEAK)
        mdd, cum, _peak = _equity_scan(
            net, block, None if tb is None else
            tuple(tb.carry[i:i + 1, :] for i in rows))
        if tb is not None:
            for i, v in zip(rows, (mdd, cum, _peak)):
                tb.carry[i:i + 1, :] = v
        mdd, eq_final = mdd[0], 1.0 + cum[0]

    active = (jnp.abs(prev) > 0) & row_ok
    wins = (net > 0) & active
    hit = _carried(tb, _C_WINS, jnp.sum(wins.astype(jnp.float32), axis=0)) / (
        _carried(tb, _C_ACTIVE, jnp.sum(active.astype(jnp.float32), axis=0))
        + _EPS)

    turnover = _carried(tb, _C_TURN, jnp.sum(jnp.abs(pos - prev), axis=0))
    years = jnp.maximum(n / jnp.float32(ppy), _EPS)
    final = jnp.maximum(eq_final, _EPS)

    # Pack the 9 metrics onto sublanes of one (16, lanes) output tile — a
    # (1, lanes)-per-metric block shape is not a legal TPU tile. The lane
    # width comes from the position block (each launcher picks its widest
    # legal block: <=512 for fused-SMA, <=256 for the band machines).
    rows = jnp.stack([
        mean / (std + _EPS) * ann,          # sharpe
        mean / (dstd + _EPS) * ann,         # sortino
        mdd,                                # max_drawdown
        eq_final - 1.0,                     # total_return
        jnp.power(final, 1.0 / years) - 1.0,  # cagr
        std * ann,                          # volatility
        hit,                                # hit_rate
        0.5 * turnover,                     # n_trades
        turnover,                           # turnover
    ], axis=0)                              # (9, lanes)
    return jnp.concatenate(
        [rows, jnp.zeros((_METRIC_ROWS - 9, pos.shape[-1]), jnp.float32)],
        axis=0)


def _sma_table(close_p, windows: tuple, W_pad: int):
    """Distinct-window SMA table, W-as-SUBLANE ``(N, W_pad, T_pad)``: one
    compensated prefix sum (``rolling.prefix_sum2``) + W static shifts
    stacked on axis 1, keeping T_pad minor.

    Two things make this layout fast: the per-window rows are pure
    elementwise shift/sub/div expressions XLA fuses into one pass (a
    (T_pad, W)-indexed ``jnp.take`` lowered to a slow XLA gather that
    alone measured ~37% of the whole sweep — bench.py roofline_stages
    "prep" stage), and T_pad staying minor avoids the 128x tile-padding
    blow-up of a (N, T_pad, 1)-sliced stack on the lane axis. The kernel
    contracts the table's leading (window) axis directly, so no transpose
    is needed anywhere. Shared with bench.py's ``roofline_stages``
    scaffold so the measured and shipped preps cannot drift.
    """
    N, T_pad = close_p.shape
    hi, lo = rolling.prefix_sum2(close_p)
    t_row = jnp.arange(T_pad)[None, :]                         # (1, T_pad)
    rows = []
    for w in windows:
        w = int(w)
        sma_w = rolling.window_diff(hi, lo, _shift_t(hi, w, 0.0),
                                    _shift_t(lo, w, 0.0)) / jnp.float32(w)
        rows.append(jnp.where(t_row >= w - 1, sma_w, 0.0))
    rows += [jnp.zeros((N, T_pad), jnp.float32)] * (W_pad - len(windows))
    return jnp.stack(rows, axis=1)                       # (N, W_pad, T_pad)


def _sma_select_and_score(sma, r, od_ref, warm_ref, tr, out_ref, *,
                          cost: float, ppy: int, epilogue: str):
    """Shared SMA selection + metrics tail (both table substrates feed it).

    Per-lane window selection as MXU contractions over the table's
    LEADING window axis (the W-major layout lets the table build use
    static shifts instead of a gather — the gather version measured ~37%
    of the whole sweep; bench.py roofline_stages).
    ONE selection matmul on the DIFFERENCE one-hot (+1 at the fast row,
    -1 at the slow row): each lane's contraction has exactly two nonzero
    terms, so d == sma_fast - sma_slow and sign(d) is the crossover —
    half the MXU work of selecting f and s separately. The difference is
    now formed HOST-side (`_grid_setup` ships one ``(W_pad, lanes)``
    selector instead of two): exact 0/±1 integers either way, half the
    selector VMEM stream and one fewer per-cell pass. HIGHEST precision:
    the default bf16 pass truncates price-level SMAs enough to flip
    sign(d) near crossovers.
    """
    T_pad = sma.shape[1]
    d = jax.lax.dot_general(
        sma, od_ref[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)   # (T_pad, lanes)

    lanes = od_ref.shape[1]   # wider-than-128 param blocks: fewer cells
                              # amortize per-cell overhead (bench.py
                              # roofline_stages measured +16% at 512)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (T_pad, lanes), 0)
    warm = warm_ref[0, :][None, :]            # (1, lanes) max(fast, slow)
    valid = t_idx >= (warm.astype(jnp.int32) - 1)
    pos = jnp.where(valid, jnp.sign(d), 0.0)
    out_ref[0, 0] = _metrics_tail(pos, r, t_idx, tr, cost=cost, ppy=ppy,
                                  epilogue=epilogue)


def _kernel(r_ref, sma_ref, od_ref, warm_ref, *refs,
            cost: float, ppy: int, T_real: int | None, epilogue: str):
    tr, out_ref = _unpack_tr(refs, T_real)
    r = r_ref[0]                     # (T_pad, 1) -> broadcasts over lanes
    sma = sma_ref[0]                 # (W_pad, T_pad) — W-major table
    _sma_select_and_score(sma, r, od_ref, warm_ref, tr, out_ref,
                          cost=cost, ppy=ppy, epilogue=epilogue)


def _kernel_inline(r_ref, cs_ref, od_ref, warm_ref, *refs,
                   cost: float, ppy: int, T_real: int | None,
                   windows: tuple, W_pad: int, epilogue: str):
    """The `_kernel` selection design with IN-KERNEL table construction.

    Instead of streaming an XLA-built ``(N, W_pad, T_pad)`` SMA table from
    HBM, this variant takes only the close prefix pair ``(N, 2, T_pad)`` and
    rebuilds the W-major table into a persistent VMEM scratch once per
    ticker — at param-block ``j == 0``; the Pallas TPU grid is sequential
    (last axis innermost), so the scratch built there is still live for
    ``j = 1..n_blocks-1``. Row values use the exact op sequence of
    :func:`_sma_table` (sub, div by ``float32(w)``, warmup mask); the
    rotate's wrapped lanes are zeroed before the subtraction, reproducing
    ``_shift_t``'s zero fill. On CPU (interpret) the result is
    bit-identical to the HBM-table path (tested incl. multi-block). On
    TPU, Mosaic and XLA lower the f32 division differently, so some table
    entries differ by 1 ULP (measured: ~8% of entries for larger windows),
    which can flip knife-edge crossovers in ~0.01% of backtests — the same
    rounding class as the MXU selection matmul, and within every verify
    budget (bench --verify with this substrate: SMA 0/40000 entry flips,
    0 best-param flips). This removes the XLA table passes + the table
    HBM round-trip (measured ~4-5% median end-to-end, DESIGN.md).
    """
    *head, sma_scr = refs
    tr, out_ref = _unpack_tr(tuple(head), T_real)

    @pl.when(pl.program_id(1) == 0)
    def _build():
        _build_sma_scratch(cs_ref[0], sma_scr, windows, W_pad)

    r = r_ref[0]
    _sma_select_and_score(sma_scr[:], r, od_ref, warm_ref, tr,
                          out_ref, cost=cost, ppy=ppy, epilogue=epilogue)


def _build_sma_scratch(cs, sma_scr, windows: tuple, W_pad: int):
    """Fill a ``(W_pad, T_pad)`` VMEM scratch with the W-major SMA table of
    the series whose prefix pair ``cs`` is ``(2, T_pad)`` (`_prefix_rows`)
    — `_sma_table`'s exact op sequence (rotate + zero wrapped lanes,
    window difference, divide by ``float32(w)``, warmup mask). Shared by
    the SMA and OBV inline kernels; call under ``pl.when(j == 0)``."""
    T_pad = cs.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, T_pad), 1)
    for k, w in enumerate(windows):
        w = int(w)
        sma_w = _lagged_window_sum(cs, w, lane) / jnp.float32(w)
        sma_scr[k:k + 1, :] = jnp.where(lane >= w - 1, sma_w, 0.0)
    for k in range(len(windows), W_pad):
        # One-hot weights are zero on pad rows, but 0 * garbage VMEM
        # could still be NaN — zero them.
        sma_scr[k:k + 1, :] = jnp.zeros((1, T_pad), jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("windows", "T_pad", "W_pad", "P_real", "T_real", "cost",
                     "ppy", "interpret", "table", "lanes_env", "epilogue"))
def _fused_call(close, onehot_d, warm, t_real, *, windows: tuple,
                T_pad: int, W_pad: int, P_real: int, T_real: int | None,
                cost: float, ppy: int, interpret: bool,
                table: str = "inline", lanes_env: int = 0,
                epilogue: str = _EPILOGUE_DEFAULT):
    """Table prep + pallas call in ONE jit: the prep is ~500 XLA ops and must
    not run eagerly (each eager op is its own host dispatch — measured 13x
    slower end-to-end).

    ``table`` selects the SMA-table substrate: ``"inline"`` rebuilds it in
    VMEM scratch inside the kernel (`_kernel_inline` — no XLA table passes,
    no table HBM round-trip); ``"hbm"`` is the classic XLA-built
    ``(N, W_pad, T_pad)`` table streamed per ticker (`_kernel`), kept as
    the A/B twin the roofline stages are cut from. Bit-identical on CPU;
    on TPU see `_kernel_inline` for the 1-ULP division-lowering caveat.
    """
    N, T = close.shape
    epilogue = _interp_epilogue(epilogue, T_pad, interpret)
    close_p = _pad_last(close, T_pad)
    returns3 = _rets3(close_p)
    P_pad = onehot_d.shape[1]
    # sign kernel: no compose ladder
    lanes = _widest_lanes(P_pad, 512, T_pad, lanes_env)
    n_blocks = P_pad // lanes
    grid = (N, n_blocks)
    if table == "inline":
        kernel = functools.partial(_kernel_inline, cost=cost, ppy=ppy,
                                   T_real=T_real, windows=windows,
                                   W_pad=W_pad, epilogue=epilogue)
        table_arg = _prefix_rows(close_p)                  # (N, 2, T_pad)
        table_spec = pl.BlockSpec((1, 2, T_pad), lambda i, j: (i, 0, 0),
                                  memory_space=pltpu.VMEM)
        scratch = [pltpu.VMEM((W_pad, T_pad), jnp.float32)]
    else:
        sma_table = _sma_table(close_p, windows, W_pad)
        kernel = functools.partial(_kernel, cost=cost, ppy=ppy,
                                   T_real=T_real, epilogue=epilogue)
        table_arg = sma_table
        table_spec = pl.BlockSpec((1, W_pad, T_pad), lambda i, j: (i, 0, 0),
                                  memory_space=pltpu.VMEM)
        scratch = []
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, T_pad, 1), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            table_spec,
            pl.BlockSpec((W_pad, lanes), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, lanes), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ] + _tr_specs(T_real),
        out_specs=pl.BlockSpec(
            (1, 1, _METRIC_ROWS, lanes), lambda i, j: (i, j, 0, 0),
            memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (N, n_blocks, _METRIC_ROWS, lanes), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
        name="sma_crossover_kernel",
        compiler_params=_COMPILER_PARAMS,
    )(returns3, table_arg, onehot_d, warm,
      *_tr_args(t_real, T_real))
    # (N, n_blocks, 16, 128) -> nine (N, P_real) fields. The slice to P_real
    # stays inside the jit: eagerly slicing nine arrays after the call costs
    # nine more host dispatches.
    return Metrics(*(
        jnp.reshape(out[:, :, k, :], (N, P_pad))[:, :P_real]
        for k in range(9)))


def _check_carry_out_args(carry_out: bool, t_real) -> None:
    """Argument-only carry_out validation, hoisted to every wrapper's
    entry so an invalid call raises BEFORE the kernel sweep runs (the
    sweep is seconds of work at real shapes; the check is free)."""
    if carry_out and t_real is not None:
        raise ValueError(
            "carry_out=True supports uniform full-history panels only "
            "(a streaming checkpoint summarizes ONE panel state; ragged "
            "groups checkpoint per panel)")


def _carry_out_tail(metrics, strategy: str, fields: dict, grid: dict, *,
                    t_real, cost, ppy, epilogue):
    """The shared ``carry_out=True`` tail of every public sweep wrapper:
    return ``(metrics, carry)`` where the carry is the streaming
    checkpoint (``streaming.recurrent.StreamCarry``) of this sweep —
    the scan-form pass that makes every later ΔT-bar append O(ΔT)
    (``streaming.recurrent.append_step``). The carry is built by the
    generic-model scan form (the kernels' rounding twin on CPU, the
    documented knife-edge class on TPU); the kernel metrics are returned
    untouched alongside it. Argument validation lives in
    `_check_carry_out_args`, hoisted to the wrappers' entries."""
    del t_real   # validated (None) at wrapper entry
    from ..streaming import recurrent

    carry = recurrent.build_carry(
        strategy, fields, grid, cost=float(cost),
        periods_per_year=int(ppy), epilogue=epilogue)
    return metrics, carry


def fused_sma_sweep(close, fast, slow, *, t_real=None, cost: float = 0.0,
                    periods_per_year: int = 252,
                    interpret: bool | None = None,
                    table: str | None = None,
                    epilogue: str | None = None,
                    carry_out: bool = False) -> Metrics:
    """Fused SMA-crossover sweep: ``(N, T)`` closes x ``(P,)`` param lanes.

    ``fast``/``slow`` are the *flat* per-combo window arrays (use
    :func:`~..parallel.sweep.product_grid`), concrete (not traced) — the
    distinct-window table layout is computed host-side. Windows are bar
    counts and must be integral. Returns :class:`~.metrics.Metrics` with
    ``(N, P)`` fields matching the generic sweep path: bit-level on CPU; on
    TPU the MXU's 3xbf16 selection matmul can flip a *knife-edge* crossover
    (|fast_sma - slow_sma| ~ 1e-7 relative) — measured ~1 backtest in 8000
    differing by one round-trip on GBM data, all other entries tight.

    ``table`` picks the SMA-table substrate (default env ``DBX_SMA_TABLE``
    or ``"inline"``): ``"inline"`` rebuilds the W-major table in VMEM
    scratch inside the kernel once per ticker — no XLA table passes, no
    table HBM round-trip, measured ~1.04x median / up to ~1.15x the
    ``"hbm"`` headline on-chip — while ``"hbm"`` streams the XLA-built
    table (the roofline_stages scaffold's twin). Bit-identical on CPU
    (tested); on TPU the substrates can differ at ~0.01% of knife-edge
    crossovers (1-ULP division lowering, see `_kernel_inline`) — the
    fused-vs-generic verify budgets hold for both (bench --verify).
    ``epilogue`` picks the metrics-tail substrate (env ``DBX_EPILOGUE``,
    default ``"scan"`` — the single-pass carry scan; ``"ladder"`` keeps
    the O(T log T) shift-ladder fallback, see `_equity_scan`).
    ``carry_out=True`` additionally returns the streaming checkpoint of
    this sweep (see `_carry_out_tail`) as ``(metrics, carry)``.
    """
    _check_carry_out_args(carry_out, t_real)
    if interpret is None:
        interpret = interpret_default()
    close = jnp.asarray(close, jnp.float32)
    fast = np.asarray(fast)
    slow = np.asarray(slow)
    T = close.shape[1]
    P = fast.shape[0]

    windows, onehot_d, warm = _grid_setup(
        fast.astype(np.float32).tobytes(), slow.astype(np.float32).tobytes())
    table = _family_table("sma", table)
    m = _fused_call(close, onehot_d, warm,
                    _t_real_col(t_real, close),
                    windows=windows,
                    T_pad=_round_up(T, 8), W_pad=onehot_d.shape[0],
                    P_real=P, T_real=T if t_real is None else None,
                    cost=float(cost), ppy=int(periods_per_year),
                    interpret=bool(interpret), table=table,
                    lanes_env=resolve_lanes_cap(),
                    epilogue=_resolve_epilogue(epilogue))
    if not carry_out:
        return m
    return _carry_out_tail(m, "sma_crossover", {"close": close},
                           {"fast": fast, "slow": slow}, t_real=t_real,
                           cost=cost, ppy=periods_per_year,
                           epilogue=epilogue)


def _prefix_compose3(pm, p0, pp):
    """Prefix-compose per-bar 3-state transition maps over the sublane axis.

    ``(pm, p0, pp)[t]`` give the next state when the previous state is
    -1/0/+1. Composition of such maps is associative, so the full position
    path evaluates as a log2(T_pad)-round doubling ladder — no serial scan
    (mirrors ``ops.signals.band_hysteresis_assoc``). Returns the composed
    maps; a start-state of flat means ``p0`` IS the position path.
    """
    T_pad = pm.shape[0]
    # Identity fill (-1/0/+1) pads the shifted reads.
    span = 1
    while span < T_pad:
        em = _shift_down(pm, span, -1.0)
        e0 = _shift_down(p0, span, 0.0)
        ep = _shift_down(pp, span, 1.0)
        pm, p0, pp = (
            jnp.where(em < 0, pm, jnp.where(em > 0, pp, p0)),
            jnp.where(e0 < 0, pm, jnp.where(e0 > 0, pp, p0)),
            jnp.where(ep < 0, pm, jnp.where(ep > 0, pp, p0)),
        )
        span *= 2
    return pm, p0, pp


def _select3(state, m, z, p):
    """The component of composed 3-state maps for entry ``state``."""
    return jnp.where(state < 0, m, jnp.where(state > 0, p, z))


def _compose3_path(pm, p0, pp, epilogue: str, state=None):
    """Position path of a 3-state machine from its per-bar transition maps,
    starting flat, or from the ``(1, lanes)`` ``state`` an earlier T block
    ended in (a T-blocked cell).

    ``"ladder"``: the full-T doubling ladder (`_prefix_compose3`), O(T log T).
    ``"scan"`` (default): ONE sequential pass over T-blocks — each block's
    maps compose locally (log2(B) rounds), the entry STATE carried from the
    previous block selects the component, and the block's last row is the
    next carry. Map composition and component selection are pure selects
    (no float arithmetic), so the two substrates are BIT-IDENTICAL on every
    backend; the scan does O(T log B) = O(T) work — the band machines'
    ~55%-of-tail compose cost (the 179-vs-76 ``vpu_ops_per_cell_bar``
    spread vs the sign kernels) drops to the sign kernels' class."""
    if epilogue == "ladder":
        pm, p0, pp = _prefix_compose3(pm, p0, pp)
        # From flat the 0-component is the path.
        return p0 if state is None else _select3(state, pm, p0, pp)
    T_pad = pm.shape[0]
    outs = []
    for s, e in _spans(T_pad, _scan_block(T_pad, epilogue)):
        m, z, p = _prefix_compose3(pm[s:e], p0[s:e], pp[s:e])
        pos = z if state is None else _select3(state, m, z, p)
        outs.append(pos)
        state = pos[e - s - 1:]
    return jnp.concatenate(outs, axis=0)


def _band_ladder(z, valid, k, z_exit, epilogue: str = _EPILOGUE_DEFAULT,
                 state=None):
    """Band-hysteresis position path over ``(T_pad, 128)`` tiles, in-kernel.

    ``k``/``z_exit`` broadcast against the tile (scalars or (1, 128) lanes);
    ``state`` is the position a T-blocked cell's earlier blocks ended in.
    """
    # Per-bar transition maps (next state when previous state is -1/0/+1).
    entered = jnp.where(z < -k, 1.0, jnp.where(z > k, -1.0, 0.0))
    pm = jnp.where(valid & (z > z_exit), -1.0, 0.0)
    p0 = jnp.where(valid, entered, 0.0)
    pp = jnp.where(valid & (z < -z_exit), 1.0, 0.0)
    return _compose3_path(pm, p0, pp, epilogue, state)


def _band_cell_core(z_wt, r_ref, ow_ref, k_ref, warm_ref, refs, T_real,
                    t_blocked: bool = False):
    """Shared head of every band-family cell (Bollinger hysteresis, band
    touch; RSI and VWAP reuse those kernels): ragged/uniform unpack, the
    z-selection matmul, warmup mask and band lanes.

    ``z_wt`` is the ``(W_pad, T_pad)`` z-table VALUE — read from an HBM-
    streamed input block or from the in-kernel VMEM scratch build; T on
    lanes, so HBM tiling pads W to a sublane multiple (8) instead of a
    lane multiple (128); at the baseline grid's ~20 distinct windows the
    old (T, W)-minor layout inflated every table and prep intermediate
    6.4x (same fix as the pairs kernel). A T-blocked cell
    (``t_blocked``) sees one block of the history, its bars numbered from
    the block's first. Returns ``(tr, out_ref, r, z, t_idx, valid, k,
    tb)``, ``tb`` the :class:`_TBlock` (None in a one-block cell).
    """
    T_pad = r_ref.shape[1]
    tb = None
    if t_blocked:
        refs, tb = _t_block_of(refs, T_pad)
    tr, out_ref = _unpack_tr(refs, T_real)
    r = r_ref[0]                     # (T_pad, 1)
    dn = (((0,), (0,)), ((), ()))
    z = jax.lax.dot_general(z_wt, ow_ref[:], dn,
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)  # (T_pad,128)

    lanes = ow_ref.shape[1]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (T_pad, lanes), 0)
    if tb is not None:
        t_idx = t_idx + tb.t0
    warm = warm_ref[0, :][None, :]
    valid = t_idx >= (warm.astype(jnp.int32) - 1)
    k = k_ref[0, :][None, :]                         # (1, lanes) entry band
    return tr, out_ref, r, z, t_idx, valid, k, tb


def _band_cell_prologue(r_ref, z_ref, ow_ref, k_ref, warm_ref, refs, T_real,
                        t_blocked: bool = False):
    """`_band_cell_core` over an HBM-streamed ``(1, W_pad, T_pad)`` block."""
    return _band_cell_core(z_ref[0], r_ref, ow_ref, k_ref, warm_ref, refs,
                           T_real, t_blocked)


def _band_cell_finish(machine: str, z, valid, k, z_exit, r, t_idx, tr,
                      out_ref, *, cost: float, ppy: int, epilogue: str,
                      tb=None):
    """Tail of both Bollinger-family cells — one body for both table
    substrates so the position semantics cannot drift between them.

    ``"hysteresis"``: the 3-state band machine (enter outside ±k, exit
    through ±z_exit). ``"touch"``: memoryless — exposure is which band
    you are currently outside of (``models.bollinger.bollinger_touch``),
    so the compose ladder drops out entirely. A T-blocked cell (``tb``)
    starts its machine from the carried position and rewrites its output
    at every block; the last block's rows are final."""
    if machine == "touch":
        pos = jnp.where(z < -k, 1.0, jnp.where(z > k, -1.0, 0.0))
        pos = jnp.where(valid, pos, 0.0)
    else:
        pos = _band_ladder(z, valid, k, z_exit, epilogue,
                           None if tb is None
                           else tb.carry[_C_POS:_C_POS + 1, :])
    out_ref[0, 0] = _metrics_tail(pos, r, t_idx, tr, cost=cost, ppy=ppy,
                                  epilogue=epilogue, tb=tb)


def _boll_kernel(r_ref, z_ref, ow_ref, k_ref, warm_ref, *refs,
                 cost: float, ppy: int, z_exit: float,
                 T_real: int | None, epilogue: str = _EPILOGUE_DEFAULT,
                 t_blocked: bool = False):
    """Bollinger mean-reversion cell: z-selection matmul + hysteresis
    machine (blocked compose scan by default, see `_compose3_path`)."""
    tr, out_ref, r, z, t_idx, valid, k, tb = _band_cell_prologue(
        r_ref, z_ref, ow_ref, k_ref, warm_ref, refs, T_real, t_blocked)
    _band_cell_finish("hysteresis", z, valid, k, z_exit, r, t_idx, tr,
                      out_ref, cost=cost, ppy=ppy, epilogue=epilogue, tb=tb)


def _touch_kernel(r_ref, z_ref, ow_ref, k_ref, warm_ref, *refs,
                  cost: float, ppy: int, z_exit: float,
                  T_real: int | None, epilogue: str = _EPILOGUE_DEFAULT,
                  t_blocked: bool = False):
    """Band-touch cell: the memoryless Bollinger variant (see
    :func:`_band_cell_finish`). ``z_exit`` is unused (the machine has no
    exit memory); the parameter stays so the kernel is plug-compatible
    with ``_boll_kernel`` in :func:`_fused_boll_call`."""
    tr, out_ref, r, z, t_idx, valid, k, tb = _band_cell_prologue(
        r_ref, z_ref, ow_ref, k_ref, warm_ref, refs, T_real, t_blocked)
    _band_cell_finish("touch", z, valid, k, z_exit, r, t_idx, tr,
                      out_ref, cost=cost, ppy=ppy, epilogue=epilogue, tb=tb)


def _build_boll_z_scratch(c, cs, csx, csx2, z_scr, windows: tuple,
                          W_pad: int):
    """Fill a ``(W_pad, T_pad)`` VMEM scratch with the W-major Bollinger
    z-table of the series whose close row is ``(1, T_pad)`` and whose
    close / centered / centered-square prefix pairs are ``(2, T_pad)``
    each (`_prefix_rows`) — the exact op sequence of `_fused_boll_call`'s
    XLA prep (compensated windowed sums, rolling.py's series-centered
    cancellation guard, eps=1e-12, warmup zero-fill), with `_shift_t`'s
    zero fill reproduced as rotate + zero the wrapped lanes. Call under
    ``pl.when(j == 0)``."""
    T_pad = cs.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, T_pad), 1)
    for i, w in enumerate(windows):
        w = int(w)
        w_f = jnp.float32(w)
        m = _lagged_window_sum(cs, w, lane) / w_f
        s1 = _lagged_window_sum(csx, w, lane)
        s2 = _lagged_window_sum(csx2, w, lane)
        var = jnp.maximum((s2 - s1 * s1 / w_f) / w_f, 0.0)
        z_w = (c - m) / (jnp.sqrt(var) + 1e-12)
        z_scr[i:i + 1, :] = jnp.where(lane >= w - 1, z_w, 0.0)
    for i in range(len(windows), W_pad):
        # One-hot weights are zero on pad rows, but 0 * garbage VMEM
        # could still be NaN — zero them (same discipline as
        # `_build_sma_scratch`).
        z_scr[i:i + 1, :] = jnp.zeros((1, T_pad), jnp.float32)


def _band_kernel_inline(r_ref, c_ref, cs_ref, csx_ref, csx2_ref, ow_ref,
                        k_ref, warm_ref, *refs, cost: float, ppy: int,
                        z_exit: float, T_real: int | None, machine: str,
                        windows: tuple, W_pad: int,
                        epilogue: str = _EPILOGUE_DEFAULT):
    """Both Bollinger-family cells with IN-KERNEL z-table construction.

    Takes the close row plus three prefix pairs ``(N, 2, T_pad)`` instead
    of the XLA-built ``(N, W_pad, T_pad)`` z-table and rebuilds the
    W-major table into persistent VMEM scratch once per ticker at
    param-block ``j == 0`` (same scratch-persistence contract as
    `_kernel_inline`). This deletes the largest XLA prep in the file —
    three windowed sums + var/sqrt over table-shaped intermediates — and
    the z-table HBM round-trip (~61 MB at headline shapes; the prep
    measured ~17% of bollinger's and ~34% of touch's end-to-end wall).
    Bit-identical on CPU interpret mode (tested); on TPU Mosaic's f32
    div/sqrt lowering differs from XLA's by ~1 ULP on some entries — the
    knife-edge flip class every verify budget already covers."""
    *head, z_scr = refs

    @pl.when(pl.program_id(1) == 0)
    def _build():
        _build_boll_z_scratch(c_ref[0], cs_ref[0], csx_ref[0], csx2_ref[0],
                              z_scr, windows, W_pad)

    tr, out_ref, r, z, t_idx, valid, k, _ = _band_cell_core(
        z_scr[:], r_ref, ow_ref, k_ref, warm_ref, tuple(head), T_real)
    _band_cell_finish(machine, z, valid, k, z_exit, r, t_idx, tr,
                      out_ref, cost=cost, ppy=ppy, epilogue=epilogue)


_BAND_KERNELS = {"hysteresis": _boll_kernel, "touch": _touch_kernel}


def _pad_w(tbl, W_pad: int):
    """Zero-pad an ``(N, W, T_pad)`` table's window axis up to ``W_pad``."""
    N, W, T_pad = tbl.shape
    if W_pad == W:
        return tbl
    return jnp.concatenate(
        [tbl, jnp.zeros((N, W_pad - W, T_pad), jnp.float32)], axis=1)


# Window rows per block of `windowed_sum3` (see there).
_WS3_BLOCK = 32


def _cumsum_window_tools(windows: tuple, T_pad: int):
    """Scaffolding for per-distinct-window cumsum-difference rolling sums.

    Returns ``(w_col, w_f, t_row, windowed_sum, windowed_sum3)`` where the
    two closures map ``(N, T_pad)`` / ``(N, W, T_pad)`` inputs to windowed
    trailing sums, replicating ``rolling.rolling_sum``'s float op order
    (compensated prefix pair, ``window_diff`` against the shifted read).
    Tables built with these are (N, W, T_pad) — T on the minor axis — so
    HBM tiling pads W to a sublane multiple (8), not a lane multiple (128).
    """
    w_col = jnp.asarray(np.asarray(windows, np.int32))[:, None]  # (W,1)
    w_f = w_col.astype(jnp.float32)[None]                        # (1,W,1)
    t_row = jnp.arange(T_pad)[None, :]                           # (1,T_pad)

    def windowed_sum(series, *, prefix=None, as_pair=False):     # (N,T_pad) ->
        # Per-window shifted reads as STATIC slice+concat (plain copies
        # XLA fuses), NOT a (W, T_pad)-indexed gather: the gather version
        # of the SMA table measured ~37% of that whole sweep (bench.py
        # roofline_stages), and windowed_sum3 below learned the same
        # lesson earlier. Bit-identical: window rows are compile-time
        # constants, zero-filled for t < w exactly like the old
        # clipped-gather + in-window mask. ``prefix`` passes the series'
        # prefix pair ready-made (``rolling.prefix_prod2``'s exact
        # products); ``as_pair`` keeps each window sum as the
        # ``rolling.window_sum2`` pair.
        hi, lo = rolling.prefix_sum2(series) if prefix is None else prefix

        def lagged(cs):                                          # (N,W,T_pad)
            return jnp.stack([_shift_t(cs, int(w), 0.0) for w in windows],
                             axis=1)
        combine = rolling.window_sum2 if as_pair else rolling.window_diff
        return combine(hi[:, None, :], lo[:, None, :], lagged(hi),
                       lagged(lo))

    def windowed_sum3(series):                                   # (N,W,T_pad)
        # Per-row shifted reads as STATIC slices, not take_along_axis: the
        # 3-D gather measured ~185 ms alone at the 500x20x1280 baseline
        # (the cumsum itself is ~12 ms); static shifts are plain copies and
        # bit-identical (window rows are compile-time constants here).
        # Rows go in blocks of _WS3_BLOCK windows, each written in place
        # into one zero buffer: over 128 rows at once the v5e compiler
        # gave the VWAP and pairs preps ~130 full (N, W, T_pad) temps
        # (8.4 GB at 16 x 8,192 bars; 64 MiB with the blocks).
        def lagged(cs, block):
            shifted = jnp.zeros_like(cs)
            # A window covering the whole padded axis has no shifted read
            # at all (the old clipped-gather + in-window mask yielded an
            # all-zero row there — same result, and the warmup mask
            # downstream keeps such degenerate lanes flat anyway).
            for i, w in enumerate(block):
                if w < T_pad:
                    shifted = jax.lax.dynamic_update_slice(
                        shifted, cs[:, i:i + 1, :T_pad - w], (0, i, w))
            return shifted

        blocks = []
        for j in range(0, len(windows), _WS3_BLOCK):
            block = windows[j:j + _WS3_BLOCK]
            hi, lo = rolling.prefix_sum2(series[:, j:j + _WS3_BLOCK])
            blocks.append(rolling.window_diff(hi, lo, lagged(hi, block),
                                              lagged(lo, block)))
        return jnp.concatenate(blocks, axis=1)

    return w_col, w_f, t_row, windowed_sum, windowed_sum3


# The longest history a band-machine cell holds in VMEM whole: every
# family compiles there for the v5e under _COMPILER_PARAMS
# (tests/test_tpu_compile.py). Past it the cell walks T in blocks of
# _T_BLOCK_LONG bars, one (T_blk, lanes) f32 tile (2 MiB at 256 lanes)
# per live array, the working set of a 2,048-bar daily cell.
_T_BLOCK_MAX = 8192
_T_BLOCK_LONG = 2048
# The strategies whose fused kernel is a band-machine cell launched by
# `_band_machine_pallas`, so takes a history of any length: the router
# (rpc.compute.bar_capped) caps every other family at _T_BLOCK_MAX.
T_BLOCKED_FAMILIES = frozenset({"bollinger", "bollinger_touch", "rsi",
                                "stochastic", "keltner", "vwap_reversion"})


def _band_t_pad(T: int, t_block: int | None = None) -> int:
    """Padded bars of a band-machine launch over ``T`` bars: a lane
    multiple (128) that is one block, or a whole number of T blocks
    (``t_block`` bars each, by default :func:`_t_block`'s)."""
    T_pad = _round_up(T, 128)
    if t_block is None and T_pad > _T_BLOCK_MAX:
        t_block = _T_BLOCK_LONG
    return T_pad if t_block is None else _round_up(T_pad, t_block)


def _t_block(T_pad: int) -> int | None:
    """Bars per T block of a band-machine launch over ``T_pad`` padded
    bars: None (one block, the whole history in VMEM) up to
    ``_T_BLOCK_MAX``, ``_T_BLOCK_LONG`` past it."""
    return None if T_pad <= _T_BLOCK_MAX else _T_BLOCK_LONG


def _band_machine_pallas(kernel, close_p, z_table, onehot_w, k_lanes, warm,
                         t_real, *, T_pad: int, W_pad: int, P_real: int,
                         T_real: int | None, interpret: bool, name: str,
                         lanes_cap: int = 256, aux_rows=(),
                         scratch_shapes=(), lanes_env: int = 0,
                         t_block: int | None = None):
    """Shared launch for every band-machine strategy (Bollinger, RSI, VWAP):
    returns column + ``(N, W_pad, T_pad)`` z-table + one-hot/band/warmup
    lanes into ``_boll_kernel``-shaped cells, :class:`Metrics` out.

    ``t_block`` (bars, dividing ``T_pad``) walks the history in T blocks:
    a third grid axis, innermost and sequential, over ``(1, t_block, 1)``
    return and ``(1, W_pad, t_block)`` z-table blocks, with the cell's
    carry in VMEM scratch (`_TBlock`); the kernel takes ``t_blocked``.
    None: :func:`_t_block`'s choice, which keeps histories up to
    ``_T_BLOCK_MAX`` in one block, the program of the one-block cell.

    ``lanes_cap`` defaults to 256 — the hysteresis cell's 3-state compose
    ladder keeps ~6 (T_pad, lanes) arrays live, so 512 lanes would press
    the VMEM budget. The ladder-free touch cell overrides to 512 (sign-
    kernel class).

    ``z_table=None`` selects the in-kernel substrate: ``aux_rows`` (each
    ``(N, T_pad)`` or ``(N, k, T_pad)``, delivered as ``(1, 1, T_pad)`` /
    ``(1, k, T_pad)`` lane-major blocks) and ``scratch_shapes`` carry the
    VMEM-scratch z-table build instead (`_band_kernel_inline`). ``name``
    is the kernel's name in the device trace, one per strategy."""
    N = close_p.shape[0]
    P_pad = k_lanes.shape[1]
    if t_block is None:
        t_block = _t_block(T_pad)
    blocked = t_block is not None
    if blocked and (z_table is None or T_pad % t_block or t_block % 8):
        raise ValueError(
            f"a T-blocked band launch takes an HBM z-table and a block of "
            f"a multiple of 8 bars dividing T_pad={T_pad} (got "
            f"t_block={t_block}, z_table={z_table is not None})")
    T_blk = t_block if blocked else T_pad
    lanes = _widest_lanes(P_pad, lanes_cap, T_blk, lanes_env)
    n_blocks = P_pad // lanes
    if blocked:
        grid = (N, n_blocks, T_pad // T_blk)
        kernel = functools.partial(kernel, t_blocked=True)
        scratch_shapes = [pltpu.VMEM((_METRIC_ROWS, lanes), jnp.float32)]

        def spec(shape, index):
            return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)
    else:
        grid = (N, n_blocks)

        def spec(shape, index):
            return pl.BlockSpec(shape, lambda i, j: index(i, j, 0),
                                memory_space=pltpu.VMEM)
    table_specs = [] if z_table is None else [
        spec((1, W_pad, T_blk), lambda i, j, t: (i, 0, t))]
    table_args = [] if z_table is None else [z_table]
    aux_rows = [row if row.ndim == 3 else row[:, None, :]
                for row in aux_rows]
    aux_specs = [spec((1, row.shape[1], T_pad), lambda i, j, t: (i, 0, 0))
                 for row in aux_rows]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            spec((1, T_blk, 1), lambda i, j, t: (i, t, 0)),
        ] + table_specs + aux_specs + [
            spec((W_pad, lanes), lambda i, j, t: (0, j)),
            spec((1, lanes), lambda i, j, t: (0, j)),
            spec((1, lanes), lambda i, j, t: (0, j)),
        ] + _tr_specs(T_real),
        out_specs=spec((1, 1, _METRIC_ROWS, lanes),
                       lambda i, j, t: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (N, n_blocks, _METRIC_ROWS, lanes), jnp.float32),
        scratch_shapes=list(scratch_shapes),
        interpret=interpret,
        compiler_params=_T_BLOCKED_PARAMS if blocked else _COMPILER_PARAMS,
        name=name,
    )(_rets3(close_p), *table_args, *aux_rows, onehot_w, k_lanes, warm,
      *_tr_args(t_real, T_real))
    return Metrics(*(
        jnp.reshape(out[:, :, k, :], (N, P_pad))[:, :P_real]
        for k in range(9)))


@functools.partial(
    jax.jit,
    static_argnames=("windows", "T_pad", "W_pad", "P_real", "T_real", "cost",
                     "ppy", "z_exit", "machine", "interpret", "table",
                     "lanes_env", "epilogue", "t_block"))
def _fused_boll_call(close, onehot_w, k_lanes, warm, t_real, *, windows: tuple,
                     T_pad: int, W_pad: int, P_real: int, T_real: int | None,
                     cost: float, ppy: int, z_exit: float, interpret: bool,
                     machine: str = "hysteresis", table: str = "inline",
                     lanes_env: int = 0, epilogue: str = _EPILOGUE_DEFAULT,
                     t_block: int | None = None):
    """Z-score table prep + pallas call in one jit (same dispatch-economy
    rationale as ``_fused_call``).

    The table replicates ``rolling.rolling_zscore``'s exact float op order so
    CPU interpret-mode results are bit-identical to the generic path:
    numerator from the *uncentered* rolling mean, std from series-centered
    second moments (rolling.py's cancellation guard), eps=1e-12.

    ``table="inline"`` (default) ships only the close row + three prefix
    pairs to the kernel and rebuilds the z-table in VMEM scratch
    (`_band_kernel_inline`) — the three windowed sums + var/sqrt XLA prep
    and the z-table HBM round-trip measured ~17% (hysteresis) / ~34%
    (touch) of end-to-end wall at headline shapes. ``"hbm"`` keeps the
    XLA-built table as the A/B twin, and serves every T-blocked launch
    (``t_block``, see `_band_machine_pallas`): the in-kernel build needs
    the whole history in VMEM.
    """
    N, T = close.shape
    if t_block is None:
        t_block = _t_block(T_pad)
    if t_block is not None:
        table = "hbm"
    epilogue = _interp_epilogue(epilogue, T_pad, interpret)
    close_p = _pad_last(close, T_pad)
    # The memoryless touch cell has no compose ladder: sign-kernel VMEM
    # class, so it takes the sign kernels' 512-lane blocks (measured +5%
    # in the 3x interleaved on-chip A/B).
    lanes_cap = 512 if machine == "touch" else 256
    # Center with the mean over the REAL bars only (the generic path sees the
    # unpadded series); the pad region's xc values never reach a real output.
    xc = close_p - _real_mean(close_p, t_real, T)
    if table == "inline":
        kernel = functools.partial(_band_kernel_inline, cost=cost, ppy=ppy,
                                   z_exit=z_exit, T_real=T_real,
                                   machine=machine, windows=windows,
                                   W_pad=W_pad, epilogue=epilogue)
        return _band_machine_pallas(
            kernel, close_p, None, onehot_w, k_lanes, warm, t_real,
            T_pad=T_pad, W_pad=W_pad, P_real=P_real, T_real=T_real,
            interpret=interpret, lanes_cap=lanes_cap,
            name=("bollinger_touch_kernel" if machine == "touch"
                  else "bollinger_kernel"),
            aux_rows=[close_p, _prefix_rows(close_p), _prefix_rows(xc),
                      _prefix_rows(xc * xc)],
            scratch_shapes=[pltpu.VMEM((W_pad, T_pad), jnp.float32)],
            lanes_env=lanes_env)

    w_col, w_f, t_row, windowed_sum, _ = _cumsum_window_tools(windows, T_pad)
    m = windowed_sum(close_p) / w_f                              # rolling mean
    s1 = windowed_sum(xc)
    s2 = windowed_sum(xc * xc)
    var = jnp.maximum((s2 - s1 * s1 / w_f) / w_f, 0.0)
    z_table = (close_p[:, None, :] - m) / (jnp.sqrt(var) + 1e-12)
    z_table = _pad_w(jnp.where((t_row >= w_col - 1)[None], z_table, 0.0),
                     W_pad)

    kernel = functools.partial(_BAND_KERNELS[machine], cost=cost, ppy=ppy,
                               z_exit=z_exit, T_real=T_real,
                               epilogue=epilogue)
    return _band_machine_pallas(
        kernel, close_p, z_table, onehot_w, k_lanes, warm, t_real,
        T_pad=T_pad, W_pad=W_pad, P_real=P_real, T_real=T_real,
        interpret=interpret, lanes_cap=lanes_cap, lanes_env=lanes_env,
        name=("bollinger_touch_kernel" if machine == "touch"
              else "bollinger_kernel"), t_block=t_block)


def _bollinger_family_sweep(close, window, k, *, machine: str, z_exit: float,
                            t_real, cost: float, periods_per_year: int,
                            interpret: bool | None,
                            table: str | None = None,
                            epilogue: str | None = None,
                            carry_out: bool = False,
                            t_block: int | None = None) -> Metrics:
    """Shared prep for both Bollinger-family wrappers (one z-table/grid
    pipeline, the ``machine`` picks the cell; ``table`` picks the z-table
    substrate — env ``DBX_BOLL_TABLE`` or ``"inline"``; ``t_block`` bars
    per T block, see `_band_machine_pallas`)."""
    _check_carry_out_args(carry_out, t_real)
    if carry_out and machine == "hysteresis" and float(z_exit) != 0.0:
        raise ValueError(
            "carry_out=True requires z_exit=0 for the bollinger machine "
            "(the streaming family follows models.bollinger, which exits "
            "at the rolling mean)")
    if interpret is None:
        interpret = interpret_default()
    close = jnp.asarray(close, jnp.float32)
    window = np.asarray(window)
    k = np.asarray(k, np.float32)
    T = close.shape[1]

    windows, onehot_w, k_lanes, warm = _boll_grid_setup(
        window.astype(np.float32).tobytes(), k.tobytes())
    # T_pad is a lane multiple (128): T sits on the table's minor axis AND
    # on the working tiles' sublane axis.
    m = _fused_boll_call(close, onehot_w, k_lanes, warm,
                         _t_real_col(t_real, close),
                         windows=windows,
                         T_pad=_band_t_pad(T, t_block),
                         W_pad=onehot_w.shape[0],
                         P_real=window.shape[0],
                         T_real=T if t_real is None else None,
                         cost=float(cost), ppy=int(periods_per_year),
                         z_exit=float(z_exit), machine=machine,
                         interpret=bool(interpret),
                         table=_family_table("boll", table),
                         lanes_env=resolve_lanes_cap(),
                         epilogue=_resolve_epilogue(epilogue),
                         t_block=t_block)
    if not carry_out:
        return m
    return _carry_out_tail(
        m, "bollinger" if machine == "hysteresis" else "bollinger_touch",
        {"close": close}, {"window": window, "k": k}, t_real=t_real,
        cost=cost, ppy=periods_per_year, epilogue=epilogue)


def fused_bollinger_touch_sweep(close, window, k, *, t_real=None,
                                cost: float = 0.0,
                                periods_per_year: int = 252,
                                interpret: bool | None = None,
                                table: str | None = None,
                                epilogue: str | None = None,
                                carry_out: bool = False,
                                t_block: int | None = None) -> Metrics:
    """Fused band-touch sweep: the path-free Bollinger variant.

    Same z-table and grid layout as :func:`fused_bollinger_sweep`, but the
    position is memoryless (long/short while outside the ±k band, flat
    inside — ``models.bollinger.bollinger_touch``), so the cell skips the
    hysteresis ladder. Matches ``run_sweep(..., "bollinger_touch")``:
    bit-level on CPU interpret mode; the usual MXU knife-edge caveat on
    TPU.
    """
    return _bollinger_family_sweep(
        close, window, k, machine="touch", z_exit=0.0, t_real=t_real,
        cost=cost, periods_per_year=periods_per_year, interpret=interpret,
        table=table, epilogue=epilogue, carry_out=carry_out,
        t_block=t_block)


def fused_bollinger_sweep(close, window, k, *, t_real=None,
                          z_exit: float = 0.0,
                          cost: float = 0.0, periods_per_year: int = 252,
                          interpret: bool | None = None,
                          table: str | None = None,
                          epilogue: str | None = None,
                          carry_out: bool = False,
                          t_block: int | None = None) -> Metrics:
    """Fused Bollinger mean-reversion sweep: ``(N, T)`` closes x ``(P,)`` lanes.

    ``window``/``k`` are flat per-combo arrays (:func:`product_grid` order);
    windows must be integral bar counts. Matches the generic
    ``run_sweep(..., "bollinger")`` path (``models.bollinger`` +
    ``signals.band_hysteresis_assoc``): bit-level on CPU interpret mode; on
    TPU the MXU z-selection matmul shares the SMA kernel's knife-edge caveat
    for |z - k| ~ 1e-7 relative. BASELINE.json configs[2] is this workload.
    """
    return _bollinger_family_sweep(
        close, window, k, machine="hysteresis", z_exit=z_exit,
        t_real=t_real, cost=cost, periods_per_year=periods_per_year,
        interpret=interpret, table=table, epilogue=epilogue,
        carry_out=carry_out, t_block=t_block)




def _distinct_windows(vals: np.ndarray, what: str) -> np.ndarray:
    """Validate integral bar counts and return the sorted distinct windows."""
    if not np.allclose(vals, np.round(vals)):
        raise ValueError(
            f"fused sweep {what} are bar counts and must be integral; got "
            f"non-integer values "
            f"(e.g. {vals[~np.isclose(vals, np.round(vals))][0]})")
    return np.unique(np.round(vals)).astype(np.float32)


def _window_onehot(windows: np.ndarray, vals: np.ndarray, W_pad: int,
                   P_pad: int) -> np.ndarray:
    """(W_pad, P_pad) selector, one 1.0 per real lane.

    Search with the same rounding used to build ``windows``, or a value
    like 200.001 (passes the integrality tolerance) lands one row off.
    """
    oh = np.zeros((W_pad, P_pad), np.float32)
    idx = np.searchsorted(windows, np.round(vals).astype(np.float32))
    oh[idx, np.arange(vals.shape[0])] = 1.0
    return oh


@functools.lru_cache(maxsize=4)
def _boll_grid_setup(window_bytes: bytes, k_bytes: bytes):
    """Distinct windows + device-resident one-hot/k/warmup lanes (cached, same
    rationale as :func:`_grid_setup`)."""
    window = np.frombuffer(window_bytes, np.float32)
    k = np.frombuffer(k_bytes, np.float32)
    P = window.shape[0]
    windows = _distinct_windows(window, "windows")
    # One-hot contracts over W as the *sublane* dim of both operands (the
    # table is (W, T)-major), so W pads to 8, not 128.
    W_pad = _round_up(max(windows.shape[0], 1), 8)
    P_pad = _round_up(max(P, 1), _LANES)
    oh = _window_onehot(windows, window, W_pad, P_pad)

    k_lanes = np.full((1, P_pad), np.float32(np.inf))
    k_lanes[0, :P] = k            # padded lanes never enter (k = +inf)
    warm = np.ones((1, P_pad), np.float32)
    warm[0, :P] = window
    return (tuple(int(w) for w in windows), _const(oh),
            _const(k_lanes), _const(warm))


def _pairs_kernel(zh_ref, ow_ref, k_ref, zx_ref,
                  warm_ref, *refs, cost: float, ppy: int,
                  T_real: int | None, epilogue: str = _EPILOGUE_DEFAULT):
    """Pairs-trade cell: one stacked selection matmul + hysteresis + PnL.

    The per-pair z-score and *hedged-return* tables arrive stacked along
    the lane (T) axis as one ``(W_pad, 2*T_pad)`` block, so ONE MXU
    contraction selects both per lane — the skinny (K = W_pad) selection
    matmuls are pass-overhead-bound, and prep already knows the spread
    return ``(r_y - prev_beta * r_x) / max(1 + |prev_beta|, 1)``
    (gross-exposure normalized, mirroring ``models.pairs.pair_backtest``;
    the beta shift is baked in). The shared band ladder turns z into the
    position path; ``net = prev_pos * hr - cost * |Δpos|`` shares only
    ``_metrics_pack`` with the single-asset tail.
    """
    tr, out_ref = _unpack_tr(refs, T_real)
    T_pad = zh_ref.shape[2] // 2
    # The table is (W_pad, 2*T_pad) — T on lanes, so the HBM layout pads W
    # up to a sublane multiple (8) instead of a lane multiple (128); the
    # 12.8x HBM blow-up of a W-minor table layout dominated the first cut
    # of this kernel (measured: 601 of 716 ms/sweep in prep). The selection
    # contracts dim 0 of both operands (tbl^T @ onehot on the MXU).
    dn = (((0,), (0,)), ((), ()))
    zh = jax.lax.dot_general(zh_ref[0], ow_ref[:], dn,
                             preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)
    z = zh[:T_pad]                                     # (T_pad, lanes)
    hr = zh[T_pad:]                                    # hedged spread return

    lanes = ow_ref.shape[1]          # widest legal param block (launcher)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (T_pad, lanes), 0)
    warm = warm_ref[0, :][None, :]                     # (1, lanes) = 2*lb - 1
    valid = t_idx >= (warm.astype(jnp.int32) - 1)
    k = k_ref[0, :][None, :]                           # per-lane z_entry
    zx = zx_ref[0, :][None, :]                         # per-lane z_exit

    pos = _band_ladder(z, valid, k, zx, epilogue)

    row_ok = t_idx < tr
    pos_last = _row_at(pos, tr, t_idx, keepdims=True)
    pos = jnp.where(row_ok, pos, pos_last)
    prev = _shift_down(pos, 1, 0.0)
    net = prev * hr - cost * jnp.abs(pos - prev)
    out_ref[0, 0] = _metrics_pack(pos, prev, net, row_ok, t_idx, tr,
                                  ppy=ppy, epilogue=epilogue)


@functools.partial(
    jax.jit,
    static_argnames=("windows", "T_pad", "W_pad", "P_real", "T_real", "cost",
                     "ppy", "interpret", "epilogue"))
def _fused_pairs_call(y_close, x_close, onehot_w, k_lanes, zx_lanes, warm,
                      t_real, *,
                      windows: tuple, T_pad: int, W_pad: int, P_real: int,
                      T_real: int | None,
                      cost: float, ppy: int, interpret: bool,
                      epilogue: str = _EPILOGUE_DEFAULT):
    """Beta/z table prep + pallas call in one jit.

    The tables follow ``rolling.rolling_ols`` / ``rolling.rolling_zscore``'s
    formulas (series-centered moments, eps=1e-12, warmup fill 0 so the warmup
    spread is exactly ``y`` — ``models.pairs.pair_signals`` semantics). Both
    the OLS moment sums and the z-score's per-(pair, window) sums ride the
    same cumsum-differencing closures as the generic path
    (:func:`_cumsum_window_tools`), so the whole signal prep rounds like the
    reference algebra (see :func:`fused_pairs_sweep`).
    """
    N, T = y_close.shape
    epilogue = _interp_epilogue(epilogue, T_pad, interpret)
    y_p, x_p = _pad_last(y_close, T_pad), _pad_last(x_close, T_pad)

    # Tables are built (N, W, T_pad) — T on the minor axis — so HBM tiling
    # pads W to a sublane multiple (8) rather than a lane multiple (128).
    # BOTH the per-pair OLS moments and the per-(pair, window) z-score sums
    # difference compensated prefix sums as ``rolling.rolling_ols`` /
    # ``rolling.rolling_sum`` do, with static shifted reads.
    # Selection-stability is why (round 4):
    # the previous block-banded MXU tree sums evaluated the z windowed sums
    # in a different summation order than the generic path's cumsum
    # difference, and that rounding gap was the fleet's worst entry-flip
    # rate (0.77% of cells, the only unstable best-param argmax in
    # VERIFY_r03). Matching the op order collapses the disagreement to the
    # same class as the other kernels; the 3-D minor-axis cumsum with
    # static per-row shifts costs about the same as the two band einsums it
    # replaces (A/B'd as full entry-point timings on the chip, see
    # DESIGN.md).
    w_col, w_f, t_row, _, windowed_sum3 = _cumsum_window_tools(windows, T_pad)

    # Rolling OLS of y on x per distinct lookback: rolling.rolling_ols's
    # double-f32 window moments of the real-bar-centred legs
    # (rolling.ols_slope2), each lagged read a static shift. Windows go in
    # blocks of _WS3_BLOCK (windowed_sum3's reason: the pairs of (N, W,
    # T_pad) moment tables at once overflow HBM at the bar cap).
    mx = _real_mean(x_p, t_real, T)                              # (N,1)
    my = _real_mean(y_p, t_real, T)
    xc, yc = x_p - mx, y_p - my
    prefixes = (rolling.prefix_sum2(xc), rolling.prefix_sum2(yc),
                rolling.prefix_prod2(xc, xc), rolling.prefix_prod2(xc, yc))

    def ols_block(block):              # -> beta, residual, (N, B, T_pad)
        def wsum2(pair):
            def lag(cs):
                return jnp.stack([_shift_t(cs, int(w), 0.0) for w in block],
                                 axis=1)
            hi, lo = pair
            return rolling.window_sum2(hi[:, None, :], lo[:, None, :],
                                       lag(hi), lag(lo))
        wb = jnp.asarray(np.asarray(block, np.float32))[None, :, None]
        sx, sy, sxx, sxy = (wsum2(p) for p in prefixes)
        beta = rolling.ols_slope2(sx, sy, sxx, sxy, wb, 1e-12)
        # The residual y - (alpha + beta x) on the centred legs
        # (rolling.rolling_ols_residual).
        return beta, ((yc[:, None, :] - (sy[0] + sy[1]) / wb)
                      - beta * (xc[:, None, :] - (sx[0] + sx[1]) / wb))
    parts = [ols_block(windows[j:j + _WS3_BLOCK])
             for j in range(0, len(windows), _WS3_BLOCK)]
    beta = jnp.concatenate([b for b, _ in parts], axis=1)
    resid = jnp.concatenate([r for _, r in parts], axis=1)
    ok_w = (t_row >= w_col - 1)[None]                            # OLS warmup
    beta_tbl = jnp.where(ok_w, beta, 0.0)
    # Warmup spread is y - (0 + 0*x) = y (rolling_ols fill=0.0); those bars
    # feed the z-score's *series mean* and early windowed sums, so they must
    # hold exactly y for parity with the generic path.
    spread = jnp.where(ok_w, resid, y_p[:, None, :])

    # Rolling z-score of the spread over the same lookback.
    sp_mean = _real_mean(spread, t_real, T)
    sc = spread - sp_mean
    s1 = windowed_sum3(sc)
    s2 = windowed_sum3(sc * sc)
    varz = jnp.maximum((s2 - s1 * s1 / w_f) / w_f, 0.0)
    mz = windowed_sum3(spread) / w_f
    z = (spread - mz) / (jnp.sqrt(varz) + 1e-12)
    # Valid only after OLS warmup + z warmup: t >= 2*lb - 2. Zeroing the rest
    # also keeps NaN/Inf out of the selection matmul.
    z_tbl = jnp.where((t_row >= 2 * w_col - 2)[None], z, 0.0)

    # Hedged spread return per (pair, window), beta shift baked in: the
    # kernel's net is just prev_pos * hr - costs, and ONE stacked selection
    # matmul picks (z, hr) per lane instead of separate z/beta contractions
    # (the K = W_pad matmul is pass-overhead-bound, so halving the passes
    # matters more than the FLOPs). Same float op order as the old
    # in-kernel form — prev_beta, gross, and the division are untouched.
    ry = _rets3(y_p)[:, :, 0][:, None, :]                        # (N,1,T_pad)
    rx = _rets3(x_p)[:, :, 0][:, None, :]
    beta_prev = jnp.concatenate(
        [jnp.zeros((N, beta_tbl.shape[1], 1), jnp.float32),
         beta_tbl[:, :, :-1]], axis=2)
    gross = 1.0 + jnp.abs(beta_prev)
    hr_tbl = (ry - beta_prev * rx) / jnp.maximum(gross, 1.0)

    if W_pad > len(windows):
        zpad = jnp.zeros((N, W_pad - len(windows), T_pad), jnp.float32)
        z_tbl = jnp.concatenate([z_tbl, zpad], axis=1)
        hr_tbl = jnp.concatenate([hr_tbl, zpad], axis=1)
    zh_tbl = jnp.concatenate([z_tbl, hr_tbl], axis=2)   # (N, W_pad, 2*T_pad)

    P_pad = k_lanes.shape[1]
    # 256-lane cap: the band ladder + two (T_pad, lanes) selection halves
    # keep the same VMEM budget class as the band machines.
    lanes = _widest_lanes(P_pad, 256)
    n_blocks = P_pad // lanes
    kernel = functools.partial(_pairs_kernel, cost=cost, ppy=ppy,
                               T_real=T_real, epilogue=epilogue)
    out = pl.pallas_call(
        kernel,
        grid=(N, n_blocks),
        in_specs=[
            pl.BlockSpec((1, W_pad, 2 * T_pad), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((W_pad, lanes), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, lanes), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, lanes), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, lanes), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ] + _tr_specs(T_real),
        out_specs=pl.BlockSpec(
            (1, 1, _METRIC_ROWS, lanes), lambda i, j: (i, j, 0, 0),
            memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (N, n_blocks, _METRIC_ROWS, lanes), jnp.float32),
        interpret=interpret,
        name="pairs_kernel",
        compiler_params=_COMPILER_PARAMS,
    )(zh_tbl, onehot_w, k_lanes, zx_lanes,
      warm, *_tr_args(t_real, T_real))
    return Metrics(*(
        jnp.reshape(out[:, :, k, :], (N, P_pad))[:, :P_real]
        for k in range(9)))


def fused_pairs_sweep(y_close, x_close, lookback, z_entry, *, t_real=None,
                      z_exit=0.0,
                      cost: float = 0.0, periods_per_year: int = 252,
                      interpret: bool | None = None,
                      epilogue: str | None = None,
                      carry_out: bool = False) -> Metrics:
    """Fused rolling-OLS pairs sweep: ``(N, T)`` pair legs x ``(P,)`` lanes.

    ``lookback``/``z_entry`` are flat per-combo arrays (:func:`product_grid`
    order); ``z_exit`` may be a scalar or a per-combo array. Lookbacks are bar
    counts and must be integral. Matches :func:`~..models.pairs.run_pairs_sweep`
    (BASELINE.json configs[3]) to f32 tolerance: every windowed sum in the
    prep — the OLS moments AND the spread z-score's — is cumsum-differenced
    in ``rolling.rolling_sum``'s exact float op order, so beta/alpha/z all
    round like the generic path and only MXU-selection knife edges remain.
    (Round 4: the z sums were previously block-banded MXU tree sums, whose
    different summation order made pairs the fleet's worst entry-flip rate
    — 0.77% of cells and the only unstable best-param argmax; matching the
    op order measured 7/20000 cells = 0.035% flips, best-param flips 0, and
    8.33 vs 7.96 M/s. ``bench.py --verify`` re-quantifies and BUDGETS both
    every round.)
    """
    _check_carry_out_args(carry_out, t_real)
    if interpret is None:
        interpret = interpret_default()
    y_close = jnp.asarray(y_close, jnp.float32)
    x_close = jnp.asarray(x_close, jnp.float32)
    lookback = np.asarray(lookback, np.float32)
    z_entry = np.asarray(z_entry, np.float32)
    z_exit_arr = np.ascontiguousarray(
        np.broadcast_to(np.asarray(z_exit, np.float32), lookback.shape))
    T = y_close.shape[1]
    P = lookback.shape[0]

    windows, onehot_w, k_lanes, zx_lanes, warm = _pairs_grid_setup(
        lookback.tobytes(), z_entry.tobytes(), z_exit_arr.tobytes())
    # T_pad is a lane multiple (128): T sits on the tables' minor axis AND on
    # the working tiles' sublane axis, so 128 satisfies both constraints.
    m = _fused_pairs_call(y_close, x_close, onehot_w, k_lanes, zx_lanes,
                          warm, _t_real_col(t_real, y_close),
                          windows=windows,
                          T_pad=_round_up(T, 128), W_pad=onehot_w.shape[0],
                          P_real=P, T_real=T if t_real is None else None,
                          cost=float(cost),
                          ppy=int(periods_per_year),
                          interpret=bool(interpret),
                          epilogue=_resolve_epilogue(epilogue))
    if not carry_out:
        return m
    return _carry_out_tail(
        m, "pairs", {"close": y_close, "close2": x_close},
        {"lookback": lookback, "z_entry": z_entry, "z_exit": z_exit_arr},
        t_real=t_real, cost=cost, ppy=periods_per_year, epilogue=epilogue)


@functools.lru_cache(maxsize=4)
def _pairs_grid_setup(lb_bytes: bytes, ze_bytes: bytes, zx_bytes: bytes):
    """Distinct lookbacks + device-resident one-hot/band/warmup lanes
    (cached, same rationale as :func:`_grid_setup`)."""
    lookback = np.frombuffer(lb_bytes, np.float32)
    z_entry = np.frombuffer(ze_bytes, np.float32)
    z_exit = np.frombuffer(zx_bytes, np.float32)
    P = lookback.shape[0]
    windows = _distinct_windows(lookback, "lookbacks")
    # The one-hot contracts over W as the *sublane* dim of both operands
    # (tables are (W, T)-major), so W pads to 8, not 128.
    W_pad = _round_up(max(windows.shape[0], 1), 8)
    P_pad = _round_up(max(P, 1), _LANES)
    oh = _window_onehot(windows, lookback, W_pad, P_pad)

    k_lanes = np.full((1, P_pad), np.float32(np.inf))
    k_lanes[0, :P] = z_entry      # padded lanes never enter (z_entry = +inf)
    zx_lanes = np.zeros((1, P_pad), np.float32)
    zx_lanes[0, :P] = z_exit
    warm = np.ones((1, P_pad), np.float32)
    warm[0, :P] = 2.0 * lookback - 1.0   # OLS warmup + z-score warmup
    return (tuple(int(w) for w in windows), _const(oh),
            _const(k_lanes), _const(zx_lanes), _const(warm))


@functools.lru_cache(maxsize=4)
def _grid_setup(fast_bytes: bytes, slow_bytes: bytes):
    """Distinct windows + device-resident one-hot/warmup arrays per grid.

    Cached: rebuilding these in numpy per call forces a fresh host->device
    transfer of ~2 MB every sweep — a measurable cost next to a sub-100ms
    kernel. The cache is deliberately small (count-
    based, and each entry's device arrays scale with P_pad): a few recent
    grids cover the steady-state sweep/bench loop without pinning HBM for
    stale grids.
    """
    fast = np.frombuffer(fast_bytes, np.float32)
    slow = np.frombuffer(slow_bytes, np.float32)
    P = fast.shape[0]
    windows = _distinct_windows(np.concatenate([fast, slow]), "windows")
    # The SMA table is W-major ((N, W_pad, T_pad), T on lanes), so W pads
    # to a SUBLANE multiple (8) only — a 128-pad here would 4x the table
    # HBM, the per-cell table DMA (~17% of wall time per the roofline
    # accounting), and the MXU contraction width for small grids.
    W_pad = _round_up(max(windows.shape[0], 1), 8)
    P_pad = _round_up(max(P, 1), _LANES)

    warm = np.zeros((1, P_pad), np.float32)
    warm[0, :P] = np.maximum(fast, slow)
    warm[0, P:] = 1.0
    # ONE difference selector (+1 fast row, -1 slow row) built host-side:
    # exact 0/±1 integers (identical to the in-kernel subtraction it
    # replaces), half the per-cell selector VMEM stream.
    oh_d = (_window_onehot(windows, fast, W_pad, P_pad)
            - _window_onehot(windows, slow, W_pad, P_pad))
    return (tuple(int(w) for w in windows), _const(oh_d), _const(warm))


# ---------------------------------------------------------------------------
# Momentum and Donchian fused kernels (T-minor tables, shared machinery)
# ---------------------------------------------------------------------------

# NOTE: channel/warmup fills use a finite 1e30 instead of +/-inf — an inf
# entry in a selection table would turn the one-hot MXU contraction into
# 0 * inf = NaN. Closes are ~1e2, so comparisons behave identically.


def _ema_rows(x, alpha: float):
    """EMA along the last axis with a scalar decay, as a shift-based
    doubling ladder (the prep-side twin of the in-kernel ``_ema_ladder``).

    Delegates to ``rolling.ema_ladder`` — the SAME function the generic
    models (MACD, TRIX) evaluate their EMAs with, which is what makes the
    fused and generic paths rounding twins (the parity fix that took MACD
    from 26/6400 verify flips to 2). Keep this a delegation, not a copy:
    a drifting twin silently reintroduces that flip class. (The ladder is
    also what makes compile time tractable: XLA compiles associative_scan's
    deep slice graph ~30x slower at the bench shape.)
    """
    return rolling.ema_ladder(x, alpha=jnp.float32(alpha))


def _mom_signal_tail(past_tbl, r, close, ol_ref, warm_ref, tr, out_ref, *,
                     cost: float, ppy: int, epilogue: str):
    """Shared momentum selection + metrics tail (both table substrates).

    The signal is exact — the past-close table holds raw close values, the
    one-hot contraction copies one of them per lane, and
    ``sign(close - past)`` involves no rounding at all."""
    T_pad = past_tbl.shape[1]
    dn = (((0,), (0,)), ((), ()))
    past = jax.lax.dot_general(past_tbl, ol_ref[:], dn,
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)

    lanes = ol_ref.shape[1]            # widest legal param block (launcher)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (T_pad, lanes), 0)
    warm = warm_ref[0, :][None, :]     # lookback + 1
    valid = t_idx >= (warm.astype(jnp.int32) - 1)
    pos = jnp.where(valid, jnp.sign(close - past), 0.0)
    out_ref[0, 0] = _metrics_tail(pos, r, t_idx, tr, cost=cost, ppy=ppy,
                                  epilogue=epilogue)


def _mom_kernel(r_ref, c_ref, past_ref, ol_ref, warm_ref, *refs,
                cost: float, ppy: int, T_real: int | None, epilogue: str):
    tr, out_ref = _unpack_tr(refs, T_real)
    _mom_signal_tail(past_ref[0], r_ref[0], c_ref[0], ol_ref, warm_ref, tr,
                     out_ref, cost=cost, ppy=ppy, epilogue=epilogue)


def _mom_kernel_inline(r_ref, c_ref, crow_ref, ol_ref, warm_ref, *refs,
                       cost: float, ppy: int, T_real: int | None,
                       windows: tuple, W_pad: int, epilogue: str):
    """Momentum with the past-close table built in VMEM scratch.

    The XLA prep's table is a clipped gather ``close_p[max(t - w, 0)]``;
    here each distinct lookback's row is a lane-rotate of the close row
    with the wrapped region replaced by ``close_p[0]`` — the same values
    bit-for-bit (raw closes, no arithmetic), so this substrate is exact
    on every backend, unlike the SMA inline table's division caveat.
    Built once per ticker at param-block ``j == 0`` (see `_kernel_inline`
    for the scratch-persistence contract)."""
    *head, past_scr = refs
    tr, out_ref = _unpack_tr(tuple(head), T_real)
    T_pad = r_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _build():
        crow = crow_ref[0]                                 # (1, T_pad)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, T_pad), 1)
        first = crow[:, :1]                                # clip-gather fill
        for k, w in enumerate(windows):
            w = int(w)
            if w < T_pad:
                row = jnp.where(lane >= w, _rot_lanes(crow, w), first)
            else:
                row = jnp.broadcast_to(first, crow.shape)
            past_scr[k:k + 1, :] = row
        for k in range(len(windows), W_pad):
            past_scr[k:k + 1, :] = jnp.zeros((1, T_pad), jnp.float32)

    _mom_signal_tail(past_scr[:], r_ref[0], c_ref[0], ol_ref, warm_ref, tr,
                     out_ref, cost=cost, ppy=ppy, epilogue=epilogue)


def _don_latch_tail(sig_tbl, r, ow_ref, warm_ref, tr, out_ref, *,
                    cost: float, ppy: int, epilogue: str):
    """Shared Donchian breakout-sign selection + latch machine + metrics.

    The latch machine is a 3-state prefix composition (breakout latches
    the position until the opposite channel is touched — associative like
    the band machine, so the same log-depth ladder applies; mirrors
    ``models.donchian``'s lax.scan). The one-hot contraction copies exact
    values in {-1, 0, +1}, so thresholding at ±0.5 recovers the booleans
    exactly."""
    T_pad = sig_tbl.shape[1]
    dn = (((0,), (0,)), ((), ()))
    s = jax.lax.dot_general(sig_tbl, ow_ref[:], dn,
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    up = s > 0.5
    down = s < -0.5

    lanes = ow_ref.shape[1]            # widest legal param block (launcher)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (T_pad, lanes), 0)
    warm = warm_ref[0, :][None, :]     # window + 1
    valid = t_idx >= (warm.astype(jnp.int32) - 1)
    # Latch transition maps (up wins over down, else hold the prior state),
    # invalid bars force flat — models.donchian's scan body, vectorized.
    enter = lambda hold: jnp.where(up, 1.0, jnp.where(down, -1.0, hold))
    pm = jnp.where(valid, enter(-1.0), 0.0)
    p0 = jnp.where(valid, enter(0.0), 0.0)
    pp = jnp.where(valid, enter(1.0), 0.0)
    pos = _compose3_path(pm, p0, pp, epilogue)
    out_ref[0, 0] = _metrics_tail(pos, r, t_idx, tr, cost=cost, ppy=ppy,
                                  epilogue=epilogue)


def _don_kernel(r_ref, c_ref, sig_ref, ow_ref, warm_ref, *refs,
                cost: float, ppy: int, T_real: int | None, epilogue: str):
    """Donchian cell over the XLA-built breakout-sign table.

    The per-(ticker, window) breakout sign (+1 above the prior channel
    high, -1 below the prior low, up wins) is precomputed in prep — ONE
    table and one selection matmul where separate high/low channel tables
    would need two of each. The close column (``c_ref``) is unused here;
    it rides the shared momentum/donchian plumbing
    (:func:`_single_window_pallas`)."""
    del c_ref
    tr, out_ref = _unpack_tr(refs, T_real)
    _don_latch_tail(sig_ref[0], r_ref[0], ow_ref, warm_ref, tr, out_ref,
                    cost=cost, ppy=ppy, epilogue=epilogue)


def _don_kernel_inline(r_ref, c_ref, crow_ref, hi_ref, lo_ref, ow_ref,
                       warm_ref, *refs, cost: float, ppy: int,
                       T_real: int | None, windows: tuple, W_pad: int,
                       epilogue: str):
    """Donchian with the breakout-sign table built in VMEM scratch.

    Rebuilds `_extrema_table`'s shared sparse-table range query in-kernel
    from the raw high/low rows — log2(max window) doubling levels once,
    then each window's channel is the max/min of two overlapping spans —
    and compares the close row against the 1-bar-shifted channels to form
    the ±1/0 sign rows. Max/min and comparisons of raw prices are exact,
    so this substrate matches the XLA-table path bit-for-bit on every
    backend (same algebra, same neutral fills). Built once per ticker at
    param-block ``j == 0`` (see `_kernel_inline` for the scratch
    contract)."""
    del c_ref
    *head, sig_scr = refs
    tr, out_ref = _unpack_tr(tuple(head), T_real)
    T_pad = r_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _build():
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, T_pad), 1)

        def shifted_row(row, s: int, fill: float):
            # `_shift_t`'s semantics on a (1, T_pad) lane-major row.
            if s == 0:
                return row
            if s >= T_pad:
                return jnp.full_like(row, fill)
            return jnp.where(lane >= s, _rot_lanes(row, s), fill)

        def levels_of(src, op, neutral: float):
            max_k = max(int(w).bit_length() - 1 for w in windows)
            levels = [src]
            for k in range(max_k):
                levels.append(op(levels[k],
                                 shifted_row(levels[k], 1 << k, neutral)))
            return levels

        # Only the two log2(max window) level stacks stay live; each
        # window's channel combine + prior-bar shift + breakout compare
        # fuses into its own loop step. (Materializing all per-window
        # rows first OOMs VMEM stack: a (1, T_pad) row occupies a full
        # 8-sublane tile, so 2 x W live rows is ~16x the scratch size.)
        hi_levels = levels_of(hi_ref[0], jnp.maximum, float("-inf"))
        lo_levels = levels_of(lo_ref[0], jnp.minimum, float("inf"))
        crow = crow_ref[0]
        for k, w in enumerate(windows):
            w = int(w)
            kk = w.bit_length() - 1             # largest 2^kk <= w
            hi = jnp.maximum(hi_levels[kk],
                             shifted_row(hi_levels[kk], w - (1 << kk),
                                         float("-inf")))
            lo = jnp.minimum(lo_levels[kk],
                             shifted_row(lo_levels[kk], w - (1 << kk),
                                         float("inf")))
            hi = jnp.where(lane >= w - 1, hi, 1e30)
            lo = jnp.where(lane >= w - 1, lo, -1e30)
            hi_prev = shifted_row(hi, 1, 1e30)
            lo_prev = shifted_row(lo, 1, -1e30)
            sig_scr[k:k + 1, :] = jnp.where(
                crow >= hi_prev, 1.0,
                jnp.where(crow <= lo_prev, -1.0, 0.0))
        for k in range(len(windows), W_pad):
            sig_scr[k:k + 1, :] = jnp.zeros((1, T_pad), jnp.float32)

    _don_latch_tail(sig_scr[:], r_ref[0], ow_ref, warm_ref, tr, out_ref,
                    cost=cost, ppy=ppy, epilogue=epilogue)


def _single_window_pallas(kernel, close, tables, onehot_w, warm, t_real, *,
                          T_pad: int, W_pad: int, P_real: int,
                          T_real: int | None, interpret: bool, name: str,
                          aux_rows=(), scratch_shapes=(), lanes_cap=_LANES,
                          lanes_env: int = 0):
    """Shared pallas_call plumbing for the momentum/donchian kernels:
    returns + close columns, one or two (N, W_pad, T_pad) tables, the
    one-hot/warmup lanes, optional ragged lengths.

    ``aux_rows`` are extra ``(N, T_pad)`` series delivered to the kernel as
    ``(1, 1, T_pad)`` lane-major rows (T on lanes), and ``scratch_shapes``
    are forwarded to ``pallas_call`` — together they carry the in-kernel
    (VMEM-scratch) table builders, which take raw series rows instead of
    XLA-built ``(N, W_pad, T_pad)`` tables (see `_kernel_inline` for the
    pattern and the scratch-persistence contract). ``name`` is the
    kernel's name in the device trace, one per strategy.
    """
    N = close.shape[0]
    P_pad = onehot_w.shape[1]
    lanes = _widest_lanes(P_pad, lanes_cap, T_pad, lanes_env)
    n_blocks = P_pad // lanes
    table_specs = [
        pl.BlockSpec((1, W_pad, T_pad), lambda i, j: (i, 0, 0),
                     memory_space=pltpu.VMEM)
        for _ in tables
    ]
    aux_specs = [
        pl.BlockSpec((1, 1, T_pad), lambda i, j: (i, 0, 0),
                     memory_space=pltpu.VMEM)
        for _ in aux_rows
    ]
    out = pl.pallas_call(
        kernel,
        grid=(N, n_blocks),
        in_specs=[
            pl.BlockSpec((1, T_pad, 1), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, T_pad, 1), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ] + table_specs + aux_specs + [
            pl.BlockSpec((W_pad, lanes), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, lanes), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ] + _tr_specs(T_real),
        out_specs=pl.BlockSpec(
            (1, 1, _METRIC_ROWS, lanes), lambda i, j: (i, j, 0, 0),
            memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (N, n_blocks, _METRIC_ROWS, lanes), jnp.float32),
        scratch_shapes=list(scratch_shapes),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
        name=name,
    )(_rets3(close), close[..., None], *tables,
      *(row[:, None, :] for row in aux_rows), onehot_w, warm,
      *_tr_args(t_real, T_real))
    return Metrics(*(
        jnp.reshape(out[:, :, k, :], (N, P_pad))[:, :P_real]
        for k in range(9)))


@functools.partial(
    jax.jit,
    static_argnames=("windows", "T_pad", "W_pad", "P_real", "T_real", "cost",
                     "ppy", "interpret", "table", "lanes_env", "epilogue"))
def _fused_mom_call(close, onehot_l, warm, t_real, *, windows: tuple,
                    T_pad: int, W_pad: int, P_real: int, T_real: int | None,
                    cost: float, ppy: int, interpret: bool,
                    table: str = "inline", lanes_env: int = 0,
                    epilogue: str = _EPILOGUE_DEFAULT):
    """Past-close table prep + pallas call in one jit.

    ``table="hbm"``: the table is a single clipped XLA gather of raw
    closes — exact values, no arithmetic. ``table="inline"`` (default):
    the kernel rebuilds the same rows in VMEM scratch by lane-rotating the
    close row (`_mom_kernel_inline`) — bit-identical on every backend (no
    arithmetic either way), with no XLA gather and no table HBM stream.
    """
    epilogue = _interp_epilogue(epilogue, T_pad, interpret)
    close_p = _pad_last(close, T_pad)
    if table == "inline":
        kernel = functools.partial(_mom_kernel_inline, cost=cost, ppy=ppy,
                                   T_real=T_real, windows=windows,
                                   W_pad=W_pad, epilogue=epilogue)
        return _single_window_pallas(
            kernel, close_p, [], onehot_l, warm, t_real, T_pad=T_pad,
            W_pad=W_pad, P_real=P_real, T_real=T_real, interpret=interpret,
            name="momentum_kernel",
            aux_rows=[close_p],
            scratch_shapes=[pltpu.VMEM((W_pad, T_pad), jnp.float32)],
            lanes_cap=512, lanes_env=lanes_env)
    w_col = jnp.asarray(np.asarray(windows, np.int32))[:, None]  # (W,1)
    t_row = jnp.arange(T_pad)[None, :]
    gather_idx = jnp.clip(t_row - w_col, 0, T_pad - 1)           # (W,T_pad)
    past_tbl = _pad_w(jnp.take(close_p, gather_idx, axis=1), W_pad)
    kernel = functools.partial(_mom_kernel, cost=cost, ppy=ppy,
                               T_real=T_real, epilogue=epilogue)
    return _single_window_pallas(
        kernel, close_p, [past_tbl], onehot_l, warm, t_real, T_pad=T_pad,
        W_pad=W_pad, P_real=P_real, T_real=T_real, interpret=interpret,
        name="momentum_kernel",
        lanes_cap=512, lanes_env=lanes_env)


def _extrema_table(src_p, windows: tuple, mode: str, warm_fill: float):
    """All distinct-window rolling extrema of padded ``(N, T_pad)`` rows as
    one ``(N, W, T_pad)`` stack, via a SHARED sparse table.

    Per-window doubling ladders cost O(W · T log W) passes; instead build
    log2(max window) doubling levels once — ``level[k][t]`` covers
    ``x[t-2^k+1 .. t]`` — then every window is the max/min of TWO
    overlapping spans (the classic sparse-table range query). Max/min of
    raw prices either way: bit-identical to ``rolling.rolling_max``,
    ~6x fewer elementwise passes at the 125-distinct-window bench grid.
    Warmup bars (t < w-1) take ``warm_fill``.
    """
    op = jnp.maximum if mode == "max" else jnp.minimum
    neutral = float("-inf") if mode == "max" else float("inf")
    t_row = jnp.arange(src_p.shape[-1])[None, :]
    max_k = max((int(w)).bit_length() - 1 for w in windows)
    levels = [src_p]
    for k in range(max_k):
        levels.append(op(levels[k], _shift_t(levels[k], 1 << k, neutral)))
    rows = []
    for w in windows:
        w = int(w)
        k = w.bit_length() - 1                  # largest 2^k <= w
        row = op(levels[k], _shift_t(levels[k], w - (1 << k), neutral))
        rows.append(jnp.where(t_row >= w - 1, row, warm_fill))
    return jnp.stack(rows, axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("windows", "T_pad", "W_pad", "P_real", "T_real", "cost",
                     "ppy", "interpret", "table", "epilogue"))
def _fused_don_call(close, hi_src, lo_src, onehot_w, warm, t_real, *,
                    windows: tuple, T_pad: int, W_pad: int, P_real: int,
                    T_real: int | None, cost: float, ppy: int,
                    interpret: bool, table: str = "hbm",
                    epilogue: str = _EPILOGUE_DEFAULT):
    """Channel-extrema table prep + pallas call in one jit. Windows are
    static, so all distinct windows' rolling max/min come from one shared
    sparse table (:func:`_extrema_table`); max/min of exact prices is
    exact, so the channel — and hence every breakout comparison — matches
    the generic path bit-for-bit.

    ``hi_src``/``lo_src`` are the columns the channel extrema come from:
    the close itself for the close-only variant, the HIGH/LOW columns for
    the classic channel (``models.donchian._positions_hl``). ±1e30 stands
    in for the generic path's ±inf warmup fill; the channel values are
    consumed only by prep-side comparisons here (the kernel sees the
    finite sign table), and no finite price ever clears 1e30, so every
    breakout comparison is identical.

    ``table="inline"`` skips the XLA tables entirely: the kernel rebuilds
    the same sparse-table range query and breakout comparisons in VMEM
    scratch (`_don_kernel_inline`) — bit-identical on every backend
    (max/min and compares of raw prices are exact both ways). It measured
    a wash on-chip, so the shipped default stays ``"hbm"``
    (DESIGN.md "In-kernel table construction")."""
    epilogue = _interp_epilogue(epilogue, T_pad, interpret)
    close_p = _pad_last(close, T_pad)
    if table == "inline":
        kernel = functools.partial(_don_kernel_inline, cost=cost, ppy=ppy,
                                   T_real=T_real, windows=windows,
                                   W_pad=W_pad, epilogue=epilogue)
        return _single_window_pallas(
            kernel, close_p, [], onehot_w, warm, t_real,
            T_pad=T_pad, W_pad=W_pad, P_real=P_real, T_real=T_real,
            interpret=interpret,
            name="donchian_kernel",
            aux_rows=[close_p, _pad_last(hi_src, T_pad),
                      _pad_last(lo_src, T_pad)],
            scratch_shapes=[pltpu.VMEM((W_pad, T_pad), jnp.float32)],
            lanes_cap=256)
    hi_tbl = _extrema_table(_pad_last(hi_src, T_pad), windows, "max", 1e30)
    lo_tbl = _extrema_table(_pad_last(lo_src, T_pad), windows, "min", -1e30)
    # Channel known at the close of t-1, applied to bar t; collapsing both
    # channel tables into ONE breakout-sign table (+1 above the prior
    # high, -1 below the prior low, up wins — the latch's exact
    # precedence) halves the per-cell table traffic and selection matmuls.
    hi_prev = _shift_t(hi_tbl, 1, 1e30)
    lo_prev = _shift_t(lo_tbl, 1, -1e30)
    c3 = close_p[:, None, :]
    sig_tbl = _pad_w(jnp.where(c3 >= hi_prev, 1.0,
                               jnp.where(c3 <= lo_prev, -1.0, 0.0)), W_pad)
    kernel = functools.partial(_don_kernel, cost=cost, ppy=ppy,
                               T_real=T_real, epilogue=epilogue)
    return _single_window_pallas(
        kernel, close_p, [sig_tbl], onehot_w, warm, t_real,
        T_pad=T_pad, W_pad=W_pad, P_real=P_real, T_real=T_real,
        interpret=interpret, lanes_cap=256,
        name="donchian_kernel")


def _resolve_table(table: str | None, env_var: str, default: str,
                   tuned_key: str | None = None) -> str:
    """Shared table-substrate knob: explicit arg > per-family env > tuned
    schedule > default.

    ``"inline"`` builds the window table in VMEM scratch inside the kernel;
    ``"hbm"`` streams the XLA-built table (kept as the A/B twin). An
    invalid tuned value degrades to the default instead of raising."""
    if table is None:
        table = os.environ.get(env_var)
        if table is None:
            tuned = _tuned_value(tuned_key) if tuned_key else None
            table = tuned if tuned in ("inline", "hbm") else default
    if table not in ("inline", "hbm"):
        raise ValueError(f"table must be 'inline' or 'hbm', got {table!r}")
    return table


# (env var, shipped default) per table-substrate family; donchian stays
# "hbm" by measurement (the inline rebuild A/B'd a wash on-chip — DESIGN.md
# "In-kernel table construction").
_TABLE_FAMILIES = {
    "sma": ("DBX_SMA_TABLE", "inline"),
    "boll": ("DBX_BOLL_TABLE", "inline"),
    "mom": ("DBX_MOM_TABLE", "inline"),
    "don": ("DBX_DON_TABLE", "hbm"),
    "obv": ("DBX_OBV_TABLE", "inline"),
}


def _family_table(family: str, table: str | None) -> str:
    """Resolve a wrapper's table substrate from the single source of truth.

    Every sweep wrapper with a table knob MUST route through this (not a
    literal (env, default) pair) so ``substrate_defaults()`` /
    ``route_substrates()`` — and the observability surfaces built on them —
    can never report a different substrate than the kernel serves."""
    return _resolve_table(table, *_TABLE_FAMILIES[family],
                          tuned_key=f"table_{family}")

# Strategy name (rpc.compute registry key) -> table family, for the route
# substrate counters. Strategies without an in-kernel table substrate
# always stream the XLA-built table ("hbm", no knob).
_STRATEGY_TABLE_FAMILY = {
    "sma_crossover": "sma",
    "bollinger": "boll",
    "bollinger_touch": "boll",
    "momentum": "mom",
    "donchian": "don",
    "donchian_hl": "don",
    "obv_trend": "obv",
}


def substrate_defaults() -> dict:
    """The live (env-resolved) kernel substrate defaults, host-side.

    One stop for observability surfaces — the worker backend publishes
    this as the ``dbx_fused_substrate_info`` gauge labels so a fleet
    operator can read per-worker which epilogue / table / lane-block
    substrate is serving without grepping logs (GetStats ``obs_json``,
    ``/stats.json``, ``obs.dump``). Raises on invalid env values — the
    same validation the sweep call would hit, surfaced at backend start.
    """
    out = {"epilogue": _resolve_epilogue(None),
           "lanes_cap": str(resolve_lanes_cap())}
    for fam, (env_var, default) in _TABLE_FAMILIES.items():
        out[f"table_{fam}"] = _resolve_table(None, env_var, default,
                                             tuned_key=f"table_{fam}")
    return out


def route_substrates(strategy: str) -> dict:
    """``{"epilogue": ..., "table": ...}`` the named strategy's sweep would
    run under right now (env-resolved defaults) — the label set for the
    per-group ``dbx_fused_substrate_total`` route counter."""
    fam = _STRATEGY_TABLE_FAMILY.get(strategy)
    table = ("hbm" if fam is None else _family_table(fam, None))
    return {"epilogue": _resolve_epilogue(None), "table": table}


def fused_momentum_sweep(close, lookback, *, t_real=None, cost: float = 0.0,
                         periods_per_year: int = 252,
                         interpret: bool | None = None,
                         table: str | None = None,
                         epilogue: str | None = None,
                         carry_out: bool = False) -> Metrics:
    """Fused time-series momentum sweep: ``(N, T)`` closes x ``(P,)`` lanes.

    Matches ``run_sweep(..., "momentum")`` with an *exact* signal (the
    past-close selection involves no arithmetic); metrics carry the usual
    f32 reduction tolerance. ``table`` picks the past-close-table substrate
    (env ``DBX_MOM_TABLE``): both are exact, see :func:`_fused_mom_call`.
    """
    _check_carry_out_args(carry_out, t_real)
    if interpret is None:
        interpret = interpret_default()
    close = jnp.asarray(close, jnp.float32)
    lookback = np.asarray(lookback)
    T = close.shape[1]
    windows, onehot_l, warm = _single_window_grid_setup(
        lookback.astype(np.float32).tobytes(), 1.0, "lookbacks")
    m = _fused_mom_call(close, onehot_l, warm, _t_real_col(t_real, close),
                        windows=windows, T_pad=_round_up(T, 128),
                        W_pad=onehot_l.shape[0], P_real=lookback.shape[0],
                        T_real=T if t_real is None else None,
                        cost=float(cost), ppy=int(periods_per_year),
                        interpret=bool(interpret),
                        table=_family_table("mom", table),
                        lanes_env=resolve_lanes_cap(),
                        epilogue=_resolve_epilogue(epilogue))
    if not carry_out:
        return m
    return _carry_out_tail(m, "momentum", {"close": close},
                           {"lookback": lookback}, t_real=t_real,
                           cost=cost, ppy=periods_per_year,
                           epilogue=epilogue)


def fused_donchian_sweep(close, window, *, t_real=None, cost: float = 0.0,
                         periods_per_year: int = 252,
                         interpret: bool | None = None,
                         table: str | None = None,
                         epilogue: str | None = None,
                         carry_out: bool = False) -> Metrics:
    """Fused Donchian-breakout sweep: ``(N, T)`` closes x ``(P,)`` lanes.

    Matches ``run_sweep(..., "donchian")``: the channel extrema are exact
    (max/min of raw closes), so breakout comparisons and the latch path are
    bit-identical to the generic scan; metrics carry f32 tolerance.
    ``table`` picks the sign-table substrate (env ``DBX_DON_TABLE``): both
    are exact, see :func:`_fused_don_call`.
    """
    _check_carry_out_args(carry_out, t_real)
    if interpret is None:
        interpret = interpret_default()
    close = jnp.asarray(close, jnp.float32)
    window = np.asarray(window)
    T = close.shape[1]
    windows, onehot_w, warm = _single_window_grid_setup(
        window.astype(np.float32).tobytes(), 1.0, "windows")
    m = _fused_don_call(close, close, close, onehot_w, warm,
                        _t_real_col(t_real, close),
                        windows=windows, T_pad=_round_up(T, 128),
                        W_pad=onehot_w.shape[0], P_real=window.shape[0],
                        T_real=T if t_real is None else None,
                        cost=float(cost), ppy=int(periods_per_year),
                        interpret=bool(interpret),
                        table=_family_table("don", table),
                        epilogue=_resolve_epilogue(epilogue))
    if not carry_out:
        return m
    return _carry_out_tail(m, "donchian", {"close": close},
                           {"window": window}, t_real=t_real, cost=cost,
                           ppy=periods_per_year, epilogue=epilogue)


def fused_donchian_hl_sweep(close, high, low, window, *, t_real=None,
                            cost: float = 0.0, periods_per_year: int = 252,
                            interpret: bool | None = None,
                            table: str | None = None,
                            epilogue: str | None = None,
                            carry_out: bool = False) -> Metrics:
    """Fused high/low-channel Donchian sweep: ``(N, T)`` panels x ``(P,)``.

    Matches ``run_sweep(..., "donchian_hl")`` — breakout when the close
    clears the trailing extreme of the *highs*/*lows* (the classic channel;
    the first fused kernel consuming the high/low columns). Channel extrema
    are exact, so breakouts and the latch path are bit-identical to the
    generic scan; metrics carry f32 tolerance.
    """
    _check_carry_out_args(carry_out, t_real)
    if interpret is None:
        interpret = interpret_default()
    close = jnp.asarray(close, jnp.float32)
    high = jnp.asarray(high, jnp.float32)
    low = jnp.asarray(low, jnp.float32)
    window = np.asarray(window)
    T = close.shape[1]
    windows, onehot_w, warm = _single_window_grid_setup(
        window.astype(np.float32).tobytes(), 1.0, "windows")
    m = _fused_don_call(close, high, low, onehot_w, warm,
                        _t_real_col(t_real, close),
                        windows=windows, T_pad=_round_up(T, 128),
                        W_pad=onehot_w.shape[0], P_real=window.shape[0],
                        T_real=T if t_real is None else None,
                        cost=float(cost), ppy=int(periods_per_year),
                        interpret=bool(interpret),
                        table=_family_table("don", table),
                        epilogue=_resolve_epilogue(epilogue))
    if not carry_out:
        return m
    return _carry_out_tail(
        m, "donchian_hl", {"close": close, "high": high, "low": low},
        {"window": window}, t_real=t_real, cost=cost,
        ppy=periods_per_year, epilogue=epilogue)


@functools.partial(
    jax.jit,
    static_argnames=("windows", "T_pad", "W_pad", "P_real", "T_real", "cost",
                     "ppy", "interpret", "epilogue", "t_block"))
def _fused_stoch_call(close, high, low, onehot_w, band_lanes, warm, t_real,
                      *, windows: tuple, T_pad: int, W_pad: int, P_real: int,
                      T_real: int | None, cost: float, ppy: int,
                      interpret: bool, epilogue: str = _EPILOGUE_DEFAULT,
                      t_block: int | None = None):
    """%K table prep + the *Bollinger* kernel: the centered stochastic
    oscillator is just another z-score feeding the shared band machine
    (enter beyond ±band, exit at the 50 centerline: z_exit = 0).

    Channel extrema come from the shared sparse table
    (:func:`_extrema_table`) over the HIGH/LOW columns — exact max/min, so
    %K sees bit-identical channel values to the generic
    ``models.stochastic`` path; the %K arithmetic replicates
    ``stochastic_k``'s float op order (flat channels fall back to the
    neutral 50)."""
    epilogue = _interp_epilogue(epilogue, T_pad, interpret)
    close_p = _pad_last(close, T_pad)
    hi_tbl = _extrema_table(_pad_last(high, T_pad), windows, "max", 1e30)
    lo_tbl = _extrema_table(_pad_last(low, T_pad), windows, "min", -1e30)
    rng = hi_tbl - lo_tbl
    k_tbl = jnp.where(
        rng > _EPS,
        100.0 * (close_p[:, None, :] - lo_tbl) / (rng + _EPS),
        50.0) - 50.0
    w_col = jnp.asarray(np.asarray(windows, np.int32))[:, None]  # (W,1)
    t_row = jnp.arange(T_pad)[None, :]
    z_table = _pad_w(jnp.where((t_row >= w_col - 1)[None], k_tbl, 0.0),
                     W_pad)
    kernel = functools.partial(_boll_kernel, cost=cost, ppy=ppy,
                               z_exit=0.0, T_real=T_real, epilogue=epilogue)
    return _band_machine_pallas(
        kernel, close_p, z_table, onehot_w, band_lanes, warm, t_real,
        T_pad=T_pad, W_pad=W_pad, P_real=P_real, T_real=T_real,
        interpret=interpret,
        name="stochastic_kernel", t_block=t_block)


def fused_stochastic_sweep(close, high, low, window, band, *, t_real=None,
                           cost: float = 0.0, periods_per_year: int = 252,
                           interpret: bool | None = None,
                           epilogue: str | None = None,
                           carry_out: bool = False,
                           t_block: int | None = None) -> Metrics:
    """Fused stochastic-%K reversion sweep: ``(N, T)`` panels x ``(P,)``.

    ``window``/``band`` are flat per-combo arrays (:func:`product_grid`
    order); windows must be integral bar counts. Matches
    ``run_sweep(..., "stochastic")`` (``models.stochastic``): bit-level on
    CPU interpret mode; the usual MXU knife-edge caveat on TPU. The second
    fused kernel consuming the high/low columns (after the HL-Donchian).
    """
    _check_carry_out_args(carry_out, t_real)
    if interpret is None:
        interpret = interpret_default()
    close = jnp.asarray(close, jnp.float32)
    high = jnp.asarray(high, jnp.float32)
    low = jnp.asarray(low, jnp.float32)
    window = np.asarray(window)
    band = np.asarray(band, np.float32)
    T = close.shape[1]

    # _boll_grid_setup's shapes fit exactly: warm = window, band lanes in
    # the k slot (padded lanes get band = +inf and never enter).
    windows, onehot_w, band_lanes, warm = _boll_grid_setup(
        window.astype(np.float32).tobytes(), band.tobytes())
    m = _fused_stoch_call(close, high, low, onehot_w, band_lanes, warm,
                          _t_real_col(t_real, close),
                          windows=windows, T_pad=_band_t_pad(T, t_block),
                          W_pad=onehot_w.shape[0],
                          P_real=window.shape[0],
                          T_real=T if t_real is None else None,
                          cost=float(cost), ppy=int(periods_per_year),
                          interpret=bool(interpret),
                          epilogue=_resolve_epilogue(epilogue),
                          t_block=t_block)
    if not carry_out:
        return m
    return _carry_out_tail(
        m, "stochastic", {"close": close, "high": high, "low": low},
        {"window": window, "band": band}, t_real=t_real, cost=cost,
        ppy=periods_per_year, epilogue=epilogue)


@functools.partial(
    jax.jit,
    static_argnames=("windows", "T_pad", "W_pad", "P_real", "T_real", "cost",
                     "ppy", "interpret", "epilogue", "t_block"))
def _fused_keltner_call(close, high, low, onehot_w, k_lanes, warm, t_real,
                        *, windows: tuple, T_pad: int, W_pad: int,
                        P_real: int, T_real: int | None, cost: float,
                        ppy: int, interpret: bool,
                        epilogue: str = _EPILOGUE_DEFAULT,
                        t_block: int | None = None):
    """Keltner z-table prep + the *Bollinger* kernel: the ATR-normalized
    deviation from the EMA midline feeds the shared band machine (enter
    beyond ±k ATRs, exit at the midline re-cross: z_exit = 0).

    Per distinct window: the EMA midline runs as the shift-ladder
    (``_ema_rows`` — float-order differs from the generic
    ``associative_scan`` EMA, the RSI/MACD caveat) and the ATR is a
    cumsum-difference windowed mean of the true range. Warmup rows — where
    the generic path's NaN-filled rolling mean makes ``atr > eps`` False
    and the deviation falls back to exactly 0 — are forced to 0, as is the
    zero-ATR (constant-price) fallback."""
    epilogue = _interp_epilogue(epilogue, T_pad, interpret)
    close_p = _pad_last(close, T_pad)
    high_p = _pad_last(high, T_pad)
    low_p = _pad_last(low, T_pad)
    w_col, w_f, t_row, windowed_sum, _ = _cumsum_window_tools(windows, T_pad)

    prev_close = jnp.concatenate([close_p[:, :1], close_p[:, :-1]], axis=-1)
    tr = jnp.maximum(high_p - low_p,
                     jnp.maximum(jnp.abs(high_p - prev_close),
                                 jnp.abs(low_p - prev_close)))
    atr = windowed_sum(tr) / w_f                                 # (N,W,T_pad)
    mids = jnp.stack(
        [_ema_rows(close_p, 2.0 / (float(w) + 1.0)) for w in windows],
        axis=1)
    dev = close_p[:, None, :] - mids
    have = (t_row >= (w_col - 1))[None] & (atr > _EPS)
    z_table = _pad_w(jnp.where(have, dev / (atr + _EPS), 0.0), W_pad)

    kernel = functools.partial(_boll_kernel, cost=cost, ppy=ppy,
                               z_exit=0.0, T_real=T_real, epilogue=epilogue)
    return _band_machine_pallas(
        kernel, close_p, z_table, onehot_w, k_lanes, warm, t_real,
        T_pad=T_pad, W_pad=W_pad, P_real=P_real, T_real=T_real,
        interpret=interpret,
        name="keltner_kernel", t_block=t_block)


def fused_keltner_sweep(close, high, low, window, k, *, t_real=None,
                        cost: float = 0.0, periods_per_year: int = 252,
                        interpret: bool | None = None,
                        epilogue: str | None = None,
                        carry_out: bool = False,
                        t_block: int | None = None) -> Metrics:
    """Fused Keltner-channel reversion sweep: ``(N, T)`` panels x ``(P,)``.

    ``window``/``k`` are flat per-combo arrays (:func:`product_grid`
    order); windows must be integral bar counts. Matches
    ``run_sweep(..., "keltner")`` (``models.keltner``) to f32 tolerance
    (the in-prep EMA ladder rounds differently from the generic
    ``associative_scan`` — the RSI/MACD caveat — so knife-edge midline
    crossings can resolve differently; quantified by ``bench.py
    --verify``).
    """
    _check_carry_out_args(carry_out, t_real)
    if interpret is None:
        interpret = interpret_default()
    close = jnp.asarray(close, jnp.float32)
    high = jnp.asarray(high, jnp.float32)
    low = jnp.asarray(low, jnp.float32)
    window = np.asarray(window)
    k = np.asarray(k, np.float32)
    T = close.shape[1]

    windows, onehot_w, k_lanes, warm = _boll_grid_setup(
        window.astype(np.float32).tobytes(), k.tobytes())
    m = _fused_keltner_call(close, high, low, onehot_w, k_lanes, warm,
                            _t_real_col(t_real, close),
                            windows=windows, T_pad=_band_t_pad(T, t_block),
                            W_pad=onehot_w.shape[0],
                            P_real=window.shape[0],
                            T_real=T if t_real is None else None,
                            cost=float(cost), ppy=int(periods_per_year),
                            interpret=bool(interpret),
                            epilogue=_resolve_epilogue(epilogue),
                            t_block=t_block)
    if not carry_out:
        return m
    return _carry_out_tail(
        m, "keltner", {"close": close, "high": high, "low": low},
        {"window": window, "k": k}, t_real=t_real, cost=cost,
        ppy=periods_per_year, epilogue=epilogue)


@functools.lru_cache(maxsize=8)
def _single_window_grid_setup(vals_bytes: bytes, warm_offset: float,
                              what: str):
    """Distinct windows + one-hot/warmup lanes for single-window-axis
    strategies (momentum, donchian). ``warm = value + warm_offset``."""
    vals = np.frombuffer(vals_bytes, np.float32)
    P = vals.shape[0]
    windows = _distinct_windows(vals, what)
    W_pad = _round_up(max(windows.shape[0], 1), 8)
    P_pad = _round_up(max(P, 1), _LANES)
    oh = _window_onehot(windows, vals, W_pad, P_pad)
    warm = np.ones((1, P_pad), np.float32)
    warm[0, :P] = vals + warm_offset
    return (tuple(int(w) for w in windows), _const(oh),
            _const(warm))


@functools.partial(
    jax.jit,
    static_argnames=("windows", "T_pad", "W_pad", "P_real", "T_real", "cost",
                     "ppy", "interpret", "epilogue", "t_block"))
def _fused_rsi_call(close, onehot_p, band_lanes, warm, t_real, *,
                    windows: tuple, T_pad: int, W_pad: int, P_real: int,
                    T_real: int | None, cost: float, ppy: int,
                    interpret: bool, epilogue: str = _EPILOGUE_DEFAULT,
                    t_block: int | None = None):
    """RSI table prep + the *Bollinger* kernel: ``rsi - 50`` is just another
    z-score feeding the shared band machine (enter beyond ±band, exit at the
    centerline), so the whole kernel is reused verbatim with z_exit=0.

    Each distinct period's Wilder EMA (static alpha = 1/period) runs as the
    shift-ladder (``_ema_rows``) over ``(N, T_pad)`` —
    ``models.rsi.rsi_index``'s formula per window, float-order modulo the
    scan algorithm.
    """
    epilogue = _interp_epilogue(epilogue, T_pad, interpret)
    close_p = _pad_last(close, T_pad)
    diff = jnp.diff(close_p, axis=-1, prepend=close_p[..., :1])
    gains = jnp.maximum(diff, 0.0)
    losses = jnp.maximum(-diff, 0.0)
    # Per-distinct-period EMAs via the shift-ladder (see _ema_rows: the
    # associative_scan version compiled ~30x slower with no runtime win; a
    # batched (W, N, T_pad) scan was also slower on chip).
    rows = []
    for p_ in windows:
        alpha = 1.0 / float(p_)
        ag = _ema_rows(gains, alpha)
        al = _ema_rows(losses, alpha)
        rsi = 100.0 - 100.0 / (1.0 + ag / (al + 1e-12))
        rows.append(rsi - 50.0)
    z_tbl = _pad_w(jnp.stack(rows, axis=1), W_pad)               # (N,W,T_pad)

    kernel = functools.partial(_boll_kernel, cost=cost, ppy=ppy,
                               z_exit=0.0, T_real=T_real, epilogue=epilogue)
    return _band_machine_pallas(
        kernel, close_p, z_tbl, onehot_p, band_lanes, warm, t_real,
        T_pad=T_pad, W_pad=W_pad, P_real=P_real, T_real=T_real,
        interpret=interpret,
        name="rsi_kernel", t_block=t_block)


def fused_rsi_sweep(close, period, band, *, t_real=None, cost: float = 0.0,
                    periods_per_year: int = 252,
                    interpret: bool | None = None,
                    epilogue: str | None = None,
                    carry_out: bool = False,
                    t_block: int | None = None) -> Metrics:
    """Fused RSI mean-reversion sweep: ``(N, T)`` closes x ``(P,)`` lanes.

    ``period``/``band`` are flat per-combo arrays (:func:`product_grid`
    order); periods must be integral bar counts. Matches
    ``run_sweep(..., "rsi")`` (``models.rsi``) to f32 tolerance.
    """
    _check_carry_out_args(carry_out, t_real)
    if interpret is None:
        interpret = interpret_default()
    close = jnp.asarray(close, jnp.float32)
    period = np.asarray(period)
    band = np.asarray(band, np.float32)
    T = close.shape[1]
    windows, onehot_p, band_lanes, warm = _rsi_grid_setup(
        period.astype(np.float32).tobytes(), band.tobytes())
    m = _fused_rsi_call(close, onehot_p, band_lanes, warm,
                        _t_real_col(t_real, close),
                        windows=windows, T_pad=_band_t_pad(T, t_block),
                        W_pad=onehot_p.shape[0], P_real=period.shape[0],
                        T_real=T if t_real is None else None,
                        cost=float(cost), ppy=int(periods_per_year),
                        interpret=bool(interpret),
                        epilogue=_resolve_epilogue(epilogue),
                        t_block=t_block)
    if not carry_out:
        return m
    return _carry_out_tail(m, "rsi", {"close": close},
                           {"period": period, "band": band}, t_real=t_real,
                           cost=cost, ppy=periods_per_year,
                           epilogue=epilogue)


@functools.lru_cache(maxsize=4)
def _rsi_grid_setup(period_bytes: bytes, band_bytes: bytes):
    """Distinct periods + one-hot/band/warmup lanes (warm = period + 1)."""
    period = np.frombuffer(period_bytes, np.float32)
    band = np.frombuffer(band_bytes, np.float32)
    P = period.shape[0]
    windows = _distinct_windows(period, "periods")
    W_pad = _round_up(max(windows.shape[0], 1), 8)
    P_pad = _round_up(max(P, 1), _LANES)
    oh = _window_onehot(windows, period, W_pad, P_pad)
    band_lanes = np.full((1, P_pad), np.float32(np.inf))
    band_lanes[0, :P] = band      # padded lanes never enter (band = +inf)
    warm = np.ones((1, P_pad), np.float32)
    warm[0, :P] = period + 1.0    # models.rsi: valid_mask(T, period + 1)
    return (tuple(int(w) for w in windows), _const(oh),
            _const(band_lanes), _const(warm))


def _ema_ladder(x, a):
    """Per-lane EMA over the sublane axis: ``y[t] = (1-a)*y[t-1] + a*x[t]``
    with ``y[0] = x[0]`` and a per-lane decay ``a`` ((1, 128) or scalar).

    The first-order recurrence is associative under
    ``(A2,B2) ∘ (A1,B1) = (A1*A2, A2*B1 + B2)``, so it evaluates as a
    log-depth doubling ladder — the in-kernel analogue of
    ``ops.rolling.ema``'s associative_scan, needed here because the decay
    varies per *lane* (each param lane has its own span).
    """
    T_pad = x.shape[0]
    t0 = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) == 0
    A = jnp.where(t0, 0.0, jnp.broadcast_to(1.0 - a, x.shape))
    B = jnp.where(t0, x, a * x)
    span = 1
    while span < T_pad:
        Ae = _shift_down(A, span, 1.0)   # identity element (A=1, B=0)
        Be = _shift_down(B, span, 0.0)
        A, B = Ae * A, A * Be + B
        span *= 2
    return B


def _macd_kernel(r_ref, ema_ref, od_ref, asig_ref, warm_ref, *refs,
                 cost: float, ppy: int, T_real: int | None, epilogue: str):
    """MACD cell: one span-table selection gives the macd line; the signal
    line is a per-lane EMA (decay = 2/(signal_span+1)) evaluated with the
    in-kernel associative ladder; position = sign(macd - signal)."""
    tr, out_ref = _unpack_tr(refs, T_real)
    T_pad = r_ref.shape[1]
    r = r_ref[0]
    dn = (((0,), (0,)), ((), ()))
    # Difference one-hot (+1 fast row, -1 slow row), built HOST-side like
    # the SMA selector: one matmul yields the macd line directly — half
    # the MXU work and selector stream of separate f/s selections.
    macd = jax.lax.dot_general(ema_ref[0], od_ref[:], dn,
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)
    a_sig = asig_ref[0, :][None, :]                  # (1, lanes)
    sig = _ema_ladder(macd, a_sig)

    lanes = od_ref.shape[1]          # widest legal param block (launcher)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (T_pad, lanes), 0)
    warm = warm_ref[0, :][None, :]                   # slow + signal - 1
    valid = t_idx >= (warm.astype(jnp.int32) - 1)
    pos = jnp.where(valid, jnp.sign(macd - sig), 0.0)
    out_ref[0, 0] = _metrics_tail(pos, r, t_idx, tr, cost=cost, ppy=ppy,
                                  epilogue=epilogue)


@functools.partial(
    jax.jit,
    static_argnames=("spans", "T_pad", "W_pad", "P_real", "T_real", "cost",
                     "ppy", "interpret", "epilogue"))
def _fused_macd_call(close, onehot_d, a_sig, warm, t_real, *,
                     spans: tuple, T_pad: int, W_pad: int, P_real: int,
                     T_real: int | None, cost: float, ppy: int,
                     interpret: bool, epilogue: str = _EPILOGUE_DEFAULT):
    """Distinct-span EMA table prep + pallas call in one jit.

    The EMA table is built from the *demeaned* close — ``macd`` is
    shift-invariant (``models.macd``, which demeans identically), and the
    demeaned series keeps the f32 error proportional to price deviations
    rather than price level. Returns still come from the raw series.
    """
    epilogue = _interp_epilogue(epilogue, T_pad, interpret)
    close_p = _pad_last(close, T_pad)
    N = close.shape[0]
    close_dm = close_p - close_p[..., :1]
    rows = [_ema_rows(close_dm, 2.0 / (float(s) + 1.0)) for s in spans]
    ema_tbl = jnp.stack(rows, axis=1)                            # (N,W,T_pad)
    if W_pad > len(spans):
        ema_tbl = jnp.concatenate(
            [ema_tbl, jnp.zeros((N, W_pad - len(spans), T_pad),
                                jnp.float32)], axis=1)

    P_pad = a_sig.shape[1]
    # 256-lane cap: the per-lane signal-EMA ladder keeps several
    # (T_pad, lanes) arrays live (same budget class as the band machines).
    lanes = _widest_lanes(P_pad, 256)
    n_blocks = P_pad // lanes
    kernel = functools.partial(_macd_kernel, cost=cost, ppy=ppy,
                               T_real=T_real, epilogue=epilogue)
    out = pl.pallas_call(
        kernel,
        grid=(N, n_blocks),
        in_specs=[
            pl.BlockSpec((1, T_pad, 1), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, W_pad, T_pad), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((W_pad, lanes), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, lanes), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, lanes), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ] + _tr_specs(T_real),
        out_specs=pl.BlockSpec(
            (1, 1, _METRIC_ROWS, lanes), lambda i, j: (i, j, 0, 0),
            memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (N, n_blocks, _METRIC_ROWS, lanes), jnp.float32),
        interpret=interpret,
        name="macd_kernel",
        compiler_params=_COMPILER_PARAMS,
    )(_rets3(close_p), ema_tbl, onehot_d, a_sig, warm,
      *_tr_args(t_real, T_real))
    return Metrics(*(
        jnp.reshape(out[:, :, k, :], (N, P_pad))[:, :P_real]
        for k in range(9)))


def fused_macd_sweep(close, fast, slow, signal, *, t_real=None,
                     cost: float = 0.0, periods_per_year: int = 252,
                     interpret: bool | None = None,
                     epilogue: str | None = None,
                     carry_out: bool = False) -> Metrics:
    """Fused MACD signal-line crossover sweep: ``(N, T)`` x ``(P,)`` lanes.

    ``fast``/``slow``/``signal`` are flat per-combo span arrays
    (:func:`product_grid` order); spans must be integral. Matches
    ``run_sweep(..., "macd")`` (``models.macd``) to f32 tolerance — both
    paths demean the close and evaluate every EMA with the same
    shift-doubling ladder (``rolling.ema_ladder`` generically, ``_ema_rows``
    / ``_ema_ladder`` here), so they are rounding twins; the only residual
    divergence class is the MXU selection matmul for the macd line.
    """
    _check_carry_out_args(carry_out, t_real)
    if interpret is None:
        interpret = interpret_default()
    close = jnp.asarray(close, jnp.float32)
    fast = np.asarray(fast)
    slow = np.asarray(slow)
    signal = np.asarray(signal)
    T = close.shape[1]
    spans, onehot_d, a_sig, warm = _macd_grid_setup(
        fast.astype(np.float32).tobytes(),
        slow.astype(np.float32).tobytes(),
        signal.astype(np.float32).tobytes())
    m = _fused_macd_call(close, onehot_d, a_sig, warm,
                         _t_real_col(t_real, close),
                         spans=spans, T_pad=_round_up(T, 128),
                         W_pad=onehot_d.shape[0], P_real=fast.shape[0],
                         T_real=T if t_real is None else None,
                         cost=float(cost), ppy=int(periods_per_year),
                         interpret=bool(interpret),
                         epilogue=_resolve_epilogue(epilogue))
    if not carry_out:
        return m
    return _carry_out_tail(
        m, "macd", {"close": close},
        {"fast": fast, "slow": slow, "signal": signal}, t_real=t_real,
        cost=cost, ppy=periods_per_year, epilogue=epilogue)


@functools.lru_cache(maxsize=4)
def _macd_grid_setup(fast_bytes: bytes, slow_bytes: bytes,
                     signal_bytes: bytes):
    """Distinct spans (fast ∪ slow) + selectors, per-lane signal decay and
    warmup (= slow + signal - 1, ``models.macd``'s rule)."""
    fast = np.frombuffer(fast_bytes, np.float32)
    slow = np.frombuffer(slow_bytes, np.float32)
    signal = np.frombuffer(signal_bytes, np.float32)
    P = fast.shape[0]
    spans = _distinct_windows(np.concatenate([fast, slow]), "spans")
    _distinct_windows(signal, "signal spans")   # validate integrality only
    W_pad = _round_up(max(spans.shape[0], 1), 8)
    P_pad = _round_up(max(P, 1), _LANES)
    # ONE difference selector (+1 fast row, -1 slow row), the SMA
    # `_grid_setup` discipline: exact 0/±1 integers, half the stream.
    oh_d = (_window_onehot(spans, fast, W_pad, P_pad)
            - _window_onehot(spans, slow, W_pad, P_pad))
    a_sig = np.zeros((1, P_pad), np.float32)
    a_sig[0, :P] = 2.0 / (signal + 1.0)
    warm = np.ones((1, P_pad), np.float32)
    warm[0, :P] = slow + signal - 1.0
    return (tuple(int(s) for s in spans), _const(oh_d),
            _const(a_sig), _const(warm))


def _obv_signal_tail(sma_tbl, r, obv, oh_ref, warm_ref, tr, out_ref, *,
                     cost: float, ppy: int, epilogue: str):
    """Shared OBV selection + metrics tail (both table substrates).

    One window-table selection gives the OBV rolling mean; position =
    ``sign(obv - sma)``. The W-major ``(W_pad, T_pad)`` table contracts
    its leading window axis (the SMA kernel's layout — a T-major/W-minor
    table pads W up to 128 lanes, a 12.8x HBM blow-up class this file
    keeps re-learning). The selection one-hot has a single nonzero per
    lane, so the MXU contraction is an exact copy — the only rounding in
    the cell is the subtraction itself."""
    T_pad = sma_tbl.shape[1]
    dn = (((0,), (0,)), ((), ()))
    sma = jax.lax.dot_general(sma_tbl, oh_ref[:], dn,
                              preferred_element_type=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST)
    lanes = oh_ref.shape[1]          # widest legal param block (launcher)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (T_pad, lanes), 0)
    warm = warm_ref[0, :][None, :]               # (1, lanes) = window
    valid = t_idx >= (warm.astype(jnp.int32) - 1)
    pos = jnp.where(valid, jnp.sign(obv - sma), 0.0)
    out_ref[0, 0] = _metrics_tail(pos, r, t_idx, tr, cost=cost, ppy=ppy,
                                  epilogue=epilogue)


def _obv_kernel(r_ref, obv_ref, sma_ref, oh_ref, warm_ref, *refs,
                cost: float, ppy: int, T_real: int | None, epilogue: str):
    tr, out_ref = _unpack_tr(refs, T_real)
    _obv_signal_tail(sma_ref[0], r_ref[0], obv_ref[0], oh_ref, warm_ref,
                     tr, out_ref, cost=cost, ppy=ppy, epilogue=epilogue)


def _obv_kernel_inline(r_ref, obv_ref, cs_ref, oh_ref, warm_ref, *refs,
                       cost: float, ppy: int, T_real: int | None,
                       windows: tuple, W_pad: int, epilogue: str):
    """OBV with the SMA-of-OBV table built in VMEM scratch from the OBV
    prefix pair (`_build_sma_scratch` — the SMA kernel's builder on a
    different series). Same division-lowering caveat as the SMA inline
    substrate (`_kernel_inline`): bit-identical on CPU, 1-ULP table
    rounding possible on TPU, gated by the same verify budgets."""
    *head, sma_scr = refs
    tr, out_ref = _unpack_tr(tuple(head), T_real)

    @pl.when(pl.program_id(1) == 0)
    def _build():
        _build_sma_scratch(cs_ref[0], sma_scr, windows, W_pad)

    _obv_signal_tail(sma_scr[:], r_ref[0], obv_ref[0], oh_ref, warm_ref,
                     tr, out_ref, cost=cost, ppy=ppy, epilogue=epilogue)


@functools.partial(
    jax.jit,
    static_argnames=("windows", "T_pad", "W_pad", "P_real", "T_real", "cost",
                     "ppy", "interpret", "table", "lanes_env", "epilogue"))
def _fused_obv_call(close, volume, onehot_w, warm, t_real, *,
                    windows: tuple, T_pad: int, W_pad: int, P_real: int,
                    T_real: int | None, cost: float, ppy: int,
                    interpret: bool, table: str = "hbm",
                    lanes_env: int = 0, epilogue: str = _EPILOGUE_DEFAULT):
    """OBV series + distinct-window SMA table prep + pallas call in one jit.

    The OBV accumulator is the SHARED ``rolling.obv_series`` (the same
    function ``models.obv`` evaluates), and the windowed mean follows the
    generic ``rolling.rolling_mean``'s cumsum-difference op order, so the
    paths are rounding twins by construction (see the SMA table comment in
    ``_fused_call`` for the gather layout rationale).
    """
    N, T = close.shape
    epilogue = _interp_epilogue(epilogue, T_pad, interpret)
    close_p = _pad_last(close, T_pad)
    vol_p = _pad_last(volume, T_pad)
    obv = rolling.obv_series(close_p, vol_p)                   # (N, T_pad)

    P_pad = onehot_w.shape[1]
    # sign kernel: no compose ladder
    lanes = _widest_lanes(P_pad, 512, T_pad, lanes_env)
    n_blocks = P_pad // lanes
    if table == "inline":
        kernel = functools.partial(_obv_kernel_inline, cost=cost, ppy=ppy,
                                   T_real=T_real, windows=windows,
                                   W_pad=W_pad, epilogue=epilogue)
        table_arg = _prefix_rows(obv)                          # (N,2,T_pad)
        table_spec = pl.BlockSpec((1, 2, T_pad), lambda i, j: (i, 0, 0),
                                  memory_space=pltpu.VMEM)
        scratch = [pltpu.VMEM((W_pad, T_pad), jnp.float32)]
    else:
        # W-major SMA table of the OBV series — `_sma_table` on a
        # different input row (same cumsum-difference op order as the
        # generic rolling mean). The previous T-major (N, T_pad, W)
        # layout padded W up to 128 lanes per intermediate; its static-
        # shift prep materialized W lane-minor (N, T_pad, 1) rows — a
        # 12.8x-class HBM blow-up that OOM'd at 500 tickers.
        kernel = functools.partial(_obv_kernel, cost=cost, ppy=ppy,
                                   T_real=T_real, epilogue=epilogue)
        table_arg = _sma_table(obv, windows, W_pad)
        table_spec = pl.BlockSpec((1, W_pad, T_pad), lambda i, j: (i, 0, 0),
                                  memory_space=pltpu.VMEM)
        scratch = []
    out = pl.pallas_call(
        kernel,
        grid=(N, n_blocks),
        in_specs=[
            pl.BlockSpec((1, T_pad, 1), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, T_pad, 1), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            table_spec,
            pl.BlockSpec((W_pad, lanes), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, lanes), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ] + _tr_specs(T_real),
        out_specs=pl.BlockSpec(
            (1, 1, _METRIC_ROWS, lanes), lambda i, j: (i, j, 0, 0),
            memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (N, n_blocks, _METRIC_ROWS, lanes), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
        name="obv_kernel",
        compiler_params=_COMPILER_PARAMS,
    )(_rets3(close_p), obv[:, :, None], table_arg, onehot_w, warm,
      *_tr_args(t_real, T_real))
    return Metrics(*(
        jnp.reshape(out[:, :, k, :], (N, P_pad))[:, :P_real]
        for k in range(9)))


def fused_obv_sweep(close, volume, window, *, t_real=None, cost: float = 0.0,
                    periods_per_year: int = 252,
                    interpret: bool | None = None,
                    table: str | None = None,
                    epilogue: str | None = None,
                    carry_out: bool = False) -> Metrics:
    """Fused OBV-trend sweep: ``(N, T)`` closes+volumes x ``(P,)`` windows.

    ``window`` is a flat per-combo window array (:func:`product_grid`
    order); windows must be integral bar counts. Matches
    ``run_sweep(..., "obv_trend")`` (``models.obv``) to f32 tolerance —
    the OBV accumulation, first-bar volume normalization, and windowed
    mean follow the generic path's exact op order, and the selection
    contraction is an exact one-hot copy. ``table`` picks the SMA-of-OBV
    table substrate (env ``DBX_OBV_TABLE``; the inline variant carries
    the SMA kernel's division-lowering caveat, `_obv_kernel_inline`).
    """
    _check_carry_out_args(carry_out, t_real)
    if interpret is None:
        interpret = interpret_default()
    close = jnp.asarray(close, jnp.float32)
    volume = jnp.asarray(volume, jnp.float32)
    window = np.asarray(window)
    T = close.shape[1]
    windows, onehot_w, warm = _obv_grid_setup(
        window.astype(np.float32).tobytes())
    m = _fused_obv_call(close, volume, onehot_w, warm,
                        _t_real_col(t_real, close),
                        windows=windows, T_pad=_round_up(T, 128),
                        W_pad=onehot_w.shape[0], P_real=window.shape[0],
                        T_real=T if t_real is None else None,
                        cost=float(cost), ppy=int(periods_per_year),
                        interpret=bool(interpret),
                        table=_family_table("obv", table),
                        lanes_env=resolve_lanes_cap(),
                        epilogue=_resolve_epilogue(epilogue))
    if not carry_out:
        return m
    return _carry_out_tail(m, "obv_trend",
                           {"close": close, "volume": volume},
                           {"window": window}, t_real=t_real, cost=cost,
                           ppy=periods_per_year, epilogue=epilogue)


@functools.lru_cache(maxsize=4)
def _obv_grid_setup(window_bytes: bytes):
    """Distinct windows + selector and warmup (= window) lanes."""
    window = np.frombuffer(window_bytes, np.float32)
    P = window.shape[0]
    windows = _distinct_windows(window, "windows")
    W_pad = _round_up(max(windows.shape[0], 1), 8)
    P_pad = _round_up(max(P, 1), _LANES)
    oh = _window_onehot(windows, window, W_pad, P_pad)
    warm = np.ones((1, P_pad), np.float32)
    warm[0, :P] = window
    return (tuple(int(w) for w in windows), _const(oh), _const(warm))


def _trix_kernel(r_ref, ema_ref, oh_ref, asig_ref, warm_ref, *refs,
                 cost: float, ppy: int, T_real: int | None, epilogue: str):
    """TRIX cell: one span-table selection gives the triple-smoothed close;
    the one-bar rate of change is computed in-kernel (a ratio, so the price
    level cancels); the signal line is a per-lane EMA ladder; position =
    sign(trix - signal)."""
    tr, out_ref = _unpack_tr(refs, T_real)
    T_pad = r_ref.shape[1]
    r = r_ref[0]
    dn = (((0,), (0,)), ((), ()))
    e3 = jax.lax.dot_general(ema_ref[0], oh_ref[:], dn,
                             preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)
    prev = _shift_down(e3, 1, 1.0)
    # Padded lanes select all-zero table rows (0/0): guard the denominator
    # so they stay finite; real lanes have positive price-level EMAs.
    denom = jnp.where(prev == 0.0, 1.0, prev)
    lanes = oh_ref.shape[1]          # widest legal param block (launcher)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (T_pad, lanes), 0)
    # trix[0] = 0 exactly, matching models.trix (prev seeds with e3[0]).
    trix = jnp.where(t_idx == 0, 0.0, e3 / denom - 1.0)
    a_sig = asig_ref[0, :][None, :]                  # (1, lanes)
    sig = _ema_ladder(trix, a_sig)

    warm = warm_ref[0, :][None, :]                   # 3*span + signal - 2
    valid = t_idx >= (warm.astype(jnp.int32) - 1)
    pos = jnp.where(valid, jnp.sign(trix - sig), 0.0)
    out_ref[0, 0] = _metrics_tail(pos, r, t_idx, tr, cost=cost, ppy=ppy,
                                  epilogue=epilogue)


@functools.partial(
    jax.jit,
    static_argnames=("spans", "T_pad", "W_pad", "P_real", "T_real", "cost",
                     "ppy", "interpret", "epilogue"))
def _fused_trix_call(close, onehot, a_sig, warm, t_real, *,
                     spans: tuple, T_pad: int, W_pad: int, P_real: int,
                     T_real: int | None, cost: float, ppy: int,
                     interpret: bool, epilogue: str = _EPILOGUE_DEFAULT):
    """Distinct-span triple-EMA table prep + pallas call in one jit."""
    epilogue = _interp_epilogue(epilogue, T_pad, interpret)
    close_p = _pad_last(close, T_pad)
    N = close.shape[0]
    rows = []
    for s in spans:
        a = 2.0 / (float(s) + 1.0)
        rows.append(_ema_rows(_ema_rows(_ema_rows(close_p, a), a), a))
    e3_tbl = jnp.stack(rows, axis=1)                             # (N,W,T_pad)
    if W_pad > len(spans):
        e3_tbl = jnp.concatenate(
            [e3_tbl, jnp.zeros((N, W_pad - len(spans), T_pad),
                               jnp.float32)], axis=1)

    P_pad = a_sig.shape[1]
    # 128 lanes: unlike MACD (+3% at 256), TRIX measured consistently ~4%
    # SLOWER at 256 (14.5-14.8 vs 15.3 M/s) — its ratio + two ladders keep
    # more live state per lane, so the wider block spills what the
    # narrower one keeps resident.
    lanes = _widest_lanes(P_pad, _LANES)
    n_blocks = P_pad // lanes
    kernel = functools.partial(_trix_kernel, cost=cost, ppy=ppy,
                               T_real=T_real, epilogue=epilogue)
    out = pl.pallas_call(
        kernel,
        grid=(N, n_blocks),
        in_specs=[
            pl.BlockSpec((1, T_pad, 1), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, W_pad, T_pad), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((W_pad, lanes), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, lanes), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, lanes), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ] + _tr_specs(T_real),
        out_specs=pl.BlockSpec(
            (1, 1, _METRIC_ROWS, lanes), lambda i, j: (i, j, 0, 0),
            memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (N, n_blocks, _METRIC_ROWS, lanes), jnp.float32),
        interpret=interpret,
        name="trix_kernel",
        compiler_params=_COMPILER_PARAMS,
    )(_rets3(close_p), e3_tbl, onehot, a_sig, warm,
      *_tr_args(t_real, T_real))
    return Metrics(*(
        jnp.reshape(out[:, :, k, :], (N, P_pad))[:, :P_real]
        for k in range(9)))


def fused_trix_sweep(close, span, signal, *, t_real=None, cost: float = 0.0,
                     periods_per_year: int = 252,
                     interpret: bool | None = None,
                     epilogue: str | None = None,
                     carry_out: bool = False) -> Metrics:
    """Fused TRIX signal-line crossover sweep: ``(N, T)`` x ``(P,)`` lanes.

    ``span``/``signal`` are flat per-combo span arrays (:func:`product_grid`
    order); spans must be integral. Matches ``run_sweep(..., "trix")``
    (``models.trix``) to f32 tolerance — both paths evaluate every EMA with
    the same shift-doubling ladder (``rolling.ema_ladder`` generically,
    ``_ema_rows`` / ``_ema_ladder`` here) and the rate-of-change ratio
    cancels the price level, so the only residual divergence class is the
    MXU selection matmul for the triple-smoothed close.
    """
    _check_carry_out_args(carry_out, t_real)
    if interpret is None:
        interpret = interpret_default()
    close = jnp.asarray(close, jnp.float32)
    span = np.asarray(span)
    signal = np.asarray(signal)
    T = close.shape[1]
    spans, onehot, a_sig, warm = _trix_grid_setup(
        span.astype(np.float32).tobytes(),
        signal.astype(np.float32).tobytes())
    m = _fused_trix_call(close, onehot, a_sig, warm,
                         _t_real_col(t_real, close),
                         spans=spans, T_pad=_round_up(T, 128),
                         W_pad=onehot.shape[0], P_real=span.shape[0],
                         T_real=T if t_real is None else None,
                         cost=float(cost), ppy=int(periods_per_year),
                         interpret=bool(interpret),
                         epilogue=_resolve_epilogue(epilogue))
    if not carry_out:
        return m
    return _carry_out_tail(m, "trix", {"close": close},
                           {"span": span, "signal": signal}, t_real=t_real,
                           cost=cost, ppy=periods_per_year,
                           epilogue=epilogue)


@functools.lru_cache(maxsize=4)
def _trix_grid_setup(span_bytes: bytes, signal_bytes: bytes):
    """Distinct spans + selector, per-lane signal decay and warmup
    (= 3*span + signal - 2, ``models.trix``'s rule)."""
    span = np.frombuffer(span_bytes, np.float32)
    signal = np.frombuffer(signal_bytes, np.float32)
    P = span.shape[0]
    spans = _distinct_windows(span, "spans")
    _distinct_windows(signal, "signal spans")   # validate integrality only
    W_pad = _round_up(max(spans.shape[0], 1), 8)
    P_pad = _round_up(max(P, 1), _LANES)
    oh = _window_onehot(spans, span, W_pad, P_pad)
    a_sig = np.zeros((1, P_pad), np.float32)
    a_sig[0, :P] = 2.0 / (signal + 1.0)
    warm = np.ones((1, P_pad), np.float32)
    warm[0, :P] = 3.0 * span + signal - 2.0
    return (tuple(int(s) for s in spans), _const(oh),
            _const(a_sig), _const(warm))


@functools.partial(
    jax.jit,
    static_argnames=("windows", "T_pad", "W_pad", "P_real", "T_real", "cost",
                     "ppy", "interpret", "epilogue", "t_block"))
def _fused_vwap_call(close, volume, onehot_w, k_lanes, warm, t_real, *,
                     windows: tuple, T_pad: int, W_pad: int, P_real: int,
                     T_real: int | None, cost: float, ppy: int,
                     interpret: bool, epilogue: str = _EPILOGUE_DEFAULT,
                     t_block: int | None = None):
    """VWAP-deviation z-table prep + the *Bollinger* kernel.

    ``models.vwap`` vectorized over the distinct-window axis: rolling VWAP =
    windowed ``sum(close*volume) / sum(volume)`` (two cumsum differences),
    the close's deviation from it z-scored over the same window, fed to the
    shared band machine (enter beyond ±k, exit when price re-crosses the
    anchor: z_exit = 0). The first fused kernel consuming the volume column.

    Replicates the generic float op order on the real-bar region (the
    deviation in double-f32 from compensated window-sum pairs,
    ``models.vwap.vwap_deviation``; uncentered rolling-mean numerator,
    series-centered second moments, eps = 1e-12). Warmup rows and
    zero-volume windows hold a deviation of exactly 0, as there.
    """
    T = close.shape[1]
    epilogue = _interp_epilogue(epilogue, T_pad, interpret)
    close_p = _pad_last(close, T_pad)
    vol_p = _pad_last(volume, T_pad)
    w_col, w_f, t_row, windowed_sum, windowed_sum3 = _cumsum_window_tools(
        windows, T_pad)

    pv = windowed_sum(None, prefix=rolling.prefix_prod2(close_p, vol_p),
                      as_pair=True)
    v = windowed_sum(vol_p, as_pair=True)
    dev = jnp.where((t_row >= (w_col - 1))[None],
                    rolling.vwap_deviation2(close_p[:, None, :], pv, v,
                                            _EPS), 0.0)

    m = windowed_sum3(dev) / w_f
    # Center with the deviation's mean over the REAL bars (rolling.py's
    # cancellation guard); the pad region never reaches a real output.
    mu = _real_mean(dev, t_real, T)
    xc = dev - mu
    s1 = windowed_sum3(xc)
    s2 = windowed_sum3(xc * xc)
    var = jnp.maximum((s2 - s1 * s1 / w_f) / w_f, 0.0)
    z_table = (dev - m) / (jnp.sqrt(var) + _EPS)
    z_table = _pad_w(jnp.where((t_row >= w_col - 1)[None], z_table, 0.0),
                     W_pad)

    kernel = functools.partial(_boll_kernel, cost=cost, ppy=ppy,
                               z_exit=0.0, T_real=T_real, epilogue=epilogue)
    return _band_machine_pallas(
        kernel, close_p, z_table, onehot_w, k_lanes, warm, t_real,
        T_pad=T_pad, W_pad=W_pad, P_real=P_real, T_real=T_real,
        interpret=interpret,
        name="vwap_kernel", t_block=t_block)


def fused_vwap_sweep(close, volume, window, k, *, t_real=None,
                     cost: float = 0.0, periods_per_year: int = 252,
                     interpret: bool | None = None,
                     epilogue: str | None = None,
                     carry_out: bool = False,
                     t_block: int | None = None) -> Metrics:
    """Fused VWAP-deviation reversion sweep: ``(N, T)`` panels x ``(P,)``.

    ``window``/``k`` are flat per-combo arrays (:func:`product_grid` order);
    windows must be integral bar counts. Matches the generic
    ``run_sweep(..., "vwap_reversion")`` path (``models.vwap`` +
    ``signals.band_hysteresis_assoc``): bit-level on CPU interpret mode; on
    TPU the MXU z-selection matmul shares the knife-edge caveat of the other
    band-machine kernels for |z - k| ~ 1e-7 relative.
    """
    _check_carry_out_args(carry_out, t_real)
    if interpret is None:
        interpret = interpret_default()
    close = jnp.asarray(close, jnp.float32)
    volume = jnp.asarray(volume, jnp.float32)
    window = np.asarray(window)
    k = np.asarray(k, np.float32)
    T = close.shape[1]
    P = window.shape[0]

    windows, onehot_w, k_lanes, warm = _vwap_grid_setup(
        window.astype(np.float32).tobytes(), k.tobytes())
    m = _fused_vwap_call(close, volume, onehot_w, k_lanes, warm,
                         _t_real_col(t_real, close),
                         windows=windows,
                         T_pad=_band_t_pad(T, t_block),
                         W_pad=onehot_w.shape[0],
                         P_real=P, T_real=T if t_real is None else None,
                         cost=float(cost), ppy=int(periods_per_year),
                         interpret=bool(interpret),
                         epilogue=_resolve_epilogue(epilogue),
                         t_block=t_block)
    if not carry_out:
        return m
    return _carry_out_tail(m, "vwap_reversion",
                           {"close": close, "volume": volume},
                           {"window": window, "k": k}, t_real=t_real,
                           cost=cost, ppy=periods_per_year,
                           epilogue=epilogue)


@functools.lru_cache(maxsize=4)
def _vwap_grid_setup(window_bytes: bytes, k_bytes: bytes):
    """Like :func:`_boll_grid_setup` but the warmup is ``2*window - 1``:
    the VWAP needs ``window`` bars and its deviation's z-score another
    ``window`` (``models.vwap._positions``'s validity rule)."""
    windows, oh, k_lanes, warm = _boll_grid_setup(window_bytes, k_bytes)
    window = np.frombuffer(window_bytes, np.float32)
    P = window.shape[0]
    warm = np.ones((1, warm.shape[1]), np.float32)
    warm[0, :P] = 2.0 * window - 1.0
    return windows, oh, k_lanes, _const(warm)


# ---------------------------------------------------------------------------
# Ragged paged panel batching (round 10)
#
# A realistic multi-ticker universe holds thousands of symbols with wildly
# different history lengths; dense batching either splits them into
# per-length launch groups or pads every panel to the group max. The paged
# mode stores field data as fixed-size T-pages in a device pool
# (rpc.page_pool.PagePool) and drives the EXISTING fused kernels through a
# per-job page table — the paged-KV discipline of PAPERS.md "Ragged Paged
# Attention" applied to OHLCV:
#
# - `_paged_gather` assembles a group's (n, T_run) field block from the
#   pool with ONE device gather per field (no host restack, no per-panel
#   h2d), then re-imposes the repeat-last padding discipline beyond each
#   ticker's real length — so the assembled block is BIT-IDENTICAL to the
#   dense `_stack_field_ragged` stack and every kernel numerics contract
#   carries over unchanged (including the carry-scan epilogue threading
#   across what are now page boundaries: pad bars earn exactly zero, so
#   the carries freeze at the last real bar regardless of how many pages
#   ride behind it).
# - `fused_paged_sweep` bins the group by PAGE COUNT, so each ticker pads
#   only to its own page boundary — pad work bounded by one page per
#   ticker instead of (t_max - t_i), and a mixed-length group costs one
#   launch per page-count class instead of one per power-of-two length
#   bucket with up-to-2x padding.
#
# The pool side (keying, eviction, upload batching) lives in
# rpc.page_pool; this section owns the kernel-facing schedule and the
# env knobs (`DBX_PAGE_BARS`, `DBX_PAGED`).
# ---------------------------------------------------------------------------

_PAGE_BARS_DEFAULT = 512


def paged_enabled() -> bool:
    """Kill switch for the paged execution path (``DBX_PAGED=0`` routes
    every group through the dense stacks; default on). Read lazily per
    backend construction — never at import time."""
    return os.environ.get("DBX_PAGED", "1") != "0"


def resolve_page_bars() -> int:
    """Validated ``DBX_PAGE_BARS`` page size (default 512 bars).

    Must be a positive multiple of 8 — pages land on the kernels' f32
    sublane tiles, so an off-tile page width would misalign every gather.
    512 balances sharing granularity (an append chain re-uploads at most
    one boundary page) against per-ticker pad waste (< 1 page) and pool
    index overhead; see DESIGN.md "Ragged paged panels".
    """
    raw = os.environ.get("DBX_PAGE_BARS")
    if not raw:
        tuned = _tuned_value("page_bars")
        if tuned is not None:
            try:
                tv = int(tuned)
            except (TypeError, ValueError):
                tv = -1
            if tv >= 8 and tv % 8 == 0:
                return tv
        return _PAGE_BARS_DEFAULT
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(
            f"DBX_PAGE_BARS={raw!r} is not an integer (expected a "
            "positive multiple of 8)") from None
    if v < 8 or v % 8:
        raise ValueError(
            f"DBX_PAGE_BARS={v} is unusable: pages must be a positive "
            "multiple of 8 bars (the f32 sublane tile)")
    return v


@functools.partial(jax.jit, static_argnames=("T_run",))
def _paged_gather(pool, table, t_real, *, T_run: int):
    """Assemble an ``(n, T_run)`` field block from the page pool.

    ``pool`` is the ``(slots, page_bars)`` f32 device pool, ``table`` the
    ``(n, max_pages)`` int32 slot table, ``t_real`` the per-ticker real
    bar counts. One gather concatenates each row's pages; the trailing
    select re-imposes the repeat-last padding discipline (bars at
    ``t >= t_real`` replay bar ``t_real - 1``) so the result is
    bit-identical to the dense repeat-last stack no matter what the
    padded table entries point at — table values beyond a ticker's last
    page only need to be in-bounds.
    """
    n = table.shape[0]
    rows = jnp.take(pool, table.reshape(-1), axis=0, mode="clip")
    rows = rows.reshape(n, -1)[:, :T_run]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (n, T_run), 1)
    tr = t_real.astype(jnp.int32)[:, None]
    last = jnp.take_along_axis(rows, jnp.maximum(tr - 1, 0), axis=1)
    return jnp.where(t_idx < tr, rows, last)


# Paged twin of the worker's fused registry: strategy -> (OHLCV fields the
# kernel consumes, grid axes, wrapper adapter). Every
# rpc.compute._FUSED_STRATEGIES entry MUST have a row here — dbxlint's
# kernel-hygiene rule probes the paged path per registry entry
# (`paged_hygiene_probe`), so a missing row surfaces as a loud finding,
# never as a silently dense-only family.
_PAGED_FAMILIES = {
    "sma_crossover": (
        ("close",), ("fast", "slow"),
        lambda a, g, **kw: fused_sma_sweep(a[0], g["fast"], g["slow"],
                                           **kw)),
    "bollinger": (
        ("close",), ("window", "k"),
        lambda a, g, **kw: fused_bollinger_sweep(a[0], g["window"],
                                                 g["k"], **kw)),
    "bollinger_touch": (
        ("close",), ("window", "k"),
        lambda a, g, **kw: fused_bollinger_touch_sweep(
            a[0], g["window"], g["k"], **kw)),
    "momentum": (
        ("close",), ("lookback",),
        lambda a, g, **kw: fused_momentum_sweep(a[0], g["lookback"], **kw)),
    "donchian": (
        ("close",), ("window",),
        lambda a, g, **kw: fused_donchian_sweep(a[0], g["window"], **kw)),
    "donchian_hl": (
        ("close", "high", "low"), ("window",),
        lambda a, g, **kw: fused_donchian_hl_sweep(
            a[0], a[1], a[2], g["window"], **kw)),
    "rsi": (
        ("close",), ("period", "band"),
        lambda a, g, **kw: fused_rsi_sweep(a[0], g["period"], g["band"],
                                           **kw)),
    "stochastic": (
        ("close", "high", "low"), ("window", "band"),
        lambda a, g, **kw: fused_stochastic_sweep(
            a[0], a[1], a[2], g["window"], g["band"], **kw)),
    "keltner": (
        ("close", "high", "low"), ("window", "k"),
        lambda a, g, **kw: fused_keltner_sweep(
            a[0], a[1], a[2], g["window"], g["k"], **kw)),
    "macd": (
        ("close",), ("fast", "slow", "signal"),
        lambda a, g, **kw: fused_macd_sweep(
            a[0], g["fast"], g["slow"], g["signal"], **kw)),
    "trix": (
        ("close",), ("span", "signal"),
        lambda a, g, **kw: fused_trix_sweep(a[0], g["span"], g["signal"],
                                            **kw)),
    "vwap_reversion": (
        ("close", "volume"), ("window", "k"),
        lambda a, g, **kw: fused_vwap_sweep(
            a[0], a[1], g["window"], g["k"], **kw)),
    "obv_trend": (
        ("close", "volume"), ("window",),
        lambda a, g, **kw: fused_obv_sweep(a[0], a[1], g["window"], **kw)),
}


def paged_supported(strategy: str) -> bool:
    """True when ``strategy`` has a paged execution row."""
    return strategy in _PAGED_FAMILIES


def paged_fields(strategy: str) -> tuple:
    """The OHLCV columns the strategy's paged path gathers."""
    return _PAGED_FAMILIES[strategy][0]


def fused_paged_sweep(strategy: str, pool, tables, t_real, grid, *,
                      cost: float = 0.0, periods_per_year: int = 252,
                      interpret: bool | None = None,
                      epilogue: str | None = None) -> Metrics:
    """Run a (possibly mixed-length) group through the fused kernels from
    the device page pool.

    ``pool`` is the ``(slots, page_bars)`` f32 pool array; ``tables`` maps
    each consumed field to a HOST-side ``(n, max_pages)`` int32 slot
    table (short rows padded with any in-bounds slot — dead under the
    assembly's repeat-last fix); ``t_real`` the per-ticker real lengths;
    ``grid`` the flat per-combo axis arrays (:func:`product_grid` order).

    Schedule: the group is binned by page count, each bin assembled by
    :func:`_paged_gather` at its own max length and swept by the family's
    fused kernel — so a ticker's pad work is bounded by ONE page and a
    heterogeneous fleet costs one launch per page-count class. A bin
    whose lengths are uniform takes the kernels' static-length fast path
    and is bit-identical to the dense fused sweep; ragged bins follow the
    documented repeat-last-pad contract (same bits as the dense ragged
    stack). ``epilogue`` routes the metrics-tail substrate exactly as in
    the dense wrappers — the carry scan threads across page boundaries
    like any other T-block boundary.
    """
    fam = _PAGED_FAMILIES.get(strategy)
    if fam is None:
        raise ValueError(
            f"strategy {strategy!r} has no paged execution row "
            f"(known: {sorted(_PAGED_FAMILIES)})")
    fields, _, call = fam
    missing = [f for f in fields if f not in tables]
    if missing:
        raise ValueError(
            f"paged sweep for {strategy!r} needs page tables for fields "
            f"{list(fields)}; missing {missing}")
    t_real = np.asarray(t_real, np.int32).reshape(-1)
    n = t_real.shape[0]
    if n == 0:
        raise ValueError("paged sweep over an empty group")
    B = int(pool.shape[1])
    pages_of = -(-t_real // B)
    bins: dict = {}
    for i, p in enumerate(pages_of):
        bins.setdefault(int(p), []).append(i)

    kw = dict(cost=float(cost), periods_per_year=int(periods_per_year),
              interpret=interpret, epilogue=epilogue)
    parts = []
    order: list = []
    for p, idx in sorted(bins.items()):
        t_bin = t_real[idx]
        T_bin = int(t_bin.max())
        tr_dev = jnp.asarray(t_bin, jnp.int32)
        arrays = [
            _paged_gather(pool,
                          jnp.asarray(np.asarray(tables[f],
                                                 np.int32)[idx][:, :p]),
                          tr_dev, T_run=T_bin)
            for f in fields]
        uniform = bool((t_bin == T_bin).all())
        parts.append(call(arrays, grid,
                          t_real=None if uniform else t_bin, **kw))
        order.extend(idx)
    if len(parts) == 1:
        return parts[0]
    inv = np.empty(n, np.int64)
    inv[np.asarray(order)] = np.arange(n)
    inv = jnp.asarray(inv)
    return Metrics(*(jnp.concatenate(cols, axis=0)[inv]
                     for cols in zip(*parts)))


# One representative value per grid axis for the tiny hygiene probe —
# the paged twin of analysis.jaxpr_rules._AXIS_VALUES (windows small and
# integral, MACD/TRIX fast < slow, 18 real bars clear every warmup).
_PAGED_PROBE_AXES = {
    "fast": [2.0], "slow": [5.0], "window": [3.0], "k": [1.0],
    "lookback": [2.0], "period": [3.0], "band": [20.0], "signal": [2.0],
    "span": [2.0],
}
_PAGED_PROBE_BARS = (20, 18)    # ragged pair, both 3 pages of 8 bars


def paged_hygiene_probe(strategy: str):
    """``(fn, args)`` tracing the paged path of ``strategy`` over a tiny
    pool + page table — dbxlint's kernel-hygiene rule feeds this to
    ``jax.make_jaxpr`` under both epilogue substrates so the paged
    variants can never silently fall out of lint coverage. Raises for a
    registry entry with no paged row or probe template (the rule reports
    that as a loud finding)."""
    fields, axes, _ = _PAGED_FAMILIES[strategy]
    B = 8
    T = max(_PAGED_PROBE_BARS)
    t_real = np.asarray(_PAGED_PROBE_BARS, np.int32)
    t = np.arange(1, T + 1, dtype=np.float32)
    close = 100.0 + np.sin(t) + 0.01 * t
    by_name = {
        "close": close, "high": close * 1.01, "low": close * 0.99,
        "open": close, "volume": np.full(T, 1e4, np.float32),
    }
    pool_rows: list[np.ndarray] = []
    tables: dict = {}
    n_pages = -(-T // B)
    for f in fields:
        tbl = np.zeros((len(t_real), n_pages), np.int32)
        for i, tr in enumerate(t_real):
            series = (by_name[f][:tr] * (1.0 + 0.001 * i)).astype(
                np.float32)
            pages = [series[s:s + B] for s in range(0, tr, B)]
            pages = [np.concatenate(
                [pg, np.full(B - pg.shape[0], pg[-1], np.float32)])
                if pg.shape[0] < B else pg for pg in pages]
            slots = list(range(len(pool_rows),
                               len(pool_rows) + len(pages)))
            pool_rows.extend(pages)
            tbl[i, :len(slots)] = slots
            tbl[i, len(slots):] = slots[-1]
        tables[f] = tbl
    pool = np.stack(pool_rows)
    grid = {a: np.asarray(_PAGED_PROBE_AXES[a], np.float32) for a in axes}

    def fn(pool_arg):
        return fused_paged_sweep(strategy, pool_arg, tables, t_real, grid,
                                 interpret=True)

    return fn, (pool,)


# ---------------------------------------------------------------------------
# Scenario megakernel (round 18)
#
# A scenario panel is a pure function of (base_digest, params) — the
# scenarios.synth reproducibility contract — so a K-scenario stress sweep
# never needs K panels in HBM: one launch regenerates each panel block by
# block in-trace (the synth generator's per-block threefry schedule,
# fold_in(key, block_index)) and feeds it straight into the family's fused
# sweep. The recompute-from-seed trade of PAPERS.md "Compiler-First State
# Space Duality" layered on the paged mode's block iteration: device bytes
# are O(1) in K (a lax.map carries one scenario's working set at a time;
# only the (T_base,) base panel persists), and the dispatcher ships K
# ~100-byte specs instead of K materialized panels.
#
# Families route through the SAME adapter registry as the paged path
# (_PAGED_FAMILIES) — the generator emits all five OHLCV columns, so every
# fused family is scenario-capable. DBX_SCENARIO_FUSED=0 is the kill
# switch (read host-side, per call).
# ---------------------------------------------------------------------------


def scenario_fused_enabled() -> bool:
    """Kill switch for the fused scenario-sweep path
    (``DBX_SCENARIO_FUSED=0`` keeps every scenario job on the
    dispatcher-materialized ladder rung; default on). Read lazily per
    call — never at import time, and never inside a trace."""
    return os.environ.get("DBX_SCENARIO_FUSED", "1") != "0"


def scenario_supported(strategy: str) -> bool:
    """True when ``strategy`` can serve a spec-batch scenario job (one
    adapter registry with the paged path — the generator emits every
    OHLCV column, so the two capability sets are identical by
    construction)."""
    return strategy in _PAGED_FAMILIES


@functools.lru_cache(maxsize=32)
def _scenario_sweep_fn(strategy: str, grid_items: tuple, n_bars: int,
                       block: int, regimes: int, cost: float, ppy: int,
                       interpret: bool, epilogue: str, _subs: tuple):
    """Build (and cache) the jitted generator x sweep program for one
    static configuration. ``_subs`` pins the family's live substrate
    snapshot (``route_substrates``) into the cache key: the wrappers
    resolve table/lanes knobs at trace time, so an in-process env flip
    must mint a NEW program, not silently reuse a stale compile."""
    from ..scenarios import synth

    fields, _, call = _PAGED_FAMILIES[strategy]
    grid = {k: np.frombuffer(v, np.float32) for k, v in grid_items}

    def run(open_, high, low, close, volume, seed_lo, seed_hi,
            vol_scale, shock):
        def one(xs):
            lo, hi, vs, sh = xs
            key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
            o, h, l, c, v = synth._gen_impl(
                open_, high, low, close, volume, vs, sh, key,
                n_bars=n_bars, block=block, regimes=regimes)
            by = {"open": o, "high": h, "low": l, "close": c, "volume": v}
            arrays = [by[f][None, :] for f in fields]
            m = call(arrays, grid, t_real=None, cost=cost,
                     periods_per_year=ppy, interpret=interpret,
                     epilogue=epilogue)
            return tuple(x[0] for x in m)

        # lax.map (a scan) holds ONE scenario's generated panel + sweep
        # working set live at a time — the O(1)-in-K device-byte claim.
        ms = jax.lax.map(one, (seed_lo, seed_hi, vol_scale, shock))
        return Metrics(*ms)

    return jax.jit(run)


def fused_scenario_sweep(strategy: str, base, seed_lo, seed_hi,
                         vol_scale, shock, grid, *, n_bars: int,
                         block: int, regimes: int, cost: float = 0.0,
                         periods_per_year: int = 252,
                         interpret: bool | None = None,
                         epilogue: str | None = None) -> Metrics:
    """Run K scenarios of one base panel through a family's fused sweep,
    regenerating each scenario's OHLCV in-trace — the scenario panels
    never exist in HBM.

    ``base`` maps the five OHLCV column names to ``(T_base,)`` arrays of
    the REAL panel; ``seed_lo``/``seed_hi`` are the per-scenario effective
    seed words (:func:`~..scenarios.synth.seed_words` of
    ``scenario_seed(base_digest, params)``) and ``vol_scale``/``shock``
    the per-scenario generator modulation, all ``(K,)``. The
    shape-static generator knobs (``n_bars``/``block``/``regimes``) are
    uniform across the batch — the dispatcher's spec coalescer keys on
    them. Returns :class:`Metrics` with ``(K, P)`` fields, row ``k``
    bit-matching the dense fused sweep over the host-materialized panel
    of spec ``k`` (one shared generator program — cross-pinned by test).
    """
    fam = _PAGED_FAMILIES.get(strategy)
    if fam is None:
        raise ValueError(
            f"strategy {strategy!r} has no scenario execution row "
            f"(known: {sorted(_PAGED_FAMILIES)})")
    if n_bars < 1 or block < 1 or regimes < 1:
        raise ValueError(
            f"scenario sweep needs n_bars/block/regimes >= 1 "
            f"(got {n_bars}/{block}/{regimes})")
    if interpret is None:
        interpret = interpret_default()
    grid_items = tuple(sorted(
        (k, np.asarray(v, np.float32).tobytes()) for k, v in grid.items()))
    subs = tuple(sorted(route_substrates(strategy).items()))
    fn = _scenario_sweep_fn(strategy, grid_items, int(n_bars), int(block),
                            int(regimes), float(cost),
                            int(periods_per_year), bool(interpret),
                            _resolve_epilogue(epilogue), subs)
    seed_lo = jnp.asarray(seed_lo, jnp.int32)
    seed_hi = jnp.asarray(seed_hi, jnp.int32)
    if seed_lo.ndim != 1 or seed_lo.shape != seed_hi.shape:
        raise ValueError("seed_lo/seed_hi must be matching (K,) arrays")
    if seed_lo.shape[0] == 0:
        raise ValueError("scenario sweep over an empty spec batch")
    return fn(*(jnp.asarray(np.asarray(base[f]), jnp.float32)
                for f in ("open", "high", "low", "close", "volume")),
              seed_lo, seed_hi,
              jnp.asarray(vol_scale, jnp.float32),
              jnp.asarray(shock, jnp.float32))


# 18 real base bars + 16 generated bars clear every probe axis warmup
# (windows <= 5, MACD/TRIX fast < slow) — the paged probe's sizing rule.
_SCENARIO_PROBE_BARS = 18


def scenario_hygiene_probe(strategy: str):
    """``(fn, args)`` tracing the scenario megakernel path of
    ``strategy`` — the in-trace seed fold, the per-block generator scan
    and the family sweep over the regenerated panel — for dbxlint's
    kernel-hygiene rule (both epilogue substrates, like the paged twin).
    Raises for a family with no scenario row or probe template (the rule
    reports that as a loud finding, never a crashed run)."""
    fields, axes, _ = _PAGED_FAMILIES[strategy]
    del fields
    T = _SCENARIO_PROBE_BARS
    t = np.arange(1, T + 1, dtype=np.float32)
    close = 100.0 + np.sin(t) + 0.01 * t
    base = {
        "open": close, "high": close * 1.01, "low": close * 0.99,
        "close": close, "volume": np.full(T, 1e4, np.float32),
    }
    grid = {a: np.asarray(_PAGED_PROBE_AXES[a], np.float32) for a in axes}
    args = (np.asarray([3, 5], np.int32), np.asarray([1, 2], np.int32),
            np.asarray([2.0, 1.5], np.float32),
            np.asarray([0.1, 0.0], np.float32))

    def fn(lo, hi, vs, sh):
        return fused_scenario_sweep(strategy, base, lo, hi, vs, sh, grid,
                                    n_bars=16, block=4, regimes=2,
                                    interpret=True)

    return fn, args


def scenario_certify_probe():
    """``(fn, args, integral_keys)`` for dbxcert: the fused generator x
    sweep cone on tiny pinned shapes — the flagship family's scenario
    megakernel traced end to end (seed fold -> per-block regeneration ->
    carry-scan sweep -> metrics). The in-sweep regeneration claim is
    sound only if this program is run-to-run deterministic for fixed
    seed words: the certifier asserts no nondet-class primitive reaches
    any metric output, the same machine-checked contract the
    ``scenario_synth`` cone pins for the host/materialized path. The
    two rows TOGETHER are the proof the fused and materialized rungs of
    the degradation ladder cannot silently diverge in kind."""
    from .metrics import Metrics

    probe_fn, args = scenario_hygiene_probe("sma_crossover")

    def fn(lo, hi, vs, sh):
        m = probe_fn(lo, hi, vs, sh)
        return dict(zip(Metrics._fields, m))

    return fn, args, frozenset()
