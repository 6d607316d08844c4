"""Benchmark suite: (ticker x param) backtests/sec on one chip, per config.

Headline workload = the BASELINE.json north star (configs[1]): a 500-ticker
SMA-crossover sweep over 5 years of daily bars with a 2,000-point
(fast, slow) grid — 1,000,000 full backtests (indicators, positions, PnL,
9 summary metrics) per sweep call, via the fused Pallas kernel. The suite
also measures configs[2]-[4] and the rest of the fused family: Bollinger
(500 x 1k (window, k), hysteresis and band-touch), momentum, Donchian
(close and high/low channels), stochastic %K, VWAP reversion, RSI, MACD,
TRIX, OBV trend, rolling-OLS pairs (1k pairs x 500 (lookback, z_entry)),
and walk-forward
(12 refit windows x param grid), plus an
``e2e`` config that pushes the headline workload
through a loopback gRPC dispatcher + worker (decode, RPC and metric
reporting included), printing a per-config line to stderr.

Baseline: the reference's worker processes jobs serially at 1 job/sec (its
compute slot sleeps 1 s per job — reference ``src/worker/process.rs:23``), so
``vs_baseline`` is the raw speedup over 1 backtest/sec.

Methodology: the first call (compile) is excluded; a further untimed warm-up
round absorbs the dispatch pipeline's cold start (the first dispatches pay
full round-trip latency before pipelining engages — measured 4M/s cold vs
16M/s warm for the same program, before PR 1). Timed iterations chain into a
device-side accumulator so every sweep executes, synchronized once at the end.
The persistent compilation cache (tune.compile_cache: JAX_COMPILATION_CACHE_DIR,
else <checkout>/.jax_cache) cuts fresh-process compiles.

CROSS-RUN variance caveat (r4, measured): the same config moved +-35%
between bench invocations on the chip used before PR 1 (keltner
measured 7.25 M/s inside one full-suite run and 11.35 M/s isolated
minutes later, identical code). Only BACK-TO-BACK A/B runs in one sitting
are trustworthy for optimization decisions; a single full-suite run's
per-config spread is bounded-reliable for the big picture (kernel-family
ratios, bound attribution) but not for ~20% deltas. An r4 experiment that
"fixed" keltner's apparent 41% utilization by fusing its 25 per-window
EMA prep ladders into one stacked ladder measured FASTER against the bad
baseline and 16-19% SLOWER in a controlled A/B (per-window loop wins for
keltner/rsi/macd prep); the loop stays, and the roofline's per-run
utilization figures should be read with that error bar.

Prints ONE JSON line to stdout:
    {"metric": ..., "value": N, "unit": "backtests/sec", "vs_baseline": N,
     "configs": {name: rate, ...}}

``--verify`` mode instead runs fused-vs-generic parity for every fused
kernel (SMA, Bollinger hysteresis + band-touch, momentum, Donchian close +
high/low, stochastic, VWAP, RSI, MACD, TRIX, OBV, pairs) ON THE CHIP
and prints one JSON line with max relative error and the argmax/entry flip
rates (the knife-edge MXU caveat, plus MACD's in-kernel-ladder vs
associative_scan caveat — quantified fresh each round and asserted
against per-kernel error budgets: over-budget kernels FAIL the run; see
DESIGN.md "Fused-kernel error budgets").

Env overrides (local smoke runs): DBX_BENCH_TICKERS, DBX_BENCH_BARS,
DBX_BENCH_PARAMS, DBX_BENCH_ITERS, DBX_BENCH_WARMUP, DBX_BENCH_CPU=1 to
force the CPU platform, DBX_BENCH_CONFIGS=comma list to subset configs.
"""

import json
import os
import sys
import time


def _setup_jax():
    if os.environ.get("DBX_BENCH_CPU") == "1":
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax

    if os.environ.get("DBX_BENCH_CPU") == "1":
        jax.config.update("jax_platforms", "cpu")
    # DBX_BENCH_CACHE="" turns the persistent cache off (the tests' tiny
    # in-process runs); otherwise the one resolver places it.
    if os.environ.get("DBX_BENCH_CACHE") != "":
        from distributed_backtesting_exploration_tpu.tune import compile_cache

        compile_cache.configure()
    return jax


# Approximate TPU v5e (v5 lite) peaks for the roofline model below. MXU
# f32 = the 197 bf16 TFLOP/s spec divided by the 6-pass HIGHEST-precision
# schedule every selection matmul here uses. The VPU figure is an estimate
# (1024 lanes x ~2.6 f32 ops/cycle effective); these are for RELATIVE bound
# attribution — "which resource caps this kernel" — not absolute gospel.
V5E_PEAKS = {"vpu": 4.0e12, "mxu": 3.3e13, "hbm": 8.1e11}
ROOFLINE: dict = {}


def _roofline_note(name, rate: float, n_bars: int, model: dict | None):
    """Per-kernel utilization string from a (vpu ops, mxu flops, hbm bytes)
    per-cell-bar model; records the figures for the bench JSON."""
    if not model:
        return ""
    cell_bars = rate * n_bars
    util = {res: cell_bars * per / V5E_PEAKS[res]
            for res, per in model.items()}
    bound = max(util, key=util.get)
    ROOFLINE[name] = {**{f"{r}_util": round(u, 3) for r, u in util.items()},
                      "bound": bound,
                      "vpu_ops_per_cell_bar": model.get("vpu", 0)}
    parts = ", ".join(f"{r.upper()} {100 * u:.0f}%" for r, u in util.items())
    return f" | {parts} of v5e peak -> {bound.upper()}-bound"


def _measure(run, n_backtests: int, *, iters: int, warmup: int, name: str,
             n_bars: int = 0, model: dict | None = None):
    """Compile + warm the dispatch pipeline, then time ``iters`` chained runs."""
    import jax.numpy as jnp
    import numpy as np

    t0 = time.perf_counter()
    out = run()
    first = np.asarray(out.sharpe)
    assert np.isfinite(first).all(), f"{name}: non-finite metrics"
    compile_s = time.perf_counter() - t0

    acc = jnp.float32(0.0)
    for _ in range(warmup):
        acc = acc + jnp.sum(run().sharpe)
    float(acc)  # sync

    t0 = time.perf_counter()
    acc = jnp.float32(0.0)
    for _ in range(iters):
        acc = acc + jnp.sum(run().sharpe)
    acc_val = float(acc)   # the synchronizing fetch — must not be elided
    elapsed = time.perf_counter() - t0
    assert np.isfinite(acc_val), f"{name}: non-finite accumulator"
    rate = n_backtests * iters / elapsed
    print(f"bench[{name}]: compile {compile_s:.1f}s, {iters}x {n_backtests} "
          f"backtests in {elapsed:.3f}s -> {rate/1e6:.2f}M/s"
          f"{_roofline_note(name, rate, n_bars, model)}", file=sys.stderr)
    return rate


def main():
    jax = _setup_jax()
    import jax.numpy as jnp
    import numpy as np

    from distributed_backtesting_exploration_tpu.models import base, pairs
    from distributed_backtesting_exploration_tpu.ops import fused
    from distributed_backtesting_exploration_tpu.parallel import (
        sweep, walkforward)
    from distributed_backtesting_exploration_tpu.utils import data

    # The e2e/dispatch configs push thousands of traced jobs (~5 spans
    # each) through the in-process loop; the default 512-span ring would
    # retain only the last ~100 jobs for the end-of-run "timeline"
    # digest. Size it through the DBX_SPAN_RING knob (setdefault: an
    # operator's explicit choice wins) to hold a full config's spans —
    # torn heads are dropped and counted by summarize_spans either way.
    from distributed_backtesting_exploration_tpu import obs as _obs
    os.environ.setdefault("DBX_SPAN_RING", "32768")
    _obs.configure_ring()

    n_tickers = int(os.environ.get("DBX_BENCH_TICKERS", 500))
    n_bars = int(os.environ.get("DBX_BENCH_BARS", 1260))      # 5y daily
    n_params = int(os.environ.get("DBX_BENCH_PARAMS", 2000))
    iters = int(os.environ.get("DBX_BENCH_ITERS", 10))
    warmup = int(os.environ.get("DBX_BENCH_WARMUP", 12))
    only = os.environ.get("DBX_BENCH_CONFIGS")
    only = set(only.split(",")) if only else None

    dev = jax.devices()[0]
    print(f"bench: device={dev.device_kind} tickers={n_tickers} "
          f"bars={n_bars} params={n_params}", file=sys.stderr)

    ohlcv = data.synthetic_ohlcv(n_tickers, n_bars, seed=0)
    panel = type(ohlcv)(*(jax.device_put(jnp.asarray(f), dev) for f in ohlcv))
    rates: dict[str, float] = {}

    def enabled(name):
        return only is None or name in only

    # --- Roofline models: per-(cell, bar) resource counts read off the
    # kernel structure in ops/fused.py. The per-bar recurrences (equity
    # cumsum + running-peak cummax, the band machines' 3-state compose)
    # default to the SINGLE-PASS carry scan over T-blocks (`_equity_scan`
    # / `_compose3_path`): per-row ladder work is log2(B) rounds for the
    # static scan block B instead of log2(T_pad), plus a few carry-combine
    # ops per row. `DBX_EPILOGUE=ladder` restores the full-T ladders (and
    # this model follows it, so the A/B's utilization figures stay honest):
    #   metrics tail  = ~26 reduction/PnL ops + 2 ladders x 2 ops/round + 7
    #   3-state prefix compose (band/latch machines) = 9 ops/round + 2
    #   in-kernel EMA ladder (MACD signal line)      = 5 ops/round (full-T:
    #     the signal EMA's per-lane decay is not blocked — carry state is a
    #     multiply chain, not a select)
    # MXU = 2 FLOP x W_pad contraction per selection matmul per cell-bar
    # (HIGHEST precision — the peak constant already folds the 6-pass
    # schedule). HBM = the (W_pad x T_pad) table stream amortized over
    # P_pad lanes, times (1 + prep passes over table-shaped intermediates).
    rounds = max(int(np.ceil(np.log2(max(n_bars, 2)))), 1)
    _epi = fused._resolve_epilogue(None)      # same arg>env>default chain
    if _epi == "ladder":
        tail_rounds = compose_rounds = rounds
        tail_fix = compose_fix = 0
    else:
        # The kernels' own block pick (incl. the doubling past 256 blocks
        # for long-context shapes) — the model must not re-derive it.
        _blk = fused._scan_block(-(-n_bars // 8) * 8, _epi)
        tail_rounds = compose_rounds = max(int(np.ceil(np.log2(_blk))), 1)
        tail_fix, compose_fix = 7, 2          # carry combines per row
    TAIL = 26 + 4 * tail_rounds + tail_fix    # shared metrics tail
    LADDER3 = 9 * compose_rounds + compose_fix  # band/latch 3-state compose

    def _model(vpu, n_distinct_w, p, *, w_align=8, selections=1,
               prep_passes=3):
        w_pad = -(-max(n_distinct_w, 1) // w_align) * w_align
        p_pad = -(-max(p, 1) // 128) * 128
        return {"vpu": float(vpu),
                "mxu": 2.0 * selections * w_pad,
                "hbm": 4.0 * w_pad * (1 + prep_passes) / p_pad}

    # --- configs[1] headline: fused SMA-crossover sweep -------------------
    if enabled("sma_fused"):
        n_fast = 20
        n_slow = max(n_params // n_fast, 1)
        grid = sweep.product_grid(
            fast=jnp.arange(5, 5 + n_fast, dtype=jnp.float32),
            slow=jnp.arange(30, 30 + 2 * n_slow, 2, dtype=jnp.float32))
        fa, sl = np.asarray(grid["fast"]), np.asarray(grid["slow"])
        if os.environ.get("DBX_BENCH_GENERIC") == "1":
            strat = base.get_strategy("sma_crossover")
            chunk = int(os.environ.get("DBX_BENCH_CHUNK", 100))

            def run_sma():
                return sweep.chunked_sweep(panel, strat, grid,
                                           param_chunk=chunk, cost=1e-3)
        else:
            def run_sma():
                return fused.fused_sma_sweep(panel.close, fa, sl, cost=1e-3)

        # The default substrate is the in-kernel (VMEM-scratch) table
        # (ops/fused.py `_kernel_inline`, DBX_SMA_TABLE=hbm for the A/B
        # twin): no XLA table passes and no table HBM stream, so the HBM
        # term drops to the cs + returns rows and the VPU term gains the
        # per-ticker table build amortized over the param lanes
        # (~4 ops x W_pad x 8/occupancy / P_pad per cell-bar).
        sma_inline = os.environ.get("DBX_SMA_TABLE", "inline") == "inline"
        n_w = np.unique(np.r_[fa, sl]).size
        sma_model = _model(TAIL + 4, n_w, fa.size, w_align=128,
                           prep_passes=0 if sma_inline else 3)
        if sma_inline:
            p_pad = -(-fa.size // 128) * 128
            sma_model["hbm"] = 4.0 * 2 / p_pad
            sma_model["vpu"] += 4.0 * n_w * 8 / p_pad
        rates["sma_fused"] = _measure(
            run_sma, n_tickers * sweep.grid_size(grid), iters=iters,
            warmup=warmup, name="sma_fused", n_bars=n_bars,
            model=sma_model)

    # --- roofline_stages: where the SMA kernel's cycles actually go -------
    # (VERDICT r4 weak #4: no kernel exceeds 2/3 of its modeled VPU
    # roofline and the residual was unexplained.) Cut-down variants of the
    # EXACT headline kernel — same grid, same block specs, same table prep
    # — with later stages removed, so consecutive deltas attribute wall
    # time to (selection matmul + sign) / (PnL prep) / (equity+peak shift
    # ladders) / (reductions + pack). Results feed the DESIGN.md roofline
    # accounting table; ROOFLINE["sma_stages"] records them in BENCH JSON.
    if enabled("roofline_stages"):
        import functools

        from distributed_backtesting_exploration_tpu.ops import fused as F
        from distributed_backtesting_exploration_tpu.ops.metrics import (
            Metrics)

        pl = F.pl
        pltpu = F.pltpu
        n_fast = 20
        n_slow = max(n_params // n_fast, 1)
        sgrid = sweep.product_grid(
            fast=jnp.arange(5, 5 + n_fast, dtype=jnp.float32),
            slow=jnp.arange(30, 30 + 2 * n_slow, 2, dtype=jnp.float32))
        sfa = np.asarray(sgrid["fast"])
        ssl = np.asarray(sgrid["slow"])
        windows, onehot_d, warm = F._grid_setup(
            sfa.astype(np.float32).tobytes(),
            ssl.astype(np.float32).tobytes())
        T_pad = F._round_up(n_bars, 8)
        W_pad = onehot_d.shape[0]
        P_real = sfa.shape[0]
        interp = F.interpret_default()

        def stage_kernel(r_ref, sma_ref, od_ref, warm_ref, out_ref,
                         *, stage, lanes):
            # Mirrors ops.fused._kernel exactly through the requested
            # stage, then writes a cheap stand-in tile so every variant
            # has identical I/O (measurement scaffolding only — results
            # are NOT metrics except for the "full*" stages). ``lanes``
            # parameterizes the per-cell param-block width (the block-
            # shape experiment: fewer, wider cells amortize per-cell
            # fixed overhead).
            T_pd = r_ref.shape[1]
            r = r_ref[0]
            sma = sma_ref[0]                  # (W_pad, T_pad) — W-major
            if stage == "touch":
                # Stream the table through VMEM without the contraction:
                # isolates DMA + per-cell overhead from MXU time.
                out_ref[0, 0] = jnp.full(
                    (F._METRIC_ROWS, lanes), jnp.sum(sma), jnp.float32)
                return
            d = jax.lax.dot_general(
                sma, od_ref[:], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            t_idx = jax.lax.broadcasted_iota(jnp.int32, (T_pd, lanes), 0)
            if stage == "matmul":
                out_ref[0, 0] = jnp.broadcast_to(
                    jnp.sum(d, axis=0)[None, :], (F._METRIC_ROWS, lanes))
                return
            warm_v = warm_ref[0, :][None, :]
            valid = t_idx >= (warm_v.astype(jnp.int32) - 1)
            pos = jnp.where(valid, jnp.sign(d), 0.0)
            if stage == "signal":
                out_ref[0, 0] = jnp.broadcast_to(
                    jnp.sum(pos * r, axis=0)[None, :],
                    (F._METRIC_ROWS, lanes))
                return
            tr = n_bars
            if stage in ("full", "full_ladder"):
                # The REAL shipped tail (shared code, not a copy): this
                # variant IS ops.fused._kernel end to end. "full" runs the
                # shipped single-pass carry scan; "full_ladder" the
                # O(T log T) fallback substrate — their delta over
                # no_ladders is the scan's win on this exact kernel.
                out_ref[0, 0] = F._metrics_tail(
                    pos, r, t_idx, tr, cost=1e-3, ppy=252,
                    epilogue="scan" if stage == "full" else "ladder")
                return
            # no_ladders: the shipped reductions with the two shift
            # ladders (equity cumsum + running-peak cummax) replaced by
            # one pass each — a deliberately CUT variant isolating ladder
            # cost from reduction cost (scaffolding, not metrics).
            row_ok = t_idx < tr
            pos_last = F._row_at(pos, tr, t_idx, keepdims=True)
            pos = jnp.where(row_ok, pos, pos_last)
            prev = F._shift_down(pos, 1, 0.0)
            net = prev * r - 1e-3 * jnp.abs(pos - prev)
            n_f = jnp.asarray(tr, jnp.float32)
            s1 = jnp.sum(net, axis=0)
            s2 = jnp.sum(net * net, axis=0)
            meanv = s1 / n_f
            var = jnp.maximum(s2 / n_f - meanv * meanv, 0.0)
            std = jnp.sqrt(var)
            down = jnp.minimum(net, 0.0)
            dstd = jnp.sqrt(jnp.sum(down * down, axis=0) / n_f)
            active = (jnp.abs(prev) > 0) & row_ok
            wins = (net > 0) & active
            hit = jnp.sum(wins.astype(jnp.float32), axis=0) / (
                jnp.sum(active.astype(jnp.float32), axis=0) + 1e-12)
            turnover = jnp.sum(jnp.abs(pos - prev), axis=0)
            rows = jnp.stack([s1, s2, meanv, std, dstd, hit,
                              turnover, std, s1], axis=0)
            out_ref[0, 0] = jnp.concatenate(
                [rows, jnp.zeros((F._METRIC_ROWS - 9, lanes),
                                 jnp.float32)], axis=0)

        @functools.partial(jax.jit, static_argnames=("stage", "lanes"))
        def stage_call(close, *, stage, lanes=128):
            # THE shipped table prep (shared code, not a copy).
            close_p = F._pad_last(close, T_pad)
            tbl = F._sma_table(close_p, windows, W_pad)
            r3 = F._rets3(close_p)
            P_pad = onehot_d.shape[1]
            if stage == "prep":
                # XLA table construction alone, no pallas call: the
                # host-program share of the "matmul" base.
                return jnp.broadcast_to(
                    jnp.sum(tbl, axis=(1, 2))[:, None] + r3[:, 0, :],
                    (close.shape[0], P_pad))[:, :P_real]
            nb = P_pad // lanes
            out = pl.pallas_call(
                functools.partial(stage_kernel, stage=stage, lanes=lanes),
                grid=(close.shape[0], nb),
                in_specs=[
                    pl.BlockSpec((1, T_pad, 1), lambda i, j: (i, 0, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, W_pad, T_pad), lambda i, j: (i, 0, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((W_pad, lanes), lambda i, j: (0, j),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, lanes), lambda i, j: (0, j),
                                 memory_space=pltpu.VMEM),
                ],
                out_specs=pl.BlockSpec(
                    (1, 1, F._METRIC_ROWS, lanes),
                    lambda i, j: (i, j, 0, 0), memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct(
                    (close.shape[0], nb, F._METRIC_ROWS, lanes),
                    jnp.float32),
                interpret=interp,
            )(r3, tbl, F._const(onehot_d), F._const(warm))
            return jnp.reshape(out[:, :, 0, :],
                               (close.shape[0], P_pad))[:, :P_real]

        stage_times = {}
        n_bt = n_tickers * P_real
        P_pad_all = onehot_d.shape[1]
        cases = [(stage, lanes)
                 for stage, lanes in
                 [("prep", 128), ("touch", 128), ("matmul", 128),
                  ("signal", 128), ("no_ladders", 128),
                  ("full", 128), ("full_ladder", 128), ("full", 256),
                  ("full", 512), ("full", 1024), ("no_ladders", 512)]
                 # Non-headline DBX_BENCH_PARAMS values can make P_pad
                 # smaller than (or not a multiple of) a lane case; skip
                 # those instead of building a zero/ragged grid.
                 if P_pad_all >= lanes and P_pad_all % lanes == 0]
        for stage, lanes in cases:
            def run_stage(stage=stage, lanes=lanes):
                from types import SimpleNamespace
                return SimpleNamespace(
                    sharpe=stage_call(panel.close, stage=stage,
                                      lanes=lanes))
            # _measure asserts finite sharpe; stand-in tiles are finite.
            rate = _measure(run_stage, n_bt, iters=iters, warmup=warmup,
                            name=f"sma_stage_{stage}_l{lanes}")
            stage_times[f"{stage}_l{lanes}"] = n_bt / rate  # s per sweep

        def _attribution(times, full_key="full_l128"):
            # Consecutive-delta attribution shared by the SMA and
            # bollinger scaffolds. "full" runs the SHIPPED carry-scan
            # epilogue, so ladders_delta_pct is the scan's residual share
            # (the acceptance metric); "full_ladder" re-times the
            # O(T log T) fallback substrate on the same kernel, so
            # ladder_fallback_delta_pct is the old 47.6%-class number and
            # epilogue_scan_speedup their end-to-end ratio.
            full_s = times[full_key]
            out = {
                "selection_matmul_pct": round(
                    100 * times["matmul_l128"] / full_s, 1),
                "signal_delta_pct": round(
                    100 * (times["signal_l128"] - times["matmul_l128"])
                    / full_s, 1),
                "reductions_delta_pct": round(
                    100 * (times["no_ladders_l128"] - times["signal_l128"])
                    / full_s, 1),
                "ladders_delta_pct": round(
                    100 * (full_s - times["no_ladders_l128"]) / full_s, 1),
            }
            if "full_ladder_l128" in times:
                out["ladder_fallback_delta_pct"] = round(
                    100 * (times["full_ladder_l128"]
                           - times["no_ladders_l128"])
                    / times["full_ladder_l128"], 1)
                out["epilogue_scan_speedup"] = round(
                    times["full_ladder_l128"] / full_s, 3)
            return out

        full_s = stage_times["full_l128"]
        attribution = _attribution(stage_times)
        if "full_l512" in stage_times:   # skipped for small P_pad
            attribution["wide_block_speedup_l512"] = round(
                full_s / stage_times["full_l512"], 2)
        # Shipped-path A/Bs on top of the cut stages, both through the
        # real fused_sma_sweep at its auto-picked block width: the
        # in-kernel (VMEM-scratch) table vs the XLA/HBM table (justifies
        # DBX_SMA_TABLE's "inline" default), and the carry-scan epilogue
        # vs the ladder fallback (justifies DBX_EPILOGUE's "scan").
        for mode in ("hbm", "inline"):
            rate = _measure(
                lambda mode=mode: fused.fused_sma_sweep(
                    panel.close, sfa, ssl, cost=1e-3, table=mode),
                n_bt, iters=iters, warmup=warmup,
                name=f"sma_table_{mode}")
            stage_times[f"table_{mode}"] = n_bt / rate
        attribution["inline_table_speedup"] = round(
            stage_times["table_hbm"] / stage_times["table_inline"], 3)
        for mode in ("ladder", "scan"):
            rate = _measure(
                lambda mode=mode: fused.fused_sma_sweep(
                    panel.close, sfa, ssl, cost=1e-3, epilogue=mode),
                n_bt, iters=iters, warmup=warmup,
                name=f"sma_epilogue_{mode}")
            stage_times[f"epilogue_{mode}"] = n_bt / rate
        attribution["epilogue_e2e_speedup"] = round(
            stage_times["epilogue_ladder"] / stage_times["epilogue_scan"],
            3)
        ROOFLINE["sma_stages"] = {
            **{f"{k}_s_per_sweep": round(v, 6)
               for k, v in stage_times.items()},
            **attribution}
        rates["roofline_stages_full"] = n_bt / full_s
        print(f"bench[roofline_stages]: attribution {attribution}",
              file=sys.stderr)

        # --- bollinger stages: the band-machine twin of the SMA scaffold.
        # Same cut-down discipline over the EXACT hbm-table bollinger
        # kernel (z-table prep shared with _fused_boll_call): attributes
        # the selection matmul, the 3-state compose machine (scan vs
        # ladder substrate), and the metrics-tail ladders for the family
        # whose vpu_ops_per_cell_bar sat at 179 vs the sign kernels' 76.
        n_win, n_k = 20, max(min(n_params, 1000) // 20, 1)
        rgrid = sweep.product_grid(
            k=jnp.linspace(0.5, 3.0, n_k).astype(jnp.float32),
            window=jnp.arange(10, 10 + 2 * n_win, 2, dtype=jnp.float32))
        rw = np.asarray(rgrid["window"])
        rk = np.asarray(rgrid["k"])
        bwindows, b_onehot, b_klanes, b_warm = F._boll_grid_setup(
            rw.astype(np.float32).tobytes(), rk.tobytes())
        bT_pad = F._round_up(n_bars, 128)
        bW_pad = b_onehot.shape[0]
        bP_real = rw.shape[0]
        bP_pad = b_onehot.shape[1]

        def boll_stage_kernel(r_ref, z_ref, ow_ref, k_ref, warm_ref,
                              out_ref, *, stage, lanes):
            # Mirrors ops.fused._boll_kernel through the requested stage
            # (same scaffolding contract as stage_kernel above).
            T_pd = r_ref.shape[1]
            r = r_ref[0]
            zt = z_ref[0]                    # (W_pad, T_pad) z-table
            if stage == "touch":
                out_ref[0, 0] = jnp.full(
                    (F._METRIC_ROWS, lanes), jnp.sum(zt), jnp.float32)
                return
            z = jax.lax.dot_general(
                zt, ow_ref[:], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            t_idx = jax.lax.broadcasted_iota(jnp.int32, (T_pd, lanes), 0)
            if stage == "matmul":
                out_ref[0, 0] = jnp.broadcast_to(
                    jnp.sum(z, axis=0)[None, :], (F._METRIC_ROWS, lanes))
                return
            warm_v = warm_ref[0, :][None, :]
            valid = t_idx >= (warm_v.astype(jnp.int32) - 1)
            k_l = k_ref[0, :][None, :]
            epi = "ladder" if stage.endswith("_ladder") else "scan"
            pos = F._band_ladder(z, valid, k_l, 0.0, epi)
            if stage in ("signal", "signal_ladder"):
                # + the 3-state compose machine (the band family's extra
                # cost vs sign kernels), in the requested substrate.
                out_ref[0, 0] = jnp.broadcast_to(
                    jnp.sum(pos * r, axis=0)[None, :],
                    (F._METRIC_ROWS, lanes))
                return
            tr = n_bars
            if stage in ("full", "full_ladder"):
                out_ref[0, 0] = F._metrics_tail(pos, r, t_idx, tr,
                                                cost=1e-3, ppy=252,
                                                epilogue=epi)
                return
            # no_ladders: compose machine (scan) + the one-pass reduction
            # stand-ins of the SMA scaffold.
            row_ok = t_idx < tr
            pos_last = F._row_at(pos, tr, t_idx, keepdims=True)
            pos = jnp.where(row_ok, pos, pos_last)
            prev = F._shift_down(pos, 1, 0.0)
            net = prev * r - 1e-3 * jnp.abs(pos - prev)
            n_f = jnp.asarray(tr, jnp.float32)
            s1 = jnp.sum(net, axis=0)
            s2 = jnp.sum(net * net, axis=0)
            meanv = s1 / n_f
            std = jnp.sqrt(jnp.maximum(s2 / n_f - meanv * meanv, 0.0))
            turnover = jnp.sum(jnp.abs(pos - prev), axis=0)
            rows = jnp.stack([s1, s2, meanv, std, std, s1,
                              turnover, std, s1], axis=0)
            out_ref[0, 0] = jnp.concatenate(
                [rows, jnp.zeros((F._METRIC_ROWS - 9, lanes),
                                 jnp.float32)], axis=0)

        @functools.partial(jax.jit, static_argnames=("stage", "lanes"))
        def boll_stage_call(close, *, stage, lanes=128):
            # THE shipped hbm z-table prep (_fused_boll_call's op order,
            # via the shared cumsum-window closures).
            close_p = F._pad_last(close, bT_pad)
            T = close.shape[1]
            xc = close_p - jnp.mean(close_p[:, :T], axis=1, keepdims=True)
            w_col, w_f, t_row, windowed_sum, _ = F._cumsum_window_tools(
                bwindows, bT_pad)
            m = windowed_sum(close_p) / w_f
            s1 = windowed_sum(xc)
            s2 = windowed_sum(xc * xc)
            var = jnp.maximum((s2 - s1 * s1 / w_f) / w_f, 0.0)
            z_tbl = (close_p[:, None, :] - m) / (jnp.sqrt(var) + 1e-12)
            z_tbl = F._pad_w(
                jnp.where((t_row >= w_col - 1)[None], z_tbl, 0.0), bW_pad)
            r3 = F._rets3(close_p)
            if stage == "prep":
                return jnp.broadcast_to(
                    jnp.sum(z_tbl, axis=(1, 2))[:, None] + r3[:, 0, :],
                    (close.shape[0], bP_pad))[:, :bP_real]
            nb = bP_pad // lanes
            out = pl.pallas_call(
                functools.partial(boll_stage_kernel, stage=stage,
                                  lanes=lanes),
                grid=(close.shape[0], nb),
                in_specs=[
                    pl.BlockSpec((1, bT_pad, 1), lambda i, j: (i, 0, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, bW_pad, bT_pad),
                                 lambda i, j: (i, 0, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((bW_pad, lanes), lambda i, j: (0, j),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, lanes), lambda i, j: (0, j),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, lanes), lambda i, j: (0, j),
                                 memory_space=pltpu.VMEM),
                ],
                out_specs=pl.BlockSpec(
                    (1, 1, F._METRIC_ROWS, lanes),
                    lambda i, j: (i, j, 0, 0), memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct(
                    (close.shape[0], nb, F._METRIC_ROWS, lanes),
                    jnp.float32),
                interpret=interp,
            )(r3, z_tbl, F._const(b_onehot), F._const(b_klanes),
              F._const(b_warm))
            return jnp.reshape(out[:, :, 0, :],
                               (close.shape[0], bP_pad))[:, :bP_real]

        boll_times = {}
        b_bt = n_tickers * bP_real
        for stage in ("prep", "touch", "matmul", "signal", "signal_ladder",
                      "no_ladders", "full", "full_ladder"):
            def run_bstage(stage=stage):
                from types import SimpleNamespace
                return SimpleNamespace(
                    sharpe=boll_stage_call(panel.close, stage=stage))
            rate = _measure(run_bstage, b_bt, iters=iters, warmup=warmup,
                            name=f"boll_stage_{stage}_l128")
            boll_times[f"{stage}_l128"] = b_bt / rate
        boll_attr = _attribution(boll_times)
        boll_attr["compose_delta_pct"] = round(
            100 * (boll_times["signal_l128"] - boll_times["matmul_l128"])
            / boll_times["full_l128"], 1)
        boll_attr["compose_ladder_delta_pct"] = round(
            100 * (boll_times["signal_ladder_l128"]
                   - boll_times["matmul_l128"])
            / boll_times["full_l128"], 1)
        for mode in ("ladder", "scan"):
            rate = _measure(
                lambda mode=mode: fused.fused_bollinger_sweep(
                    panel.close, rw, rk, cost=1e-3, epilogue=mode),
                b_bt, iters=iters, warmup=warmup,
                name=f"boll_epilogue_{mode}")
            boll_times[f"epilogue_{mode}"] = b_bt / rate
        boll_attr["epilogue_e2e_speedup"] = round(
            boll_times["epilogue_ladder"] / boll_times["epilogue_scan"], 3)
        ROOFLINE["bollinger_stages"] = {
            **{f"{k}_s_per_sweep": round(v, 6)
               for k, v in boll_times.items()},
            **boll_attr}
        rates["roofline_stages_boll_full"] = b_bt / boll_times["full_l128"]
        print(f"bench[roofline_stages/bollinger]: attribution {boll_attr}",
              file=sys.stderr)

    # --- configs[2]: fused Bollinger (window, k) --------------------------
    if enabled("bollinger_fused"):
        n_win, n_k = 20, max(min(n_params, 1000) // 20, 1)
        bgrid = sweep.product_grid(
            k=jnp.linspace(0.5, 3.0, n_k).astype(jnp.float32),
            window=jnp.arange(10, 10 + 2 * n_win, 2, dtype=jnp.float32))
        bw = np.asarray(bgrid["window"])
        bk = np.asarray(bgrid["k"])

        def run_boll():
            return fused.fused_bollinger_sweep(panel.close, bw, bk, cost=1e-3)

        rates["bollinger_fused"] = _measure(
            run_boll, n_tickers * sweep.grid_size(bgrid), iters=iters,
            warmup=warmup, name="bollinger_fused", n_bars=n_bars,
            model=_model(TAIL + LADDER3 + 10, np.unique(bw).size, bw.size))

    if enabled("bollinger_touch_fused"):
        n_win, n_k = 20, max(min(n_params, 1000) // 20, 1)
        tgrid = sweep.product_grid(
            k=jnp.linspace(0.5, 3.0, n_k).astype(jnp.float32),
            window=jnp.arange(10, 10 + 2 * n_win, 2, dtype=jnp.float32))
        tw = np.asarray(tgrid["window"])
        tk = np.asarray(tgrid["k"])

        def run_touch():
            return fused.fused_bollinger_touch_sweep(panel.close, tw, tk,
                                                     cost=1e-3)

        rates["bollinger_touch_fused"] = _measure(
            run_touch, n_tickers * sweep.grid_size(tgrid), iters=iters,
            warmup=warmup, name="bollinger_touch_fused", n_bars=n_bars,
            model=_model(TAIL + 8, np.unique(tw).size, tw.size))

    # --- momentum / donchian: the round-3 single-window-axis kernels ------
    if enabled("momentum_fused"):
        mlbs = np.tile(np.arange(5, 130, dtype=np.float32),
                       max(n_params // 125, 1))

        def run_mom():
            return fused.fused_momentum_sweep(panel.close, mlbs, cost=1e-3)

        # Default substrate is the in-kernel past-close table (VMEM
        # scratch, `_mom_kernel_inline`; measured +4% median / +8% best
        # over the XLA-gather table on this grid): no table HBM stream.
        mom_inline = os.environ.get("DBX_MOM_TABLE", "inline") == "inline"
        mom_model = _model(TAIL + 4, np.unique(mlbs).size, mlbs.size,
                           prep_passes=0 if mom_inline else 2)
        if mom_inline:
            mom_p_pad = -(-mlbs.size // 128) * 128
            # 3 streamed rows per ticker: returns column, close column
            # (the tail's `close - past`), and the close-row aux the
            # builder rotates (SMA streams only cs + returns = 2).
            mom_model["hbm"] = 4.0 * 3 / mom_p_pad
            mom_model["vpu"] += 4.0 * np.unique(mlbs).size * 8 / mom_p_pad
        rates["momentum_fused"] = _measure(
            run_mom, n_tickers * len(mlbs), iters=iters, warmup=warmup,
            name="momentum_fused", n_bars=n_bars,
            model=mom_model)

    if enabled("donchian_fused"):
        dwins = np.tile(np.arange(10, 135, dtype=np.float32),
                        max(min(n_params, 1000) // 125, 1))

        def run_don():
            return fused.fused_donchian_sweep(panel.close, dwins, cost=1e-3)

        rates["donchian_fused"] = _measure(
            run_don, n_tickers * len(dwins), iters=iters, warmup=warmup,
            name="donchian_fused", n_bars=n_bars,
            model=_model(TAIL + LADDER3 + 10, np.unique(dwins).size,
                         dwins.size))

    if enabled("donchian_hl_fused"):
        hwins = np.tile(np.arange(10, 135, dtype=np.float32),
                        max(min(n_params, 1000) // 125, 1))

        def run_don_hl():
            return fused.fused_donchian_hl_sweep(
                panel.close, panel.high, panel.low, hwins, cost=1e-3)

        rates["donchian_hl_fused"] = _measure(
            run_don_hl, n_tickers * len(hwins), iters=iters, warmup=warmup,
            name="donchian_hl_fused", n_bars=n_bars,
            model=_model(TAIL + LADDER3 + 10, np.unique(hwins).size,
                         hwins.size, prep_passes=4))

    # --- vwap: the volume-consuming band-machine kernel -------------------
    if enabled("vwap_fused"):
        n_win, n_k = 20, max(min(n_params, 1000) // 20, 1)
        vgrid = sweep.product_grid(
            k=jnp.linspace(0.5, 3.0, n_k).astype(jnp.float32),
            window=jnp.arange(10, 10 + 2 * n_win, 2, dtype=jnp.float32))
        vw = np.asarray(vgrid["window"])
        vk = np.asarray(vgrid["k"])

        def run_vwap():
            return fused.fused_vwap_sweep(panel.close, panel.volume, vw, vk,
                                          cost=1e-3)

        rates["vwap_fused"] = _measure(
            run_vwap, n_tickers * sweep.grid_size(vgrid), iters=iters,
            warmup=warmup, name="vwap_fused", n_bars=n_bars,
            model=_model(TAIL + LADDER3 + 10, np.unique(vw).size, vw.size,
                         prep_passes=4))

    if enabled("keltner_fused"):
        kgrid = sweep.product_grid(
            k=jnp.linspace(1.0, 3.0, max(min(n_params, 1000) // 25, 1)
                           ).astype(jnp.float32),
            window=jnp.arange(5, 55, 2, dtype=jnp.float32))
        kw = np.asarray(kgrid["window"])
        kk = np.asarray(kgrid["k"])

        def run_kelt():
            return fused.fused_keltner_sweep(
                panel.close, panel.high, panel.low, kw, kk, cost=1e-3)

        rates["keltner_fused"] = _measure(
            run_kelt, n_tickers * sweep.grid_size(kgrid), iters=iters,
            warmup=warmup, name="keltner_fused", n_bars=n_bars,
            model=_model(TAIL + LADDER3 + 10, np.unique(kw).size, kw.size,
                         prep_passes=4))

    if enabled("stochastic_fused"):
        sgrid = sweep.product_grid(
            band=jnp.linspace(10, 40, max(min(n_params, 1000) // 125, 1)
                              ).astype(jnp.float32),
            window=jnp.arange(5, 130, dtype=jnp.float32))
        sw = np.asarray(sgrid["window"])
        sb = np.asarray(sgrid["band"])

        def run_stoch():
            return fused.fused_stochastic_sweep(
                panel.close, panel.high, panel.low, sw, sb, cost=1e-3)

        rates["stochastic_fused"] = _measure(
            run_stoch, n_tickers * sweep.grid_size(sgrid), iters=iters,
            warmup=warmup, name="stochastic_fused", n_bars=n_bars,
            model=_model(TAIL + LADDER3 + 12, np.unique(sw).size, sw.size,
                         prep_passes=4))

    # --- rsi / macd: the EMA-family fused kernels -------------------------
    if enabled("rsi_fused"):
        # 25 distinct periods (not 50): each distinct period unrolls an
        # associative EMA scan in the prep, and XLA compile time scales with
        # the count.
        rp = np.tile(np.arange(5, 55, 2, dtype=np.float32),
                     max(min(n_params, 1000) // 25, 1))
        rb = np.repeat(np.linspace(10, 30, max(min(n_params, 1000) // 25, 1)
                                   ).astype(np.float32), 25)

        def run_rsi():
            return fused.fused_rsi_sweep(panel.close, rp, rb, cost=1e-3)

        rates["rsi_fused"] = _measure(
            run_rsi, n_tickers * len(rp), iters=iters, warmup=warmup,
            name="rsi_fused", n_bars=n_bars,
            model=_model(TAIL + LADDER3 + 10, np.unique(rp).size, rp.size,
                         prep_passes=4))

    if enabled("macd_fused"):
        mf = np.repeat(np.arange(5, 15, dtype=np.float32), 100)
        ms = np.tile(np.repeat(np.arange(20, 60, 4, dtype=np.float32), 10),
                     10)
        mg = np.tile(np.arange(5, 15, dtype=np.float32), 100)

        def run_macd():
            return fused.fused_macd_sweep(panel.close, mf, ms, mg, cost=1e-3)

        rates["macd_fused"] = _measure(
            run_macd, n_tickers * len(mf), iters=iters, warmup=warmup,
            name="macd_fused", n_bars=n_bars,
            model=_model(TAIL + 5 * rounds + 5,
                         np.unique(np.r_[mf, ms]).size, mf.size,
                         prep_passes=4))

    if enabled("trix_fused"):
        # 10 distinct spans x 100 signal lanes; each distinct span chains
        # THREE EMA ladders in the prep (triple smoothing), hence the
        # heavier prep_passes.
        tsp = np.repeat(np.arange(5, 15, dtype=np.float32), 100)
        tsg = np.tile(np.arange(3, 13, dtype=np.float32), 100)

        def run_trix():
            return fused.fused_trix_sweep(panel.close, tsp, tsg, cost=1e-3)

        rates["trix_fused"] = _measure(
            run_trix, n_tickers * len(tsp), iters=iters, warmup=warmup,
            name="trix_fused", n_bars=n_bars,
            model=_model(TAIL + 5 * rounds + 7, np.unique(tsp).size,
                         tsp.size, prep_passes=10))

    if enabled("obv_fused"):
        ow = np.tile(np.arange(5, 130, dtype=np.float32),
                     max(n_params // 125, 1))

        def run_obv():
            return fused.fused_obv_sweep(panel.close, panel.volume, ow,
                                         cost=1e-3)

        # Default substrate is the in-kernel SMA-of-OBV table (VMEM
        # scratch, `_obv_kernel_inline`; measured 23.9 -> 25.3 M/s over
        # the W-major XLA table): the obv/returns/cs rows are the only
        # HBM streams, the VPU term gains the amortized table build.
        obv_inline = os.environ.get("DBX_OBV_TABLE", "inline") == "inline"
        obv_model = _model(TAIL + 8, np.unique(ow).size, ow.size,
                           prep_passes=0 if obv_inline else 2)
        if obv_inline:
            obv_p_pad = -(-ow.size // 128) * 128
            obv_model["hbm"] = 4.0 * 3 / obv_p_pad
            obv_model["vpu"] += 4.0 * np.unique(ow).size * 8 / obv_p_pad
        rates["obv_fused"] = _measure(
            run_obv, n_tickers * len(ow), iters=iters, warmup=warmup,
            name="obv_fused", n_bars=n_bars,
            model=obv_model)

    # --- configs[3]: rolling-OLS pairs (lookback, z_entry) ----------------
    if enabled("pairs"):
        n_pairs = min(2 * n_tickers, 1000)
        pair_data = data.synthetic_ohlcv(2 * n_pairs, n_bars, seed=1)
        closes = jax.device_put(jnp.asarray(pair_data.close), dev)
        y_close, x_close = closes[:n_pairs], closes[n_pairs:]
        pgrid = sweep.product_grid(
            lookback=jnp.arange(20, 70, 5, dtype=jnp.float32),
            z_entry=jnp.linspace(0.5, 3.0, 50).astype(jnp.float32))
        plb = np.asarray(pgrid["lookback"])
        pze = np.asarray(pgrid["z_entry"])

        if os.environ.get("DBX_BENCH_GENERIC") == "1":
            def run_pairs():
                return pairs.chunked_pairs_sweep(
                    y_close, x_close, pgrid, param_chunk=50, cost=1e-3)
        else:
            def run_pairs():
                return fused.fused_pairs_sweep(
                    y_close, x_close, plb, pze, cost=1e-3)

        rates["pairs"] = _measure(
            run_pairs, n_pairs * sweep.grid_size(pgrid),
            iters=max(iters // 2, 3), warmup=max(warmup // 3, 2),
            name="pairs", n_bars=n_bars,
            model=_model(TAIL + LADDER3 + 15, np.unique(plb).size,
                         plb.size, selections=2, prep_passes=8))

    # --- e2e: backtests/sec THROUGH the gRPC dispatch loop ----------------
    # The reference's one perf fact is jobs/sec through its full loop
    # (1 job/sec/worker: its compute slot sleeps 1 s per job, reference
    # src/worker/process.rs:23). This config measures the same thing
    # honestly for this framework: dispatcher + worker over loopback gRPC,
    # inline DBX1 payloads, decode + RPC + metric pack-and-report included.
    def run_e2e(name, *, top_k=0):
        import tempfile
        import threading

        from distributed_backtesting_exploration_tpu.rpc.compute import (
            JaxSweepBackend)
        from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
            Dispatcher, DispatcherServer, JobQueue, PeerRegistry,
            synthetic_jobs)
        from distributed_backtesting_exploration_tpu.rpc.worker import Worker

        e2e_iters = max(iters // 3, 2)
        n_jobs = n_tickers
        e2e_grid = {
            "fast": np.arange(5, 25, dtype=np.float32),
            "slow": np.arange(30, 30 + 2 * max(n_params // 20, 1), 2,
                              dtype=np.float32)}
        combos = int(np.prod([v.size for v in e2e_grid.values()]))
        topk_kw = (dict(top_k=top_k, rank_metric="sharpe") if top_k
                   else {})

        queue = JobQueue()
        with tempfile.TemporaryDirectory() as results_dir:
            disp = Dispatcher(queue, PeerRegistry(prune_window_s=30.0),
                              results_dir=results_dir)
            srv = DispatcherServer(disp, bind="localhost:0",
                                   prune_interval_s=0.5).start()
            worker = Worker(f"localhost:{srv.port}", JaxSweepBackend(),
                            poll_interval_s=0.005, status_interval_s=0.5,
                            jobs_per_chip=100)
            wt = threading.Thread(target=worker.run, daemon=True)

            def drain(seed):
                for rec in synthetic_jobs(n_jobs, n_bars, "sma_crossover",
                                          e2e_grid, cost=1e-3, seed=seed,
                                          **topk_kw):
                    queue.enqueue(rec)
                deadline = time.monotonic() + 600.0
                while not queue.drained:
                    if time.monotonic() > deadline:
                        sys.exit(f"bench[{name}]: drain wedged for 600s — "
                                 "backend failing every batch? "
                                 f"stats={queue.stats()}")
                    time.sleep(0.002)

            try:
                wt.start()
                t0 = time.perf_counter()
                drain(seed=100)          # compile + pipeline warm-up
                compile_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                for i in range(e2e_iters):
                    drain(seed=101 + i)
                elapsed = time.perf_counter() - t0
            finally:
                worker.stop()
                wt.join(timeout=30)
                srv.stop()
            rate = n_jobs * combos * e2e_iters / elapsed
            print(f"bench[{name}]: warmup {compile_s:.1f}s, {e2e_iters}x "
                  f"{n_jobs * combos} backtests through the dispatch loop "
                  f"in {elapsed:.3f}s -> {rate/1e6:.2f}M/s "
                  f"({worker.jobs_completed} jobs)", file=sys.stderr)
            rates[name] = rate

    if enabled("e2e"):
        run_e2e("e2e")
    # Same loop with on-device top-k reduction (JobSpec.top_k): workers
    # ship 16 rows instead of the full per-combo matrix, taking the d2h
    # result transfer and the completion leg off the critical path.
    if enabled("e2e_topk"):
        run_e2e("e2e_topk", top_k=16)

    # --- e2e_local: control-plane saturation (no TPU) ----------------------
    # `e2e` above includes the device; this config measures the DISPATCHER's own ceiling: N workers with an instant
    # compute backend drain a queue of small inline jobs over loopback
    # gRPC, so every second is framework control plane — RPC serving under
    # the GIL, queue state transitions (native core), completion batching.
    # Reported as JOBS/s per worker count; the 1->2->4 scaling curve (or
    # its absence) localizes the saturation point (DESIGN.md "Control-plane
    # ceiling"). The reference's one perf fact is jobs/s through its loop.
    def _worker_wire_bytes():
        """Sum of the workers' serialized request/reply proto bytes (the
        dbx_worker_wire_bytes_total counters, shared registry) — the
        instrument behind every wire_bytes_per_job column."""
        from distributed_backtesting_exploration_tpu import obs as obs_mod

        reg = obs_mod.get_registry()
        return sum(
            reg.counter("dbx_worker_wire_bytes_total",
                        method=m, direction=d).value
            for m in ("RequestJobs", "CompleteJobs", "FetchPayload")
            for d in ("request", "reply"))

    def run_e2e_local(n_workers, n_jobs, *, job_recs=None, dedupe=True,
                      name=None):
        """The loopback control-plane drain. ``job_recs`` (a factory
        seed -> record list) overrides the default distinct-panel
        synthetic workload — the dedupe A/B passes a shared-panel
        factory; ``dedupe`` toggles dispatch-by-digest on the
        dispatcher. Returns (jobs/s, wire bytes/job)."""
        import tempfile
        import threading

        from distributed_backtesting_exploration_tpu.rpc.compute import (
            InstantBackend)
        from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
            Dispatcher, DispatcherServer, JobQueue, PeerRegistry,
            synthetic_jobs)
        from distributed_backtesting_exploration_tpu.rpc.worker import Worker

        lgrid = {"fast": np.arange(5.0, 9.0, dtype=np.float32)}
        if job_recs is None:
            def job_recs(n, seed):
                return synthetic_jobs(n, 32, "sma_crossover", lgrid,
                                      seed=seed)
        name = name or f"e2e_local_w{n_workers}"
        queue = JobQueue()
        with tempfile.TemporaryDirectory() as results_dir:
            disp = Dispatcher(queue, PeerRegistry(prune_window_s=30.0),
                              results_dir=results_dir, panel_dedupe=dedupe)
            srv = DispatcherServer(disp, bind="localhost:0",
                                   prune_interval_s=0.5).start()
            workers = [Worker(f"localhost:{srv.port}", InstantBackend(),
                              worker_id=f"local-{i}",
                              poll_interval_s=0.001, status_interval_s=0.5,
                              jobs_per_chip=32)
                       for i in range(n_workers)]
            threads = [threading.Thread(target=w.run, daemon=True)
                       for w in workers]

            def drain(n, seed):
                for rec in job_recs(n, seed):
                    queue.enqueue(rec)
                deadline = time.monotonic() + 300.0
                while not queue.drained:
                    if time.monotonic() > deadline:
                        sys.exit(f"bench[e2e_local]: drain wedged for 300s "
                                 f"— stats={queue.stats()}")
                    time.sleep(0.002)

            try:
                for t in threads:
                    t.start()
                drain(max(n_jobs // 4, 64), seed=300)   # channel warm-up
                wire0 = _worker_wire_bytes()
                t0 = time.perf_counter()
                drain(n_jobs, seed=301)
                elapsed = time.perf_counter() - t0
                wire_per_job = (_worker_wire_bytes() - wire0) / n_jobs
            finally:
                for w in workers:
                    w.stop()
                for t in threads:
                    t.join(timeout=30)
                srv.stop()
        rate = n_jobs / elapsed
        print(f"bench[{name}]: {n_jobs} instant jobs, "
              f"{n_workers} worker(s), substrate={queue.substrate}, "
              f"dedupe={'on' if dedupe else 'off'} -> {rate:.0f} jobs/s, "
              f"{wire_per_job:.0f} wire B/job", file=sys.stderr)
        rates[name] = rate
        return rate, wire_per_job

    if enabled("e2e_local"):
        n_local_jobs = int(os.environ.get("DBX_BENCH_LOCAL_JOBS", 1500))
        wcounts = tuple(int(x) for x in os.environ.get(
            "DBX_BENCH_LOCAL_WORKERS", "1,2,4").split(","))
        wire_cols = {}
        for n_workers in wcounts:
            _, wb = run_e2e_local(n_workers, n_local_jobs)
            wire_cols[f"w{n_workers}"] = round(wb, 1)
        # Dispatch-by-digest A/B on the workload the feature exists for:
        # many jobs sharing ONE panel (a grid sweep re-ships the same
        # OHLC bytes in every job). Dedupe-on ships the panel once per
        # worker and digest-only afterwards — the jobs/s delta is exactly
        # the per-job payload marshalling the control-plane ceiling
        # measured as its floor.
        from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
            JobRecord)
        from distributed_backtesting_exploration_tpu.utils import (
            data as dd_data)

        dd_bars = int(os.environ.get("DBX_BENCH_DEDUPE_BARS", 4096))
        dd_jobs_n = max(n_local_jobs // 2, 48)
        dd_series = dd_data.synthetic_ohlcv(1, dd_bars, seed=500)
        dd_blob = dd_data.to_wire_bytes(
            type(dd_series)(*(np.asarray(f[0]) for f in dd_series)))
        dd_grid = {"fast": np.arange(5.0, 9.0, dtype=np.float32)}

        def dd_recs(n, seed):
            return [JobRecord(id=f"dd-{seed}-{i}",
                              strategy="sma_crossover", grid=dd_grid,
                              ohlcv=dd_blob) for i in range(n)]

        r_on, wb_on = run_e2e_local(1, dd_jobs_n, job_recs=dd_recs,
                                    dedupe=True,
                                    name="e2e_local_dedupe_on")
        r_off, wb_off = run_e2e_local(1, dd_jobs_n, job_recs=dd_recs,
                                      dedupe=False,
                                      name="e2e_local_dedupe_off")
        ROOFLINE["e2e_local"] = {
            "wire_bytes_per_job": wire_cols,
            "dedupe": {
                "panel_bytes": len(dd_blob),
                "jobs": dd_jobs_n,
                "jobs_per_s_on": round(r_on, 1),
                "jobs_per_s_off": round(r_off, 1),
                "dedupe_speedup": round(r_on / max(r_off, 1e-9), 3),
                "wire_bytes_per_job_on": round(wb_on, 1),
                "wire_bytes_per_job_off": round(wb_off, 1),
                "wire_reduction": round(wb_off / max(wb_on, 1e-9), 1)}}

    # --- direct_dispatch: the dispatcher-attributable ceiling -------------
    # e2e_local_w* runs dispatcher AND workers as threads of ONE Python
    # process on this 1-core box, so its flat w1->w4 curve measures the
    # shared GIL/core, not dispatcher scaling (VERDICT r4 weak #5). This
    # instrument removes the worker loop entirely: a bare client cycle
    # (RequestJobs -> CompleteJobs) against the served dispatcher, so every
    # second is gRPC serving + queue state machine + per-job marshalling —
    # DESIGN.md "Control-plane ceiling"'s direct-dispatch rows, recorded in
    # BENCH JSON instead of prose.
    def run_direct_dispatch(batch, n_jobs):
        import tempfile

        import grpc

        from distributed_backtesting_exploration_tpu.rpc import (
            backtesting_pb2 as pb, service)
        from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
            Dispatcher, DispatcherServer, JobQueue, PeerRegistry,
            synthetic_jobs)

        lgrid = {"fast": np.arange(5.0, 9.0, dtype=np.float32)}
        queue = JobQueue()
        with tempfile.TemporaryDirectory() as results_dir:
            disp = Dispatcher(queue, PeerRegistry(prune_window_s=30.0),
                              results_dir=results_dir)
            srv = DispatcherServer(disp, bind="localhost:0",
                                   prune_interval_s=5.0).start()
            channel = grpc.insecure_channel(
                f"localhost:{srv.port}",
                options=service.default_channel_options(),
                compression=grpc.Compression.Gzip)
            stub = service.DispatcherStub(channel)

            def cycle(n, seed):
                for rec in synthetic_jobs(n, 32, "sma_crossover", lgrid,
                                          seed=seed):
                    queue.enqueue(rec)
                done = 0
                wire = 0
                while done < n:
                    req = pb.JobsRequest(
                        worker_id="direct", chips=1, jobs_per_chip=batch)
                    reply = stub.RequestJobs(req)
                    if not reply.jobs:
                        break
                    wire += req.ByteSize() + reply.ByteSize()
                    creq = pb.CompleteBatch(
                        worker_id="direct",
                        items=[pb.CompleteItem(id=j.id, metrics=b"",
                                               elapsed_s=0.0)
                               for j in reply.jobs])
                    crep = stub.CompleteJobs(creq)
                    wire += creq.ByteSize() + crep.ByteSize()
                    done += len(reply.jobs)
                return done, wire

            try:
                cycle(max(n_jobs // 4, 64), seed=400)   # warm the channel
                t0 = time.perf_counter()
                done, wire = cycle(n_jobs, seed=401)
                elapsed = time.perf_counter() - t0
            finally:
                channel.close()
                srv.stop()
        rate = done / elapsed
        wire_per_job = wire / max(done, 1)
        name = f"direct_dispatch_b{batch}"
        print(f"bench[{name}]: {done} inline jobs, bare client cycle, "
              f"batch {batch}, substrate={queue.substrate} -> "
              f"{rate:.0f} jobs/s, {wire_per_job:.0f} wire B/job",
              file=sys.stderr)
        rates[name] = rate
        return rate, wire_per_job

    if enabled("direct_dispatch"):
        dd_jobs = int(os.environ.get("DBX_BENCH_LOCAL_JOBS", 1500))
        r32, wb32 = run_direct_dispatch(32, dd_jobs)
        _, wb128 = run_direct_dispatch(128, dd_jobs)
        # Regression floor: DESIGN.md measured ~5.9k jobs/s at batch 32 on
        # this 1-core box; 2k leaves 3x headroom for a loaded machine
        # while still catching an order-of-magnitude regression.
        if r32 < 2000:
            print(f"bench[direct_dispatch]: WARNING batch-32 ceiling "
                  f"{r32:.0f} jobs/s is below the 2k regression floor "
                  "(DESIGN.md measured ~5.9k)", file=sys.stderr)
        ROOFLINE["direct_dispatch_floor"] = {
            "batch32_jobs_per_s": round(r32, 1), "floor": 2000,
            "floor_ok": bool(r32 >= 2000),
            "wire_bytes_per_job": {"b32": round(wb32, 1),
                                   "b128": round(wb128, 1)}}

        # Lockdep A/B: the same batch-32 cycle with the runtime lock
        # sanitizer (analysis.lockdep) instrumenting every package lock
        # — its overhead is a tracked number, and the shim must hold
        # the same 2k floor so DBX_LOCKDEP=1 is viable on live fleets.
        # Queue/dispatcher are constructed INSIDE run_direct_dispatch,
        # after install, so the hot-path locks are really wrapped.
        from distributed_backtesting_exploration_tpu.analysis import (
            lockdep)

        # Restore the PRIOR state afterwards: an in-process caller (the
        # roofline test fixture, a DBX_LOCKDEP=1 harness run) must keep
        # its shim AND its accumulated tables — a pre-existing violation
        # must survive the bench, so an already-active harness is never
        # reset; this block then reports the run's DELTA. (Under an
        # already-active shim the "off" baseline above was itself
        # instrumented, so overhead_pct reads ~0 there — the tracked
        # number comes from the normal uninstrumented bench run.)
        was_active = lockdep.active()
        if was_active:
            base = lockdep.report()
            base_edges, base_viol = base["edges"], len(base["violations"])
        else:
            lockdep.install()
            lockdep.reset()
            base_edges = base_viol = 0
        try:
            r32_ld, _ = run_direct_dispatch(32, dd_jobs)
            ld = lockdep.report()
        finally:
            if not was_active:
                lockdep.uninstall()
        edges = ld["edges"] - base_edges
        violations = len(ld["violations"]) - base_viol
        print(f"bench[direct_dispatch]: lockdep on -> {r32_ld:.0f} jobs/s "
              f"({(r32 - r32_ld) / max(r32, 1e-9) * 100:+.1f}% vs off), "
              f"{edges} edges, {violations} violations",
              file=sys.stderr)
        ROOFLINE["direct_dispatch_floor"]["lockdep"] = {
            "batch32_jobs_per_s": round(r32_ld, 1),
            "overhead_pct": round((r32 - r32_ld) / max(r32, 1e-9) * 100,
                                  1),
            "floor_ok": bool(r32_ld >= 2000),
            "edges": edges,
            "violations": violations}

    # --- fleet_telemetry: gossip overhead + staleness (round 15) ----------
    # Two instruments. (a) The direct_dispatch floor re-measured with a
    # telemetry frame built and attached per poll (obs/fleet.py
    # WorkerTelemetry -> JobsRequest.telemetry_json -> FleetView merge):
    # the frame build + dispatcher merge are the ONLY delta vs the off
    # arm, so the jobs/s gap IS the gossip's control-plane cost — the
    # acceptance bar says <= 5% with the 2k floor holding. (b) A tiny
    # real-worker loopback fleet (instant backend) drained while the
    # FleetView is sampled: every live worker must appear in /fleet.json
    # with frame staleness within 2 poll periods (fleet_staleness_p95_s).
    def run_fleet_direct(batch, n_jobs, telemetry):
        import tempfile

        import grpc

        from distributed_backtesting_exploration_tpu.obs import (
            fleet as fleet_mod)
        from distributed_backtesting_exploration_tpu.rpc import (
            backtesting_pb2 as pb, service)
        from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
            Dispatcher, DispatcherServer, JobQueue, PeerRegistry,
            synthetic_jobs)

        lgrid = {"fast": np.arange(5.0, 9.0, dtype=np.float32)}
        queue = JobQueue()
        counters = {"jobs": 0}
        telem = None
        if telemetry:
            telem = fleet_mod.WorkerTelemetry(
                "direct", stats_fn=lambda: {
                    "jobs_completed": counters["jobs"], "busy": 1})
        with tempfile.TemporaryDirectory() as results_dir:
            disp = Dispatcher(queue, PeerRegistry(prune_window_s=30.0),
                              results_dir=results_dir)
            srv = DispatcherServer(disp, bind="localhost:0",
                                   prune_interval_s=5.0).start()
            channel = grpc.insecure_channel(
                f"localhost:{srv.port}",
                options=service.default_channel_options(),
                compression=grpc.Compression.Gzip)
            stub = service.DispatcherStub(channel)

            def cycle(n, seed):
                for rec in synthetic_jobs(n, 32, "sma_crossover", lgrid,
                                          seed=seed):
                    queue.enqueue(rec)
                done = 0
                while done < n:
                    req = pb.JobsRequest(
                        worker_id="direct", chips=1, jobs_per_chip=batch,
                        telemetry_json=(telem.take_frame_json()
                                        if telem is not None else ""))
                    reply = stub.RequestJobs(req)
                    if not reply.jobs:
                        break
                    stub.CompleteJobs(pb.CompleteBatch(
                        worker_id="direct",
                        items=[pb.CompleteItem(id=j.id, metrics=b"",
                                               elapsed_s=0.0)
                               for j in reply.jobs]))
                    done += len(reply.jobs)
                    counters["jobs"] += len(reply.jobs)
                return done

            try:
                cycle(max(n_jobs // 4, 64), seed=700)   # warm the channel
                t0 = time.perf_counter()
                done = cycle(n_jobs, seed=701)
                elapsed = time.perf_counter() - t0
                frames = disp.fleet.frame_sizes()
            finally:
                channel.close()
                srv.stop()
        return done / elapsed, frames

    def run_fleet_e2e(n_workers, n_jobs, poll_s):
        import tempfile
        import threading
        import urllib.request

        import grpc

        from distributed_backtesting_exploration_tpu.obs import (
            fleet as fleet_mod)
        from distributed_backtesting_exploration_tpu.rpc import (
            backtesting_pb2 as pb, service)
        from distributed_backtesting_exploration_tpu.rpc.compute import (
            InstantBackend)
        from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
            Dispatcher, DispatcherServer, JobQueue, PeerRegistry,
            synthetic_jobs)
        from distributed_backtesting_exploration_tpu.rpc.worker import (
            Worker)

        lgrid = {"fast": np.arange(5.0, 9.0, dtype=np.float32)}
        queue = JobQueue()
        ages: list[float] = []
        # Straggler probes: two extra fleet members polling the REAL
        # RequestJobs leg whose frames carry their own execute-stage
        # streams — a healthy bulk and an artificially slowed worker.
        # The slow one must come out flagged in the merged view, and
        # the fleet execute histogram must fold their streams exactly.
        probe_stats = {}
        for wid, durs in (("fleet-fast", [0.001] * 100),
                          ("fleet-slow", [0.8] * 4)):
            st = fleet_mod._StageStats()
            for d in durs:
                st.observe({"name": "worker.execute", "dur_s": d})
            probe_stats[wid] = st
        stop_probes = threading.Event()

        def probe_loop(wid, port):
            telem = fleet_mod.WorkerTelemetry(
                wid, stats_fn=lambda: {"busy": 0},
                stages=probe_stats[wid])
            ch = grpc.insecure_channel(
                f"localhost:{port}",
                options=service.default_channel_options())
            stub = service.DispatcherStub(ch)
            try:
                while not stop_probes.is_set():
                    try:
                        reply = stub.RequestJobs(pb.JobsRequest(
                            worker_id=wid, chips=1, jobs_per_chip=1,
                            telemetry_json=telem.take_frame_json()),
                            timeout=10.0)
                        if reply.jobs:
                            stub.CompleteJobs(pb.CompleteBatch(
                                worker_id=wid,
                                items=[pb.CompleteItem(
                                    id=j.id, metrics=b"", elapsed_s=0.0)
                                    for j in reply.jobs]), timeout=10.0)
                    except grpc.RpcError:
                        pass
                    stop_probes.wait(poll_s)
            finally:
                ch.close()

        with tempfile.TemporaryDirectory() as results_dir:
            disp = Dispatcher(queue, PeerRegistry(prune_window_s=30.0),
                              results_dir=results_dir)
            srv = DispatcherServer(disp, bind="localhost:0",
                                   prune_interval_s=0.5, metrics_port=0,
                                   metrics_host="127.0.0.1").start()
            workers = [Worker(f"localhost:{srv.port}", InstantBackend(),
                              worker_id=f"fleet-{i}",
                              poll_interval_s=poll_s,
                              status_interval_s=0.5, jobs_per_chip=16)
                       for i in range(n_workers)]
            threads = [threading.Thread(target=w.run, daemon=True)
                       for w in workers]
            threads += [threading.Thread(target=probe_loop,
                                         args=(wid, srv.port),
                                         daemon=True)
                        for wid in probe_stats]
            # Freshness contract under test: "staleness <= 2 poll
            # periods" holds for IDLE workers too only when the
            # heartbeat rides the poll cadence — the operator knob this
            # config pins. Set immediately before the try whose finally
            # restores it, so a constructor failure above cannot leak
            # the override into the rest of the process (worker/probe
            # threads read it lazily, after start()).
            prior_hb = os.environ.get("DBX_FLEET_HEARTBEAT_S")
            os.environ["DBX_FLEET_HEARTBEAT_S"] = str(poll_s)
            try:
                for t in threads:
                    t.start()
                for rec in synthetic_jobs(n_jobs, 32, "sma_crossover",
                                          lgrid, seed=702):
                    queue.enqueue(rec)
                deadline = time.monotonic() + 300.0
                while not queue.drained:
                    if time.monotonic() > deadline:
                        sys.exit("bench[fleet_telemetry]: drain wedged "
                                 f"for 300s — stats={queue.stats()}")
                    # Sample the live view mid-drain: per-worker frame
                    # ages feed the staleness p95.
                    snap = disp.fleet.snapshot()
                    ages.extend(w["age_s"]
                                for w in snap["workers"].values())
                    time.sleep(0.01)
                # Let the probes' frames land even on a tiny drain.
                deadline = time.monotonic() + 10.0
                while (len(disp.fleet.snapshot()["workers"])
                       < n_workers + len(probe_stats)
                       and time.monotonic() < deadline):
                    time.sleep(0.05)
                # The served route, end to end (dbxtop's feed).
                url = (f"http://127.0.0.1:{srv.metrics.port}"
                       "/fleet.json")
                with urllib.request.urlopen(url, timeout=10) as resp:
                    doc = json.loads(resp.read())
                frames = disp.fleet.frame_sizes()
            finally:
                stop_probes.set()
                for w in workers:
                    w.stop()
                for t in threads:
                    t.join(timeout=30)
                srv.stop()
                if prior_hb is None:
                    os.environ.pop("DBX_FLEET_HEARTBEAT_S", None)
                else:
                    os.environ["DBX_FLEET_HEARTBEAT_S"] = prior_hb
        return doc, ages, frames

    if enabled("fleet_telemetry"):
        ft_jobs = int(os.environ.get("DBX_BENCH_LOCAL_JOBS", 1500))
        ft_e2e_jobs = int(os.environ.get("DBX_BENCH_FLEET_JOBS", 600))
        ft_workers = int(os.environ.get("DBX_BENCH_FLEET_WORKERS", 2))
        # The production default poll period: "staleness <= 2 poll
        # periods" is measured against the cadence a real fleet runs at
        # (the frame rate floor DBX_FLEET_FRAME_MIN_S sits inside it).
        ft_poll = float(os.environ.get("DBX_BENCH_FLEET_POLL_S", 0.25))
        # Interleaved best-of-3 per arm: this box's run-to-run jitter
        # (~±5%) is the same order as the overhead bar, so a single
        # off-then-on pair confounds drift with cost; the best of three
        # interleaved trials isolates the arm's floor (the microbench
        # puts the true per-poll cost at ~2 µs suppressed / ~90 µs per
        # built frame — ~1-2% at saturation).
        r_off, r_on, on_frames = 0.0, 0.0, []
        for _ in range(3):
            r, _ = run_fleet_direct(32, ft_jobs, telemetry=False)
            r_off = max(r_off, r)
            r, f = run_fleet_direct(32, ft_jobs, telemetry=True)
            if r > r_on:
                r_on, on_frames = r, f
        overhead_pct = (r_off - r_on) / max(r_off, 1e-9) * 100
        doc, ages, e2e_frames = run_fleet_e2e(ft_workers, ft_e2e_jobs,
                                              ft_poll)
        from distributed_backtesting_exploration_tpu.obs import (
            timeline as tl_mod)

        frames = sorted(e2e_frames or on_frames)
        frame_p50 = frames[len(frames) // 2] if frames else 0
        # Same p95 estimator as the tenant queue-wait instrument — one
        # quantile method across the report's keys.
        stale_p95 = tl_mod._quantile(sorted(ages), 0.95) if ages else 0.0
        stale_bar = 2 * ft_poll
        expected_ids = ({f"fleet-{i}" for i in range(ft_workers)}
                        | {"fleet-fast", "fleet-slow"})
        workers_seen = set(doc.get("workers", {}))
        # The artificially slowed probe must come out flagged in the
        # merged view (the live straggler rule), and the fleet execute
        # histogram must equal the deterministic fold of the per-worker
        # rows (own-scope streams summed; proc-scope streams once per
        # pid) — the merged-histogram exactness contract, re-checked on
        # the SERVED document.
        straggler_flagged = "execute" in doc["workers"].get(
            "fleet-slow", {}).get("stragglers", [])
        own_n, own_sum = 0, 0.0
        per_pid: dict = {}
        for w in doc["workers"].values():
            if w.get("stale"):
                continue
            st = w.get("stages", {}).get("execute",
                                         {"n": 0, "sum_s": 0.0})
            if w.get("scope") == "worker":
                own_n += st["n"]
                own_sum += st["sum_s"]
            else:
                cur = per_pid.get(w["pid"])
                if cur is None or st["n"] > cur[0]:
                    per_pid[w["pid"]] = (st["n"], st["sum_s"])
        exp_n = own_n + sum(n for n, _ in per_pid.values())
        exp_sum = own_sum + sum(s for _, s in per_pid.values())
        ex = doc["fleet"]["stages"]["execute"]
        merge_exact = (ex["n"] == exp_n
                       and abs(ex["sum_s"] - exp_sum) < 1e-6)
        rates["fleet_telemetry"] = r_on
        ROOFLINE["fleet_telemetry"] = {
            "jobs": ft_jobs, "batch": 32,
            "jobs_per_s_off": round(r_off, 1),
            "jobs_per_s_on": round(r_on, 1),
            "telemetry_overhead_pct": round(overhead_pct, 1),
            "overhead_ok": bool(overhead_pct <= 5.0),
            "floor_ok": bool(r_on >= 2000),
            "frame_bytes_p50": frame_p50,
            "frames_sampled": len(frames),
            "e2e_jobs": ft_e2e_jobs, "e2e_workers": ft_workers,
            "e2e_poll_s": ft_poll,
            "workers_seen": len(workers_seen),
            "all_workers_visible": bool(expected_ids <= workers_seen),
            "fleet_staleness_p95_s": round(stale_p95, 4),
            "staleness_bar_s": round(stale_bar, 4),
            "staleness_ok": bool(stale_p95 <= stale_bar),
            "straggler_flagged": bool(straggler_flagged),
            "histogram_merge_exact": bool(merge_exact),
        }
        print(f"bench[fleet_telemetry]: direct b32 off {r_off:.0f} -> on "
              f"{r_on:.0f} jobs/s ({overhead_pct:+.1f}%), frame p50 "
              f"{frame_p50} B; e2e {ft_workers}+2 workers @ poll "
              f"{ft_poll * 1e3:.0f}ms -> {len(workers_seen)} visible, "
              f"staleness p95 {stale_p95 * 1e3:.0f}ms "
              f"(bar {stale_bar * 1e3:.0f}ms), straggler "
              f"{'flagged' if straggler_flagged else 'NOT FLAGGED'}, "
              f"merge {'exact' if merge_exact else 'MISMATCH'}",
              file=sys.stderr)

    # --- flight: recorder-armed overhead + residual plane (round 17) ------
    # Two instruments. (a) The direct_dispatch floor re-measured with the
    # flight recorder ARMED (DBX_FLIGHT_DIR set): the hot path never
    # builds a bundle — trigger() is a counter bump plus a dedupe-map
    # probe, and the happy path fires no trigger at all — so the
    # acceptance bar is <= 2% overhead with the 2k floor holding and
    # ZERO bundles written during the run; a capture_now smoke afterwards
    # proves the armed recorder really writes. (b) A deterministic
    # synthetic residual stream through CostModelTracker (durations are
    # computed FROM the op model — no wall clock), so the drift plane's
    # math — calibration warmup, signed EWMA, exact-fold histogram,
    # rank-interpolated quantiles — lands in BENCH JSON with a known
    # answer (costmodel_residual_{p50,p95}).
    if enabled("flight"):
        import tempfile

        from distributed_backtesting_exploration_tpu.obs import (
            costmodel as cm_mod, flight as flight_mod)
        from distributed_backtesting_exploration_tpu.obs.registry import (
            Registry)

        fl_jobs = int(os.environ.get("DBX_BENCH_LOCAL_JOBS", 1500))
        prior_fdir = os.environ.pop("DBX_FLIGHT_DIR", None)
        r_off = r_on = 0.0
        bundles_during = -1
        capture_ok = False
        try:
            with tempfile.TemporaryDirectory() as fdir:
                # Interleaved best-of-3 per arm (the fleet_telemetry
                # jitter argument: run-to-run drift on this box is the
                # same order as the overhead bar).
                for _ in range(3):
                    os.environ.pop("DBX_FLIGHT_DIR", None)
                    flight_mod.reset()
                    r, _ = run_direct_dispatch(32, fl_jobs)
                    r_off = max(r_off, r)
                    os.environ["DBX_FLIGHT_DIR"] = fdir
                    flight_mod.reset()
                    r, _ = run_direct_dispatch(32, fl_jobs)
                    r_on = max(r_on, r)
                bundles_during = len(
                    [f for f in os.listdir(fdir) if f.endswith(".json")])
                capture_ok = flight_mod.capture_now(
                    "admin", subject="bench-smoke") is not None
        finally:
            flight_mod.reset()
            if prior_fdir is None:
                os.environ.pop("DBX_FLIGHT_DIR", None)
            else:
                os.environ["DBX_FLIGHT_DIR"] = prior_fdir
        overhead_pct = (r_off - r_on) / max(r_off, 1e-9) * 100

        # (b) Synthetic residual stream: calibrate a private tracker at a
        # constant seconds-per-unit, then feed durations the model
        # predicts times 2**r for a fixed drift set — one guaranteed
        # blowout (first scored obs, before the calibration can absorb
        # anything), a +2 tail, a +0.5 body, a near-zero floor.
        tr = cm_mod.CostModelTracker(registry=Registry())
        spu0 = 1e-6
        base = {"name": "worker.execute",
                "kernel": "fused:sma_crossover",
                "bars": 2048, "combos": 64, "jobs": 1}
        units = cm_mod._model_units("sma_crossover", 2048, 64)

        def feed(r_log2):
            tr.observe(dict(base, dur_s=units * spu0 * (2.0 ** r_log2)))

        feed(0.0)                   # seeds the calibration at spu0
        for _ in range(cm_mod.warmup_n() - 1):
            feed(0.0)               # finish warmup; EWMA stays at spu0
        for r_log2 in [3.5] + [0.1] * 8 + [0.5] * 8 + [2.0] * 3:
            feed(r_log2)
        cm_snap = tr.snapshot()
        res_p50 = cm_mod.residual_quantile(cm_snap["buckets"], 0.5)
        res_p95 = cm_mod.residual_quantile(cm_snap["buckets"], 0.95)

        rates["flight"] = r_on
        ROOFLINE["flight"] = {
            "jobs": fl_jobs, "batch": 32,
            "jobs_per_s_off": round(r_off, 1),
            "jobs_per_s_on": round(r_on, 1),
            "overhead_pct": round(overhead_pct, 1),
            "overhead_ok": bool(overhead_pct <= 2.0),
            "floor_ok": bool(r_on >= 2000),
            "bundles_during_run": bundles_during,
            "quiet_ok": bool(bundles_during == 0),
            "capture_smoke_ok": bool(capture_ok),
            "costmodel_obs": cm_snap["n"],
            "costmodel_blowouts": cm_snap["blowouts"],
            "costmodel_residual_p50": round(res_p50, 4),
            "costmodel_residual_p95": round(res_p95, 4),
        }
        print(f"bench[flight]: direct b32 off {r_off:.0f} -> armed "
              f"{r_on:.0f} jobs/s ({overhead_pct:+.1f}%), "
              f"{bundles_during} bundle(s) during run, capture smoke "
              f"{'ok' if capture_ok else 'FAILED'}; synthetic residuals "
              f"p50 {res_p50:+.2f} / p95 {res_p95:+.2f} log2, "
              f"{cm_snap['blowouts']} blowout(s)", file=sys.stderr)

    # --- decision_plane: recorder-armed overhead + shadow scorer (r19) ----
    # Two instruments, the flight config's shape verbatim. (a) The
    # direct_dispatch floor re-measured with the decision plane recording
    # (DBX_DECISIONS on, the default) vs killed (=0): the hot path only
    # builds one small dict per dispatched job and deque-appends the
    # batch — scoring runs on the plane's own thread — so the acceptance
    # bar is <= 2% overhead with the 2k floor holding. Measurement:
    # five PAIRED rounds (killed then armed, back to back) and the
    # MEDIAN of the per-round deltas — this box's run-to-run swing
    # (±35%, DESIGN.md) is an order past the bar being measured, and
    # independent best-of-N arms inherit all of it; pairing cancels the
    # minutes-scale drift and the median rejects the symmetric
    # remainder. (b) A
    # deterministic synthetic decision stream through a private
    # DecisionPlane over a two-worker fleet (one holding the panel in
    # its top-K sketch, one not), placements split 12 resident / 4 not:
    # regret and agreement land in BENCH JSON with a known answer
    # (agreement 75%, regret = payload bytes over the nominal h2d rate
    # for every mis-placed decision).
    if enabled("decision_plane"):
        from distributed_backtesting_exploration_tpu.obs import (
            decisions as dec_mod)
        from distributed_backtesting_exploration_tpu.obs.registry import (
            Registry)

        dp_jobs = int(os.environ.get("DBX_BENCH_LOCAL_JOBS", 1500))
        prior_dec = os.environ.get("DBX_DECISIONS")
        r_off = r_on = 0.0
        dp_deltas = []
        try:
            for _ in range(5):
                os.environ["DBX_DECISIONS"] = "0"
                ro, _ = run_direct_dispatch(32, dp_jobs)
                os.environ["DBX_DECISIONS"] = "1"
                rn, _ = run_direct_dispatch(32, dp_jobs)
                r_off = max(r_off, ro)
                r_on = max(r_on, rn)
                dp_deltas.append((ro - rn) / max(ro, 1e-9) * 100)
        finally:
            if prior_dec is None:
                os.environ.pop("DBX_DECISIONS", None)
            else:
                os.environ["DBX_DECISIONS"] = prior_dec
        overhead_pct = sorted(dp_deltas)[len(dp_deltas) // 2]

        # (b) Synthetic shadow-score stream with a known answer.
        dp_digest = "ab" * 32
        dp_panel_b = 100_000_000

        class _DpFleet:
            def snapshot(self):
                return {"workers": {
                    "fast": {"stale": False, "age_s": 0.1,
                             "caches": {"panel_topk": [
                                 {"d": dp_digest[:12], "b": 1}]}},
                    "slow": {"stale": False, "age_s": 0.1,
                             "caches": {}}}}

        plane = dec_mod.DecisionPlane(fleet=_DpFleet(),
                                      registry=Registry())
        try:
            placements = ["fast"] * 12 + ["slow"] * 4
            plane.submit([
                {"jid": f"dp-{i}", "trace_id": f"dp-{i}", "worker": wid,
                 "tenant": "default", "strategy": "sma_crossover",
                 "combos": 64.0, "affinity_skips": 0, "wfq": None,
                 "digest": dp_digest, "panel_b": dp_panel_b,
                 "append_parent": "", "base_len": 0, "bars": 2048,
                 "t_take": float(i), "route": "full"}
                for i, wid in enumerate(placements)])
            scored = plane.flush(timeout=30.0)
            dp_snap = plane.snapshot()
        finally:
            plane.close()
        want_regret = dp_panel_b / dec_mod.h2d_rate_bps()

        rates["decision_plane"] = r_on
        ROOFLINE["decision_plane"] = {
            "jobs": dp_jobs, "batch": 32,
            "jobs_per_s_off": round(r_off, 1),
            "jobs_per_s_on": round(r_on, 1),
            "decision_overhead_delta_pct": round(overhead_pct, 1),
            "overhead_rounds_pct": [round(d, 1) for d in dp_deltas],
            "overhead_ok": bool(overhead_pct <= 2.0),
            "floor_ok": bool(r_on >= 2000),
            "shadow_scored": dp_snap["n_scored"] if scored else -1,
            "shadow_agreement_pct": dp_snap["agreement"]["pct"],
            "regret_p50": dp_snap["regret"]["p50_s"],
            "regret_p95": dp_snap["regret"]["p95_s"],
            "regret_expected_s": round(want_regret, 4),
        }
        print(f"bench[decision_plane]: direct b32 killed {r_off:.0f} -> "
              f"recording {r_on:.0f} jobs/s (median paired delta "
              f"{overhead_pct:+.1f}%); "
              f"shadow stream {dp_snap['n_scored']} scored, agreement "
              f"{dp_snap['agreement']['pct']:.0f}%, regret p50 "
              f"{dp_snap['regret']['p50_s']:.4f}s / p95 "
              f"{dp_snap['regret']['p95_s']:.4f}s (expected "
              f"{want_regret:.4f}s per mis-placement)", file=sys.stderr)

    # --- e2e_local_placement: locality-scored placement A/B (round 20) ----
    # The live placement stage's acceptance instrument: the SAME mixed
    # append-chain / paged-repeat / cold workload drained twice through
    # the loopback control plane — locality-blind (DBX_PLACEMENT=0, the
    # round-19 pure-WFQ path) vs placement-live — against a backend that
    # charges the simulated stage ladder keyed on what each worker
    # actually holds: a carry-store hit prices PL_CARRY_S, a full
    # reprice PL_REPRICE_S, and a panel miss adds PL_TRANSFER_S on top.
    # The dispatcher cannot cheat the sleeps — only routing jobs to the
    # worker holding the parent/panel avoids the expensive legs.
    # DBX_DECISIONS_H2D_GBPS is pinned so the op model's transfer term
    # matches the simulated link; the defer cap is raised because the
    # 2 ms poll loop burns a poll-scaled budget in milliseconds (the
    # production default assumes polls a batch-duration apart).
    # regret_seconds_{shadow,live} are the shadow scorer's measured
    # regret sums per arm: the live policy must leave strictly less on
    # the table than blind WFQ (regret_ok: live < shadow).
    if enabled("e2e_local_placement"):
        import tempfile
        import threading

        from distributed_backtesting_exploration_tpu import obs as obs_mod
        from distributed_backtesting_exploration_tpu.rpc import (
            panel_store as pl_store)
        from distributed_backtesting_exploration_tpu.rpc.compute import (
            Completion)
        from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
            Dispatcher, DispatcherServer, JobQueue, JobRecord, PeerRegistry)
        from distributed_backtesting_exploration_tpu.rpc.worker import Worker
        from distributed_backtesting_exploration_tpu.utils import (
            data as pl_data)

        # Workload scale knobs: the tier-1 fixture shrinks the run to a
        # few seconds (structure test — the 1.5x bar belongs to the
        # real-size run, like the decision_plane bench discipline).
        pl_scale = float(os.environ.get("DBX_BENCH_PL_SCALE", 1.0))
        PL_REPRICE_S = 0.100 * pl_scale
        PL_CARRY_S = 0.002 * pl_scale
        PL_TRANSFER_S = 0.060 * pl_scale
        pl_bars, pl_step = 1024, 64
        pl_chains = int(os.environ.get("DBX_BENCH_PL_CHAINS", 10))
        pl_links = int(os.environ.get("DBX_BENCH_PL_LINKS", 20))
        pl_panels = 4
        pl_repeats = min(4, pl_links)
        pl_cold = min(8, pl_links)
        pl_grid = {"fast": np.arange(5.0, 9.0, dtype=np.float32)}

        class LocalityBackend:
            """Charges the stage ladder against what THIS worker holds:
            carry hit vs full reprice, resident panel vs h2d leg. Keys
            on digests only — digest-only dispatch never ships bytes it
            would not read anyway."""

            chips = 1

            def __init__(self):
                self.held: set[str] = set()

            def process(self, jobs):
                out = []
                for job in jobs:
                    base = job.append_parent_digest
                    if base and base in self.held:
                        dt = PL_CARRY_S
                    else:
                        dt = PL_REPRICE_S
                        if job.panel_digest not in self.held:
                            dt += PL_TRANSFER_S
                    time.sleep(dt)
                    self.held.add(job.panel_digest)
                    out.append(Completion(job.id, b"", dt,
                                          trace_id=job.trace_id))
                return out

        def pl_blob(seed, n):
            s = pl_data.synthetic_ohlcv(1, n, seed=seed)
            return pl_data.to_wire_bytes(
                type(s)(*(np.asarray(f[0][:n]) for f in s)))

        def pl_records():
            """The deterministic mixed workload, rebuilt per arm (fresh
            JobRecord objects — deferral bookkeeping must start cold).
            Chains are real append streams: every link extends the
            PREVIOUS link, so carry state lives only where the previous
            link ran."""
            master = pl_data.synthetic_ohlcv(
                1, pl_bars + pl_links * pl_step, seed=700)
            chains = []
            for c in range(pl_chains):
                links, prev_d, prev_n = [], "", 0
                for k in range(pl_links):
                    n = pl_bars + k * pl_step
                    blob = pl_data.to_wire_bytes(type(master)(
                        *(np.asarray(f[0][:n]) + c for f in master)))
                    links.append(JobRecord(
                        id=f"pl-c{c}-l{k}", strategy="sma_crossover",
                        grid=pl_grid, ohlcv=blob,
                        append_parent=prev_d, append_base_len=prev_n))
                    prev_d, prev_n = pl_store.panel_digest(blob), n
                chains.append(links)
            repeat_blobs = [pl_blob(710 + p, pl_bars)
                            for p in range(pl_panels)]
            cold_blobs = [pl_blob(730 + i, pl_bars) for i in range(pl_cold)]
            recs = []
            for r in range(pl_links):
                for links in chains:
                    recs.append(links[r])
                for p, blob in enumerate(repeat_blobs):
                    if r < pl_repeats:
                        recs.append(JobRecord(
                            id=f"pl-r{p}-{r}", strategy="sma_crossover",
                            grid=pl_grid, ohlcv=blob))
                if r < pl_cold:
                    recs.append(JobRecord(
                        id=f"pl-x{r}", strategy="sma_crossover",
                        grid=pl_grid, ohlcv=cold_blobs[r]))
            return recs

        def run_placement_arm(tag, live):
            env = {"DBX_PLACEMENT": "1" if live else "0",
                   "DBX_PLACEMENT_DEFER_CAP": "64",
                   "DBX_DECISIONS_H2D_GBPS": "0.0007",
                   "DBX_DECISIONS_RATE": "100000"}
            prior = {k: os.environ.get(k) for k in env}
            os.environ.update(env)
            reg = obs_mod.get_registry()
            counts0 = {o: reg.counter("dbx_placement_total", outcome=o).value
                       for o in ("served", "deferred", "cap")}
            queue = JobQueue()
            try:
                with tempfile.TemporaryDirectory() as results_dir:
                    disp = Dispatcher(queue, PeerRegistry(prune_window_s=30.0),
                                      results_dir=results_dir,
                                      panel_dedupe=True)
                    srv = DispatcherServer(disp, bind="localhost:0",
                                           prune_interval_s=0.5).start()
                    workers = [Worker(f"localhost:{srv.port}",
                                      LocalityBackend(),
                                      worker_id=f"pl-{i}",
                                      poll_interval_s=0.002,
                                      status_interval_s=0.5,
                                      jobs_per_chip=2)
                               for i in range(2)]
                    threads = [threading.Thread(target=w.run, daemon=True)
                               for w in workers]
                    try:
                        for t in threads:
                            t.start()
                        recs = pl_records()
                        for rec in recs:
                            queue.enqueue(rec)
                        t0 = time.perf_counter()
                        deadline = time.monotonic() + 300.0
                        while not queue.drained:
                            if time.monotonic() > deadline:
                                sys.exit(f"bench[e2e_local_placement/{tag}]: "
                                         f"drain wedged for 300s — "
                                         f"stats={queue.stats()}")
                            time.sleep(0.002)
                        elapsed = time.perf_counter() - t0
                        disp.decisions.flush(timeout=30.0)
                        snap = disp.decisions.snapshot()
                    finally:
                        for w in workers:
                            w.stop()
                        for t in threads:
                            t.join(timeout=30)
                        srv.stop()
            finally:
                for k, v in prior.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
            counts = {o: reg.counter("dbx_placement_total", outcome=o).value
                      - counts0[o] for o in ("served", "deferred", "cap")}
            rate = len(recs) / elapsed
            print(f"bench[e2e_local_placement/{tag}]: {len(recs)} jobs, "
                  f"2 workers -> {rate:.0f} jobs/s, regret sum "
                  f"{snap['regret']['sum_s']:.3f}s over "
                  f"{snap['n_scored']} scored, placement counts "
                  f"{counts}", file=sys.stderr)
            return rate, snap, counts, len(recs)

        r_blind, snap_blind, _, _ = run_placement_arm("blind", live=False)
        r_live, snap_live, pl_counts, pl_n = run_placement_arm(
            "live", live=True)
        pl_polls = sum(pl_counts.values())
        pl_speedup = r_live / max(r_blind, 1e-9)
        regret_shadow = snap_blind["regret"]["sum_s"]
        regret_live = snap_live["regret"]["sum_s"]

        rates["e2e_local_placement"] = r_live
        ROOFLINE["e2e_local_placement"] = {
            "jobs": pl_n, "workers": 2,
            "jobs_per_s_blind": round(r_blind, 1),
            "jobs_per_s_live": round(r_live, 1),
            "placement_speedup": round(pl_speedup, 3),
            "defer_rate": round(
                pl_counts["deferred"] / max(pl_polls, 1), 4),
            "admit_counts": {o: int(v) for o, v in pl_counts.items()},
            "regret_seconds_shadow": round(regret_shadow, 4),
            "regret_seconds_live": round(regret_live, 4),
            "scored_shadow": snap_blind["n_scored"],
            "scored_live": snap_live["n_scored"],
            "speedup_ok": bool(pl_speedup >= 1.5),
            "regret_ok": bool(regret_live < regret_shadow),
        }
        print(f"bench[e2e_local_placement]: blind {r_blind:.0f} -> live "
              f"{r_live:.0f} jobs/s ({pl_speedup:.2f}x), regret "
              f"{regret_shadow:.3f}s -> {regret_live:.3f}s, defer rate "
              f"{pl_counts['deferred'] / max(pl_polls, 1):.3f}",
              file=sys.stderr)

    # --- queue_machine: the state machine alone, both substrates ----------
    # (VERDICT r4 weak #5 / next #7: the native DbxJobQueue driven per job
    # over ctypes measured ~2x SLOWER than the dict fallback; the batched
    # API — one crossing per take/complete batch — is the fix. This
    # microbench drives full lifecycle cycles, batch 32, through BOTH
    # substrates and records them side by side.)
    def run_queue_machine(substrate):
        from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
            JobQueue, JobRecord)
        from distributed_backtesting_exploration_tpu.runtime import (
            _core as native_core)

        if substrate == "native" and not native_core.available():
            print("bench[queue_machine]: native core unavailable, skipping",
                  file=sys.stderr)
            return
        n_q_jobs = int(os.environ.get("DBX_BENCH_QUEUE_JOBS", 20000))
        recs = [JobRecord(id=f"q{i}", strategy="s", grid={}, ohlcv=b"x")
                for i in range(n_q_jobs)]
        best = 0.0
        for _ in range(3):   # best-of-3: this box's load varies ~50%
            q = JobQueue(use_native=(substrate == "native"))
            assert q.substrate == substrate
            t0 = time.perf_counter()
            for i in range(0, n_q_jobs, 32):   # RPC-sized intake batches
                q.enqueue_many(recs[i:i + 32])
            while True:
                got = q.take(32, "w")
                if not got:
                    break
                q.complete_batch([r.id for r, _ in got], "w")
            elapsed = time.perf_counter() - t0
            assert q.drained and q.stats()["jobs_completed"] == n_q_jobs
            best = max(best, n_q_jobs / elapsed)
        print(f"bench[queue_machine_{substrate}]: {n_q_jobs} full "
              f"enqueue->take(32)->complete_batch cycles, best of 3 "
              f"-> {best / 1e3:.0f}k jobs/s", file=sys.stderr)
        rates[f"queue_machine_{substrate}"] = best

    if enabled("queue_machine"):
        run_queue_machine("python")
        run_queue_machine("native")
        # The C-ABI grain — a native shell driving DbxJobQueue with no
        # foreign-function crossing (its real habitat; the reason the
        # native machine exists even though the Python-driven default
        # substrate is python).
        bench_bin = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "cpp", "build", "dbx_core_bench")
        if os.path.exists(bench_bin):
            import re
            import subprocess
            try:
                out = subprocess.run([bench_bin, "200000"],
                                     capture_output=True, text=True,
                                     timeout=120)
                m = re.search(r"-> (\d+) jobs/s", out.stdout)
                if out.returncode == 0 and m:
                    rates["queue_machine_native_cabi"] = float(m.group(1))
                    print("bench[queue_machine_native_cabi]: "
                          + out.stdout.strip(), file=sys.stderr)
            except (OSError, subprocess.SubprocessError) as e:
                print(f"bench[queue_machine_native_cabi]: skipped ({e})",
                      file=sys.stderr)

    # --- streaming_append: O(ΔT) live-bar serving A/B ---------------------
    # ROADMAP item 1's acceptance instrument: the same appended ΔT-bar
    # slice priced two ways — (A) the recurrent form advancing a carry
    # checkpoint (streaming.recurrent.append_step, the AppendBars serving
    # path) vs (B) today's cost model, a full scan-form reprice of the
    # whole (T+ΔT)-bar panel. Both run in-process on fixed shapes with
    # the compile walls warmed out, so the ratio is pure steady-state
    # work; `append_speedup` is the >=50x acceptance number at the
    # headline T=8192 / ΔT=16 (knobs DBX_BENCH_STREAM_T / _DT). The wire
    # columns record what AppendBars ships (one DBX1 ΔT slice) vs what a
    # full re-dispatch would (the whole extended panel).
    if enabled("streaming_append"):
        from distributed_backtesting_exploration_tpu.streaming import (
            recurrent as stream_rc)

        s_T = int(os.environ.get("DBX_BENCH_STREAM_T", 8192))
        s_DT = int(os.environ.get("DBX_BENCH_STREAM_DT", 16))
        s_iters = max(min(iters, 10), 3)
        sgrid = {k: np.asarray(v) for k, v in sweep.product_grid(
            fast=np.arange(5.0, 13.0, dtype=np.float32),
            slow=np.arange(30.0, 46.0, 4.0, dtype=np.float32)).items()}
        s_combos = int(sgrid["fast"].size)
        s_close = np.asarray(data.synthetic_ohlcv(
            1, s_T + s_DT * (s_iters + 1), seed=77).close)

        carry0 = stream_rc.build_carry("sma_crossover",
                                       {"close": s_close[:, :s_T]}, sgrid)
        # Warm both forms: the A/B must time steady-state work, not jit.
        np.asarray(stream_rc.finalize(stream_rc.append_step(
            carry0, {"close": s_close[:, s_T:s_T + s_DT]})).sharpe)
        np.asarray(stream_rc.finalize(stream_rc.build_carry(
            "sma_crossover", {"close": s_close[:, :s_T + s_DT]},
            sgrid)).sharpe)

        t0 = time.perf_counter()
        c = carry0
        for i in range(s_iters):
            lo = s_T + i * s_DT
            c = stream_rc.append_step(
                c, {"close": s_close[:, lo:lo + s_DT]})
            np.asarray(stream_rc.finalize(c).sharpe)   # the served result
        t_append = (time.perf_counter() - t0) / s_iters

        # Full reprice at a FIXED (T+ΔT) length per update: same compiled
        # shape every iteration (a per-update growing length would time
        # recompiles, not work).
        t0 = time.perf_counter()
        for _ in range(s_iters):
            np.asarray(stream_rc.finalize(stream_rc.build_carry(
                "sma_crossover", {"close": s_close[:, :s_T + s_DT]},
                sgrid)).sharpe)
        t_full = (time.perf_counter() - t0) / s_iters

        wire_full = 8 + 4 * 5 * (s_T + s_DT)     # DBX1: magic+T+5 f32[T]
        wire_delta = 8 + 4 * 5 * s_DT
        speedup = t_full / max(t_append, 1e-9)
        ROOFLINE["streaming_append"] = {
            "bars_base": s_T, "delta_bars": s_DT, "updates": s_iters,
            "combos": s_combos,
            "append_s_per_update": round(t_append, 6),
            "full_reprice_s_per_update": round(t_full, 6),
            "append_speedup": round(speedup, 2),
            "wire_bytes_full": wire_full,
            "wire_bytes_delta": wire_delta,
            "wire_reduction": round(wire_full / wire_delta, 1)}
        rates["streaming_append"] = 1.0 / max(t_append, 1e-9)
        print(f"bench[streaming_append]: T={s_T} dT={s_DT} "
              f"P={s_combos}: append {t_append * 1e3:.2f} ms/update vs "
              f"full reprice {t_full * 1e3:.1f} ms -> {speedup:.1f}x "
              f"(wire {wire_full}B -> {wire_delta}B)", file=sys.stderr)

    # --- certify: dbxcert numerics-certifier analysis cost ----------------
    # The certifier (analysis.certify) is a CI-gate stage like lint and
    # proto-drift: its wall is tracked per family exactly like every
    # compute stage, so a registry/analysis growth that would blow the
    # tier-1 budget shows up in BENCH JSON first. certify_wall_s maps
    # family -> seconds to certify its 4 rows (2 epilogue substrates x
    # {build_carry, append_step}); "digest" covers the scenario-synth +
    # wire-splice digest cones. DBX_BENCH_CERTIFY_FAMILIES subsets the
    # registry for tiny runs.
    if enabled("certify"):
        from distributed_backtesting_exploration_tpu.analysis import (
            certify as dbxcert)

        fams_env = os.environ.get("DBX_BENCH_CERTIFY_FAMILIES")
        fams = ([f.strip() for f in fams_env.split(",") if f.strip()]
                if fams_env else None)
        t0 = time.perf_counter()
        certify_rows, certify_walls = dbxcert.timed_rows(families=fams)
        certify_total = time.perf_counter() - t0
        ROOFLINE["certify"] = {
            "certify_wall_s": {k: round(v, 4)
                               for k, v in certify_walls.items()},
            "rows": len(certify_rows),
            "wall_s_total": round(certify_total, 4)}
        rates["certify"] = len(certify_rows) / max(certify_total, 1e-9)
        print(f"bench[certify]: {len(certify_rows)} rows in "
              f"{certify_total:.2f}s "
              f"({len(certify_walls) - 1} families + digest cones)",
              file=sys.stderr)

    # --- modelcheck: dbxmc interleaving/crash-point explorer cost ---------
    # The model checker (analysis.modelcheck) is a CI-gate stage like
    # lint and certify: its schedule throughput rides BENCH JSON so a
    # queue-code or invariant-table growth that would blow the tier-1
    # budget shows up here first. schedules/crash_points are summed over
    # every available substrate (python + native when loadable);
    # DBX_BENCH_MC_SCHEDULES subsets the sweep for tiny runs.
    if enabled("modelcheck"):
        from distributed_backtesting_exploration_tpu.analysis import (
            modelcheck as dbxmc)

        mc_cfg = dbxmc.MCConfig(
            ops=int(os.environ.get("DBX_MC_OPS", "12")),
            seed=int(os.environ.get("DBX_MC_SEED", "0")),
            schedules=int(os.environ.get("DBX_BENCH_MC_SCHEDULES", "120")))
        mc_res = dbxmc.explore(mc_cfg, dbxmc.available_substrates())
        ROOFLINE["modelcheck"] = {
            "schedules": mc_res["schedules"],
            "crash_points": mc_res["crash_points"],
            "boundaries": mc_res["boundaries"],
            "violations": len(mc_res["violations"]),
            "wall_s": mc_res["wall_s"]}
        rates["modelcheck"] = (mc_res["schedules"]
                               / max(mc_res["wall_s"], 1e-9))
        print(f"bench[modelcheck]: {mc_res['schedules']} schedules, "
              f"{mc_res['crash_points']} crash points, "
              f"{len(mc_res['violations'])} violations in "
              f"{mc_res['wall_s']:.2f}s", file=sys.stderr)

    # --- fanout: live signal fan-out scaling (serve/, ROADMAP item 3) -----
    # The serving-cost contract measured end to end: N subscriptions over
    # M symbol chains (all sharing one param block per symbol -> M unique
    # streams), one tick-only AppendBars per symbol, an instant-backend
    # worker draining the advance jobs over loopback gRPC, and every
    # push delivered through real server-streaming Subscribe calls.
    # `advances_per_tick` MUST equal unique streams per chain (1 here) —
    # carry advances scale with streams, not subscribers — and
    # `pushes_per_advance` is the fan-out amplification (N/M). Tick-to-
    # push latency is client-measured (same host, same clock): recv wall
    # minus the PushUpdate's dispatcher tick stamp; the p99 bar is
    # bench-pinned at 2s on this box (loopback + instant compute — the
    # number is the SERVING tier's overhead, not kernel wall).
    if enabled("fanout"):
        import tempfile
        import threading

        import grpc as grpc_mod

        from distributed_backtesting_exploration_tpu import obs as obs_mod
        from distributed_backtesting_exploration_tpu.rpc import (
            backtesting_pb2 as fan_pb, service as fan_service,
            wire as fan_wire)
        from distributed_backtesting_exploration_tpu.rpc.compute import (
            InstantBackend)
        from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
            Dispatcher, DispatcherServer, JobQueue, JobRecord,
            PeerRegistry)
        from distributed_backtesting_exploration_tpu.rpc.worker import (
            Worker)

        sub_n = int(os.environ.get("DBX_BENCH_SUB_N", 10000))
        n_symbols = int(os.environ.get("DBX_BENCH_SUB_SYMBOLS", 1000))
        n_conns = min(int(os.environ.get("DBX_BENCH_SUB_CONNS", 32)),
                      sub_n)
        fan_bars = 64
        fan_grid = {"fast": np.arange(5.0, 9.0, dtype=np.float32)}
        hist = data.synthetic_ohlcv(n_symbols, fan_bars + 1, seed=700)

        def sym_cut(i, lo, hi):
            return data.to_wire_bytes(
                type(hist)(*(np.asarray(f[i, lo:hi]) for f in hist)))

        base_recs = [JobRecord(id=f"fan-{i}", strategy="sma_crossover",
                               grid=fan_grid, ohlcv=sym_cut(i, 0, fan_bars))
                     for i in range(n_symbols)]

        class _FanCollector:
            """Drains one Subscribe stream; samples tick->recv wall."""

            def __init__(self, stub, request, expected):
                self.lat: list[float] = []
                self.expected = expected
                self._call = stub.Subscribe(request)
                self.thread = threading.Thread(target=self._drain,
                                               daemon=True)
                self.thread.start()

            def _drain(self):
                try:
                    for item in self._call:
                        if item.tick_unix:
                            self.lat.append(time.time() - item.tick_unix)
                        if len(self.lat) >= self.expected:
                            break
                except grpc_mod.RpcError:
                    pass

            def stop(self):
                self._call.cancel()
                self.thread.join(timeout=10)

        queue = JobQueue()
        reg = obs_mod.get_registry()
        adv0 = reg.counter("dbx_stream_advances_total").value
        drop0 = reg.counter("dbx_sub_pushes_total",
                            outcome="dropped").value
        with tempfile.TemporaryDirectory() as results_dir:
            disp = Dispatcher(queue, PeerRegistry(prune_window_s=60.0),
                              results_dir=results_dir)
            srv = DispatcherServer(disp, bind="localhost:0",
                                   prune_interval_s=0.5,
                                   max_workers=n_conns + 16).start()
            worker = Worker(f"localhost:{srv.port}", InstantBackend(),
                            worker_id="fanout-worker",
                            poll_interval_s=0.001, status_interval_s=0.5,
                            jobs_per_chip=64)
            wt = threading.Thread(target=worker.run, daemon=True)
            channel = grpc_mod.insecure_channel(
                f"localhost:{srv.port}",
                options=fan_service.default_channel_options())
            stub = fan_service.DispatcherStub(channel)
            collectors = []
            try:
                wt.start()
                for rec in base_recs:
                    queue.enqueue(rec)
                deadline = time.monotonic() + 300.0
                while not queue.drained:
                    if time.monotonic() > deadline:
                        sys.exit("bench[fanout]: base drain wedged — "
                                 f"stats={queue.stats()}")
                    time.sleep(0.005)
                # N subscriptions spread so each symbol's subscribers
                # land on DISTINCT connections (a connection naming the
                # same stream twice is deduped by design — one
                # membership, one push — so per-stream fan-out is
                # counted in connections). Symbol s's k-th subscriber
                # rides connection (s + k) % n_conns: with
                # subs-per-symbol <= n_conns they are all distinct.
                per_sym = sub_n // n_symbols
                if per_sym > n_conns:
                    sys.exit("bench[fanout]: DBX_BENCH_SUB_CONNS "
                             f"({n_conns}) < subscribers per symbol "
                             f"({per_sym}) — a connection would hold "
                             "duplicate interests in one stream, which "
                             "dedupes to one push")
                per_conn = [[] for _ in range(n_conns)]
                for j in range(sub_n):
                    s, k = divmod(j, per_sym) if per_sym else (j, 0)
                    s %= n_symbols
                    per_conn[(s + k) % n_conns].append(fan_pb.JobSpec(
                        strategy="sma_crossover",
                        panel_digest=base_recs[s].panel_digest,
                        grid=fan_wire.grid_to_proto(fan_grid),
                        periods_per_year=252))
                for c, interests in enumerate(per_conn):
                    collectors.append(_FanCollector(
                        stub, fan_pb.SubscribeRequest(
                            subscriber_id=f"fan-c{c}",
                            interests=interests),
                        expected=len(interests)))
                deadline = time.monotonic() + 120.0
                while disp.hub.stats()["interests"] < sub_n:
                    if time.monotonic() > deadline:
                        sys.exit("bench[fanout]: subscriptions never "
                                 f"registered — {disp.hub.stats()}")
                    time.sleep(0.01)
                t0 = time.perf_counter()
                for i, rec in enumerate(base_recs):
                    r = stub.AppendBars(fan_pb.AppendRequest(
                        worker_id="feed", panel_digest=rec.panel_digest,
                        base_len=fan_bars,
                        delta=sym_cut(i, fan_bars, fan_bars + 1),
                        job=fan_pb.JobSpec()))
                    if not r.ok:
                        sys.exit(f"bench[fanout]: tick {i} rejected: "
                                 f"{r.detail}")
                t_ticks = time.perf_counter() - t0
                deadline = time.monotonic() + 300.0
                while any(len(c.lat) < c.expected for c in collectors):
                    if time.monotonic() > deadline:
                        got = sum(len(c.lat) for c in collectors)
                        # Drop-and-count is legal under load; report
                        # what arrived rather than wedging (the keys
                        # below carry the drop counter).
                        print(f"bench[fanout]: {got}/{sub_n} pushes "
                              "after 300s (rest dropped or late)",
                              file=sys.stderr)
                        break
                    time.sleep(0.01)
                t_all = time.perf_counter() - t0
            finally:
                for c in collectors:
                    c.stop()
                worker.stop()
                wt.join(timeout=30)
                channel.close()
                srv.stop()
        lat = sorted(x for c in collectors for x in c.lat)
        advances = reg.counter("dbx_stream_advances_total").value - adv0
        dropped = reg.counter("dbx_sub_pushes_total",
                              outcome="dropped").value - drop0
        from distributed_backtesting_exploration_tpu.obs.timeline import (
            _quantile)

        p99 = _quantile(lat, 0.99)
        p99_bar_s = 2.0
        ROOFLINE["fanout"] = {
            "subscriptions": sub_n, "symbols": n_symbols,
            "connections": n_conns,
            "unique_streams": n_symbols,
            "ticks": n_symbols,
            "advances_total": int(advances),
            "advances_per_tick": round(advances / max(n_symbols, 1), 4),
            "advances_eq_streams": bool(advances == n_symbols),
            "pushes_delivered": len(lat),
            "pushes_dropped": int(dropped),
            "pushes_per_advance": round(len(lat) / max(advances, 1), 2),
            "tick_to_push_p50_s": round(_quantile(lat, 0.50), 6),
            "tick_to_push_p99_s": round(p99, 6),
            "p99_bar_s": p99_bar_s,
            "p99_ok": bool(p99 <= p99_bar_s),
            "tick_wall_s": round(t_ticks, 3),
            "drain_wall_s": round(t_all, 3)}
        rates["fanout"] = len(lat) / max(t_all, 1e-9)
        print(f"bench[fanout]: {sub_n} subs / {n_symbols} symbols on "
              f"{n_conns} conns: {advances} advances "
              f"({advances / max(n_symbols, 1):.2f}/tick, streams="
              f"{n_symbols}), {len(lat)} pushes "
              f"({len(lat) / max(advances, 1):.1f}/advance, "
              f"{dropped} dropped), tick->push p50 "
              f"{_quantile(lat, 0.5) * 1e3:.1f}ms p99 {p99 * 1e3:.1f}ms, "
              f"drain {t_all:.1f}s", file=sys.stderr)

    # --- e2e_local_tenants: 3-tenant adversarial fairness A/B -------------
    # ROADMAP item 5's acceptance instrument: a whale tenant's oversized
    # grid sweep (many jobs x many combos) must not blow up a small
    # tenant's p95 queue wait. Two loopback drains with the SAME small-
    # tenant workload — (solo) the two small tenants without the whale,
    # (contended) the whale's whole backlog enqueued AHEAD of them — and
    # per-tenant p95 queue_wait measured through the PR 4 timeline
    # profiler (per-job critical-path stage attribution over the span
    # ring), tenants keyed by job-id prefix. Under the WFQ pop the whale
    # only interleaves at its combo-weighted share, so the ratio stays
    # near 1; the pre-tenancy FIFO would make it backlog/backlog (~5x
    # at the default sizes).
    def run_tenant_pass(tag, tenant_jobs, *, jobs_per_chip=8):
        import tempfile
        import threading

        from distributed_backtesting_exploration_tpu.rpc.compute import (
            InstantBackend)
        from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
            Dispatcher, DispatcherServer, JobQueue, PeerRegistry)
        from distributed_backtesting_exploration_tpu.rpc.worker import (
            Worker)

        queue = JobQueue()
        n_total = 0
        with tempfile.TemporaryDirectory() as results_dir:
            disp = Dispatcher(queue, PeerRegistry(prune_window_s=30.0),
                              results_dir=results_dir)
            srv = DispatcherServer(disp, bind="localhost:0",
                                   prune_interval_s=0.5).start()
            worker = Worker(f"localhost:{srv.port}", InstantBackend(),
                            worker_id=f"tenant-bench-{tag}",
                            poll_interval_s=0.001, status_interval_s=0.5,
                            jobs_per_chip=jobs_per_chip)
            wt = threading.Thread(target=worker.run, daemon=True)
            try:
                wt.start()
                t0 = time.perf_counter()
                for recs in tenant_jobs:
                    for rec in recs:
                        queue.enqueue(rec)
                    n_total += len(recs)
                deadline = time.monotonic() + 600.0
                while not queue.drained:
                    if time.monotonic() > deadline:
                        sys.exit(f"bench[e2e_local_tenants:{tag}]: drain "
                                 f"wedged for 600s — stats={queue.stats()}")
                    time.sleep(0.002)
                elapsed = time.perf_counter() - t0
            finally:
                worker.stop()
                wt.join(timeout=30)
                srv.stop()
        return n_total / elapsed

    if enabled("e2e_local_tenants"):
        from distributed_backtesting_exploration_tpu import obs as obs_mod
        from distributed_backtesting_exploration_tpu.obs import (
            timeline as tl_mod)
        from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
            JobRecord)
        from distributed_backtesting_exploration_tpu.utils import (
            data as t_data)

        n_small = int(os.environ.get("DBX_BENCH_TENANT_SMALL_JOBS", 64))
        n_whale = int(os.environ.get("DBX_BENCH_TENANT_WHALE_JOBS", 512))
        whale_combos = int(os.environ.get(
            "DBX_BENCH_TENANT_WHALE_COMBOS", 64))
        t_series = t_data.synthetic_ohlcv(1, 32, seed=910)
        t_blob = t_data.to_wire_bytes(
            type(t_series)(*(np.asarray(f[0]) for f in t_series)))
        small_grid = {"fast": np.arange(5.0, 9.0, dtype=np.float32)}
        whale_grid = {"fast": np.arange(
            5.0, 5.0 + whale_combos, dtype=np.float32)}

        def tenant_recs(tag, tenant, n, grid):
            return [JobRecord(id=f"{tag}:{tenant}-{i}",
                              strategy="sma_crossover", grid=grid,
                              ohlcv=t_blob, tenant=tenant)
                    for i in range(n)]

        def tenant_p95(tag, tenant):
            tls = tl_mod.reconstruct(obs_mod.recent_spans())
            # Same torn-job discipline as timeline.summarize_spans: ring
            # eviction tears a job's queue_wait head span first, and a
            # torn timeline's queue_wait stage reads ~0 — keeping it
            # would silently deflate the fairness p95 at scaled-up
            # whale sizes.
            tls = {t: tl for t, tl in tls.items()
                   if any(s["name"] == "job.queue_wait"
                          for s in tl.spans)}
            per_job = (tl_mod.summarize(
                tls, min_straggler_jobs=1 << 30)["per_job"]
                if tls else [])
            waits = sorted(j["stages"]["queue_wait"] for j in per_job
                           if j["job"].startswith(f"{tag}:{tenant}-"))
            if not waits:
                # Honest-numbers policy: a fairness bar must never pass
                # on zero measurements (ring eviction at scaled-up whale
                # sizes tears the small tenants' spans FIRST).
                sys.exit(f"bench[e2e_local_tenants]: no surviving "
                         f"queue_wait timelines for {tag}:{tenant} — "
                         "span ring too small for this job count")
            return tl_mod._quantile(waits, 0.95), len(waits)

        r_solo = run_tenant_pass("solo", [
            tenant_recs("solo", "small_a", n_small, small_grid),
            tenant_recs("solo", "small_b", n_small, small_grid)])
        solo = {t: tenant_p95("solo", t) for t in ("small_a", "small_b")}
        p95_solo = max(v[0] for v in solo.values())
        r_cont = run_tenant_pass("cont", [
            # Adversarial order: the whale's WHOLE sweep lands first.
            tenant_recs("cont", "whale", n_whale, whale_grid),
            tenant_recs("cont", "small_a", n_small, small_grid),
            tenant_recs("cont", "small_b", n_small, small_grid)])
        cont = {t: tenant_p95("cont", t)
                for t in ("whale", "small_a", "small_b")}
        per_tenant = {t: round(v[0], 6) for t, v in cont.items()}
        p95_cont = max(per_tenant["small_a"], per_tenant["small_b"])
        ratio = p95_cont / max(p95_solo, 1e-9)
        ROOFLINE["e2e_local_tenants"] = {
            # Sample counts per p95 (no silent caps: the quantiles above
            # are only as good as the timelines that survived the ring).
            "tenant_queue_wait_samples": {
                **{f"solo_{t}": v[1] for t, v in solo.items()},
                **{f"contended_{t}": v[1] for t, v in cont.items()}},
            "small_jobs": n_small, "whale_jobs": n_whale,
            "small_combos_per_job": int(small_grid["fast"].size),
            "whale_combos_per_job": whale_combos,
            "tenant_p95_queue_wait_solo": round(p95_solo, 6),
            "tenant_p95_queue_wait_contended": round(p95_cont, 6),
            "fairness_ratio": round(ratio, 3),
            "fairness_ok": bool(ratio <= 2.0),
            "per_tenant_p95_contended": per_tenant,
            "jobs_per_s_solo": round(r_solo, 1),
            "jobs_per_s_contended": round(r_cont, 1)}
        rates["e2e_local_tenants"] = r_cont
        print(f"bench[e2e_local_tenants]: whale {n_whale}x{whale_combos} "
              f"combos vs 2x{n_small} small jobs: small p95 queue_wait "
              f"{p95_solo * 1e3:.1f}ms solo -> {p95_cont * 1e3:.1f}ms "
              f"contended = {ratio:.2f}x (bar: <= 2x)", file=sys.stderr)

    # --- scenario_sweep: digest-seeded synthetic-panel generation ---------
    # The scenario workload's two facts: (a) generator throughput — a
    # (digest, params) spec replaces shipping/storing a whole panel, so
    # the generation rate IS the workload's ingest ceiling; (b) the e2e
    # dispatcher path — scenario jobs materialize through the panel
    # store at first take and drain like ordinary content-addressed
    # jobs. Reproducibility (same spec -> same digest) is asserted here
    # too: it is the property that makes the spec a valid wire unit.
    if enabled("scenario_sweep"):
        import dataclasses as dc
        import tempfile
        import threading

        from distributed_backtesting_exploration_tpu import (
            scenarios as scn_mod)
        from distributed_backtesting_exploration_tpu.rpc import (
            backtesting_pb2 as s_pb)
        from distributed_backtesting_exploration_tpu.rpc.compute import (
            InstantBackend)
        from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
            Dispatcher, DispatcherServer, JobQueue, PeerRegistry,
            scenario_jobs, synthetic_jobs)
        from distributed_backtesting_exploration_tpu.rpc.panel_store \
            import panel_digest
        from distributed_backtesting_exploration_tpu.rpc.worker import (
            Worker)
        from distributed_backtesting_exploration_tpu.utils import (
            data as s_data)

        s_bars = int(os.environ.get("DBX_BENCH_SCENARIO_BARS", 2048))
        s_n = int(os.environ.get("DBX_BENCH_SCENARIO_N", 32))
        s_series = s_data.synthetic_ohlcv(1, s_bars, seed=900)
        s_blob = s_data.to_wire_bytes(
            type(s_series)(*(np.asarray(f[0]) for f in s_series)))
        params0 = scn_mod.ScenarioParams(block=16, regimes=3,
                                         vol_scale=2.0, shock=0.01)
        # Warm the generator jit: the rate must time steady-state work.
        scn_mod.scenario_panel_bytes(s_blob, params0)
        t0 = time.perf_counter()
        blobs = [scn_mod.scenario_panel_bytes(
            s_blob, dc.replace(params0, seed=i)) for i in range(s_n)]
        gen_elapsed = time.perf_counter() - t0
        redo = scn_mod.scenario_panel_bytes(s_blob,
                                            dc.replace(params0, seed=0))
        deterministic = redo == blobs[0]
        spec_bytes = 32 + s_pb.ScenarioSpec(
            base_digest=panel_digest(s_blob), n_bars=s_bars, block=16,
            regimes=3, vol_scale=2.0, shock=0.01,
            seed=s_n).ByteSize()

        # e2e: the sweep as DISPATCHER work — one real job carries the
        # base panel, the scenario jobs ride as specs and materialize
        # through the panel store at first take.
        queue = JobQueue()
        base_rec = synthetic_jobs(1, 16, "sma_crossover",
                                  {"fast": np.asarray([3.0], np.float32)},
                                  seed=901)[0]
        base_rec.ohlcv = s_blob
        queue.enqueue(base_rec)
        for rec in scenario_jobs(base_rec.panel_digest, s_n,
                                 "sma_crossover",
                                 {"fast": np.arange(5.0, 9.0,
                                                    dtype=np.float32)},
                                 params=params0.to_dict()):
            queue.enqueue(rec)
        with tempfile.TemporaryDirectory() as results_dir:
            disp = Dispatcher(queue, PeerRegistry(prune_window_s=30.0),
                              results_dir=results_dir)
            srv = DispatcherServer(disp, bind="localhost:0",
                                   prune_interval_s=0.5).start()
            worker = Worker(f"localhost:{srv.port}", InstantBackend(),
                            worker_id="scenario-bench",
                            poll_interval_s=0.001, status_interval_s=0.5,
                            jobs_per_chip=8)
            wt = threading.Thread(target=worker.run, daemon=True)
            try:
                wt.start()
                t0 = time.perf_counter()
                deadline = time.monotonic() + 600.0
                while not queue.drained:
                    if time.monotonic() > deadline:
                        sys.exit("bench[scenario_sweep]: drain wedged for "
                                 f"600s — stats={queue.stats()}")
                    time.sleep(0.002)
                e2e_rate = (s_n + 1) / (time.perf_counter() - t0)
            finally:
                worker.stop()
                wt.join(timeout=30)
                srv.stop()

        ROOFLINE["scenario_sweep"] = {
            "panels": s_n, "bars": s_bars,
            "gen_s_per_panel": round(gen_elapsed / s_n, 6),
            "panels_per_s": round(s_n / gen_elapsed, 2),
            "bar_rate": round(s_n * s_bars / gen_elapsed, 1),
            "digest_deterministic": bool(deterministic),
            "panel_bytes": len(blobs[0]),
            "spec_bytes": spec_bytes,
            "spec_wire_reduction": round(len(blobs[0])
                                         / max(spec_bytes, 1), 1),
            "jobs_per_s_e2e": round(e2e_rate, 1)}
        rates["scenario_sweep"] = s_n / gen_elapsed
        print(f"bench[scenario_sweep]: {s_n} panels x {s_bars} bars "
              f"generated at {s_n / gen_elapsed:.1f} panels/s "
              f"(deterministic={deterministic}), spec {spec_bytes}B vs "
              f"panel {len(blobs[0])}B, e2e {e2e_rate:.0f} jobs/s",
              file=sys.stderr)

    # --- scenario_megakernel: fused in-trace generation vs materialized ---
    # The round-18 A/B: the SAME scenario sweep drained twice through a
    # real dispatcher+worker loop — once on the spec-batch megakernel
    # route (one carrier JobSpec, panels regenerated in-trace inside the
    # sweep launch, never materialized) and once with the kill switch
    # down (every panel generated host-side, stored, shipped). Two facts
    # ride the JSON: the scenarios/s ratio, and the panel-store
    # bytes-vs-K curve — flat in K for the fused route (only the base
    # panel is content-addressed) and growing for the materialized one.
    if enabled("scenario_megakernel"):
        import tempfile
        import threading

        from distributed_backtesting_exploration_tpu.rpc.compute import (
            JaxSweepBackend)
        from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
            Dispatcher, DispatcherServer, JobQueue, PeerRegistry,
            scenario_jobs, synthetic_jobs)
        from distributed_backtesting_exploration_tpu.rpc.worker import (
            Worker)
        from distributed_backtesting_exploration_tpu.utils import (
            data as mk_data)

        mk_bars = int(os.environ.get("DBX_BENCH_MEGAKERNEL_BARS", 512))
        mk_k = max(int(os.environ.get("DBX_BENCH_MEGAKERNEL_K", 48)), 4)
        mk_grid = {"fast": np.arange(3.0, 7.0, dtype=np.float32),
                   "slow": np.arange(12.0, 44.0, 8.0, dtype=np.float32)}
        mk_combos = int(np.prod([len(v) for v in mk_grid.values()]))
        mk_params = {"n_bars": mk_bars, "block": 16, "regimes": 3,
                     "vol_scale": 2.0, "shock": 0.01}
        mk_series = mk_data.synthetic_ohlcv(1, mk_bars, seed=910)
        mk_blob = mk_data.to_wire_bytes(
            type(mk_series)(*(np.asarray(f[0]) for f in mk_series)))

        def mk_leg(k: int, fused: bool):
            """Drain base + ``k`` scenario jobs through a fresh
            in-process dispatcher + JAX worker on the chosen route;
            returns ``(elapsed_s, panel-store stats at drain)``."""
            prior = os.environ.get("DBX_SCENARIO_FUSED")
            os.environ["DBX_SCENARIO_FUSED"] = "1" if fused else "0"
            try:
                queue = JobQueue()
                base_rec = synthetic_jobs(
                    1, 16, "sma_crossover",
                    {"fast": np.asarray([3.0], np.float32),
                     "slow": np.asarray([12.0], np.float32)}, seed=911)[0]
                base_rec.ohlcv = mk_blob
                queue.enqueue(base_rec)
                for rec in scenario_jobs(base_rec.panel_digest, k,
                                         "sma_crossover", mk_grid,
                                         params=mk_params):
                    queue.enqueue(rec)
                with tempfile.TemporaryDirectory() as results_dir:
                    disp = Dispatcher(queue,
                                      PeerRegistry(prune_window_s=30.0),
                                      results_dir=results_dir)
                    srv = DispatcherServer(disp, bind="localhost:0",
                                           prune_interval_s=0.5).start()
                    # jobs_per_chip >= K+1: one poll takes the whole
                    # sweep, so the fused route coalesces it into ONE
                    # carrier launch (the shape the megakernel serves).
                    worker = Worker(f"localhost:{srv.port}",
                                    JaxSweepBackend(),
                                    worker_id="megakernel-bench",
                                    poll_interval_s=0.001,
                                    status_interval_s=0.5,
                                    jobs_per_chip=k + 1)
                    wt = threading.Thread(target=worker.run, daemon=True)
                    try:
                        wt.start()
                        t0 = time.perf_counter()
                        deadline = time.monotonic() + 600.0
                        while not queue.drained:
                            if time.monotonic() > deadline:
                                sys.exit("bench[scenario_megakernel]: "
                                         f"drain wedged for 600s (fused="
                                         f"{fused}, K={k}) — stats="
                                         f"{queue.stats()}")
                            time.sleep(0.002)
                        elapsed = time.perf_counter() - t0
                    finally:
                        worker.stop()
                        wt.join(timeout=30)
                        srv.stop()
                return elapsed, queue.panel_store.stats()
            finally:
                if prior is None:
                    os.environ.pop("DBX_SCENARIO_FUSED", None)
                else:
                    os.environ["DBX_SCENARIO_FUSED"] = prior

        # Warm both routes at full K first: the fused launch compiles
        # per (K, shape) bucket, so the timed full-K legs must hit a
        # warm cache (smaller curve points compile fresh — their elapsed
        # only annotates the curve, never the headline rates).
        mk_leg(mk_k, True)
        mk_leg(mk_k, False)
        mk_ks = sorted({max(mk_k // 4, 2), max(mk_k // 2, 2), mk_k})
        curve_fused, curve_mat = [], []
        for k in mk_ks:
            el, st = mk_leg(k, True)
            curve_fused.append({"k": k, "elapsed_s": round(el, 4),
                                "store_panels": st["panels"],
                                "store_bytes": st["bytes"]})
        for k in mk_ks:
            el, st = mk_leg(k, False)
            curve_mat.append({"k": k, "elapsed_s": round(el, 4),
                              "store_panels": st["panels"],
                              "store_bytes": st["bytes"]})
        mk_fused_rate = mk_ks[-1] / curve_fused[-1]["elapsed_s"]
        mk_mat_rate = mk_ks[-1] / curve_mat[-1]["elapsed_s"]
        mk_fused_bytes = [p["store_bytes"] for p in curve_fused]
        ROOFLINE["scenario_megakernel"] = {
            "scenarios": mk_k, "bars": mk_bars, "combos": mk_combos,
            "fused_scn_per_s": round(mk_fused_rate, 2),
            "materialized_scn_per_s": round(mk_mat_rate, 2),
            "speedup": round(mk_fused_rate / max(mk_mat_rate, 1e-9), 2),
            "store_bytes_by_k_fused": curve_fused,
            "store_bytes_by_k_materialized": curve_mat,
            # O(1)-in-K device/store residency: the fused curve holds
            # exactly the base panel at every K.
            "store_bytes_flat_in_k": bool(
                max(mk_fused_bytes) == min(mk_fused_bytes)),
        }
        rates["scenario_megakernel"] = mk_fused_rate
        print(f"bench[scenario_megakernel]: {mk_k} scenarios x "
              f"{mk_combos} combos @ {mk_bars} bars -> fused "
              f"{mk_fused_rate:.1f} scn/s vs materialized "
              f"{mk_mat_rate:.1f} scn/s "
              f"({mk_fused_rate / max(mk_mat_rate, 1e-9):.2f}x), store "
              f"bytes flat in K: "
              f"{max(mk_fused_bytes) == min(mk_fused_bytes)}",
              file=sys.stderr)

    # --- configs[4]: walk-forward (12 refit windows x grid) ---------------
    if enabled("walkforward"):
        train = n_bars // 2 - 30
        test = max((n_bars - train) // 12, 1)
        wgrid = sweep.product_grid(
            fast=jnp.arange(5, 25, dtype=jnp.float32),
            slow=jnp.arange(30, 130, 5, dtype=jnp.float32))
        n_windows = int((n_bars - train) // test)
        strat = base.get_strategy("sma_crossover")

        import functools
        from types import SimpleNamespace

        # Generic walk_forward is ONE fused XLA program end to end and wins
        # at this grid size (11.5M/s vs 5.5M/s measured for the
        # walk_forward_fused two-phase split at P=400 — the fused train
        # kernel only pays off at much larger param grids). Set
        # DBX_BENCH_WF_FUSED=1 to measure the fused variant.
        if os.environ.get("DBX_BENCH_WF_FUSED") == "1":
            wfa = np.asarray(wgrid["fast"])
            wsl = np.asarray(wgrid["slow"])

            def run_wf():
                r = walkforward.walk_forward_fused(
                    panel, strat, wgrid,
                    functools.partial(fused.fused_sma_sweep, fast=wfa,
                                      slow=wsl, cost=1e-3),
                    train=train, test=test, cost=1e-3)
                return SimpleNamespace(sharpe=r.oos_metrics.sharpe)
        else:
            def run_wf():
                r = walkforward.walk_forward(
                    panel, strat, wgrid, train=train, test=test, cost=1e-3)
                return SimpleNamespace(sharpe=r.oos_metrics.sharpe)

        rates["walkforward"] = _measure(
            run_wf, n_tickers * sweep.grid_size(wgrid) * n_windows,
            iters=max(iters // 2, 3), warmup=max(warmup // 3, 2),
            name="walkforward")

    # --- long-context: one >64k-bar history through the serving path -----
    # (VERDICT r4 item 1: the route a worker takes for jobs beyond the
    # fused VMEM cap. On a multi-chip host the bar axis shards over the
    # chips via parallel.timeshard — the same code rpc.compute routes to;
    # on one chip it is the generic sweep that single-chip workers serve.)
    if enabled("long_context"):
        lc_bars = int(os.environ.get("DBX_BENCH_LC_BARS", 65537))
        lc_grid = sweep.product_grid(
            fast=jnp.arange(5, 13, dtype=jnp.float32),
            slow=jnp.arange(30, 70, 10, dtype=jnp.float32))   # P = 32
        lc_ohlcv = data.synthetic_ohlcv(1, lc_bars, seed=7)
        lc_strat = base.get_strategy("sma_crossover")
        lc_devs = jax.devices()
        if len(lc_devs) > 1:
            from jax.sharding import (
                Mesh, NamedSharding, PartitionSpec as Pspec)

            from distributed_backtesting_exploration_tpu.parallel import (
                timeshard)

            T_pad = -(-lc_bars // len(lc_devs)) * len(lc_devs)
            close_np = np.asarray(lc_ohlcv.close, np.float32)
            if T_pad > lc_bars:
                close_np = np.concatenate(
                    [close_np,
                     np.repeat(close_np[:, -1:], T_pad - lc_bars, 1)], 1)
            tmesh = Mesh(np.asarray(lc_devs), (timeshard.TIME_AXIS,))
            sh_close = jax.device_put(
                close_np,
                NamedSharding(tmesh, Pspec(None, timeshard.TIME_AXIS)))
            lc_combos = [
                (int(f), int(s))
                for f, s in zip(np.asarray(lc_grid["fast"]),
                                np.asarray(lc_grid["slow"]))]
            lc_tr = None if T_pad == lc_bars else lc_bars

            @jax.jit
            def _run_lc_sharded(c):
                ms = [timeshard.sharded_sma_backtest(
                          tmesh, c, f, s, cost=1e-3, t_real=lc_tr)
                      for f, s in lc_combos]
                return jnp.stack([m.sharpe for m in ms], axis=-1)

            def run_lc():
                from types import SimpleNamespace
                return SimpleNamespace(sharpe=_run_lc_sharded(sh_close))
        else:
            lc_panel = type(lc_ohlcv)(
                *(jax.device_put(jnp.asarray(f), dev) for f in lc_ohlcv))

            def run_lc():
                return sweep.jit_sweep(lc_panel, lc_strat, lc_grid,
                                       cost=1e-3)

        rates["long_context"] = _measure(
            run_lc, sweep.grid_size(lc_grid), iters=max(iters // 2, 3),
            warmup=max(warmup // 3, 2), name="long_context")
        print(f"bench[long_context]: {lc_bars} bars x "
              f"{sweep.grid_size(lc_grid)} params on {len(lc_devs)} "
              f"device(s) -> "
              f"{rates['long_context'] * lc_bars / 1e6:.1f}M bar-backtests/s",
              file=sys.stderr)

    # --- ragged_paged: mixed-length fleet through the device page pool ----
    # ROADMAP item 2's acceptance instrument: a log-spaced mixed-length
    # universe swept through the page tables (fused_paged_sweep — one
    # launch per page-count class, pad bounded by one page per ticker)
    # vs the SAME total bar count as one uniform-length dense sweep.
    # `paged_vs_uniform_ratio` is the <=1.3x acceptance number;
    # `launches_*`/`pad_bars_*` record what the paged schedule saves over
    # the dense power-of-two length bucketing (the pre-round-10 grouping
    # rule, reproduced here from the wire byte-length formula), and
    # `pool_bytes_per_ticker` the device-residency cost.
    if enabled("ragged_paged"):
        from distributed_backtesting_exploration_tpu.rpc.page_pool import (
            PagePool)

        rp_n = int(os.environ.get("DBX_BENCH_RAGGED_TICKERS", 1024))
        rp_spread = float(os.environ.get("DBX_BENCH_RAGGED_SPREAD", 8))
        rp_tmax = n_bars
        rp_B = fused.resolve_page_bars()
        rp_lens = np.unique(np.round(np.geomspace(
            max(rp_tmax / rp_spread, 64), rp_tmax, rp_n)).astype(np.int64),
            return_inverse=False)
        # geomspace collapses duplicates at tiny scales; tile back to rp_n.
        rp_lens = np.sort(np.resize(rp_lens, rp_n))
        rp_total = int(rp_lens.sum())
        rp_Tu = max(int(rp_total // rp_n), 64)
        rgrid = {k: np.asarray(v) for k, v in sweep.product_grid(
            fast=np.arange(5.0, 13.0, dtype=np.float32),
            slow=np.arange(30.0, 46.0, 4.0, dtype=np.float32)).items()}
        rp_P = int(rgrid["fast"].size)

        rp_panel = data.synthetic_ohlcv(rp_n, rp_tmax, seed=11)
        rp_close = np.asarray(rp_panel.close, np.float32)
        rp_series = [data.OHLCV(*(np.asarray(f, np.float32)[i, :t]
                                  for f in rp_panel))
                     for i, t in enumerate(rp_lens)]
        rp_pool = PagePool(
            max_bytes=2 * rp_n * (-(-rp_tmax // rp_B)) * rp_B * 4)
        prep = rp_pool.prepare([f"rp{i}" for i in range(rp_n)], rp_series,
                               ("close",))
        if prep is None:
            sys.exit("bench[ragged_paged]: page pool rejected the fleet")
        rp_pool_arr, rp_tables, _ = prep
        rp_treal = np.asarray(rp_lens, np.int32)

        from types import SimpleNamespace

        def run_paged():
            m = fused.fused_paged_sweep(
                "sma_crossover", rp_pool_arr, rp_tables, rp_treal, rgrid,
                cost=1e-3)
            return SimpleNamespace(sharpe=m.sharpe)

        def run_uniform():
            m = fused.fused_sma_sweep(rp_close[:, :rp_Tu], rgrid["fast"],
                                      rgrid["slow"], cost=1e-3)
            return SimpleNamespace(sharpe=m.sharpe)

        rp_iters = max(min(iters, 5), 2)
        rp_warm = max(min(warmup, 2), 1)
        rate_paged = _measure(run_paged, rp_n * rp_P, iters=rp_iters,
                              warmup=rp_warm, name="ragged_paged")
        rate_uni = _measure(run_uniform, rp_n * rp_P, iters=rp_iters,
                            warmup=rp_warm, name="ragged_paged_uniform")
        t_paged = rp_n * rp_P / rate_paged
        t_uni = rp_n * rp_P / rate_uni

        # Dense-bucketing counterfactual (the pre-round-10 grouping rule:
        # power-of-two buckets on the DBX1 wire byte length, then each
        # bucket repeat-last padded to its own max).
        wire_len = 8 + 4 * 5 * rp_lens          # DBX1: magic+T+5 f32[T]
        buckets: dict = {}
        for t, wl in zip(rp_lens, wire_len):
            buckets.setdefault(int(wl).bit_length(), []).append(int(t))
        pad_dense = sum(max(ts) * len(ts) - sum(ts)
                        for ts in buckets.values())
        pages_per = -(-rp_lens // rp_B)
        pad_paged = int((pages_per * rp_B - rp_lens).sum())
        launches_paged = int(np.unique(pages_per).size)
        pool_stats = rp_pool.stats()

        ratio = t_paged / max(t_uni, 1e-9)
        ROOFLINE["ragged_paged"] = {
            "tickers": rp_n, "t_max": int(rp_lens.max()),
            "t_min": int(rp_lens.min()), "total_bars": rp_total,
            "uniform_bars": rp_Tu, "combos": rp_P,
            "page_bars": rp_B,
            "paged_s_per_sweep": round(t_paged, 6),
            "uniform_s_per_sweep": round(t_uni, 6),
            "paged_vs_uniform_ratio": round(ratio, 3),
            "ratio_ok": bool(ratio <= 1.3),
            "launches_dense": len(buckets),
            "launches_paged": launches_paged,
            "pad_bars_dense": int(pad_dense),
            "pad_bars_paged": pad_paged,
            "pool_bytes": pool_stats["bytes"],
            "pool_bytes_per_ticker": round(pool_stats["bytes"] / rp_n, 1),
        }
        rates["ragged_paged"] = rate_paged
        print(f"bench[ragged_paged]: {rp_n} tickers x {rp_P} combos, "
              f"lengths {int(rp_lens.min())}..{int(rp_lens.max())} "
              f"(B={rp_B}): paged/uniform {ratio:.2f}x, launches "
              f"{len(buckets)} dense -> {launches_paged} paged, pad bars "
              f"{pad_dense} -> {pad_paged}", file=sys.stderr)

    # --- autotune: substrate autotuner -------------------------------------
    # ROADMAP item 4's acceptance instrument: per-family A/B of the
    #     hardcoded substrate defaults vs the autotuner's measured winner for this (shape-bucket, platform) —
    #     `autotuned_vs_default_speedup{family}` records the MEASURED
    #     ratio on this box plus the deterministic MODELED twin from the
    #     op-model prior (the on-chip expectation, recorded like PR 3's
    #     modeled acceptance when no chip is in the round's loop).
    # The fleet compile-cache exchange is covered by tests/test_tune.py's
    # gRPC round trip; it needs a second cache directory, and the one
    # resolver (tune.compile_cache) places exactly one.
    if enabled("autotune"):
        import contextlib
        import threading

        from distributed_backtesting_exploration_tpu import (
            tune as tune_mod)

        at_bars = int(os.environ.get("DBX_BENCH_AUTOTUNE_BARS", 512))
        at_tickers = int(os.environ.get("DBX_BENCH_AUTOTUNE_TICKERS", 4))
        at_reps = max(min(iters, 5), 2)
        at_panel = data.synthetic_ohlcv(at_tickers, at_bars, seed=21)
        at_close = np.asarray(at_panel.close, np.float32)
        at_hi = np.asarray(at_panel.high, np.float32)
        at_lo = np.asarray(at_panel.low, np.float32)

        fa16 = np.tile(np.arange(3.0, 7.0, dtype=np.float32), 4)
        sl16 = np.repeat(np.arange(10.0, 18.0, 2.0,
                                   dtype=np.float32), 4)
        w16 = np.tile(np.arange(4.0, 8.0, dtype=np.float32), 4)
        k16 = np.repeat(np.linspace(0.5, 2.0, 4,
                                    dtype=np.float32), 4)
        lb16 = np.arange(2.0, 18.0, dtype=np.float32)
        at_cases = {
            "sma_crossover": lambda **kw: fused.fused_sma_sweep(
                at_close, fa16, sl16, cost=1e-3, **kw),
            "bollinger": lambda **kw: fused.fused_bollinger_sweep(
                at_close, w16, k16, cost=1e-3, **kw),
            "momentum": lambda **kw: fused.fused_momentum_sweep(
                at_close, lb16, cost=1e-3, **kw),
            "stochastic": lambda **kw: fused.fused_stochastic_sweep(
                at_close, at_hi, at_lo, w16, k16 * 20 + 40, cost=1e-3,
                **kw),
            "obv_trend": lambda **kw: fused.fused_obv_sweep(
                at_close,
                np.asarray(at_panel.volume, np.float32), w16 + 2,
                cost=1e-3, **kw),
        }

        def at_wall(run, substrates=None):
            ctx = (fused.tuned_schedule(substrates) if substrates
                   else contextlib.nullcontext())
            with ctx:
                jax.block_until_ready(run().sharpe)   # compile + warm
                t0 = time.perf_counter()
                for _ in range(at_reps):
                    jax.block_until_ready(run().sharpe)
                return (time.perf_counter() - t0) / at_reps

        prior_mode = os.environ.get("DBX_AUTOTUNE")
        os.environ["DBX_AUTOTUNE"] = prior_mode or "1"
        at_sched = tune_mod.ScheduleRegistry()
        tuner = tune_mod.Autotuner(at_sched)
        fam_rows = {}
        speedups, speedups_modeled = {}, {}
        try:
            platform = jax.default_backend()
            for fam, run in at_cases.items():
                n_combos = 16
                bucket = tune_mod.shape_bucket(at_bars, n_combos)
                winner = tuner.tune(
                    fam, bucket, platform, n_bars=at_bars,
                    n_combos=n_combos,
                    measure=lambda subs, run=run: at_wall(run, subs))
                t_default = at_wall(run)
                t_tuned = at_wall(run, winner)
                defaults = fused.substrate_defaults()
                d_subs = {"epilogue": defaults["epilogue"],
                          "lanes_cap": defaults["lanes_cap"]}
                tf = fused._STRATEGY_TABLE_FAMILY.get(fam)
                if tf:
                    d_subs[f"table_{tf}"] = defaults[f"table_{tf}"]
                m_default = tune_mod.modeled_cost(
                    fam, d_subs, n_bars=at_bars, n_combos=n_combos)
                m_tuned = tune_mod.modeled_cost(
                    fam, winner or d_subs, n_bars=at_bars,
                    n_combos=n_combos)
                speedups[fam] = round(t_default / max(t_tuned, 1e-9), 3)
                speedups_modeled[fam] = round(
                    m_default / max(m_tuned, 1e-9), 3)
                fam_rows[fam] = {
                    "bucket": bucket,
                    "default_s_per_sweep": round(t_default, 6),
                    "tuned_s_per_sweep": round(t_tuned, 6),
                    "substrates": winner,
                }
                print(f"bench[autotune:{fam}]: default "
                      f"{t_default * 1e3:.2f} ms -> tuned "
                      f"{t_tuned * 1e3:.2f} ms ({speedups[fam]:.2f}x, "
                      f"modeled {speedups_modeled[fam]:.2f}x) "
                      f"{winner}", file=sys.stderr)
        finally:
            if prior_mode is None:
                os.environ.pop("DBX_AUTOTUNE", None)
            else:
                os.environ["DBX_AUTOTUNE"] = prior_mode

        ROOFLINE["autotune"] = {
            "bars": at_bars, "tickers": at_tickers, "combos": 16,
            "platform": platform,
            "autotuned_vs_default_speedup": speedups,
            "autotuned_vs_default_speedup_modeled": speedups_modeled,
            "families": fam_rows,
            "speedup_families_ok": sum(
                1 for v in speedups.values() if v >= 1.2),
        }
        rates["autotune"] = 1.0 / max(
            sum(r["tuned_s_per_sweep"] for r in fam_rows.values()), 1e-9)
        print(f"bench[autotune]: speedups {speedups} (modeled "
              f"{speedups_modeled})", file=sys.stderr)

    # --- pipeline: the round-14 double-buffered executor A/B --------------
    # Saturated-worker e2e: ONE worker drains the same distinct-panel
    # workload under the serial compute loop (DBX_PIPELINE=0 — the
    # round-13 worker) and under the pipelined executor (DBX_PIPELINE=1).
    # DBX_PREFETCH is pinned OFF in both arms: the staged backend has no
    # prefetch hook, so leaving it on would label the A/B as covering a
    # leg that never executes (the prefetch legs get their coverage from
    # the integration tests and the live-worker drive). jobs/s is the
    # acceptance headline; the overlap-aware timeline digest
    # (summarize_spans(..., overlap=True) over the span ring) is the
    # mechanism check — submit+collect lane seconds per covered wall
    # second on the worker — and the per-stage attribution before/after
    # shows where the serial wall went.
    #
    # The backend is the calibrated staged replay below, NOT the live
    # jax backend: on this CPU twin the XLA "device" IS the host core,
    # so with real kernels a pipelined A/B measures one core's scheduler
    # contention (two sweeps time-slicing), not executor overlap — the
    # same reason e2e_local instruments the control plane with
    # InstantBackend. The host staging wall is CALIBRATED from the real
    # jax backend's measured submit wall on this exact workload; the
    # device execute+d2h wall is modeled as a GIL-free wait (a real
    # accelerator computes without the host). The on-chip round
    # re-records this config with the live backend (ROADMAP caveat).
    if enabled("pipeline"):
        import threading

        from distributed_backtesting_exploration_tpu.obs import (
            timeline as tl_mod)
        from distributed_backtesting_exploration_tpu.utils import (
            data as dbx_data)
        from distributed_backtesting_exploration_tpu.ops.metrics import (
            Metrics as _Metrics)
        from distributed_backtesting_exploration_tpu.rpc import (
            backtesting_pb2 as pb, compute as compute_mod, wire as wire_mod)
        from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
            Dispatcher, DispatcherServer, JobQueue, PeerRegistry,
            synthetic_jobs)
        from distributed_backtesting_exploration_tpu.rpc.worker import (
            Worker)

        p_jobs = int(os.environ.get("DBX_BENCH_PIPELINE_JOBS", 64))
        # Bars bound the per-job wire payload: past ~1k bars the gzip'd
        # RequestJobs replies take longer than a batch's compute on this
        # box, the input channel never buffers ahead, and the A/B
        # measures the control plane instead of the executor.
        p_bars = int(os.environ.get("DBX_BENCH_PIPELINE_BARS", 512))
        p_fast = int(os.environ.get("DBX_BENCH_PIPELINE_FAST", 8))
        p_slow = int(os.environ.get("DBX_BENCH_PIPELINE_SLOW", 8))
        p_batch = int(os.environ.get("DBX_BENCH_PIPELINE_BATCH", 4))
        # 0 = balanced (device wall == calibrated host wall): the regime
        # double buffering targets — overlap at any other ratio is
        # bounded by min(host, device)/max(host, device).
        p_device_ms = float(os.environ.get("DBX_BENCH_PIPELINE_DEVICE_MS",
                                           0.0))
        p_grid = {
            "fast": np.arange(2.0, 2.0 + p_fast, dtype=np.float32),
            "slow": np.arange(32.0, 32.0 + 2 * p_slow, 2,
                              dtype=np.float32)}

        # Calibration: the real backend's warm submit wall (decode +
        # stack + jit dispatch) for this exact batch shape — the host
        # staging wall the staged backend replays.
        cal_recs = synthetic_jobs(p_batch, p_bars, "sma_crossover",
                                  p_grid, seed=6999)
        cal_specs = [pb.JobSpec(id=r.id, strategy=r.strategy,
                                ohlcv=r.ohlcv,
                                grid=wire_mod.grid_to_proto(r.grid),
                                cost=r.cost, periods_per_year=252)
                     for r in cal_recs]
        cal = compute_mod.JaxSweepBackend(use_fused=False)
        for _ in range(2):
            cal.collect(cal.submit(cal_specs))      # compile + warm
        cal_walls = []
        for _ in range(5):
            t0 = time.perf_counter()
            h = cal.submit(cal_specs)
            cal_walls.append(time.perf_counter() - t0)
            cal.collect(h)
        # Floor the replayed wall: the loopback control plane adds
        # ms-scale jitter per batch (polls, gzip, GIL handoffs on the
        # 1-core box), and with stage walls near that scale the A/B
        # measures the jitter, not the executor. The floor keeps the
        # calibrated PROFILE (balanced stages) while making the stage
        # walls dominate what they are divided by.
        p_host_floor_ms = float(os.environ.get(
            "DBX_BENCH_PIPELINE_HOST_FLOOR_MS", 12.0))
        host_s = max(sorted(cal_walls)[len(cal_walls) // 2],
                     p_host_floor_ms / 1e3)
        device_s = p_device_ms / 1e3 if p_device_ms > 0 else host_s

        _empty_dbxm = wire_mod.metrics_to_bytes(_Metrics(
            *(np.zeros(1, np.float32) for _ in _Metrics._fields)))

        class _StagedPipelineBackend:
            """Replays the calibrated host staging wall with real array
            work over the actual payloads (wire decode + per-field
            stacks, re-stacked until the measured wall elapses) and
            models the device execute+d2h wall as a deadline wait. Emits
            the real worker.decode / worker.d2h spans so the timeline
            digest attributes stages for BOTH loop modes."""

            chips = 1

            def submit(self, jobs):
                jobs = list(jobs)
                pairs = _obs.job_trace_pairs(jobs)
                t0_wall, t0 = time.time(), time.perf_counter()
                deadline = t0 + host_s
                series = [dbx_data.from_wire_bytes(j.ohlcv) for j in jobs]
                while True:
                    [np.stack([np.asarray(getattr(s, f), np.float32)
                               for s in series])
                     for f in ("close", "high", "low")]
                    if time.perf_counter() >= deadline:
                        break
                _obs.emit_span("worker.decode", t0_wall,
                               time.perf_counter() - t0, pairs=pairs,
                               jobs=len(jobs), cache_hit=False)
                return jobs, time.monotonic() + device_s

            def collect(self, handle):
                jobs, t_done = handle
                pairs = _obs.job_trace_pairs(jobs)
                t0_wall, t0 = time.time(), time.perf_counter()
                delay = t_done - time.monotonic()
                if delay > 0:
                    time.sleep(delay)   # the device computes host-free
                out = [compute_mod.Completion(j.id, _empty_dbxm, device_s,
                                              trace_id=j.trace_id)
                       for j in jobs]
                _obs.emit_span("worker.d2h", t0_wall,
                               time.perf_counter() - t0, pairs=pairs,
                               jobs=len(jobs), cache_hit=False)
                return out

            def process(self, jobs):
                return self.collect(self.submit(jobs))

        def run_pipeline_mode(pipeline_on: bool):
            """One saturated-worker drain; returns (jobs/s, overlap-aware
            timeline digest of the measured window)."""
            prior = {k: os.environ.get(k)
                     for k in ("DBX_PIPELINE", "DBX_PREFETCH")}
            os.environ["DBX_PIPELINE"] = "1" if pipeline_on else "0"
            os.environ["DBX_PREFETCH"] = "0"
            queue = JobQueue()
            disp = Dispatcher(queue, PeerRegistry(prune_window_s=60.0))
            srv = DispatcherServer(disp, bind="localhost:0",
                                   prune_interval_s=1.0).start()
            backend = _StagedPipelineBackend()
            # max_inflight_batches=4: the input channel buffers ahead of
            # the depth-2 pipeline, so a slow poll (gzip'd replies on a
            # loaded core) starves neither loop mode.
            w = Worker(f"localhost:{srv.port}", backend,
                       poll_interval_s=0.001, status_interval_s=0.5,
                       jobs_per_chip=p_batch, max_inflight_batches=4)
            t = threading.Thread(target=w.run, daemon=True)
            seed0 = 7000 if pipeline_on else 8000

            def drain(n, seed):
                for rec in synthetic_jobs(n, p_bars, "sma_crossover",
                                          p_grid, seed=seed):
                    queue.enqueue(rec)
                deadline = time.monotonic() + 600.0
                while not queue.drained:
                    if time.monotonic() > deadline:
                        sys.exit("bench[pipeline]: drain wedged for 600s "
                                 f"— stats={queue.stats()}")
                    time.sleep(0.005)

            try:
                t.start()
                # Warm-up drain: compiles + channel warm, outside the clock.
                drain(max(p_jobs // 4, p_batch * 3), seed0)
                # Fresh ring (same DBX_SPAN_RING capacity) so the overlap
                # digest covers ONLY the measured window of THIS mode.
                _obs.configure_ring()
                t0 = time.perf_counter()
                drain(p_jobs, seed0 + 1)
                elapsed = time.perf_counter() - t0
            finally:
                w.stop()
                t.join(timeout=60)
                srv.stop()
                for k, v in prior.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
            digest = tl_mod.summarize_spans(_obs.recent_spans(),
                                            overlap=True)
            return p_jobs / elapsed, digest

        r_serial, tl_serial = run_pipeline_mode(False)
        r_piped, tl_piped = run_pipeline_mode(True)
        _obs.configure_ring()   # end-of-run digest: not this A/B's

        def _stage_totals(tl):
            return {k: v["total_s"]
                    for k, v in tl.get("stages", {}).items()
                    if v["total_s"] > 0}

        ov_piped = tl_piped.get("overlap", {}).get("overlap_factor", 1.0)
        ov_serial = tl_serial.get("overlap", {}).get("overlap_factor", 1.0)
        rates["pipeline"] = r_piped
        ROOFLINE["pipeline"] = {
            "jobs": p_jobs, "bars": p_bars,
            "combos_per_job": p_fast * p_slow, "batch": p_batch,
            "host_stage_ms": round(host_s * 1e3, 3),
            "device_stage_ms": round(device_s * 1e3, 3),
            "jobs_per_s_serial": round(r_serial, 2),
            "jobs_per_s_pipelined": round(r_piped, 2),
            "pipeline_speedup": round(r_piped / max(r_serial, 1e-9), 3),
            "overlap_factor": round(ov_piped, 3),
            "overlap_factor_serial": round(ov_serial, 3),
            "stages_serial": _stage_totals(tl_serial),
            "stages_pipelined": _stage_totals(tl_piped),
        }
        print(f"bench[pipeline]: {p_jobs} jobs x {p_fast * p_slow} combos "
              f"@ {p_bars} bars, batch={p_batch} -> serial "
              f"{r_serial:.2f} jobs/s, pipelined {r_piped:.2f} jobs/s "
              f"({r_piped / max(r_serial, 1e-9):.2f}x), overlap "
              f"{ov_serial:.2f} -> {ov_piped:.2f}", file=sys.stderr)

    if not rates:
        known = ("sma_fused, bollinger_fused, bollinger_touch_fused, "
                 "momentum_fused, donchian_fused, donchian_hl_fused, "
                 "keltner_fused, stochastic_fused, vwap_fused, rsi_fused, "
                 "macd_fused, trix_fused, obv_fused, pairs, e2e, e2e_topk, "
                 "e2e_local, e2e_local_tenants, scenario_sweep, "
                 "scenario_megakernel, "
                 "direct_dispatch, queue_machine, streaming_append, "
                 "fanout, ragged_paged, autotune, walkforward, "
                 "long_context, roofline_stages, pipeline, "
                 "fleet_telemetry, certify")
        sys.exit(f"bench: no configs ran — DBX_BENCH_CONFIGS={only} matched "
                 f"nothing (known: {known})")
    # The headline is the north-star config when it ran; otherwise label the
    # line with whichever config it actually reports (a DBX_BENCH_CONFIGS
    # subset must not masquerade as the SMA headline).
    headline_name = ("sma_fused" if "sma_fused" in rates
                     else next(iter(rates)))
    if headline_name == "sma_fused":
        metric = ("backtests/sec/chip (ticker x param combos), "
                  "SMA-crossover sweep, 5y daily bars")
    else:
        metric = (f"backtests/sec/chip (ticker x param combos), "
                  f"config={headline_name}")
    # Live per-phase attribution: the obs registry every configured layer
    # recorded into during this run (RPC latency histograms from the e2e /
    # direct-dispatch configs, decode/submit/collect splits and kernel
    # wall from the worker backend, journal fsync timing). Snapshotting it
    # into BENCH JSON gives the roofline numbers their runtime
    # counterparts (metric names in DESIGN.md "Observability").
    from distributed_backtesting_exploration_tpu import obs as obs_mod
    from distributed_backtesting_exploration_tpu.obs import (
        timeline as timeline_mod)

    print(json.dumps({
        "metric": metric,
        "value": round(rates[headline_name], 1),
        "unit": "backtests/sec",
        # reference worker: 1 backtest/sec
        "vs_baseline": round(rates[headline_name], 1),
        "configs": {k: round(v, 1) for k, v in rates.items()},
        # Per-kernel utilization model (% of approximate v5e peaks +
        # binding resource); see the roofline comment in main().
        "roofline": ROOFLINE,
        "obs": obs_mod.get_registry().summaries(prefix="dbx_"),
        # Distributed-trace digest of the e2e configs: the dispatcher+
        # worker loops run in-process, so the completed-span ring already
        # holds every job's stitched timeline — critical-path stage
        # attribution (queue-wait/dispatch/transport/decode/compile/
        # execute/d2h/report) and straggler flags, no JSONL file needed.
        # {} when no traced e2e config ran (kernel-only benches).
        "timeline": timeline_mod.summarize_spans(obs_mod.recent_spans()),
    }))


def verify():
    """Fused-vs-generic parity ON THE CHIP: max rel err + flip rates."""
    jax = _setup_jax()
    import jax.numpy as jnp
    import numpy as np

    from distributed_backtesting_exploration_tpu.models import base, pairs
    from distributed_backtesting_exploration_tpu.ops import fused
    from distributed_backtesting_exploration_tpu.parallel import sweep
    from distributed_backtesting_exploration_tpu.utils import data
    from distributed_backtesting_exploration_tpu.utils import (
        verify as verify_mod)

    n_tickers = int(os.environ.get("DBX_BENCH_TICKERS", 100))
    n_bars = int(os.environ.get("DBX_BENCH_BARS", 1260))
    dev = jax.devices()[0]
    ohlcv = data.synthetic_ohlcv(n_tickers, n_bars, seed=0)
    panel = type(ohlcv)(*(jax.device_put(jnp.asarray(f), dev) for f in ohlcv))
    out = {"device": dev.device_kind}

    def strat_case(strat_name, grid, run_fused):
        return (lambda: sweep.jit_sweep(panel, base.get_strategy(strat_name),
                                        dict(grid), cost=1e-3),
                lambda: run_fused(grid))

    if n_tickers < 2:
        sys.exit("bench --verify: the pairs case needs DBX_BENCH_TICKERS >= 2 "
                 "(each pair takes two ticker series)")
    n_pairs = n_tickers // 2
    y_close, x_close = panel.close[:n_pairs], panel.close[n_pairs:2 * n_pairs]
    pgrid = sweep.product_grid(
        lookback=jnp.arange(10, 50, 2, dtype=jnp.float32),
        z_entry=jnp.linspace(0.5, 3.0, 20).astype(jnp.float32))

    cases = {
        "sma": strat_case(
            "sma_crossover",
            sweep.product_grid(
                fast=jnp.arange(5, 25, dtype=jnp.float32),
                slow=jnp.arange(30, 70, 2, dtype=jnp.float32)),
            lambda g: fused.fused_sma_sweep(
                panel.close, np.asarray(g["fast"]), np.asarray(g["slow"]),
                cost=1e-3),
        ),
        "bollinger": strat_case(
            "bollinger",
            sweep.product_grid(
                k=jnp.linspace(0.5, 3.0, 20).astype(jnp.float32),
                window=jnp.arange(10, 50, 2, dtype=jnp.float32)),
            lambda g: fused.fused_bollinger_sweep(
                panel.close, np.asarray(g["window"]), np.asarray(g["k"]),
                cost=1e-3),
        ),
        "momentum": strat_case(
            "momentum",
            sweep.product_grid(
                lookback=jnp.arange(5, 85, 2, dtype=jnp.float32)),
            lambda g: fused.fused_momentum_sweep(
                panel.close, np.asarray(g["lookback"]), cost=1e-3),
        ),
        "bollinger_touch": strat_case(
            "bollinger_touch",
            sweep.product_grid(
                k=jnp.linspace(0.5, 3.0, 20).astype(jnp.float32),
                window=jnp.arange(10, 50, 2, dtype=jnp.float32)),
            lambda g: fused.fused_bollinger_touch_sweep(
                panel.close, np.asarray(g["window"]), np.asarray(g["k"]),
                cost=1e-3),
        ),
        "donchian": strat_case(
            "donchian",
            sweep.product_grid(
                window=jnp.arange(10, 90, 2, dtype=jnp.float32)),
            lambda g: fused.fused_donchian_sweep(
                panel.close, np.asarray(g["window"]), cost=1e-3),
        ),
        "donchian_hl": strat_case(
            "donchian_hl",
            sweep.product_grid(
                window=jnp.arange(10, 90, 2, dtype=jnp.float32)),
            lambda g: fused.fused_donchian_hl_sweep(
                panel.close, panel.high, panel.low,
                np.asarray(g["window"]), cost=1e-3),
        ),
        "vwap": strat_case(
            "vwap_reversion",
            sweep.product_grid(
                k=jnp.linspace(0.5, 3.0, 20).astype(jnp.float32),
                window=jnp.arange(10, 50, 2, dtype=jnp.float32)),
            lambda g: fused.fused_vwap_sweep(
                panel.close, panel.volume, np.asarray(g["window"]),
                np.asarray(g["k"]), cost=1e-3),
        ),
        "stochastic": strat_case(
            "stochastic",
            sweep.product_grid(
                band=jnp.linspace(10.0, 40.0, 4).astype(jnp.float32),
                window=jnp.arange(5, 85, 2, dtype=jnp.float32)),
            lambda g: fused.fused_stochastic_sweep(
                panel.close, panel.high, panel.low,
                np.asarray(g["window"]), np.asarray(g["band"]), cost=1e-3),
        ),
        "keltner": strat_case(
            "keltner",
            sweep.product_grid(
                k=jnp.linspace(1.0, 3.0, 4).astype(jnp.float32),
                window=jnp.arange(5, 85, 2, dtype=jnp.float32)),
            lambda g: fused.fused_keltner_sweep(
                panel.close, panel.high, panel.low,
                np.asarray(g["window"]), np.asarray(g["k"]), cost=1e-3),
        ),
        "rsi": strat_case(
            "rsi",
            sweep.product_grid(
                period=jnp.arange(5, 45, 2, dtype=jnp.float32),
                band=jnp.linspace(10.0, 30.0, 4).astype(jnp.float32)),
            lambda g: fused.fused_rsi_sweep(
                panel.close, np.asarray(g["period"]), np.asarray(g["band"]),
                cost=1e-3),
        ),
        "macd": strat_case(
            "macd",
            sweep.product_grid(
                fast=jnp.arange(5, 13, dtype=jnp.float32),
                slow=jnp.arange(20, 52, 8, dtype=jnp.float32),
                signal=jnp.asarray([5.0, 9.0], jnp.float32)),
            lambda g: fused.fused_macd_sweep(
                panel.close, np.asarray(g["fast"]), np.asarray(g["slow"]),
                np.asarray(g["signal"]), cost=1e-3),
        ),
        "trix": strat_case(
            "trix",
            sweep.product_grid(
                span=jnp.arange(5, 45, 2, dtype=jnp.float32),
                signal=jnp.asarray([4.0, 9.0], jnp.float32)),
            lambda g: fused.fused_trix_sweep(
                panel.close, np.asarray(g["span"]), np.asarray(g["signal"]),
                cost=1e-3),
        ),
        "obv": strat_case(
            "obv_trend",
            sweep.product_grid(
                window=jnp.arange(5, 85, 2, dtype=jnp.float32)),
            lambda g: fused.fused_obv_sweep(
                panel.close, panel.volume, np.asarray(g["window"]),
                cost=1e-3),
        ),
        "pairs": (
            # Chunked generic reference: the unchunked vmap materializes the
            # whole (pairs, P, T) hysteresis-scan tree at once — several GB
            # at verify scale, which crashes/OOMs the chip.
            lambda: pairs.chunked_pairs_sweep(y_close, x_close, pgrid,
                                              param_chunk=40, cost=1e-3),
            lambda: fused.fused_pairs_sweep(
                y_close, x_close, np.asarray(pgrid["lookback"]),
                np.asarray(pgrid["z_entry"]), cost=1e-3),
        ),
    }
    # Per-kernel error budgets (utils.verify — the one table chip_smoke.py
    # asserts too): numeric regressions FAIL the verify run loudly
    # instead of drifting across rounds.
    over_budget = []
    for name, (run_ref, run_fused) in cases.items():
        row = verify_mod.compare_sharpe(name, run_ref().sharpe,
                                        run_fused().sharpe)
        out[name] = row
        status = ""
        if row["over_budget"]:
            over_budget.append(name)
            status = (f"  OVER BUDGET (flip_rate cap {row['flip_cap']:.4f}, "
                      f"argmax cap {row['argmax_cap']})")
        print(f"verify[{name}]: {row['entry_flips']}/{row['cells']} entry "
              f"flips ({row['nan_mismatches']} NaN), "
              f"{row['best_param_flips']}/{row['n_tickers']} "
              f"best-param flips{status}", file=sys.stderr)
    out["over_budget"] = over_budget
    print(json.dumps(out))
    if over_budget:
        sys.exit(f"bench --verify: kernels over their error budget: "
                 f"{', '.join(over_budget)} — a numeric regression, not "
                 "drift; see DESIGN.md 'Fused-kernel error budgets'")


if __name__ == "__main__":
    extra = [a for a in sys.argv[1:] if a != "--verify"]
    if extra:
        # An unrecognized flag (--help included) must NOT fall through to
        # the full 20-minute bench run.
        sys.exit(
            "usage: python bench.py [--verify]\n"
            "  (no flag)  full throughput bench; prints one JSON line\n"
            "  --verify   on-chip fused-vs-generic parity sweep\n"
            "config via env: DBX_BENCH_TICKERS/BARS/PARAMS/ITERS/WARMUP, "
            "DBX_BENCH_CONFIGS=name,name,...")
    if "--verify" in sys.argv[1:]:
        verify()
    else:
        main()
