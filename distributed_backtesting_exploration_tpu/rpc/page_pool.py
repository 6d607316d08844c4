"""Device-resident OHLCV page pool (ragged paged panel batching).

The worker's :class:`~.compute.PanelCache` caches whole ``(5, T)`` field
blocks per panel digest — which duplicates an append-extended panel's
entire history next to its base and shares nothing between overlapping
histories. This module is the third cache level that fixes both: field
data is stored as fixed-size **T-pages** (``DBX_PAGE_BARS`` bars each,
default 512) in ONE device-resident ``(capacity, page_bars)`` f32 pool,
and a sweep group is described by a per-job **page table** of int32 slot
indices into that pool — the paged-KV discipline of PAPERS.md "Ragged
Paged Attention" applied to OHLCV panels, with the pool kept
block-decomposed and never materialized densely per panel (the "Large
Scale Distributed Linear Algebra With TPUs" discipline).

Addressing: a page is keyed by the blake2b-64 hash of its (repeat-last
padded) bytes, and a ``(panel_digest, field)`` memo maps a panel to its
key list. Content keys are what make sharing structural rather than
special-cased:

- an append-extended panel (PR 6 delta chains) reuses **all of its
  base's full pages** — only the boundary page (whose tail changed from
  pad to real bars) and the new tail pages upload, O(ΔT/page_bars + 1)
  instead of O(T);
- two digests with overlapping histories (the same listing fetched at
  different dates, scenario twins sharing a base) share every aligned
  identical page, so device bytes grow sublinearly in ticker count.

Bounded by ``DBX_PAGE_POOL_MB`` where it is set, else by an eighth of
the device's memory (its ``memory_stats()`` limit; 64 MB on a device
that reports none), with LRU slot reuse; the pool array grows
geometrically up to the bound, so idle workers do not pin the full
budget. Uploads batch all of a group's missing pages into
one donated scatter (in-place on backends with buffer donation), and a
group whose working set cannot fit — or whose pages would evict each
other mid-assembly — is REJECTED (``prepare`` returns None) so the
caller falls back to the dense path instead of thrashing.

NOTE on the functional pool array: ``prepare()`` returns the pool as a
jax array; an upload donates the previous array, so callers must always
gather from the MOST RECENT returned pool (holding an older one across
a later uploading ``prepare`` raises on use — by design, not a leak).
Gathers launched before the upload are unaffected (functional arrays).

Threading contract: ``prepare()`` is single-writer — only the worker's
compute thread calls it (the same contract as the backend's submit
path). The index lock exists for the stats surface, which is scraped
from the gRPC thread; the device upload itself runs outside it so a
cold upload (or its first-call jit compile) can never stall a metrics
scrape.
"""

from __future__ import annotations

import collections
import hashlib
import os
import threading

import numpy as np

from .. import obs
from ..ops.fused import resolve_page_bars

_DEFAULT_POOL_MB = 64
# The share of the device's memory the pool may take where
# DBX_PAGE_POOL_MB is unset: an eighth, beside the third one launch may
# take (rpc.compute.JaxSweepBackend._HBM_BUDGET) and the results in
# flight. A universe of long histories then stays resident (500 x
# 98,280 minute bars of close and volume are 393 MB of pages; the v5e's
# eighth is ~2 GB) instead of re-uploading every job's pages.
_DEVICE_SHARE = 8
_MIN_SLOTS = 8              # smallest useful pool (growth floor)
_PANEL_MEMO_CAP = 16384     # (digest, field) -> page-key lists retained


def pool_max_bytes() -> int:
    """Pool byte bound, read lazily (import-time env capture would pin
    the knob before tests/operators can set it): ``DBX_PAGE_POOL_MB``
    where set, else ``1 / _DEVICE_SHARE`` of the first local device's
    memory limit, and never under the 64 MB default."""
    raw = os.environ.get("DBX_PAGE_POOL_MB")
    if raw is not None:
        return int(float(raw) * 1024 * 1024)
    return max(_DEFAULT_POOL_MB * 1024 * 1024,
               device_memory_limit() // _DEVICE_SHARE)


def device_memory_limit() -> int:
    """Bytes the first local device lets a process hold
    (``memory_stats()["bytes_limit"]``), or 0 where it reports none (the
    CPU)."""
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", 0))


def page_key(page_bytes: bytes) -> str:
    """blake2b-64 hex of a page's padded bytes — the pool's content
    address. Content (not (digest, page_idx)) keying is what lets an
    append chain reuse its base's full pages and overlapping histories
    share across digests."""
    return hashlib.blake2b(page_bytes, digest_size=8).hexdigest()


def paginate(values: np.ndarray, page_bars: int) -> list[np.ndarray]:
    """Split a 1-D f32 series into ``page_bars``-sized pages; the final
    partial page is repeat-last padded to full width so page content is
    canonical (two panels sharing a full-page prefix hash identically)
    and pad bars inside a page already obey the kernels' repeat-last
    discipline."""
    v = np.ascontiguousarray(np.asarray(values, np.float32))
    out = []
    for s in range(0, v.shape[0], page_bars):
        page = v[s:s + page_bars]
        if page.shape[0] < page_bars:
            page = np.concatenate(
                [page, np.full(page_bars - page.shape[0], page[-1],
                               np.float32)])
        out.append(page)
    return out


class PagePool:
    """Byte-bounded device pool of fixed-size f32 T-pages + host index."""

    def __init__(self, *, page_bars: int | None = None,
                 max_bytes: int | None = None,
                 registry: "obs.Registry | None" = None):
        self.page_bars = int(page_bars if page_bars is not None
                             else resolve_page_bars())
        self.max_bytes = (pool_max_bytes() if max_bytes is None
                          else int(max_bytes))
        page_nbytes = self.page_bars * 4
        self.capacity = max(1, self.max_bytes // page_nbytes)
        self._lock = threading.Lock()
        # Writer serialization (round 14): `prepare` now has TWO callers —
        # the compute thread's submit path and the worker control loop's
        # prefetch warm-up — so whole-prepare runs (index mutation +
        # device upload + pool swap) serialize on this outer lock. The
        # inner `_lock` still guards only the host index, so a stats
        # scrape never waits behind a device upload/compile. Acquisition
        # order is always _write_lock -> _lock.
        self._write_lock = threading.Lock()
        self._pool = None                 # (alloc, page_bars) f32 device
        self._alloc = 0                   # allocated slots (grows to cap)
        self._slots: collections.OrderedDict = collections.OrderedDict()
        #   page key -> slot, LRU-ordered (most recent last)
        self._free: list[int] = []
        self._panel_memo: collections.OrderedDict = collections.OrderedDict()
        #   (panel_digest, field) -> list[page key]
        self._scatter = None
        reg = registry or obs.get_registry()
        self._reg = reg
        # Pre-created for the full (bounded) OHLCV column vocabulary so
        # the /metrics surface is stable from the first scrape — the
        # PanelCache discipline.
        self._c_hits: dict = {}
        self._c_misses: dict = {}
        for fld in ("open", "high", "low", "close", "volume"):
            self._hit_counter(fld, True)
            self._hit_counter(fld, False)
        self._c_rejects = reg.counter(
            "dbx_page_pool_rejects_total",
            help="groups the page pool could not hold (caller fell back "
                 "to the dense path)")
        self._g_bytes = reg.gauge(
            "dbx_page_pool_bytes",
            help="bytes of live pages in the device page pool")
        self._g_pages = reg.gauge(
            "dbx_page_pool_pages", help="live pages in the device page pool")

    # Bounded label vocabulary: OHLCV column names only (the fused specs'
    # ``fields`` tuples), never runtime ids.
    def _hit_counter(self, field: str, hit: bool):
        table = self._c_hits if hit else self._c_misses
        c = table.get(field)
        if c is None:
            name = ("dbx_page_pool_hits_total" if hit
                    else "dbx_page_pool_misses_total")
            c = table[field] = self._reg.counter(
                name, help="page-pool page lookups by OHLCV field "
                           "(hit = page already device-resident)",
                field=field)
        return c

    def _publish(self) -> None:
        self._g_pages.set(len(self._slots))
        self._g_bytes.set(len(self._slots) * self.page_bars * 4)

    def _keys_for(self, digest: str, field: str, values) -> list[str]:
        """Page keys of one panel leg, memoized per (digest, field) so a
        cache-hot panel costs zero hashing. Digestless panels hash every
        time (no stable memo key — correct, just slower)."""
        memo_key = (digest, field) if digest else None
        if memo_key is not None:
            keys = self._panel_memo.get(memo_key)
            if keys is not None and keys[0] == len(values):
                self._panel_memo.move_to_end(memo_key)
                return keys[1]
        pages = paginate(values, self.page_bars)
        keys = [page_key(p.tobytes()) for p in pages]
        if memo_key is not None:
            self._panel_memo[memo_key] = (len(values), keys)
            while len(self._panel_memo) > _PANEL_MEMO_CAP:
                self._panel_memo.popitem(last=False)
        return keys

    def _ensure_alloc(self, n_slots: int):
        """Grow the device array geometrically up to ``capacity``.
        Called with ``self._lock`` HELD (a ``prepare`` helper)."""
        import jax.numpy as jnp

        if n_slots <= self._alloc:
            return
        new_alloc = max(_MIN_SLOTS, self._alloc or _MIN_SLOTS)
        while new_alloc < n_slots:
            new_alloc *= 2
        new_alloc = min(new_alloc, self.capacity)
        new = jnp.zeros((new_alloc, self.page_bars), jnp.float32)
        if self._pool is not None and self._alloc:
            new = new.at[:self._alloc].set(self._pool)
        # No suppression needed: dbxlint's interprocedural lock-discipline
        # proves every caller path (prepare/_take_slot) holds the lock.
        self._free.extend(range(self._alloc, new_alloc))
        self._pool = new
        self._alloc = new_alloc

    def _take_slot(self, pinned: set) -> int | None:
        """A free slot, growing the pool or evicting the least-recently
        used unpinned page; None when every live page is pinned (the
        current group itself cannot fit). Called with ``self._lock``
        HELD (a ``prepare`` helper)."""
        if not self._free and self._alloc < self.capacity:
            self._ensure_alloc(self._alloc + 1)
        if self._free:
            return self._free.pop()
        victim = next((k for k in self._slots if k not in pinned), None)
        if victim is None:
            return None
        return self._slots.pop(victim)

    def _upload(self, pool, slots: list[int], pages: list[np.ndarray]):
        """Batched scatter of missing pages into ``pool``; padded to a
        power-of-two page count so the jit signature set stays bounded.
        NON-donating (round 14): a caller's sweep dispatches its gather
        against the pool array its own ``prepare`` returned, OUTSIDE any
        lock — a donating scatter from the other writer (the control
        loop's prefetch warm-up) would delete that array between return
        and dispatch. The old buffer lives until its in-flight readers
        drop it; the transient double allocation is bounded by one pool.
        Runs OUTSIDE the index lock — see ``prepare``."""
        import jax
        import jax.numpy as jnp

        if self._scatter is None:
            self._scatter = jax.jit(lambda pool, s, p: pool.at[s].set(p))
        k = len(slots)
        k_pad = 1 << (k - 1).bit_length()
        slots = slots + [slots[-1]] * (k_pad - k)
        pages = pages + [pages[-1]] * (k_pad - k)
        return self._scatter(
            pool, jnp.asarray(np.asarray(slots, np.int32)),
            jnp.asarray(np.stack(pages)))

    def prepare(self, digests, series_list, fields):
        """Resolve a sweep group against the pool.

        ``digests``/``series_list`` are per-job panel digests and decoded
        panels; ``fields`` the OHLCV columns the kernel consumes. Returns
        ``(pool_array, tables, info)`` where ``tables[field]`` is the
        ``(n, max_pages)`` int32 slot table (short rows padded with their
        own last slot — the values there are dead under the assembly's
        repeat-last fix) and ``info`` counts newly uploaded pages and
        their in-page pad bars; or None when the group cannot fit
        (caller falls back to the dense path).

        Thread-safe for its two writers (compute submit + control-loop
        prefetch): whole runs serialize on ``_write_lock``, so a
        returned pool array always contains every page its tables
        reference, and concurrent warm-ups cannot interleave half an
        index update with another group's upload.
        """
        with self._write_lock:
            return self._prepare_serialized(digests, series_list, fields)

    def _prepare_serialized(self, digests, series_list, fields):
        with self._lock:
            per_field_keys: dict[str, list[list[str]]] = {f: []
                                                          for f in fields}
            needed: collections.OrderedDict = collections.OrderedDict()
            #   key -> (field, values, page_idx) for pages to build on miss
            hits: dict[str, int] = {f: 0 for f in fields}
            miss: dict[str, int] = {f: 0 for f in fields}
            for d, s in zip(digests, series_list):
                for f in fields:
                    values = np.asarray(getattr(s, f), np.float32)
                    keys = self._keys_for(d, f, values)
                    per_field_keys[f].append(keys)
                    for pi, key in enumerate(keys):
                        if key in self._slots:
                            if key not in needed:
                                hits[f] += 1
                        elif key not in needed:
                            miss[f] += 1
                        needed.setdefault(key, (f, values, pi))
            if len(needed) > self.capacity:
                self._c_rejects.inc()
                return None
            pinned = set(needed)
            # Allocate slots for misses (evicting only unpinned LRU).
            new_slots: list[int] = []
            new_keys: list[str] = []
            new_pages: list[np.ndarray] = []
            pad_new = 0
            for key, (f, values, pi) in needed.items():
                if key in self._slots:
                    self._slots.move_to_end(key)
                    continue
                slot = self._take_slot(pinned)
                if slot is None:         # cannot happen after the cap
                    self._c_rejects.inc()  # check, but stay defensive
                    for k in new_keys:   # unwind this group's part-insert
                        self._free.append(self._slots.pop(k))
                    return None
                lo = pi * self.page_bars
                page = paginate(values[lo:lo + self.page_bars],
                                self.page_bars)[0]
                pad_new += self.page_bars - min(
                    self.page_bars, len(values) - lo)
                self._slots[key] = slot
                new_slots.append(slot)
                new_keys.append(key)
                new_pages.append(page)
            for f in fields:
                if hits[f]:
                    self._hit_counter(f, True).inc(hits[f])
                if miss[f]:
                    self._hit_counter(f, False).inc(miss[f])
            if not new_slots and self._pool is None:
                self._ensure_alloc(_MIN_SLOTS)   # empty pool, warm group
            max_pages = max(
                (len(k) for ks in per_field_keys.values() for k in ks),
                default=1)
            tables = {}
            for f in fields:
                tbl = np.zeros((len(series_list), max_pages), np.int32)
                for i, keys in enumerate(per_field_keys[f]):
                    row = [self._slots[k] for k in keys]
                    tbl[i, :len(row)] = row
                    tbl[i, len(row):] = row[-1]   # dead under repeat-last
                tables[f] = tbl
            self._publish()
            pool = self._pool
        # Device upload OUTSIDE the index lock: the scatter dispatch (and
        # its first-call jit compile, seconds per pow2 shape class) must
        # not stall a concurrent /metrics or GetStats scrape blocking on
        # stats(). Safe under the pool's writer-serialization contract:
        # every prepare() holds `_write_lock` end to end, and stats()
        # never reads `_pool` — only the index updated above.
        if new_slots:
            pool = self._upload(pool, new_slots, new_pages)
            # Writer-serialized (caller holds _write_lock end to end);
            # the index lock guards stats(), which never reads the array.
            # dbxlint: disable=lock-discipline -- writer-serialized under _write_lock
            self._pool = pool
        return pool, tables, {"pages_new": len(new_slots),
                              "pad_bars_new": int(pad_new)}

    def stats(self) -> dict:
        with self._lock:
            return {"pages": len(self._slots),
                    "bytes": len(self._slots) * self.page_bars * 4,
                    "page_bars": self.page_bars,
                    "alloc_slots": self._alloc,
                    "capacity_slots": self.capacity,
                    "max_bytes": self.max_bytes}
