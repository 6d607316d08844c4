"""Fleet-shared persistent XLA compile cache (runtime half of tune/).

Two cooperating layers:

1. **Local persistent cache** — :func:`configure` turns on JAX's
   persistent compilation cache at the one directory
   :func:`default_cache_dir` resolves (tests/conftest.py, bench.py,
   chip_smoke.py and the dispatcher/worker mains all call it). Every cache entry is keyed
   by jax's own HLO/config hash, so re-runs of unchanged kernels skip
   straight to execution.

2. **Fleet exchange** — the dispatcher hosts a byte-bounded
   :class:`CompileStore` of cache entries and two RPCs ride the PR-5
   content-addressing discipline: workers ``OfferCompiled`` entries their
   local compiles just wrote, and ``FetchCompiled`` the listing + any
   entries they lack, installing them into their local cache dir BEFORE
   jax looks — a cold worker's first sweep then hits the persistent cache
   and skips compilation entirely when any peer has compiled that kernel
   before. :class:`CacheSync` is the worker-side scanner/installer.

Wire keys are ``blake2b-128(file name | jax version | backend platform)``:
the file name already IS jax's content hash of (serialized HLO, compile
options — which fold the substrate tuple via the jit static args), and
folding the jax version + platform keeps entries from one generation or
chip type from ever being installed into another's cache. Payloads are
opaque bytes; a corrupt or irrelevant entry is at worst an unused file
jax ignores (its own integrity checks re-compile on mismatch) — the
degradation ladder never fails a job.
"""

from __future__ import annotations

import hashlib
import logging
import os
import tempfile
import threading

from .. import obs

log = logging.getLogger("dbx.tune")

_DEFAULT_STORE_MB = 256
# Entries larger than this never cross the wire (a single pathological
# executable must not evict the whole fleet store).
_MAX_ENTRY_BYTES = 64 * 1024 * 1024


def compile_store_max_bytes() -> int:
    """``DBX_COMPILE_CACHE_MB`` store bound (lazy read, default 256 MB)."""
    return int(float(os.environ.get("DBX_COMPILE_CACHE_MB",
                                    _DEFAULT_STORE_MB)) * 1024 * 1024)


# The package's parent: the checkout root (`<checkout>/.jax_cache` is
# listed in .gitignore).
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """THE persistent compile cache directory: ``JAX_COMPILATION_CACHE_DIR``
    when the environment sets it, else ``<checkout>/.jax_cache`` — a fixed
    path, so a restarted worker finds its own entries."""
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def configure(*, min_compile_time_s: float = 0.5,
              min_entry_bytes: int = 0) -> str | None:
    """Turn on JAX's persistent compilation cache at
    :func:`default_cache_dir` — the one resolver worker, dispatcher,
    bench, the test harness and ``chip_smoke.py`` share. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and no
    directory is set in code. Returns the directory, or None when jax
    refuses the options — callers then compile uncached, never fail.

    jax's XLA sub-caches stay off: jax places them inside the cache
    directory and folds their paths into every entry's key, so two
    workers whose directories differ (checkouts at different paths)
    would never hit each other's entries, fleet-exchanged or not."""
    path = default_cache_dir()
    try:
        import jax

        if not os.environ.get(_ENV):
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_compile_time_s)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          min_entry_bytes)
    except Exception:
        log.exception("persistent compile cache left off")
        return None
    return path


def attach(registry: "obs.Registry | None" = None) -> "CacheSync | None":
    """A :class:`CacheSync` on the jax cache dir ALREADY configured in
    this process (a test harness's or operator's choice is respected),
    configuring the default dir only when none is set. None when jax is
    unusable — the worker then simply runs uncached."""
    path = None
    try:
        import jax
        path = getattr(jax.config, "jax_compilation_cache_dir", None)
    except Exception:   # pragma: no cover - jax is baked into the image
        return None
    if not path:
        path = configure()
    if not path:
        return None
    return CacheSync(path, registry=registry)


def _runtime_tag() -> str:
    try:
        import jax
        version = jax.__version__
        platform = jax.default_backend()
    except Exception:   # pragma: no cover - jax is baked into the image
        version, platform = "nojax", "none"
    return f"{version}|{platform}"


def entry_key(name: str, runtime_tag: str | None = None) -> str:
    """Fleet wire key of one cache entry: blake2b-128 over the cache file
    name (jax's own hash of the serialized HLO + compile options, which
    already fold the substrate tuple through the jit static args) plus
    the jax version and backend platform — entries never travel across
    generations or chip types."""
    tag = _runtime_tag() if runtime_tag is None else runtime_tag
    return hashlib.blake2b(f"{name}|{tag}".encode(),
                           digest_size=16).hexdigest()


class CompileStore:
    """Dispatcher-side bounded LRU of fleet compile-cache entries.

    Values are ``(name, payload)`` — the worker needs the original file
    name to install under (jax looks entries up by name). Thread-safe:
    Offer/Fetch handlers run on the gRPC pool.
    """

    def __init__(self, max_bytes: int | None = None,
                 registry: "obs.Registry | None" = None):
        from ..rpc.panel_store import ByteLRU

        self._lock = threading.Lock()
        self._lru = ByteLRU(compile_store_max_bytes()
                            if max_bytes is None else int(max_bytes),
                            nbytes_of=lambda v: len(v[1]))
        reg = registry or obs.get_registry()
        self._c_offers = reg.counter(
            "dbx_compile_offers_total",
            help="compile-cache entries accepted from workers")
        self._c_fetch = {
            outcome: reg.counter(
                "dbx_compile_fetches_total",
                help="FetchCompiled entry requests served, by outcome",
                outcome=outcome)
            for outcome in ("hit", "gone")}

    def offer(self, key: str, name: str, payload: bytes) -> bool:
        if not key or not name or not payload \
                or len(payload) > _MAX_ENTRY_BYTES:
            return False
        with self._lock:
            if key in self._lru:
                return False
            self._lru.put(key, (name, payload))
        self._c_offers.inc()
        return True

    def get(self, key: str):
        """``(name, payload)`` or None (evicted/never offered)."""
        with self._lock:
            v = self._lru.get(key)
        self._c_fetch["hit" if v is not None else "gone"].inc()
        return v

    def keys(self) -> list[str]:
        with self._lock:
            return list(self._lru._entries.keys())

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._lru), "bytes": self._lru.bytes,
                    "evictions": self._lru.evictions,
                    "max_bytes": self._lru.max_bytes}


class CacheSync:
    """Worker-side cache-dir scanner / installer (control thread only).

    Accounting contract (the ``dbx_compile_cache_{hits,misses}_total``
    families):

    - ``hits{source="local"}``  — entries already on local disk when the
      sync attached (the persistent cache pre-warmed across restarts);
    - ``misses{source="local"}`` — new files appearing from THIS process's
      own compiles (each one is a compile wall actually paid locally);
    - ``hits{source="fleet"}``  — entries installed from a peer via the
      dispatcher (a compile wall skipped entirely);
    - ``misses{source="fleet"}`` — entries requested from the dispatcher
      that came back unservable (evicted or never offered).
    """

    def __init__(self, cache_dir: str | None = None,
                 registry: "obs.Registry | None" = None,
                 runtime_tag: str | None = None):
        self.cache_dir = cache_dir or default_cache_dir()
        self._tag = _runtime_tag() if runtime_tag is None else runtime_tag
        self._key_to_name: dict[str, str] = {}
        self._seen_names: set[str] = set()
        # Keys whose entries this worker REFUSED (foreign jax version /
        # platform): remembered so missing() stops re-requesting them —
        # a mixed-generation fleet must not re-download the foreign
        # entry set on every sync tick, forever.
        self._rejected_keys: set[str] = set()
        reg = registry or obs.get_registry()
        self._c = {
            (kind, source): reg.counter(
                f"dbx_compile_cache_{kind}_total",
                help=("persistent-compile-cache entries, by source "
                      "(local = this worker's own disk/compiles, fleet = "
                      "exchanged through the dispatcher)"),
                source=source)
            for kind in ("hits", "misses")
            for source in ("local", "fleet")}
        try:
            os.makedirs(self.cache_dir, exist_ok=True)
        except OSError:
            pass
        # Pre-warmed entries (e.g. a restart onto its own cache dir):
        # local hits — compiles this process will never pay.
        for name, _ in self._scan():
            self._register(name)
            self._c[("hits", "local")].inc()

    def _scan(self):
        try:
            with os.scandir(self.cache_dir) as it:
                # Dot-files are never cache entries: our own interrupted
                # .dbx_fetch_* temps (and other writers' partials) must
                # not be counted as local compiles or offered under
                # names no peer's jax would ever look up.
                ents = [(e.name, e.stat().st_size) for e in it
                        if e.is_file() and not e.name.startswith(".")]
        except OSError:
            return []
        return sorted(ents)

    def _register(self, name: str) -> str:
        key = entry_key(name, self._tag)
        self._key_to_name[key] = name
        self._seen_names.add(name)
        return key

    def poll_new(self) -> list[tuple[str, str, bytes]]:
        """New cache files since the last poll — local compiles this
        process just paid for — as ``(key, name, payload)`` offers.
        Counted as local misses (the wall was actually spent here)."""
        out = []
        for name, size in self._scan():
            if name in self._seen_names or size > _MAX_ENTRY_BYTES:
                continue
            try:
                with open(os.path.join(self.cache_dir, name), "rb") as fh:
                    payload = fh.read()
            except OSError:
                continue
            key = self._register(name)
            self._c[("misses", "local")].inc()
            out.append((key, name, payload))
        return out

    def unmark(self, entries) -> None:
        """Forget ``(key, name, payload)`` offers whose RPC never reached
        the dispatcher, so the next poll re-offers them (the compile-leg
        twin of the schedule registry's ``remark_dirty``) — a transient
        dispatcher blip must not permanently drop a paid compile wall
        from fleet sharing."""
        for key, name, _payload in entries:
            self._seen_names.discard(name)
            self._key_to_name.pop(key, None)

    def missing(self, known_keys) -> list[str]:
        """The subset of a fleet listing this worker does not hold and
        has not previously refused (foreign runtime tag)."""
        return [k for k in known_keys
                if k and k not in self._key_to_name
                and k not in self._rejected_keys]

    def install(self, entries) -> int:
        """Write fetched ``(key, name, payload)`` entries into the local
        cache dir (atomic tmp+rename; jax picks them up by name on its
        next lookup). Returns entries installed — each one a compile
        skipped: ``hits{source="fleet"}``."""
        n = 0
        for key, name, payload in entries:
            if name in self._seen_names:
                continue
            if (not name or not payload or os.sep in name
                    or name != os.path.basename(name)
                    or name.startswith(".")
                    or key != entry_key(name, self._tag)):
                # Malformed, or a peer on another jax generation / chip
                # type: useless (and possibly harmful) here. Remember
                # the refusal so missing() never re-requests it.
                if len(self._rejected_keys) > 1 << 16:
                    self._rejected_keys.clear()
                self._rejected_keys.add(key)
                continue
            dest = os.path.join(self.cache_dir, name)
            try:
                fd, tmp = tempfile.mkstemp(dir=self.cache_dir,
                                           prefix=".dbx_fetch_")
                with os.fdopen(fd, "wb") as fh:
                    fh.write(payload)
                os.replace(tmp, dest)
            except OSError:
                continue
            self._register(name)
            self._c[("hits", "fleet")].inc()
            n += 1
        return n

    def count_fleet_misses(self, n: int) -> None:
        if n > 0:
            self._c[("misses", "fleet")].inc(n)
