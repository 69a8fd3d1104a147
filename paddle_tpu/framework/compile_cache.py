"""One compile-management layer: every compiled-executable cache in the
repo keys, stores, counts and (optionally) AOT-serializes through here.

Seven separately-invented executable caches accreted between PR 1 and
PR 13 — the eager-dispatch SignatureLRU (ops/dispatch.py), the fused
optimizer's aval-keyed step cache (optimizer/optimizer.py), the
StandaloneModel per-shape call cache (inference/export.py), the serving
prefill ladder plus the paged engine's decode/chunk/copy/verify/draft
executables (inference/serving.py, inference/speculative.py), the
reducer's pinned/unpinned mesh collectives (distributed/reducer.py) and
the donated model-parallel train step (distributed/auto/engine.py).
Each invented its own keying, bounds and counters.  This module is the
single service they all ride now:

* **sites** — :func:`site` returns a bounded-LRU :class:`Site` whose
  hits/builds/evictions count into the ONE ``compile.*`` registry
  family (per-site build counters ride the same family as
  ``compile.<site>_builds``).  Legacy per-family counters
  (``dispatch_cache.*``, ``fused_step.compiles``,
  ``serving.*_compiles``) remain as **aliases**: the owning module
  passes a ``legacy_inc`` adapter so its historical counters keep
  moving — fed by this layer, never double-booked.
* **donation-aware keying** — :func:`make_key` folds the executable's
  ``donate_argnums`` into the key, so a donated and a non-donated
  build of the same signature can never collide (calling a donated
  executable with live buffers consumes them; collision would be
  memory corruption, not a perf bug).
* **bucket-ladder policy** — :func:`pow2_ladder` / :func:`pick_bucket`
  / :func:`next_pow2`: the shared shape-bucketing maths the serving
  prefill ladder and the dynamic-batch StandaloneModel both use.
* **persistent-cache integration** — :func:`enable_persistent_cache`
  delegates to framework/jax_compat.py, which alone decides the
  directory (``JAX_COMPILATION_CACHE_DIR``, else
  ``PADDLE_JIT_CACHE_DIR``, else an entry point's default); the
  jax monitoring listener's ``compile.persistent_cache_*`` counters are
  absorbed into the same family.
* **AOT-serialized executables** (the production win) — with
  ``PADDLE_AOT_CACHE_DIR`` set, a site given a cross-process-stable
  ``stable_key`` serializes each executable it builds
  (``jax.experimental.serialize_executable`` via jax_compat) into a
  shared artifact directory, and a FRESH process loads it back with
  **zero XLA compiles** — no trace, no lowering, no backend compile
  (the persistent compilation cache still pays trace+lowering per
  executable and fires a backend-compile event per cache hit).  That
  is the fleet cold-start path: a replacement replica serves its first
  token from yesterday's executables.  Artifacts are self-describing
  (jax version, backend, key, payload digest); a corrupt, stale or
  mismatched artifact is REJECTED and the site degrades to today's
  build/persistent-cache path — an artifact problem can never crash
  serving, only slow its boot.

Artifacts are pickles — load them only from directories you trust
(the same trust model as the checkpoint directory).
"""
from __future__ import annotations

import collections
import hashlib
import os
import pickle
import threading

from ..observability import metrics as _metrics

ARTIFACT_ENV = "PADDLE_AOT_CACHE_DIR"
_ARTIFACT_MAGIC = "ptl-aot-v1"
_ARTIFACT_SUFFIX = ".aotx"

# one compile.* family: the unified cache counters PLUS the absorbed
# cells other layers already write under compile.* (the timeline
# backend-compile hook's count/seconds, the jax persistent-cache
# monitoring listener's hits/misses/requests) — same registry cells,
# one family view
_DEFAULTS = {
    "hits": 0, "builds": 0, "evictions": 0,
    "aot_hits": 0, "aot_misses": 0, "aot_saves": 0,
    "aot_errors": 0, "aot_stale": 0,
    "count": 0, "seconds": 0,
    "persistent_cache_hits": 0, "persistent_cache_misses": 0,
    "persistent_cache_requests": 0,
}


def _family():
    return _metrics.stats_family("compile", _DEFAULTS)


def compile_stats():
    """The ``compile.*`` family with defaults materialized — what
    ``profiler.fast_path_summary()["compile"]`` reports."""
    return dict(_family())


# --------------------------------------------------------------------------
# keying
# --------------------------------------------------------------------------

def make_key(*parts, donate=(), mesh=None):
    """Build a site key with the donation signature folded in.  A
    donated and a non-donated executable of the same abstract signature
    must NEVER share an entry (the donated one consumes its operand
    buffers), so the donate tuple is part of the identity, not an
    attribute of the value.

    ``mesh`` (ISSUE 15) is the device-mesh topology of a SHARDED
    executable (any hashable — engines pass ``("tp", degree, platform,
    ndevices)``, or ``("pp", stages, "tp", degree, platform,
    ndevices)`` on a pipeline-staged ('pp','tp') mesh, ISSUE 20): a
    tensor-parallel build partitions its program over the mesh and a
    pipeline-staged one additionally bakes the 1F1B stage decomposition
    in, so the same abstract signature on a different topology is
    a different executable.  ``None`` (single-device) keys exactly as
    before, so every pre-TP call site is unchanged — and a pp==1 mesh
    keys identically to its pre-pp tp-only form."""
    key = tuple(parts) + (("donate", tuple(donate)),)
    if mesh is not None:
        key += (("mesh", mesh),)
    return key


def stable_hash(s, n=20):
    """Deterministic short hex digest of a stable-key string — the
    artifact filename, identical across processes and machines."""
    return hashlib.blake2b(s.encode(), digest_size=n).hexdigest()


# --------------------------------------------------------------------------
# bucket-ladder policy (shared shape-bucketing maths)
# --------------------------------------------------------------------------

def next_pow2(n):
    """Smallest power of two >= n (the dynamic-batch pad ladder)."""
    b = 1
    while b < n:
        b *= 2
    return b


def pow2_ladder(lo, hi):
    """lo, 2lo, 4lo, ... capped at (and always including) hi."""
    out = []
    b = lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return tuple(out)


def pick_bucket(n, ladder):
    """Smallest ladder rung >= n; raises ValueError when none fits."""
    for b in ladder:
        if n <= b:
            return b
    raise ValueError(f"no bucket in {ladder} fits size {n}")


# --------------------------------------------------------------------------
# AOT artifact store
# --------------------------------------------------------------------------

_artifact_dir_override = [None]


def set_artifact_dir(path):
    """Programmatically point the AOT store somewhere (None: back to the
    ``PADDLE_AOT_CACHE_DIR`` env).  Returns the previous override."""
    prev = _artifact_dir_override[0]
    _artifact_dir_override[0] = str(path) if path else None
    return prev


def artifact_dir():
    """The active artifact directory, or None (AOT disabled)."""
    return _artifact_dir_override[0] or os.environ.get(ARTIFACT_ENV) or None


class ArtifactStore:
    """One shared artifact directory of serialized executables, keyed by
    the blake2b of a cross-process-stable key string.  Every artifact is
    self-describing (magic, full key, jax version, backend, payload
    digest) and every load re-verifies all of it — a stale (different
    jax/backend), corrupt (digest mismatch, truncated pickle) or
    colliding (different full key) artifact is rejected with a named
    reason, never half-loaded."""

    def __init__(self, root):
        self.root = str(root)

    def _path(self, stable_key):
        return os.path.join(self.root,
                            stable_hash(stable_key) + _ARTIFACT_SUFFIX)

    def _env(self):
        import jax
        return {"jax": jax.__version__,
                "backend": jax.default_backend()}

    def save(self, stable_key, compiled, topology=None):
        """Serialize one AOT-compiled executable; atomic publish (a
        concurrent reader sees the old artifact or the new one, never a
        torn write).  Raises on serialization failure — the caller
        counts and degrades.

        ``topology`` (ISSUE 15) names the device mesh a SHARDED
        executable was compiled for (e.g. ``"tp/2/cpu/2"``, or
        ``"pp/2/tp/2/cpu/4"`` for a pipeline-staged build, ISSUE 20);
        it lands in the artifact header and loads verify it, so a
        sharded binary is never deserialized onto a mismatched mesh —
        a pp x tp stage-loop executable on a tp-only mesh reads back
        ``"stale"``, never a wrong-program dispatch.  ``None`` marks a
        single-device executable — artifacts written before the field
        existed read back as ``None`` too, so they stay valid."""
        from . import jax_compat
        payload = jax_compat.aot_serialize_compiled(compiled)
        rec = dict(self._env())
        rec.update(magic=_ARTIFACT_MAGIC, key=stable_key,
                   topology=topology,
                   digest=hashlib.blake2b(payload, digest_size=20)
                   .hexdigest(),
                   payload=payload)
        os.makedirs(self.root, exist_ok=True)
        path = self._path(stable_key)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump(rec, f)
        os.replace(tmp, path)
        return path

    def _load_record(self, stable_key, topology=None):
        """(record, reason): the VALIDATED artifact record (magic, full
        key, jax/backend env, device topology, payload digest all
        checked) or (None, "miss"|"stale"|"corrupt").  Shared by
        :meth:`load` and :meth:`validate` so the skip-the-warmup
        decision and the actual deserialization can never disagree
        about what counts as loadable."""
        path = self._path(stable_key)
        if not os.path.exists(path):
            return None, "miss"
        try:
            with open(path, "rb") as f:
                rec = pickle.load(f)
            if (not isinstance(rec, dict)
                    or rec.get("magic") != _ARTIFACT_MAGIC):
                return None, "corrupt"
            if rec.get("key") != stable_key:         # digest collision
                return None, "stale"
            env = self._env()
            if (rec.get("jax") != env["jax"]
                    or rec.get("backend") != env["backend"]):
                return None, "stale"
            # mesh attestation (ISSUE 15): a sharded executable only
            # loads onto the exact topology it was compiled for; both
            # sides None = single-device (pre-field artifacts included)
            if rec.get("topology") != topology:
                return None, "stale"
            payload = rec["payload"]
            digest = hashlib.blake2b(payload, digest_size=20).hexdigest()
            if digest != rec.get("digest"):
                return None, "corrupt"
            return rec, None
        except Exception:                                  # noqa: BLE001
            # truncated/garbage pickle: never crash the boot
            return None, "corrupt"

    def validate(self, stable_key, topology=None):
        """Full header+digest validation WITHOUT deserializing the
        executable — the warmup skip-this-compile-wave probe."""
        rec, reason = self._load_record(stable_key, topology=topology)
        return rec is not None, reason

    def load(self, stable_key, topology=None):
        """(callable, reason): the deserialized executable and None, or
        (None, "miss"|"stale"|"corrupt") — the caller maps reasons onto
        the aot_* counters and falls back to building."""
        rec, reason = self._load_record(stable_key, topology=topology)
        if rec is None:
            return None, reason
        try:
            from . import jax_compat
            return jax_compat.aot_deserialize_compiled(rec["payload"]), \
                None
        except Exception:                                  # noqa: BLE001
            # xla rejecting the binary: an artifact problem must never
            # crash the boot
            return None, "corrupt"


def _store():
    d = artifact_dir()
    if d is None:
        return None
    return ArtifactStore(d)


def artifact_ready(stable_key, topology=None):
    """Will a lazy load of this key actually succeed?  Validates the
    artifact header + payload digest (jax version, backend, full key,
    device topology) WITHOUT deserializing the executable.  Engines use
    it to skip warmup compile waves — a merely-EXISTING but
    stale/corrupt artifact (shared dir after a jax upgrade, or a
    sharded artifact from a different mesh) must NOT skip the wave that
    would have compiled the real executable, or the compile lands in
    live traffic instead of boot."""
    store = _store()
    if store is None:
        return False
    ok, _reason = store.validate(stable_key, topology=topology)
    return ok


# --------------------------------------------------------------------------
# the cache sites
# --------------------------------------------------------------------------

class Site:
    """One bounded LRU of compiled executables.  ``site()`` returns a
    FRESH instance per call — entries are per-owner (two engines must
    not share executables whose builders close over different configs)
    while the counters are shared by family key.

    ``get(key, build)`` returns the cached executable or acquires one:
    from the AOT artifact store when ``stable_key`` names an artifact
    (zero compiles), else by calling ``build()`` — and, when
    ``example_args`` are supplied with an active store, the built
    executable is AOT-compiled and serialized for the NEXT process.
    ``legacy_inc(event)`` (event: "build" | "hit") feeds the owning
    module's historical counters; a "build" fires once per executable
    ACQUIRED (artifact load included — ``decode_compiles == 1`` counts
    executables owned, not XLA invocations; ``compile.count`` is the
    XLA-invocation truth)."""

    def __init__(self, name, maxsize=64, legacy_inc=None):
        self.name = str(name)
        self.maxsize = int(maxsize)
        self.entries = collections.OrderedDict()
        self.lock = threading.Lock()
        self.legacy_inc = legacy_inc
        self._stats = _family()
        self._builds_key = self.name.replace(".", "_") + "_builds"

    def __len__(self):
        with self.lock:
            return len(self.entries)

    def clear(self):
        with self.lock:
            self.entries.clear()

    # ------------------------------------------------------ raw LRU ops
    def lookup(self, key):
        """Cached value or None; a hit counts and refreshes LRU order.
        May raise TypeError on an unhashable key — callers owning a
        fallback policy (eager dispatch) catch it."""
        with self.lock:
            e = self.entries.get(key)
            if e is not None:
                self.entries.move_to_end(key)
                self._stats.inc("hits")
                if self.legacy_inc is not None:
                    self.legacy_inc("hit")
            return e

    def insert(self, key, value, count_build=True):
        evicted = 0
        with self.lock:
            self.entries[key] = value
            self.entries.move_to_end(key)
            while len(self.entries) > self.maxsize:
                self.entries.popitem(last=False)
                self._stats.inc("evictions")
                evicted += 1
        if evicted and self.legacy_inc is not None:
            for _ in range(evicted):
                self.legacy_inc("evict")
        if count_build:
            self._stats.inc("builds")
            self._stats.inc(self._builds_key)
            if self.legacy_inc is not None:
                self.legacy_inc("build")
        return value

    # ---------------------------------------------------- the main API
    def get(self, key, build, *, stable_key=None, example_args=None,
            topology=None):
        """The one acquisition path.  ``build`` runs OUTSIDE the lock
        (tracing re-enters arbitrary code); a racing double-build costs
        one redundant trace, never a wrong result — last insert wins.
        ``topology`` is the sharded-executable mesh attestation threaded
        into the artifact header (None for single-device)."""
        e = self.lookup(key)
        if e is not None:
            return e
        fn = None
        store = _store() if stable_key else None
        if store is not None:
            fn, reason = store.load(stable_key, topology=topology)
            if fn is not None:
                self._stats.inc("aot_hits")
            elif reason == "miss":
                self._stats.inc("aot_misses")
            else:
                self._stats.inc("aot_errors")
                if reason == "stale":
                    self._stats.inc("aot_stale")
        if fn is None:
            fn = build()
            if store is not None and example_args is not None:
                fn = self._aot_save(store, stable_key, fn, example_args,
                                    topology)
        return self.insert(key, fn)

    def _aot_save(self, store, stable_key, fn, example_args,
                  topology=None):
        """AOT-compile ``fn`` against the example operands and publish
        the artifact.  Returns the AOT executable (so the warm process
        doesn't trace twice); any failure returns ``fn`` unchanged —
        the artifact path degrades, never breaks."""
        try:
            compiled = fn.lower(*example_args).compile()
            store.save(stable_key, compiled, topology=topology)
            self._stats.inc("aot_saves")
            return compiled
        except Exception:                                  # noqa: BLE001
            self._stats.inc("aot_errors")
            return fn


def site(name, maxsize=64, legacy_inc=None):
    """A fresh cache site counting into the shared ``compile.*``
    family.  Per-owner: call once per owning object, not per lookup."""
    return Site(name, maxsize=maxsize, legacy_inc=legacy_inc)


class SignatureLRU(Site):
    """Back-compat shim for the PR-5 API (``ops.dispatch.SignatureLRU``
    re-exports this): the old ``stats``/``compile_key``/``hit_key``
    constructor mapped onto a :class:`Site` whose legacy adapter feeds
    those counters.  New call sites should use :func:`site` with an
    explicit ``legacy_inc``."""

    def __init__(self, maxsize=64, stats=None, compile_key="compiles",
                 hit_key=None, name=None):
        def legacy(event):
            if event == "build":
                stats.inc(compile_key)
            elif event == "hit" and hit_key:
                stats.inc(hit_key)
        super().__init__(name or f"lru.{compile_key}",
                         maxsize=maxsize,
                         legacy_inc=legacy if stats is not None else None)


# --------------------------------------------------------------------------
# persistent-cache integration
# --------------------------------------------------------------------------

def enable_persistent_cache(default_dir=None):
    """Delegates to jax_compat, which alone decides the directory
    (``JAX_COMPILATION_CACHE_DIR``, then ``PADDLE_JIT_CACHE_DIR``, then
    ``default_dir``); the monitoring listener's
    ``compile.persistent_cache_*`` counters are cells of this module's
    family."""
    from . import jax_compat
    return jax_compat.enable_persistent_cache(default_dir)
