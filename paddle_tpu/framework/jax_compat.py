"""The seam for the jax API surface that moves between releases.

One installation is supported: Python 3.12 with jax/jaxlib 0.9.0 (libtpu
0.0.34 on the chip machine).  Every name below is the spelling that
installation has, called directly — there is no branch for another
release.  The seam itself stays (analysis rule PTL001): callers import
the moving names from here, so the next jax upgrade is repaired in this
file and nowhere else.
"""
from __future__ import annotations

import os

from jax import shard_map  # noqa: F401  (re-exported: the PTL001 seam)


def make_mesh(devices, axis_names):
    """``jax.sharding.Mesh`` over an already-shaped device ndarray.
    Every mesh call site routes through here (standing ROADMAP
    constraint) so a future rename — jax keeps re-homing the sharding
    types — is a one-line fix instead of a repo-wide grep."""
    from jax.sharding import Mesh
    return Mesh(devices, axis_names)


def partition_spec(*parts):
    """``jax.sharding.PartitionSpec`` by the stable import path."""
    from jax.sharding import PartitionSpec
    return PartitionSpec(*parts)


def partition_spec_class():
    """The PartitionSpec TYPE itself — for ``isinstance`` checks and the
    ``P = partition_spec_class()`` module-alias idiom (``P("dp")``
    constructs; ``isinstance(x, P)`` works, which the
    :func:`partition_spec` factory cannot offer)."""
    from jax.sharding import PartitionSpec
    return PartitionSpec


def named_sharding(mesh, spec):
    """``jax.sharding.NamedSharding`` for ``mesh`` and a PartitionSpec
    (or the tuple/None shorthand: ``named_sharding(mesh, ("dp", None))``)."""
    from jax.sharding import NamedSharding, PartitionSpec
    if not isinstance(spec, PartitionSpec):
        spec = PartitionSpec(*spec) if spec is not None else PartitionSpec()
    return NamedSharding(mesh, spec)


def with_sharding_constraint(x, mesh, spec):
    """``jax.lax.with_sharding_constraint`` with the NamedSharding built
    through :func:`named_sharding`."""
    import jax
    return jax.lax.with_sharding_constraint(x, named_sharding(mesh, spec))


def psum_scatter(x, axis_name, scatter_dimension=0, tiled=True):
    """``jax.lax.psum_scatter`` (reduce-scatter inside shard_map) —
    wrapped here because it is a version-moving manual collective like
    shard_map itself."""
    import jax
    return jax.lax.psum_scatter(x, axis_name,
                                scatter_dimension=scatter_dimension,
                                tiled=tiled)


def all_gather(x, axis_name, axis=0, tiled=True):
    """``jax.lax.all_gather`` (the inverse manual-collective of
    :func:`psum_scatter`) — wrapped for the same reason: serving's
    vocab-parallel LM head concatenates per-rank logit shards with it
    (models/gpt_hybrid.py's make_forward idiom, reused by the
    pipeline-stage serving step)."""
    import jax
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def axis_size(axis_name):
    """``jax.lax.axis_size``: the size of a mapped axis, a python int
    inside mapped code."""
    import jax
    return jax.lax.axis_size(axis_name)


def pcast_varying(x, axis_name):
    """``lax.pcast(..., to="varying")``: mark a value as differing per
    rank of a manual axis."""
    import jax
    return jax.lax.pcast(x, axis_name, to="varying")


def tpu_compiler_params(pltpu, **kwargs):
    """``pltpu.CompilerParams``."""
    return pltpu.CompilerParams(**kwargs)


def fp8_dtype():
    """The float8 storage dtype for weight-only quantized serving
    (``PagedServingEngine(quant="fp8")``): ``jnp.float8_e4m3fn`` (e4m3,
    max 448)."""
    import jax.numpy as jnp
    return jnp.float8_e4m3fn


def donation_enabled(env_var):
    """Shared buffer-donation gate: ``env_var`` 0/1 forces, "auto" (the
    default) donates everywhere but CPU, whose donation path only warns.
    Used by the fused optimizer step (``PADDLE_TPU_FUSED_DONATE``) and
    the serving engine's prefill/decode executables
    (``PADDLE_TPU_SERVING_DONATE``)."""
    import jax
    mode = os.environ.get(env_var, "auto")
    if mode == "0":
        return False
    if mode == "1":
        return True
    # a backend that fails to start raises here — "no accelerator" and
    # "the accelerator did not answer" are not the same answer
    return jax.default_backend() != "cpu"


# --------------------------------------------------------------------------
# AOT export / compiled-executable serialization (compile_cache artifacts)
# --------------------------------------------------------------------------

def jax_export_module():
    """The ``jax.export`` module (StableHLO export/deserialize,
    symbolic shapes).  jax has re-homed export twice; every export site
    routes through here so the next move is a one-line fix."""
    from jax import export
    return export


def aot_serialize_compiled(compiled):
    """One pickleable blob for a ``jit(f).lower(...).compile()``
    executable: the xla-serialized binary plus its in/out pytree defs
    (the triple ``serialize_executable.serialize`` returns) and the ids
    of the devices it was compiled for.  Loading it back in a FRESH
    process costs zero traces and zero backend compiles — the whole
    point of the artifact store."""
    import pickle
    from jax.experimental import serialize_executable as _se
    ids = [d.id for d in compiled.runtime_executable().local_devices()]
    return pickle.dumps((*_se.serialize(compiled), ids))


def aot_deserialize_compiled(blob):
    """Inverse of :func:`aot_serialize_compiled`: a callable executable
    bound to this process's devices of the same ids.  jax 0.9.0 loads a
    serialized executable onto EVERY device of the backend unless told
    which ones (``execution_devices``), so a one-device executable
    revived in an eight-device process would demand eight shards per
    argument."""
    import pickle
    import jax
    from jax.experimental import serialize_executable as _se
    payload, in_tree, out_tree, ids = pickle.loads(blob)
    by_id = {d.id: d for d in jax.devices()}
    return _se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in ids])


# --------------------------------------------------------------------------
# Persistent compilation cache
# --------------------------------------------------------------------------

_persistent_cache_dir = [None]


def checkout_cache_dir():
    """``<checkout>/.jax_cache`` (git-ignored): the fixed directory the
    entry-point scripts (``chip_smoke.py``, ``bench.py``,
    ``tools/tpu_kernel_check.py``, ``examples/``) fall back to.  Never a
    temp name, pid or time: the path is part of the cache key, so a
    directory that moves never hits."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(os.path.dirname(here)), ".jax_cache")


def resolve_cache_dir(default_dir=None):
    """Where the persistent compilation cache lives, decided in ONE
    place.  Returns ``(directory or None, placed_by_jax)``:

    1. ``JAX_COMPILATION_CACHE_DIR`` set — that directory, and
       ``placed_by_jax`` is True: jax read the variable itself at import
       and no directory is ever set in code.  Neither
       ``PADDLE_JIT_CACHE_DIR`` nor the fleet's ``jit_cache_dir=``
       argument overrides it — the chip tool (or any operator) places
       the cache from outside;
    2. else ``PADDLE_JIT_CACHE_DIR`` if set;
    3. else ``default_dir`` — entry-point scripts pass
       :func:`checkout_cache_dir`; library constructors pass nothing, so
       importing or constructing an engine under the tests never starts
       a cache nobody asked for."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if d:
        return d, True
    d = os.environ.get("PADDLE_JIT_CACHE_DIR") or default_dir
    return (str(d) if d else None), False


def enable_persistent_cache(default_dir=None):
    """Turn on jax's persistent compilation cache in the directory
    :func:`resolve_cache_dir` names, so a fresh process re-loads every
    executable it compiled last time instead of re-running XLA — the
    serving engine's warm-restart path, and what lets one chip-tool
    command share compiles between its processes.  Thresholds are
    dropped to zero (the default min-compile-time gate of 1s would skip
    exactly the small CPU executables the tests exercise).  jax memoizes
    its is-cache-used decision at first compile, so flipping the knob
    after a compile has already happened must reset that memo — done
    here via ``compilation_cache.reset_cache()``.

    No-op (returns None) when no directory is configured; returns the
    active directory otherwise.  Idempotent per directory.
    """
    d, placed_by_jax = resolve_cache_dir(default_dir)
    if d is None:
        return None
    if _persistent_cache_dir[0] == d:
        return d
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    if not placed_by_jax:
        jax.config.update("jax_compilation_cache_dir", d)
    # cache every executable, however small/fast the compile
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()  # drop the memoized cache-unused verdict
    _persistent_cache_dir[0] = d
    install_cache_event_hook()
    return d


def persistent_cache_dir():
    """The directory ``enable_persistent_cache`` activated, or None."""
    return _persistent_cache_dir[0]


# jax announces persistent-cache traffic through plain monitoring events;
# route them into counters so "did the warm restart actually skip XLA?"
# is a registry read, not a log grep
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "persistent_cache_hits",
    "/jax/compilation_cache/cache_misses": "persistent_cache_misses",
    "/jax/compilation_cache/compile_requests_use_cache":
        "persistent_cache_requests",
}
_cache_event_hook_done = [False]


def install_cache_event_hook():
    """Count persistent-compilation-cache hits/misses/requests into the
    observability registry (``compile.persistent_cache_*``).  Idempotent;
    the listener stays registered for the process lifetime."""
    if _cache_event_hook_done[0]:
        return False
    from jax import monitoring
    from ..observability import metrics as _metrics

    def _listener(event, **kw):
        name = _CACHE_EVENTS.get(event)
        if name is not None:
            try:
                _metrics.counter(f"compile.{name}").inc()
            except Exception:                          # noqa: BLE001
                pass        # telemetry must never break a compile
    monitoring.register_event_listener(_listener)
    # only after registration succeeded — a failed attempt must stay
    # retryable, not silently leave the counters dead for the process
    _cache_event_hook_done[0] = True
    return True


# --------------------------------------------------------------------------
# XLA compile hook (observability)
# --------------------------------------------------------------------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_hook = [None]


def install_compile_hook(callback):
    """Fire ``callback(kind, seconds)`` once per XLA retrace — i.e. per
    backend compile of a new executable; cache hits and repeat calls with
    known signatures never fire.  Rides ``jax.monitoring``'s duration
    listeners; the listener stays registered for the process lifetime, so
    installation
    is once-only — a second call replaces the callback rather than
    stacking listeners.  Returns True on first install."""
    first = _compile_hook[0] is None
    _compile_hook[0] = callback
    if not first:
        return False
    from jax import monitoring

    def _listener(event, duration, **kw):
        if event == _COMPILE_EVENT and _compile_hook[0] is not None:
            try:
                _compile_hook[0]("backend_compile", duration)
            except Exception:                              # noqa: BLE001
                pass        # telemetry must never break a compile
    monitoring.register_event_duration_secs_listener(_listener)
    return True
