"""Backend selection and compile-cache helpers.

The test suite, ``__graft_entry__.dryrun_multichip`` and the verify
checks run on a virtual multi-device CPU mesh (the reference suite's
same-host multi-rank trick, ``tests/run_test_suite.sh:78-82``):
:func:`force_cpu_mesh` pins the CPU platform and the device count
*before the first backend is instantiated*.  Everything else takes the
platform JAX finds — on a TPU host that is the chip, and nothing in
this package selects or falls back to another one.

One compile-cache rule (:func:`_engage_cache`): with
``JAX_COMPILATION_CACHE_DIR`` set JAX already uses that directory and
no code here touches ``jax_compilation_cache_dir``; unset, the default
is ``<checkout>/.jax_cache`` (a fixed path: a directory that moves
never hits), an
operator's namelist ``compile_cache_dir`` or the serve loop's queue
cache replace it by name, and CPU-forced processes stay uncached.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"

# persistent-compile-cache hit/miss counters (best-effort, via
# jax.monitoring): "hits" counts executables served from the on-disk
# cache, "compiles" counts every pass through the backend-compile
# timer — which wraps ``compile_or_get_cached`` and so fires on disk
# hits too (the load is timed like a compile).  Misses are therefore
# derived as ``compiles - hits``: both counters are monotone and fire
# exactly once per compile request, so deltas stay consistent even
# when the cache engages midway through a process.
# ``compile_s`` sums the timer's seconds (compile or load, whichever
# the request turned out to be).  Surfaced in the telemetry run header
# so worker cold-start economics are observable.
_CACHE_STATS = {"hits": 0, "compiles": 0, "compile_s": 0.0, "dir": ""}
_cache_listener_installed = False


def _install_cache_listener():
    global _cache_listener_installed
    if _cache_listener_installed:
        return
    try:
        from jax import monitoring

        def _on_event(name, **kw):
            if "persistent_cache_hit" in name \
                    or ("compilation_cache" in name and "hit" in name
                        and "requests" not in name):
                _CACHE_STATS["hits"] += 1

        def _on_duration(name, secs, **kw):
            if name.endswith("backend_compile_duration"):
                _CACHE_STATS["compiles"] += 1
                _CACHE_STATS["compile_s"] += float(secs)

        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _cache_listener_installed = True
    except Exception:      # monitoring API drift must not kill a run
        pass


def compile_cache_stats() -> dict:
    """Snapshot of {hits, misses, dir, ...} for telemetry headers.

    ``misses`` = compile requests not served from disk (real backend
    compiles); with no cache engaged that is every compile."""
    s = dict(_CACHE_STATS)
    s["misses"] = max(0, s["compiles"] - s["hits"])
    return s


DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def _engage_cache(path: str) -> str:
    """The one place that points JAX's persistent compilation cache
    somewhere.  ``JAX_COMPILATION_CACHE_DIR`` set ⇒ JAX already reads
    it and ``jax_compilation_cache_dir`` is left alone (``path`` is
    ignored); otherwise the cache moves to ``path``.  Either way every
    entry is cached (the fused AMR programs the growth phase re-traces
    are individually small but numerous — the point is O(load)
    cold-start) and the hit/miss listener is on.  Returns the
    directory in effect."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        path = env
    else:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # JAX-level executable cache only: the XLA:CPU AOT cache keys on
    # exact host machine features and warns (worse: may SIGILL) when
    # they drift between processes; the TPU win comes from the
    # executable cache alone
    jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
    _CACHE_STATS["dir"] = path
    _install_cache_listener()
    return path


def setup_compile_cache(params) -> str:
    """Honor an explicit ``&RUN_PARAMS compile_cache_dir``.

    Called from ``__main__`` and the ensemble service BEFORE the first
    trace, so a known namelist cold-starts in O(load) instead of
    O(compile).  Unlike the package-import default
    (:func:`enable_compile_cache`) a directory the operator stated by
    name is honored on every backend, including CPU-forced runs —
    except that ``JAX_COMPILATION_CACHE_DIR`` outranks it (see
    :func:`_engage_cache`).  Returns the directory in effect ("" when
    the namelist names none).  Best-effort: an unwritable path warns
    and leaves the run as it was rather than failing it.
    """
    path = str(getattr(getattr(params, "run", params),
                       "compile_cache_dir", "") or "").strip()
    if not path:
        return ""
    try:
        return _engage_cache(os.path.expanduser(path))
    except Exception as e:
        import warnings
        warnings.warn(f"compile_cache_dir={path!r} not usable: {e}")
        return ""


def enable_compile_cache():
    """Package-import default of the persistent compilation cache.

    The AMR growth phase recompiles its fused programs whenever a level
    crosses a padding bucket; each TPU compile costs seconds to tens of
    seconds while the device work itself is milliseconds (the reference
    pays zero — Fortran compiles once at build time).  The persistent
    cache makes every recompile after the first sighting of a shape a
    disk hit instead.  Called from ``ramses_tpu/__init__``; config
    only — no backend is initialised here.  Best-effort: a read-only
    checkout must not break the solver.
    """
    # CPU-forced runs (tests, the driver's dryrun, verify checks) skip
    # the default: XLA:CPU executables are AOT machine code whose
    # feature-set check warns on every load (and can in principle
    # SIGILL), polluting driver artifacts.  TPU is where recompiles
    # cost tens of seconds, and TPU runs never force JAX_PLATFORMS.
    cpu_forced = os.environ.get("JAX_PLATFORMS", "").strip().lower() \
        .startswith("cpu")
    if cpu_forced and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    try:
        _engage_cache(DEFAULT_CACHE_DIR)
    except Exception:
        pass


def force_cpu_mesh(n_devices: int):
    """Force the CPU backend with ``n_devices`` virtual devices.

    Safe to call more than once with the same count.  Raises if a JAX
    backend was already initialized on a different platform or with
    fewer devices — a loud failure instead of a silently-smaller mesh.
    Returns the first ``n_devices`` devices.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"{_COUNT_FLAG}={n_devices}"
    if _COUNT_FLAG in flags:
        flags = re.sub(rf"{_COUNT_FLAG}=\d+", flag, flags)
    else:
        flags = (flags + " " + flag).strip()
    os.environ["XLA_FLAGS"] = flags

    import jax

    jax.config.update("jax_platforms", "cpu")
    # CPU-forced processes stay uncached unless the caller exported
    # JAX_COMPILATION_CACHE_DIR: package import may have engaged the
    # default before this call (its guard only covers runs that
    # exported JAX_PLATFORMS=cpu before importing ramses_tpu), and
    # XLA:CPU cache entries are AOT machine code (load warnings /
    # SIGILL risk)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", None)
        _CACHE_STATS["dir"] = ""
    devices = jax.devices()
    if devices[0].platform != "cpu":
        raise RuntimeError(
            f"CPU platform could not be forced: backend already "
            f"initialized on {devices[0].platform!r}. Call force_cpu_mesh "
            f"before any other jax use in the process.")
    if len(devices) < n_devices:
        raise RuntimeError(
            f"requested {n_devices} virtual CPU devices but the backend "
            f"has {len(devices)}; it was initialized before XLA_FLAGS "
            f"could be updated.")
    return devices[:n_devices]
