"""The JAX installation as this process sees it: where compiled programs
are kept, and which devices the codec will run on.

Two things every entry point that touches the device needs and nothing
else should reimplement:

* ``setup_compile_cache()`` - called once, before the first JAX use, by
  ``python -m minio_tpu.server`` and ``chip_smoke.py``'s children.
  Every distinct (batch, k, m, width, loss pattern) is its own XLA
  program and a cold TPU compile runs from about a second to over a
  minute, so a server that forgets them pays on every restart.
* ``device_info()`` - platform, device kind and count, versions, the
  cache directory in effect and per-device memory, as JAX reports them.
  The server logs it at boot and serves it in ``healthinfo`` and
  ``kernel-stats``; nothing downstream guesses the platform.
"""

from __future__ import annotations

import importlib.metadata
import os
import sys
import threading

# <checkout>/.jax_cache: a fixed path (the directory is part of JAX's
# cache key, so a temporary or per-pid name would never hit)
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

_mu = threading.Lock()
_cache_events = {"hits": 0, "misses": 0}
_listening = False


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        with _mu:
            _cache_events["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        with _mu:
            _cache_events["misses"] += 1


def _listen() -> None:
    """Count this process's persistent-cache hits and misses (idempotent)."""
    global _listening
    import jax

    with _mu:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            _listening = True


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a durable directory
    and return it.  Call before the first JAX use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own handling of
    it stands and nothing here overrides it; where it is not, the cache
    lives in ``<checkout>/.jax_cache`` (git-ignored).  Most codec
    kernels compile in about JAX's 1 s default caching threshold, so
    the threshold drops to zero unless the operator set one.  Both go
    through the environment, which JAX reads at import and children
    inherit; a JAX that was imported first is told through its config.
    """
    cache_dir = os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", _DEFAULT_CACHE_DIR
    )
    min_secs = os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0"
    )
    if "jax" in sys.modules:
        import jax

        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", float(min_secs)
        )
        _listen()
    return cache_dir


def compile_cache_stats() -> dict:
    """Directory in effect (None when no cache is configured), entries
    on disk, and this process's hits/misses since it started counting
    (setup_compile_cache or the first device_info(), whichever ran
    first - both precede the first compile in every entry point)."""
    import jax

    _listen()
    d = jax.config.jax_compilation_cache_dir
    files = 0
    if d:
        try:
            files = sum(1 for e in os.scandir(d) if e.is_file())
        except FileNotFoundError:
            files = 0  # nothing compiled yet: JAX creates it on first put
    with _mu:
        return {"dir": d, "files": files, **_cache_events}


def device_info() -> dict:
    """What JAX reports, verbatim.  Raises whatever ``jax.devices()``
    raises: a chip that is missing or held by another process is the
    caller's failure to report, not something to paper over."""
    import jax
    import jaxlib

    devices = jax.devices()
    per_device = []
    for d in devices:
        # memory_stats() is None on backends that do not report (CPU)
        stats = d.memory_stats() or {}
        per_device.append(
            {
                "id": int(d.id),
                "kind": d.device_kind,
                "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
            }
        )
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
        "compile_cache": compile_cache_stats(),
        "devices": per_device,
    }
