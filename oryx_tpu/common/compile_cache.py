"""Persistent XLA compilation cache shared by every layer.

Why this exists: the JVM reference's layers are serving traffic or
training within seconds of process start (deploy/oryx-serving/src/main/
java/com/cloudera/oryx/serving/Main.java — construct, start, await);
the TPU runtime instead pays XLA compilation for every (program, shape)
pair it touches.  JAX's persistent compilation cache keys serialized
executables by HLO fingerprint, so that cost is paid once per machine:
every later process start — a layer restart, a rolling redeploy, a
crash recovery — loads the compiled program from disk.

Where the cache lives, in order:

1. ``JAX_COMPILATION_CACHE_DIR`` exported: JAX reads it itself and this
   module sets NO directory in code — the operator (or a harness that
   keeps the cache across machines) placed it from outside, and an
   in-code override would silently move it.
2. otherwise ``oryx.compile-cache-dir``: an absolute path is used as
   written; a relative one (the default) resolves against the checkout
   root — the directory holding the ``oryx_tpu`` package — never the
   working directory, a temporary name, a pid or a timestamp.  The
   directory is part of the cache key's stability: one that moves
   never hits.
3. ``oryx.compile-cache-dir = null`` disables persistence.

The cache is enabled process-wide the first time any layer starts; the
first configuration wins (JAX holds one global cache), and later layers
in the same process inherit it.
"""

from __future__ import annotations

import logging
import os
import threading

__all__ = ["enable_from_config"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
# the checkout root: <root>/oryx_tpu/common/compile_cache.py
_CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_log = logging.getLogger(__name__)
_lock = threading.Lock()
_enabled_dir: str | None = None


def enable_from_config(config) -> str | None:
    """Enable JAX's persistent compilation cache (see module docstring
    for where it lands).  Returns the active cache dir, or None when
    disabled."""
    global _enabled_dir
    path = os.environ.get(_ENV) or None
    from_env = path is not None
    if not from_env:
        path = config.get_optional_string("oryx.compile-cache-dir")
        if path is None:
            return None
        path = os.path.join(_CHECKOUT_ROOT, path)  # no-op when absolute
    with _lock:
        if _enabled_dir is not None:
            if _enabled_dir != path:
                _log.warning(
                    "compile cache already enabled at %s; ignoring %s "
                    "(JAX holds one process-wide cache)",
                    _enabled_dir, path)
            return _enabled_dir
        import jax

        if not from_env:
            jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            config.get_double("oryx.compile-cache-min-compile-secs"))
        # entry size is a poor proxy for compile cost on this platform;
        # gate on compile time alone
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # A pallas_call carries its Mosaic module as opaque bytes that
        # jax hashes into the cache key as they are — MLIR locations
        # included, and by default those hold the whole Python call
        # stack.  The same kernel reached through `warmup`'s AOT path
        # and through the serving dispatch then never shares an entry
        # (seen on the v5e: every Pallas program the warmup wrote was a
        # miss at the first live dispatch).  Innermost-frame locations
        # make the key a function of the kernel alone.
        jax.config.update("jax_include_full_tracebacks_in_locations", False)
        _enabled_dir = path
        _log.info("persistent compilation cache at %s%s", path,
                  f" (from {_ENV})" if from_env else "")
        return path
