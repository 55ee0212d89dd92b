"""Persistent XLA compilation cache.

The crypto kernels compile large scan-heavy programs (Miller loop,
final exponentiation); on CPU XLA that is tens of seconds per shape.
A persistent on-disk cache makes every process after the first start
warm — the analog of the reference paying its worker-spawn cost once
at startup (chain/bls/multithread/index.ts:130-146).

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX takes the directory from
it and this module sets none; otherwise the cache lives at the fixed
`DEFAULT_DIR`, so that every process of a checkout finds it.
"""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(__file__))),
    ".jax_cache",
)

_enabled = False


def enable() -> None:
    global _enabled
    if _enabled:
        return
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    try:
        if d == DEFAULT_DIR:
            os.makedirs(d, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # jax latches "no persistent cache" at the process's FIRST
        # compile; a host that jitted anything before enable() would
        # silently never cache without this re-init
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
    except OSError as e:
        # The cache is an optimization only — but a node running
        # without it pays every multi-minute stage compile on EVERY
        # restart, which from the outside looks identical to a slow
        # TPU. Say so, and count it where the dashboards look
        # (lodestar_jax_persistent_cache_errors_total).
        from ..logger import get_logger
        from ..metrics import device as _telemetry

        _telemetry.record_cache_error()
        get_logger("jaxcache").warn(
            "persistent XLA compilation cache DISABLED — every stage "
            "compile will be paid again on each restart",
            {"dir": d, "err": repr(e)},
        )
    _enabled = True
