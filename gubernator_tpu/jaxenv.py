"""How a process of this repo meets JAX: where compiled programs are
kept, which platform a device backend may serve from, and what the
devices report about themselves.

Importing this module does not import jax (a launcher that spawns the
process which will hold the chip must stay off JAX itself: a chip
belongs to one process at a time); every function that needs jax
imports it when called.
"""

from __future__ import annotations

import os
import pathlib

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """The one persistent compile cache: where JAX_COMPILATION_CACHE_DIR
    says when it is set, else `<checkout>/.jax_cache` (git-ignored).
    Never a temporary, pid- or time-derived path — the path is part of
    the cache key, so a directory that moves never hits."""
    return os.environ.get(CACHE_ENV) or str(
        pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"
    )


def enable_compile_cache() -> str:
    """Point this process's JAX at `compile_cache_dir()`. With the
    variable set nothing is configured here: JAX reads it itself, and a
    `jax.config.update` would only be a second place to get it wrong."""
    d = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", d)
    return d


def require_tpu(what: str, conf_platform: str = "") -> None:
    """Refuse to run `what` on anything but a TPU unless another
    platform was asked for BY NAME: `conf_platform` (GUBER_JAX_PLATFORM
    through the config) or JAX_PLATFORMS. JAX falls back to the CPU
    with one warning when libtpu cannot initialise; a rate limiter (or
    a benchmark) that then carries on reports a host's numbers under a
    chip's name."""
    import jax

    platform = jax.default_backend()
    if platform == "tpu" or conf_platform or os.environ.get("JAX_PLATFORMS"):
        return
    raise RuntimeError(
        f"{what} needs a TPU, and JAX found none (default backend is "
        f"'{platform}'; a libtpu that failed to initialise falls back "
        "like this — its warning is above). To run on another "
        "platform on purpose, name it: JAX_PLATFORMS=cpu (or "
        "GUBER_JAX_PLATFORM=cpu for a daemon)."
    )


def device_summary() -> dict:
    """What this process's JAX runs on, as JAX reports it — the three
    fields every result line names: platform, device_kind, count."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def describe_devices(state_bytes=None) -> dict:
    """`device_summary()` plus, per device, the allocator's bytes in
    use now, at their peak since boot and at most (None where the
    backend keeps no such statistic — the CPU) and
    `state_bytes`, the engine's own account of the store + sketch bytes
    resident there ({device id: bytes})."""
    import jax

    per = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        per.append(
            {
                "id": d.id,
                "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
                "state_bytes": (state_bytes or {}).get(d.id, 0),
            }
        )
    return {**device_summary(), "devices": per}
