"""Device kernels (the TPU plane) — shared JAX runtime configuration.

Importing any kernel module routes through here, which places JAX's
persistent compilation cache: the framework's device programs are a
handful of FIXED shapes (the pinned Ed25519 verify buckets, the SHA-256
Merkle buckets, the sharded crypto plane) and one cold verify-kernel
compile costs tens of seconds to minutes, so only the first process
should ever pay it; every later node/bench/test process deserializes the
compiled executable instead.

The cache is placed FROM OUTSIDE: when `JAX_COMPILATION_CACHE_DIR` is set
JAX reads it itself and no code here (or anywhere in the tree) sets
another directory. Only when it is unset does this module pick one — a
single fixed directory inside the checkout (`.jax_cache/`, gitignored, so
a checkout never carries another machine's XLA:CPU entries). The path
does not depend on the host, the user or the process: it is part of what
a cache hit is keyed on, so a directory that moves never hits.

`compile_stats()` counts what the cache cannot hide: every executable
this process had to obtain (compiled or loaded) and the seconds spent,
so a harness can report set-up apart from its traffic window and assert
the window itself obtained none.
"""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

# THE one cache-directory setter in the tree, guarded by the variable
# being unset
if not os.environ.get(CACHE_ENV):
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
# cache every program: the default thresholds skip small/fast compiles,
# but a pool's steady state re-obtains those too in every new process
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# one entry per event; list.append is atomic, so lane threads compiling
# concurrently during prewarm cannot lose a count
_obtained_s: list[float] = []
_cache_hits: list[int] = []


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    if event == _BACKEND_COMPILE:
        _obtained_s.append(duration_secs)


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _cache_hits.append(1)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def compile_stats() -> dict:
    """Cumulative, process-wide: `executables` = programs this process
    obtained from the backend (each a jit-cache miss: an XLA compile or a
    persistent-cache load), `cache_hits` = how many of those the
    persistent cache served, `seconds` = wall time spent obtaining them.
    Snapshot before and after a window and subtract."""
    return {"executables": len(_obtained_s),
            "cache_hits": len(_cache_hits),
            "seconds": round(sum(_obtained_s), 3)}


def device_info() -> dict:
    """The device as JAX reports it — stamped on every result that names
    a device figure. Initializes the backend: only the process that is
    meant to own the chip may call this."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
