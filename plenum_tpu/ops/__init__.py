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

A hit in that cache still costs the kernel's whole trace and lowering,
because the lowered module is the cache's key. The executable store
(`plenum_tpu/ops/aot.py`, entries under `<cache dir>/plenum_aot/`) is
keyed on what decides the module instead, so the pinned verify programs
are obtained once per MACHINE: at warm-up a later process loads them,
concurrently, without entering the kernel's Python body.

`compile_stats()` counts what neither can hide: every executable this
process had to obtain (compiled, loaded from JAX's cache or loaded from
the store) and the seconds spent, so a harness can report set-up apart
from its traffic window and assert the window itself obtained none; and
how often the store engaged (`aot_loads`, `aot_stores`, `aot_rejected`)
beside how often a stored kernel's body was entered all the same
(`traces`).
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
_JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# one entry per event; list.append is atomic, so lane threads compiling
# concurrently during prewarm cannot lose a count
_obtained_s: list[float] = []
_cache_hits: list[int] = []
_aot: dict[str, list[int]] = {
    "aot_loads": [], "aot_stores": [], "aot_rejected": [], "traces": []}
# names of the kernels the executable store serves: only THEIR traces
# count (one verify trace fires this event for ~10^4 inner jnp calls too)
_stored_kernels: set[str] = set()


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    if event == _BACKEND_COMPILE:
        _obtained_s.append(duration_secs)
    elif event == _JAXPR_TRACE and kw.get("fun_name") in _stored_kernels:
        _aot["traces"].append(1)


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _cache_hits.append(1)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def count_traces_of(kernel_name: str) -> None:
    """The executable store names each kernel it serves; from then on a
    trace of that kernel shows in `compile_stats()["traces"]`."""
    _stored_kernels.add(kernel_name)


def note_aot(kind: str, seconds: float = 0.0) -> None:
    """The executable store's events. A load is an executable obtained:
    it counts in `executables` and `seconds` like a compile."""
    _aot[kind].append(1)
    if kind == "aot_loads":
        _obtained_s.append(seconds)


def compile_stats() -> dict:
    """Cumulative, process-wide: `executables` = programs this process
    obtained from the backend (each a jit-cache miss: an XLA compile or a
    persistent-cache load; or a load from the executable store),
    `cache_hits` = how many of those JAX's persistent cache served,
    `seconds` = wall time spent obtaining them (summed, so obtains that
    overlapped count in full). `aot_loads` / `aot_stores` /
    `aot_rejected` = entries of the executable store loaded, written, and
    found damaged (deleted and compiled again); `traces` = times the
    Python body of a kernel the store serves was entered.
    Snapshot before and after a window and subtract."""
    return {"executables": len(_obtained_s),
            "cache_hits": len(_cache_hits),
            "seconds": round(sum(_obtained_s), 3),
            **{kind: len(events) for kind, events in _aot.items()}}


def device_info(memory: bool = False) -> dict:
    """The device as JAX reports it — stamped on every result that names
    a device figure. Initializes the backend: only the process that is
    meant to own the chip may call this. `memory` adds the peak bytes in
    use on the fullest local device (0 where the backend keeps none)."""
    devs = jax.devices()
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if memory:
        out["memory_peak_bytes"] = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devs)
    return out
