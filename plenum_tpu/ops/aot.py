"""Executable store: each pinned device program is obtained ONCE PER
MACHINE, not once per process.

JAX's persistent cache is keyed on the lowered module, so even a hit
costs the kernel's whole trace + lowering first (the Ed25519 verify
kernel unrolls into ~110 000 device ops: tens of seconds of Python per
shape, in every process). This store is keyed on what DECIDES the
lowered module instead — the kernel's source, its abstract input
signature, the JAX and backend builds, the device — so a later process
loads the serialized executable and never enters the kernel's body.

Where: `<jax_compilation_cache_dir>/plenum_aot/<kernel>-<key>.exe` (and,
while one process compiles it, `<...>.exe.claim` beside it: processes
started together compile each entry once between them). The
directory is derived from the one cache directory JAX already has (set
from outside through `JAX_COMPILATION_CACHE_DIR`, else by
`plenum_tpu/ops/__init__.py`); clearing the store is deleting that
subdirectory. No flag and no option: an entry that is there and sound
is loaded, anything else is traced and compiled as before and then
stored. (That one compile goes past JAX's persistent cache, see
`_compiled_here`; the cache stays on for every other program.)

An entry never runs on anything but what it was built for: the key
holds every module's source under `plenum_tpu/ops/`, the function name,
each input's shape and dtype, the jax / jaxlib versions, the backend's
platform and platform_version (the libtpu build), the device kind and
ordinal, and the two environment strings XLA reads its flags from. An
entry that fails to load (truncated, unreadable by this runtime) is
deleted, counted in `compile_stats()["aot_rejected"]`, logged, and
compiled again — never skipped silently, never answered elsewhere.

An entry is a pickle around PjRt's serialized executable, so the
directory is trusted exactly as JAX's own cache beside it is: whoever
can write there can already hand this process a program to run.

Callers use this at warm-up only (`JaxEd25519Verifier.preload`); the
counters live with the other compile counters in `ops.compile_stats()`.
"""
from __future__ import annotations

import contextlib
import glob
import hashlib
import logging
import os
import pickle
import threading
import time
import zlib
from concurrent.futures import Future
from typing import Optional, Sequence

import jax
import jaxlib
from jax.experimental import serialize_executable
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from plenum_tpu import ops

logger = logging.getLogger(__name__)

DIR_NAME = "plenum_aot"
_FORMAT = "plenum-aot-1"        # entry: sha256(blob) + blob, blob = zlib(pickle)
_OPS_DIR = os.path.dirname(os.path.abspath(__file__))
# XLA reads compile flags from these; JAX's own cache key holds them too
_FLAG_ENVS = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")

# one loaded executable per key and process, single-flight: a second
# verifier (or lane on the same chip) asking while the first still loads
# waits for that load instead of starting its own
_lock = threading.Lock()
_obtained: dict[str, Future] = {}
_source_digests: dict[str, str] = {}
_compiling = 0                  # obtains inside _compiled_here()
_cache_was_on = True
# (what, kernel, shapes, start, end) on time.monotonic(): what one
# warm-up's obtains overlapped with (chip_smoke prints it)
_timeline: list[tuple] = []


def store_dir() -> Optional[str]:
    """Where entries live, or None when JAX has no cache directory (then
    nothing is stored and every obtain compiles)."""
    root = jax.config.jax_compilation_cache_dir
    return os.path.join(root, DIR_NAME) if root else None


def source_digest(directory: str = _OPS_DIR) -> str:
    """SHA-256 over every module under `plenum_tpu/ops/` (name and
    bytes): whatever a kernel is built from is in there, so an edit to
    any of them retires every entry."""
    got = _source_digests.get(directory)
    if got is None:
        h = hashlib.sha256()
        for path in sorted(glob.glob(os.path.join(directory, "*.py"))):
            with open(path, "rb") as fh:
                body = fh.read()
            name = os.path.basename(path).encode()
            h.update(b"%d:%s:%d:" % (len(name), name, len(body)))
            h.update(body)
        got = _source_digests[directory] = h.hexdigest()
    return got


def backend_fingerprint(device) -> tuple[str, ...]:
    """Everything about the runtime and the chip that decides whether an
    executable can run here."""
    client = device.client
    return (jax.__version__, jaxlib.__version__, client.platform,
            client.platform_version, device.device_kind, str(device.id),
            *(os.environ.get(name, "") for name in _FLAG_ENVS))


def entry_key(name: str, sources: str, avals: Sequence,
              fingerprint: Sequence[str]) -> str:
    """The store's key. Pure: the same four inputs give the same key in
    every process, and a change to any of them gives another."""
    parts = [_FORMAT, name, sources, *fingerprint]
    parts += [f"{tuple(a.shape)}:{jax.numpy.dtype(a.dtype).name}"
              for a in avals]
    h = hashlib.sha256()
    for part in parts:
        raw = part.encode()
        h.update(b"%d:" % len(raw))         # length-prefixed: no part
        h.update(raw)                       # can borrow from the next
    return h.hexdigest()


def timeline() -> list[dict]:
    return [dict(zip(("what", "kernel", "shapes", "start", "end"), row))
            for row in list(_timeline)]


def _entry(jitted, avals, device) -> tuple[str, Optional[str], object]:
    """-> (key, the entry's path or None without a store, the device the
    executable runs on)."""
    target = device if device is not None else jax.local_devices()[0]
    placement = "default" if device is None else "committed"
    key = entry_key(jitted.__name__, source_digest(), avals,
                    (*backend_fingerprint(target), placement))
    directory = store_dir()
    return key, directory and os.path.join(
        directory, f"{jitted.__name__}-{key}.exe"), target


def has_entry(jitted, avals, device=None) -> bool:
    """Is this machine warm for that program? (chip_smoke asks before a
    warm-up, to tell a load it may demand from a compile it must allow.)"""
    path = _entry(jitted, avals, device)[1]
    return bool(path) and os.path.exists(path)


class ClaimedElsewhere(Exception):
    """Another process of this machine is compiling this entry now."""


def obtain(jitted, avals: Sequence[jax.ShapeDtypeStruct], device=None,
           wait: bool = True):
    """-> the `jax.stages.Compiled` of `jitted` for `avals`: loaded from
    the store when a sound entry is there, else lowered and compiled as
    any jit miss is and then stored. `device` as `ops.ed25519.stage_on`
    takes it: None lowers for uncommitted inputs on the default device
    (the module today's single-chip dispatch lowers to), a device lowers
    for inputs committed to it (a lane of the multi-device ring).
    Thread-safe; obtains of different keys run concurrently (XLA
    compiles release the GIL). A LOAD is best issued from the process's
    main thread: `JaxEd25519Verifier.preload` says what it costs
    elsewhere. So where another process holds the entry's claim, a
    caller on a worker thread passes `wait=False`, gets ClaimedElsewhere
    at once, and asks again from its main thread, which waits for that
    entry and loads it there."""
    ops.count_traces_of(jitted.__name__)
    key, path, target = _entry(jitted, avals, device)
    with _lock:
        fut = _obtained.get(key)
        mine = fut is None
        if mine:
            fut = _obtained[key] = Future()
    if not mine:
        return fut.result()
    try:
        exe = _load_or_compile(jitted, path, avals, target,
                               committed=device is not None, wait=wait)
    except BaseException as e:
        with _lock:
            del _obtained[key]          # the next caller tries again
        fut.set_exception(e)
        raise
    fut.set_result(exe)
    return exe


def _load_or_compile(jitted, path: Optional[str], avals, device,
                     committed: bool, wait: bool = True):
    """Load the entry; where there is none, compile and store it, unless
    another process of this machine holds the entry's claim (four
    validators started at once each see their chip as ordinal 0 and want
    the same keys): then wait for its entry and load that."""
    name, shapes = jitted.__name__, [tuple(a.shape) for a in avals]
    claimed = False
    try:
        while path:
            if os.path.exists(path):
                t0 = time.monotonic()
                try:
                    exe = _load(path, device)
                except Exception as e:
                    # damaged, or written by a runtime this one cannot
                    # read: one recompile, loudly
                    ops.note_aot("aot_rejected")
                    logger.warning("executable store: rejected %s (%s: %s)"
                                   "; compiling again", path,
                                   type(e).__name__, e)
                    _unlink(path)
                else:
                    t1 = time.monotonic()
                    ops.note_aot("aot_loads", t1 - t0)
                    _timeline.append(("load", name, shapes, t0, t1))
                    return exe
            claimed = _claim(path)
            if claimed:
                break
            if not wait and _claimant_alive(path):
                raise ClaimedElsewhere(path)
            t0 = time.monotonic()
            _wait_for_claimant(path)
            _timeline.append(("wait", name, shapes, t0, time.monotonic()))
        return _compile_and_store(jitted, path, avals, device, committed)
    finally:
        if claimed:
            _unlink(_claim_path(path))


def _compile_and_store(jitted, path: Optional[str], avals, device,
                       committed: bool):
    name, shapes = jitted.__name__, [tuple(a.shape) for a in avals]
    t0 = time.monotonic()
    sharding = SingleDeviceSharding(device) if committed else None
    with _compiled_here():
        exe = jitted.lower(*(
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a in avals)).compile()
    t1 = time.monotonic()
    _timeline.append(("compile", name, shapes, t0, t1))
    if path:
        try:
            _store(path, exe)
        except OSError as e:
            # the executable is sound; only the next process pays
            logger.warning("executable store: could not write %s (%s)",
                           path, e)
        else:
            ops.note_aot("aot_stores")
            _timeline.append(("store", name, shapes, t1, time.monotonic()))
    return exe


# --- one compile per machine: the claim beside an entry ---------------------
# `<entry>.claim` holds the pid of the process compiling that entry. It is
# made with O_EXCL, so of processes racing for one key exactly one compiles;
# it goes when the entry is written (or the compile failed). A claim whose
# process is gone is taken over; one older than CLAIM_MAX_S is ignored, so a
# wedged claimant costs a wait, never the program.

CLAIM_MAX_S = 900.0             # > the longest compile seen (251 s, PR 26)


def _claim_path(path: str) -> str:
    return path + ".claim"


def _claim(path: str) -> bool:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    try:
        fd = os.open(_claim_path(path),
                     os.O_CREAT | os.O_EXCL | os.O_WRONLY, 0o644)
    except FileExistsError:
        return False
    with os.fdopen(fd, "w") as fh:
        fh.write(str(os.getpid()))
    return True


def _claimant_alive(path: str) -> bool:
    """Is a live process of this machine compiling `path`'s entry now?"""
    claim = _claim_path(path)
    try:
        age = time.time() - os.stat(claim).st_mtime
        with open(claim) as fh:
            pid = int(fh.read().strip() or 0)
    except (OSError, ValueError):
        return False
    if pid == os.getpid() or age > CLAIM_MAX_S:
        return False
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        pass                    # alive, someone else's
    return True


def _wait_for_claimant(path: str) -> None:
    """Until the entry is there, or its claimant is gone (a claim left
    behind is removed: the caller then claims for itself)."""
    while not os.path.exists(path):
        if not _claimant_alive(path):
            _unlink(_claim_path(path))
            return
        time.sleep(0.2)


@contextlib.contextmanager
def _compiled_here():
    """While any obtain compiles, JAX's persistent cache is off for the
    process: what the store serializes must be an executable THIS process
    compiled. One that JAX's cache deserialized serializes again into an
    entry that loads and then fails at its first execution (XLA:CPU:
    "Function ... not found"; found in PR 26), and nothing about the
    executable tells the two apart. Counted, so concurrent obtains
    overlap; a compile another thread starts meanwhile just misses the
    cache. The directory is untouched: this is the on/off switch only."""
    global _compiling, _cache_was_on
    with _lock:
        if not _compiling:
            _cache_was_on = jax.config.jax_enable_compilation_cache
            jax.config.update("jax_enable_compilation_cache", False)
            compilation_cache.reset_cache()
        _compiling += 1
    try:
        yield
    finally:
        with _lock:
            _compiling -= 1
            if not _compiling:
                jax.config.update("jax_enable_compilation_cache",
                                  _cache_was_on)
                compilation_cache.reset_cache()


def _load(path: str, device):
    with open(path, "rb") as fh:
        digest, blob = fh.read(32), fh.read()
    if len(digest) != 32 or hashlib.sha256(blob).digest() != digest:
        raise ValueError(f"{len(blob) + len(digest)} bytes, digest mismatch")
    payload, in_tree, out_tree = pickle.loads(zlib.decompress(blob))
    return serialize_executable.deserialize_and_load(
        payload, in_tree, out_tree, backend=device.client,
        execution_devices=[device])


def _store(path: str, exe) -> None:
    """Atomic: a reader sees no entry or a whole one, and of two writers
    racing on one key the later rename wins with a whole file."""
    # level 1: an XLA:TPU verify program is ~145 MB as PjRt serializes
    # it and ~25 MB so, for under a second of inflating at load against
    # ~10 s inside PjRt (v5e, PR 26)
    blob = zlib.compress(pickle.dumps(
        serialize_executable.serialize(exe),
        protocol=pickle.HIGHEST_PROTOCOL), 1)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
    try:
        with open(tmp, "wb") as fh:
            fh.write(hashlib.sha256(blob).digest())
            fh.write(blob)
        os.replace(tmp, path)
    except BaseException:
        _unlink(tmp)
        raise


def _unlink(path: str) -> None:
    try:
        os.unlink(path)
    except FileNotFoundError:
        pass
