"""The executable store (plenum_tpu/ops/aot.py): each pinned program is
obtained once per machine. Mechanics on a tiny stand-in kernel in a store
of the test's own; the verify kernel itself at ONE shape, in the
checkout's store, so only the first run on a machine compiles it."""
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np
import pytest

from plenum_tpu import ops
from plenum_tpu.ops import aot

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@jax.jit
def tiny_kernel(x, y):
    return jnp.sum(x * 2 + y, axis=0) > 40


AVALS = (jax.ShapeDtypeStruct((4, 8), np.int32),) * 2
X = np.arange(32, dtype=np.int32).reshape(4, 8)
Y = np.ones((4, 8), np.int32)
WANT = (X * 2 + Y).sum(axis=0) > 40

# the same obtain in a process of its own: what a restarted node does
CHILD = """
import json, sys
import jax, jax.numpy as jnp, numpy as np
from plenum_tpu import ops
from plenum_tpu.ops import aot

@jax.jit
def tiny_kernel(x, y):
    return jnp.sum(x * 2 + y, axis=0) > 40

exe = aot.obtain(tiny_kernel, (jax.ShapeDtypeStruct((4, 8), np.int32),) * 2)
out = exe(np.arange(32, dtype=np.int32).reshape(4, 8),
          np.ones((4, 8), np.int32))
print(json.dumps({"stats": ops.compile_stats(),
                  "out": np.asarray(out).tolist()}))
"""


def _child(cache_dir) -> dict:
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    done = subprocess.run([sys.executable, "-c", CHILD], env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.fixture
def store(tmp_path, monkeypatch):
    """A store of the test's own, and a process that has obtained
    nothing yet (the per-process memo emptied)."""
    monkeypatch.setattr(aot, "store_dir", lambda: str(tmp_path / "aot"))
    monkeypatch.setattr(aot, "_obtained", {})
    return tmp_path / "aot"


def _delta(before: dict) -> dict:
    after = ops.compile_stats()
    return {k: round(after[k] - before[k], 3) for k in after}


def _again(monkeypatch):
    """Forget what this process obtained: the next obtain is a new
    process's, as far as the store can tell."""
    monkeypatch.setattr(aot, "_obtained", {})


def test_store_then_load_in_a_fresh_process(tmp_path):
    """The first process traces, compiles and stores; the next one loads
    and never enters the kernel's body. JAX's own cache holds the same
    program by then, and the store must not serialize from it."""
    first = _child(tmp_path)
    assert first["out"] == WANT.tolist()
    assert (first["stats"]["aot_stores"], first["stats"]["aot_loads"],
            first["stats"]["traces"]) == (1, 0, 1)
    second = _child(tmp_path)
    assert second["out"] == WANT.tolist()
    assert (second["stats"]["aot_stores"], second["stats"]["aot_loads"],
            second["stats"]["traces"]) == (0, 1, 0)
    assert second["stats"]["aot_rejected"] == 0
    # a load is an executable obtained: the window rule sees it
    assert second["stats"]["executables"] >= 1

    # the jit path fills JAX's persistent cache with this very module;
    # with the store's entry gone, the next obtain must still compile in
    # its own process (cache_hits 0) and store an entry that RUNS
    for name in os.listdir(tmp_path / aot.DIR_NAME):
        os.unlink(tmp_path / aot.DIR_NAME / name)
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    jit_path = CHILD.replace("exe = aot.obtain(tiny_kernel, (jax.ShapeDtype"
                             "Struct((4, 8), np.int32),) * 2)",
                             "exe = tiny_kernel")
    assert subprocess.run([sys.executable, "-c", jit_path], env=env,
                          capture_output=True, timeout=300).returncode == 0
    assert any(n.startswith("jit_tiny_kernel") for n in os.listdir(tmp_path))
    third = _child(tmp_path)
    assert third["stats"]["cache_hits"] == 0
    assert third["stats"]["aot_stores"] == 1
    fourth = _child(tmp_path)
    assert fourth["out"] == WANT.tolist()
    assert (fourth["stats"]["aot_loads"], fourth["stats"]["traces"]) == (1, 0)


def test_compile_stats_keeps_its_keys_and_counts_a_load(store, monkeypatch):
    c0 = ops.compile_stats()
    assert list(c0)[:3] == ["executables", "cache_hits", "seconds"]
    assert set(c0) == {"executables", "cache_hits", "seconds", "aot_loads",
                       "aot_stores", "aot_rejected", "traces"}
    exe = aot.obtain(tiny_kernel, AVALS)
    assert (np.asarray(exe(X, Y)) == WANT).all()
    stored = _delta(c0)
    assert (stored["aot_stores"], stored["aot_loads"], stored["traces"],
            stored["executables"]) == (1, 0, 1, 1)
    assert len(os.listdir(store)) == 1
    # the same process again: the memo answers, nothing is obtained
    c1 = ops.compile_stats()
    assert aot.obtain(tiny_kernel, AVALS) is exe
    assert not any(_delta(c1).values())
    # a new process: one load, counted as an executable with its seconds
    _again(monkeypatch)
    loaded = aot.obtain(tiny_kernel, AVALS)
    assert loaded is not exe and (np.asarray(loaded(X, Y)) == WANT).all()
    got = _delta(c1)
    assert (got["aot_loads"], got["aot_stores"], got["traces"],
            got["executables"], got["cache_hits"]) == (1, 0, 0, 1, 0)
    assert got["seconds"] >= 0
    kinds = [row["what"] for row in aot.timeline()
             if row["kernel"] == "tiny_kernel"]
    assert kinds[-3:] == ["compile", "store", "load"]


KEY = dict(name="verify_kernel_bytes", sources="s" * 64,
           avals=(jax.ShapeDtypeStruct((512, 32), np.uint8),
                  jax.ShapeDtypeStruct((512,), np.int32)),
           fingerprint=("0.9.0", "0.9.0", "tpu", "libtpu build A",
                        "TPU v5 lite", "0", "", "", "default"))


@pytest.mark.parametrize("change", [
    dict(sources="t" + "s" * 63),                       # a source byte
    dict(name="verify_kernel_indexed"),
    dict(avals=(jax.ShapeDtypeStruct((512, 32), np.uint8),
                jax.ShapeDtypeStruct((256,), np.int32))),           # shape
    dict(avals=(jax.ShapeDtypeStruct((512, 32), np.uint8),
                jax.ShapeDtypeStruct((512,), np.uint32))),          # dtype
    dict(fingerprint=KEY["fingerprint"][:2] + ("cpu",)
         + KEY["fingerprint"][3:]),                     # platform
    dict(fingerprint=KEY["fingerprint"][:3] + ("libtpu build B",)
         + KEY["fingerprint"][4:]),                     # the libtpu build
    dict(fingerprint=KEY["fingerprint"][:4] + ("TPU v4",)
         + KEY["fingerprint"][5:]),                     # device kind
    dict(fingerprint=KEY["fingerprint"][:5] + ("1",)
         + KEY["fingerprint"][6:]),                     # device ordinal
    dict(fingerprint=("0.9.1",) + KEY["fingerprint"][1:]),          # jax
    dict(fingerprint=KEY["fingerprint"][:8] + ("committed",)),
], ids=["source", "name", "shape", "dtype", "platform", "libtpu",
        "device_kind", "ordinal", "jax", "placement"])
def test_key_holds_everything_that_decides_the_executable(change):
    assert aot.entry_key(**KEY) == aot.entry_key(**dict(KEY))
    assert aot.entry_key(**{**KEY, **change}) != aot.entry_key(**KEY)


def test_key_parts_cannot_borrow_from_each_other():
    a = aot.entry_key("ab", "c", (), ("d",))
    assert a != aot.entry_key("a", "bc", (), ("d",))
    assert a != aot.entry_key("ab", "c", (), ("", "d"))


def test_fingerprint_names_this_runtime_and_device():
    dev = jax.local_devices()[0]
    fp = aot.backend_fingerprint(dev)
    assert fp[:2] == (jax.__version__, jaxlib.__version__)
    assert fp[2:6] == (dev.client.platform, dev.client.platform_version,
                       dev.device_kind, str(dev.id))


def test_source_digest_moves_with_one_byte(tmp_path):
    (tmp_path / "a.py").write_bytes(b"x = 1\n")
    (tmp_path / "b.py").write_bytes(b"y = 2\n")
    d0 = aot.source_digest(str(tmp_path))
    assert aot.source_digest(str(tmp_path)) == d0
    aot._source_digests.pop(str(tmp_path))      # a new process reads anew
    (tmp_path / "b.py").write_bytes(b"y = 3\n")
    assert aot.source_digest(str(tmp_path)) != d0
    # the real one covers the kernel and the store itself
    assert len(aot.source_digest()) == 64


def test_changed_source_is_a_miss_then_a_new_entry(store, monkeypatch):
    aot.obtain(tiny_kernel, AVALS)
    _again(monkeypatch)
    monkeypatch.setattr(aot, "source_digest", lambda: "edited" * 8)
    c0 = ops.compile_stats()
    exe = aot.obtain(tiny_kernel, AVALS)
    got = _delta(c0)
    assert (got["aot_loads"], got["aot_stores"], got["traces"]) == (0, 1, 1)
    assert (np.asarray(exe(X, Y)) == WANT).all()
    assert len(os.listdir(store)) == 2          # the old entry never ran


@pytest.mark.parametrize("damage", ["truncated", "flipped", "empty",
                                    "not_an_executable"])
def test_damaged_entry_is_rejected_counted_removed_recompiled(
        store, monkeypatch, caplog, damage):
    aot.obtain(tiny_kernel, AVALS)
    (name,) = os.listdir(store)
    path = store / name
    body = path.read_bytes()
    if damage == "truncated":
        path.write_bytes(body[:len(body) // 2])
    elif damage == "flipped":
        path.write_bytes(body[:-1] + bytes([body[-1] ^ 1]))
    elif damage == "empty":
        path.write_bytes(b"")
    else:       # sound digest, sound pickle, nothing PjRt can load
        import hashlib
        import pickle
        blob = pickle.dumps((b"junk", None, None))
        path.write_bytes(hashlib.sha256(blob).digest() + blob)
    _again(monkeypatch)
    c0 = ops.compile_stats()
    with caplog.at_level("WARNING", logger=aot.logger.name):
        exe = aot.obtain(tiny_kernel, AVALS)
    got = _delta(c0)
    assert (got["aot_rejected"], got["aot_loads"], got["aot_stores"],
            got["traces"]) == (1, 0, 1, 1)
    assert "rejected" in caplog.text and name in caplog.text    # loudly
    assert (np.asarray(exe(X, Y)) == WANT).all()
    # the damaged file is gone; what replaced it is whole and loads
    assert os.listdir(store) == [name] and len(path.read_bytes()) > 32
    _again(monkeypatch)
    c1 = ops.compile_stats()
    aot.obtain(tiny_kernel, AVALS)
    assert (_delta(c1)["aot_loads"], _delta(c1)["aot_rejected"]) == (1, 0)


def test_racing_writers_leave_one_whole_file(store):
    exe = aot.obtain(tiny_kernel, AVALS)
    (name,) = os.listdir(store)
    path = str(store / name)
    errors, stop = [], threading.Event()

    def write():
        try:
            for _ in range(5):
                aot._store(path, exe)
        except Exception as e:      # pragma: no cover - the failure
            errors.append(e)

    def read():
        dev = jax.local_devices()[0]
        while not stop.is_set():
            try:
                aot._load(path, dev)     # never a partial entry
            except Exception as e:      # pragma: no cover - the failure
                errors.append(e)
                return

    reader = threading.Thread(target=read)
    writers = [threading.Thread(target=write) for _ in range(6)]
    reader.start()
    for t in writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    reader.join()
    assert not errors, errors
    assert os.listdir(store) == [name]          # no temp file left
    loaded = aot._load(path, jax.local_devices()[0])
    assert (np.asarray(loaded(X, Y)) == WANT).all()


def test_two_threads_one_key_obtain_once(store):
    c0 = ops.compile_stats()
    got = []
    threads = [threading.Thread(
        target=lambda: got.append(aot.obtain(tiny_kernel, AVALS)))
        for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(got) == 4 and all(g is got[0] for g in got)
    assert _delta(c0)["aot_stores"] == 1 and _delta(c0)["traces"] == 1


def _entry_path(store) -> str:
    return aot._entry(tiny_kernel, AVALS, None)[1]


def test_a_live_claim_is_waited_for_and_its_entry_loaded(store):
    """Four validators started together want the same programs: the one
    that holds the claim compiles, the others wait and load its entry."""
    path = _entry_path(store)
    sleeper = subprocess.Popen([sys.executable, "-c",
                                "import time; time.sleep(60)"])
    try:
        os.makedirs(store)
        with open(aot._claim_path(path), "w") as fh:
            fh.write(str(sleeper.pid))
        c0 = ops.compile_stats()
        with pytest.raises(aot.ClaimedElsewhere):   # a worker's answer
            aot.obtain(tiny_kernel, AVALS, wait=False)
        got = []
        waiter = threading.Thread(
            target=lambda: got.append(aot.obtain(tiny_kernel, AVALS)))
        waiter.start()
        time.sleep(0.6)
        assert waiter.is_alive() and not os.path.exists(path)
        assert _delta(c0)["aot_stores"] == 0        # it did not compile
        # the claimant finishes: entry first, then the claim goes
        aot._compile_and_store(tiny_kernel, path, AVALS,
                               jax.local_devices()[0], False)
        os.unlink(aot._claim_path(path))
        waiter.join(30)
        assert got and (np.asarray(got[0](X, Y)) == WANT).all()
        d = _delta(c0)
        assert d["aot_loads"] == 1 and d["aot_stores"] == 1  # ours above
    finally:
        sleeper.kill()
        sleeper.wait()


@pytest.mark.parametrize("stale", ["dead_claimant", "too_old", "garbage"])
def test_a_stale_claim_is_taken_over(store, stale):
    path = _entry_path(store)
    os.makedirs(store)
    gone = subprocess.Popen([sys.executable, "-c", "pass"])
    gone.wait()
    with open(aot._claim_path(path), "w") as fh:
        fh.write("not a pid" if stale == "garbage" else
                 str(gone.pid if stale == "dead_claimant" else os.getppid()))
    if stale == "too_old":
        old = time.time() - aot.CLAIM_MAX_S - 5
        os.utime(aot._claim_path(path), (old, old))
    c0 = ops.compile_stats()
    exe = aot.obtain(tiny_kernel, AVALS, wait=False)
    assert (np.asarray(exe(X, Y)) == WANT).all()
    assert _delta(c0)["aot_stores"] == 1
    assert sorted(os.listdir(store)) == [os.path.basename(path)]


def test_racing_processes_store_one_entry_between_them(tmp_path):
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    procs = [subprocess.Popen([sys.executable, "-c", CHILD], env=env,
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True) for _ in range(4)]
    stats = []
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-2000:]
        got = json.loads(out.strip().splitlines()[-1])
        assert got["out"] == WANT.tolist()
        stats.append(got["stats"])
    assert sum(s["aot_stores"] for s in stats) == 1
    assert sum(s["aot_loads"] for s in stats) == 3
    (name,) = os.listdir(tmp_path / aot.DIR_NAME)   # no claim left behind
    assert name.endswith(".exe")


def test_no_cache_directory_means_no_store(monkeypatch):
    monkeypatch.setattr(aot, "store_dir", lambda: None)
    monkeypatch.setattr(aot, "_obtained", {})
    c0 = ops.compile_stats()
    exe = aot.obtain(tiny_kernel, AVALS)
    assert (np.asarray(exe(X, Y)) == WANT).all()
    got = _delta(c0)
    assert (got["aot_stores"], got["aot_loads"], got["aot_rejected"]) \
        == (0, 0, 0)


def test_jax_cache_is_on_again_after_an_obtain(store):
    """The store's own compile goes past JAX's persistent cache (what it
    serializes must have been compiled here); nothing else may."""
    assert jax.config.jax_enable_compilation_cache
    aot.obtain(tiny_kernel, AVALS)
    assert jax.config.jax_enable_compilation_cache
    assert aot._compiling == 0


def test_loaded_call_costs_about_what_the_jit_fast_path_does(store,
                                                              monkeypatch):
    """The only new cost on the timed path: a dict lookup and
    Compiled.__call__ instead of the jit fast path. Tens of microseconds
    a dispatch at ~81 dispatches/s served, ~6/s co-hosted."""
    aot.obtain(tiny_kernel, AVALS)
    _again(monkeypatch)
    loaded = aot.obtain(tiny_kernel, AVALS)
    x, y = jnp.asarray(X), jnp.asarray(Y)

    def per_call_us(fn) -> float:
        fn(x, y).block_until_ready()
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(200):
                out = fn(x, y)
            out.block_until_ready()
            best = min(best, (time.perf_counter() - t0) / 200 * 1e6)
        return best

    jit_us, loaded_us = per_call_us(tiny_kernel), per_call_us(loaded)
    print(f"per call: jit fast path {jit_us:.1f} us, loaded executable "
          f"{loaded_us:.1f} us")
    assert loaded_us - jit_us < 500.0       # 0.5 ms would be 4 % of a wave


# --- the kernel itself, at one shape ---------------------------------------

def _signed(n: int, keys: int):
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    signers = [Ed25519Signer(seed=bytes([k + 1]) * 32) for k in range(keys)]
    items = []
    for i in range(n):
        msg = b"aot-store-%d" % i
        items.append((msg, signers[i % keys].sign(msg),
                      signers[i % keys].verkey))
    msg, sig, vk = items[1]
    items[1] = (msg + b"!", sig, vk)                    # another message
    msg, sig, vk = items[4]
    items[4] = (msg, bytes([sig[0] ^ 1]) + sig[1:], vk)     # a bit in R
    msg, sig, vk = items[6]
    items[6] = (msg, sig[:40] + bytes([sig[40] ^ 4]) + sig[41:], vk)    # S
    return items


def test_preloaded_verify_program_answers_as_the_cpu_does(monkeypatch):
    """Shape (8, 8) of verify_kernel_bytes through preload(), in the
    checkout's own store: compiled on this machine's first run only.
    Then a new process's preload: one load, no trace, and the loaded
    executable's verdict vector on a batch with corrupted signatures is
    CpuEd25519Verifier's."""
    from plenum_tpu.crypto.ed25519 import (CpuEd25519Verifier,
                                           JaxEd25519Verifier)
    from plenum_tpu.parallel.supervisor import supervise
    c0 = ops.compile_stats()
    device = JaxEd25519Verifier(min_batch=8)
    # through the wrapper a pool puts around it; (8, 1) and (5, 3) pad
    # to one program
    assert supervise(device).preload([(8, 1), (5, 3)]) == [(8, 8)]
    assert device.preload([(8, 1)]) == []                   # held already
    if _delta(c0)["aot_stores"]:
        # this machine's first run compiled and stored it: now be the
        # next process
        _again(monkeypatch)
        c0 = ops.compile_stats()
        device = JaxEd25519Verifier(min_batch=8)
        assert device.preload([(8, 1)]) == [(8, 8)]
    got = _delta(c0)
    assert (got["aot_loads"], got["traces"], got["aot_rejected"],
            got["executables"]) == (1, 0, 0, 1)
    items = _signed(8, 3)
    verdicts = device.verify_batch(items)
    assert list(verdicts) == [True, False, True, True, False, True, False,
                              True]
    assert (verdicts == CpuEd25519Verifier().verify_batch(items)).all()
    assert _delta(c0)["traces"] == 0        # the jit path was not taken


def test_preload_loads_on_the_calling_thread_and_compiles_in_others(
        monkeypatch):
    """What the store holds is loaded here, in turn (a PjRt load issued
    from the main thread costs a fifth of one issued from any other:
    PERF.md, PR 26); what it lacks compiles at once, a thread each; what
    another process is compiling meanwhile is waited for and loaded
    here too, never in a worker."""
    from plenum_tpu.crypto.ed25519 import JaxEd25519Verifier
    seen = []

    def has_entry(jitted, avals, device=None):
        return avals[0].shape[0] <= 32

    def obtain(jitted, avals, device=None, wait=True):
        m = avals[0].shape[0]
        seen.append((m, threading.get_ident(), wait))
        if m == 256 and not wait:
            raise aot.ClaimedElsewhere("another validator has it")
        return f"exe{m}"
    monkeypatch.setattr(aot, "has_entry", has_entry)
    monkeypatch.setattr(aot, "obtain", obtain)
    device = JaxEd25519Verifier()
    assert device.preload([(16, 1), (32, 1), (64, 1), (128, 1), (256, 1)]) \
        == [(16, 16), (32, 32), (64, 64), (128, 64), (256, 64)]
    here = threading.get_ident()
    assert [(m, t == here) for m, t, _ in seen[:2]] \
        == [(16, True), (32, True)]
    assert sorted(m for m, _, _ in seen[2:5]) == [64, 128, 256]
    assert all(t != here and not wait for _, t, wait in seen[2:5])
    assert [(m, t == here, wait) for m, t, wait in seen[5:]] \
        == [(256, True, True)]
    assert device._preloaded == {(16, 16): "exe16", (32, 32): "exe32",
                                 (64, 64): "exe64", (128, 64): "exe128",
                                 (256, 64): "exe256"}


def _holding(monkeypatch, min_batch, waves):
    """A verifier whose preload() 'obtained' the programs of `waves`
    (the store is stubbed: nothing compiles)."""
    from plenum_tpu.crypto.ed25519 import JaxEd25519Verifier
    monkeypatch.setattr(aot, "has_entry", lambda *a, **k: True)
    monkeypatch.setattr(
        aot, "obtain", lambda jitted, avals, device=None, wait=True:
        f"exe{avals[0].shape[0]}x{avals[2].shape[0]}")
    device = JaxEd25519Verifier(min_batch=min_batch)
    device.preload(waves)
    return device


def _todays_rule(m, n_keys, min_batch):
    """The padding rule as it stood before a verifier looked at what it
    holds (PR 33's `_pad_sizes`, word for word)."""
    m_pad = 1
    while m_pad < max(m, min_batch):
        m_pad *= 2
    small = min(64, m_pad)
    return m_pad, (small if n_keys <= small else m_pad)


@pytest.mark.parametrize("m, n_keys, want", [
    (1, 1, (64, 64)), (9, 9, (64, 64)), (9, 2, (64, 64)),
    (64, 1, (64, 64)), (64, 64, (64, 64)),
    (65, 10, (512, 64)), (65, 65, (512, 512)),
    (100, 64, (512, 64)), (100, 100, (512, 512)),
    (512, 3, (512, 64)), (512, 512, (512, 512)),
    # past every held program: the rule of before
    (513, 8, (1024, 64)), (600, 600, (1024, 1024))])
def test_a_service_pads_each_wave_to_the_smallest_program_it_holds(
        monkeypatch, m, n_keys, want):
    """The served plane's verifier (`--min-batch 512`) after the prewarm
    the service gives it: the small program and the large one with both
    key tables. Rows pad to the smallest HELD lane count that fits; the
    key table keeps its two buckets a batch shape."""
    device = _holding(monkeypatch, 512, [(512, 1), (512, 512), (64, 1)])
    assert sorted(device._preloaded) == [(64, 64), (512, 64), (512, 512)]
    assert device._pad_sizes(m, n_keys) == want


@pytest.mark.parametrize("min_batch", [1, 8, 64, 512])
@pytest.mark.parametrize("m", [1, 5, 9, 63, 64, 65, 100, 512, 513, 4096])
def test_a_verifier_that_holds_nothing_pads_as_before(min_batch, m):
    """No preload, no change: the next power of two >= max(m, min_batch),
    bit for bit, for every key count a wave of m can carry. So does a
    verifier whose dispatch is re-routed (the sharded plane, the
    limb-staged path): its preload obtains nothing."""
    from plenum_tpu.crypto.ed25519 import JaxEd25519Verifier

    class Limbs(JaxEd25519Verifier):
        _compressed_dispatch = False

    rerouted = Limbs(min_batch=min_batch)
    assert rerouted.preload([(64, 1), (512, 1)]) == []
    for device in (JaxEd25519Verifier(min_batch=min_batch), rerouted):
        for n_keys in sorted({1, min(m, 64), min(m, 65), m}):
            assert device._pad_sizes(m, n_keys) \
                == _todays_rule(m, n_keys, min_batch)


@pytest.mark.parametrize("m, n_keys, want", [
    (64, 1, (64, 64)), (64, 40, (64, 64)), (128, 1, (128, 64)),
    (128, 64, (128, 64)),
    # past the ladder: as before (a fresh shape, which pin() counts)
    (200, 3, (256, 64)), (129, 129, (256, 256)),
    # a short batch handed to the verifier directly, not through the
    # ring's packing: the held 64-lane program now, not a fresh 16-lane one
    (9, 9, (64, 64)), (65, 2, (128, 64))])
def test_a_rings_verifier_keeps_its_ladder(monkeypatch, m, n_keys, want):
    """A ring builds its verifier with min_batch 1 and pads its waves to
    its own pinned ladder (64, 128) before the verifier sees them: the
    lengths it sends are held lane counts and pad to themselves."""
    device = _holding(monkeypatch, 1, [(64, 1), (128, 1)])
    assert sorted(device._preloaded) == [(64, 64), (128, 64)]
    assert device._pad_sizes(m, n_keys) == want


def test_preload_asks_for_the_tightest_program_whatever_min_batch(
        monkeypatch):
    """preload() decides what is held, so its waves name programs by
    their own size: (64, 1) on a min_batch-512 verifier is the 64-lane
    program, and asking again once 512 is held does not turn it into
    the 512-lane one."""
    device = _holding(monkeypatch, 512, [(512, 1)])
    assert device.in_store([(64, 1), (9, 9), (100, 100)]) \
        == {(16, 16): True, (64, 64): True, (128, 128): True}
    assert device.preload([(64, 1), (512, 1)]) == [(64, 64)]
    assert device.preload([(64, 1)]) == []                  # held already
    assert sorted(device._preloaded) == [(64, 64), (512, 64)]


def test_a_dispatch_pads_while_another_thread_preloads(monkeypatch):
    """The service preloads on its loop's thread while its worker stages
    waves: `_pad_sizes` reads the held lane counts as one tuple, never a
    dict another thread is adding to, and every answer is a held shape
    that fits the wave."""
    device = _holding(monkeypatch, 512, [(512, 1)])
    stop, seen, errors = threading.Event(), set(), []

    def stage_waves():
        try:
            while not stop.is_set():
                seen.add(device._pad_sizes(9, 9))
        except Exception as e:              # asserted on below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    workers = [threading.Thread(target=stage_waves) for _ in range(4)]
    try:
        for w in workers:
            w.start()
        for lanes in (8192, 4096, 2048, 1024, 256, 128):
            device.preload([(lanes, 1)])
            device.preload([(lanes, lanes)])
    finally:
        stop.set()
        for w in workers:
            w.join(timeout=30)
        sys.setswitchinterval(old)
    assert not any(w.is_alive() for w in workers) and not errors
    assert device._pad_sizes(9, 9) == (128, 64)
    assert (512, 64) in seen and seen <= {(512, 64), (256, 64), (128, 64)}


def test_preload_leaves_rerouted_dispatch_alone():
    """A subclass that never runs the stored kernel (the sharded plane,
    the limb-staged path, a test double) obtains nothing; a host
    verifier has nothing to obtain."""
    from plenum_tpu.crypto.ed25519 import (CpuEd25519Verifier,
                                           JaxEd25519Verifier)

    class Rerouted(JaxEd25519Verifier):
        def _device_verify_bytes(self, *arrays):
            raise AssertionError("not dispatched here")

    class Double(JaxEd25519Verifier):
        def submit_batch(self, items):
            return np.ones(len(items), dtype=bool)

    class Limbs(JaxEd25519Verifier):
        _compressed_dispatch = False

    c0 = ops.compile_stats()
    for verifier in (Rerouted(), Double(), Limbs(), CpuEd25519Verifier()):
        assert verifier.preload([(16, 1), (16, 16)]) == []
    assert not any(_delta(c0).values())


def test_prewarm_preloads_every_bucket_before_its_waves():
    """CryptoPipeline.prewarm hands preload() all its buckets at once,
    before the first wave; the multi-device ring does so per lane."""
    from plenum_tpu.config import Config
    from plenum_tpu.crypto.ed25519 import JaxEd25519Verifier
    from plenum_tpu.parallel.pipeline import (CryptoPipeline,
                                              MultiDeviceCryptoPipeline)
    calls = []

    class Recording(JaxEd25519Verifier):
        def preload(self, waves):
            calls.append(("preload", sorted(waves)))
            return []

        def submit_batch(self, items):
            calls.append(("wave", len(items)))
            return np.zeros(len(items), dtype=bool)

        def collect_batch(self, token, wait=True):
            return token

    cfg = Config(PIPELINE_MIN_BUCKET=16, PIPELINE_MAX_BUCKET=64)
    for build in (lambda v: CryptoPipeline(ed_inner=v, config=cfg),
                  lambda v: MultiDeviceCryptoPipeline([v], config=cfg,
                                                      threaded=False)):
        calls.clear()
        assert build(Recording()).prewarm([32, 16]) == [16, 32]
        assert calls == [("preload", [(16, 1), (32, 1)]), ("wave", 16),
                         ("wave", 32)]


@pytest.mark.parametrize("stats, held, shapes, wrong", [
    # cold machine: both shapes traced, compiled and stored
    (dict(traces=2, aot_loads=0, aot_stores=2, aot_rejected=0), 0, 2, None),
    # warm machine: both loaded, nothing traced
    (dict(traces=0, aot_loads=2, aot_stores=0, aot_rejected=0), 2, 2, None),
    # one shape new since the last call
    (dict(traces=1, aot_loads=1, aot_stores=1, aot_rejected=0), 1, 2, None),
    # warm machine, yet a prewarmed shape was traced
    (dict(traces=1, aot_loads=1, aot_stores=0, aot_rejected=0), 2, 2,
     "1 verify traces with 2/2 shapes already in the store"),
    # a damaged entry cost a recompile: loud here too
    (dict(traces=1, aot_loads=1, aot_stores=1, aot_rejected=1), 1, 2,
     "1 store entries rejected"),
], ids=["cold", "warm", "one_new", "traced_on_warm", "rejected"])
def test_chip_smoke_fails_a_trace_on_a_warm_machine(stats, held, shapes,
                                                    wrong):
    import chip_smoke
    problems = chip_smoke.store_failures("phase", stats, held, shapes)
    if wrong is None:
        assert problems == []
    else:
        assert len(problems) == 1 and wrong in problems[0]


def test_chip_smoke_asks_the_store_what_it_holds(monkeypatch):
    """shapes_held is has_entry over the padded shapes, per lane device."""
    import chip_smoke
    asked = []

    def has_entry(jitted, avals, device=None):
        asked.append((jitted.__name__, tuple(a.shape for a in avals),
                      device))
        return avals[0].shape[0] == 64
    monkeypatch.setattr(aot, "has_entry", has_entry)
    assert chip_smoke.shapes_held([(64, 1), (128, 1), (512, 512)]) == (1, 3)
    assert [(a[1][0][0], a[1][2][0]) for a in asked] \
        == [(64, 64), (128, 64), (512, 512)]
    assert all(a[0] == "verify_kernel_bytes" and a[2] is None
               for a in asked)
