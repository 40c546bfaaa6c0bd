"""Bring-up guards for the device path (PR 21): where the compile cache
lives, that chip_smoke.py refuses to pass without a TPU or with a device
that fell back, that warm-up cannot swallow a failed dispatch, and that a
compile inside a blocking submit is not booked as a deadline miss.

No test here compiles anything; the tiny CPU rehearsal of the whole
script does, and is `slow`.
"""
import json
import os
import re
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRINT_CACHE_DIR = ("import plenum_tpu.ops, jax; "
                   "print(jax.config.jax_compilation_cache_dir)")


def _child_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **extra)
    return env


def _cache_dir_seen_by_child(**env) -> tuple[str, int]:
    proc = subprocess.Popen([sys.executable, "-c", PRINT_CACHE_DIR],
                            cwd=REPO, env=_child_env(**env),
                            stdout=subprocess.PIPE, text=True)
    out, _ = proc.communicate(timeout=120)
    assert proc.returncode == 0
    return out.strip().splitlines()[-1], proc.pid


def test_compile_cache_is_placed_from_outside(tmp_path):
    want = str(tmp_path / "placed-from-outside")
    seen, _ = _cache_dir_seen_by_child(JAX_COMPILATION_CACHE_DIR=want)
    assert seen == want


def test_compile_cache_default_is_one_fixed_checkout_path(tmp_path):
    """Unset: the same in-checkout path whatever the process or the home
    directory — no host fingerprint, no per-user location."""
    a, pid_a = _cache_dir_seen_by_child(HOME=str(tmp_path / "home-a"))
    b, pid_b = _cache_dir_seen_by_child(HOME=str(tmp_path / "home-b"))
    assert pid_a != pid_b
    assert a == b == os.path.join(REPO, ".jax_cache")


def test_one_cache_dir_setter_in_the_tree():
    """No code path sets another cache directory: one setter, in
    plenum_tpu/ops/__init__.py, guarded by the variable being unset."""
    setters = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in (".git", "scratch",
                                                ".jax_cache", "chiprun_out",
                                                "__pycache__", "tests")]
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as fh:
                    src = fh.read()
                if re.search(r"""update\(\s*["']jax_compilation_cache_dir""",
                             src) or "set_cache_dir(" in src:
                    setters.append(os.path.relpath(path, REPO))
    assert setters == [os.path.join("plenum_tpu", "ops", "__init__.py")]
    with open(os.path.join(REPO, setters[0])) as fh:
        src = fh.read()
    guard = src.index("if not os.environ.get(CACHE_ENV):")
    assert guard < src.index('update("jax_compilation_cache_dir"')


def test_chip_smoke_exits_nonzero_without_a_tpu():
    """Held to the CPU and without the rehearsal argument: non-zero, no
    result line, and no work done (the first child stops at the device
    query)."""
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=_child_env(), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "platform: cpu" in proc.stdout
    assert "need 1 x tpu" in proc.stderr
    assert time.monotonic() - t0 < 120


def _supervised_faulty():
    from plenum_tpu.crypto.ed25519 import CpuEd25519Verifier, Ed25519Signer
    from plenum_tpu.parallel.faults import FaultyVerifier
    from plenum_tpu.parallel.supervisor import supervise
    faulty = FaultyVerifier(CpuEd25519Verifier())
    signer = Ed25519Signer(seed=b"bringup".ljust(32, b"\0"))

    def items(tag):
        msgs = [b"%s-%d" % (tag, i) for i in range(4)]
        return [(m, signer.sign(m), signer.verkey) for m in msgs]

    return supervise(faulty), faulty, items


def test_smoke_zero_fallback_check_fails_on_a_raising_device(monkeypatch,
                                                            capsys):
    """chip_smoke's no-fallback rule, fed real supervisor stats: a healthy
    window passes; a window in which the injected device raised — and the
    supervisor quietly answered from the CPU — fails (and a phase with
    such a problem exits non-zero), as does a window with no device batch
    at all or a compile inside it."""
    import chip_smoke
    sup, faulty, items = _supervised_faulty()
    s0 = sup.supervisor_stats()
    assert sup.verify_batch(items(b"ok")).all()
    s1 = sup.supervisor_stats()
    assert chip_smoke.window_failures("w", [s0], [s1], {"x": 0}) == []

    faulty.drop()                       # submit_batch raises
    assert sup.verify_batch(items(b"dropped")).all()    # CPU answered
    s2 = sup.supervisor_stats()
    problems = chip_smoke.window_failures("w", [s1], [s2], {})
    assert any("device_errors" in p and "fallback_batches" in p
               for p in problems), problems
    assert any("no device batch" in p for p in problems), problems
    monkeypatch.setitem(chip_smoke.CHILD_PHASES, "single",
                        lambda sizes, seed, rehearsal: {"problems": problems})
    assert chip_smoke.run_phase("single", seed=1, rehearsal=True) == 1
    assert '"ok": false' in capsys.readouterr().out

    assert chip_smoke.window_failures(
        "w", [s0], [s1], {"executables obtained": 1}) \
        == ["w: executables obtained grew by 1 in the window"]
    assert chip_smoke.window_failures("w", [], [], {})    # nothing to judge


def test_prewarm_raises_when_the_device_did_not_answer():
    """Warm-up must not swallow a failed dispatch: the supervised inner
    would answer it from the CPU and pin() would then enforce a bucket
    that never compiled."""
    from plenum_tpu.config import Config
    from plenum_tpu.crypto.ed25519 import JaxEd25519Verifier
    from plenum_tpu.parallel.faults import FaultyVerifier
    from plenum_tpu.parallel.pipeline import (CryptoPipeline,
                                              MultiDeviceCryptoPipeline)
    from plenum_tpu.parallel.supervisor import supervise
    cfg = Config(PIPELINE_MIN_BUCKET=16, PIPELINE_MAX_BUCKET=16)
    for build in (lambda v: CryptoPipeline(ed_inner=v, config=cfg),
                  lambda v: MultiDeviceCryptoPipeline([v], config=cfg)):
        faulty = FaultyVerifier(JaxEd25519Verifier())
        faulty.drop()                   # raises before any kernel runs
        pipe = build(supervise(faulty))
        with pytest.raises(RuntimeError, match="did not run on the device"):
            pipe.prewarm([16])
        assert pipe.compiled_shapes == 0


def test_blocking_submit_does_not_eat_the_deadline(monkeypatch):
    """A compile blocks inside submit_batch. Under a node's cycle-latched
    clock it surfaces as a jump at the next cycle; the deadline must
    start where the blocking submit returned, or every cold shape is a
    deadline miss hedged on the CPU."""
    from plenum_tpu.crypto.ed25519 import CpuEd25519Verifier
    from plenum_tpu.parallel import supervisor as sv

    class SlowToSubmit(CpuEd25519Verifier):
        def submit_batch(self, items):
            time.sleep(0.05)            # "compiling"
            return list(items)

        def collect_batch(self, token, wait=True):
            return None                 # still running on the device

    _, _, items = _supervised_faulty()
    clock = [100.0]
    for threshold, hedged in ((0.01, False), (1.0, True)):
        monkeypatch.setattr(sv, "_BLOCKING_SUBMIT_S", threshold)
        sup = sv.SupervisedVerifier(
            SlowToSubmit(), now=lambda: clock[0],
            budget=sv.DeadlineBudget(base=0.2, per_item_initial=0.0,
                                     min_s=0.2))
        clock[0] = 100.0                # latched: frozen across the submit
        tok = sup.submit_batch(items(b"cold-%d" % hedged))
        clock[0] = 100.21               # next cycle: 0.05 blocked + 0.16
        got = sup.collect_batch(tok, wait=False)
        assert (got is not None) == hedged
        assert sup.stats["deadline_misses"] == int(hedged)


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal():
    """The whole script at tiny size on the CPU (it compiles one verify
    program: minutes). Output is labelled a rehearsal."""
    proc = subprocess.run([sys.executable, "chip_smoke.py", "--rehearse-cpu"],
                          cwd=REPO, env=_child_env(), capture_output=True,
                          text=True, timeout=1500)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is True and "rehearsal" in last
    assert last["device"]["platform"] == "cpu"
    assert "[four_chip] not run" in proc.stdout
