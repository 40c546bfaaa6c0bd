"""Nothing outlives a run: the process-group kill and the /proc scan.

Every process of a run carries PLENUM_BENCH_RUN=<marker> in its
environment (children inherit it). `reap` ends a process group and then
everything else that carries the marker, and returns only when a scan of
/proc finds none. Imports nothing but the standard library."""
from __future__ import annotations

import ctypes
import os
import signal
import time

MARKER_VAR = "PLENUM_BENCH_RUN"
TERM_WAIT_S = 10.0          # SIGTERM -> SIGKILL
KILL_WAIT_S = 30.0          # SIGKILL -> gone (a chip owner can be slow)


def become_subreaper() -> None:
    """Orphans of this process's descendants reparent to it, so it can
    wait() for them instead of leaving zombies to whoever is pid 1."""
    try:
        ctypes.CDLL(None, use_errno=True).prctl(36, 1, 0, 0, 0)
    except (OSError, AttributeError):
        pass                # no prctl: the scan below still finds them


def _is_live(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat", "rb") as fh:
            state = fh.read().rsplit(b")", 1)[1].split()[0]
    except (OSError, IndexError):
        return False
    return state not in (b"Z", b"X")


def marked(marker: str, skip: tuple = ()) -> list[int]:
    """Live processes, other than `skip` and this one, whose environment
    carries the marker."""
    needle = f"{MARKER_VAR}={marker}".encode()
    me, out = os.getpid(), []
    for name in os.listdir("/proc"):
        if not name.isdigit() or int(name) == me or int(name) in skip:
            continue
        try:
            with open(f"/proc/{name}/environ", "rb") as fh:
                env = fh.read()
        except OSError:
            continue
        if needle in env.split(b"\0") and _is_live(int(name)):
            out.append(int(name))
    return out


def _collect_children() -> None:
    """wait() for whatever has already ended (this process's own children
    and, as a subreaper, its adopted orphans)."""
    while True:
        try:
            pid, _ = os.waitpid(-1, os.WNOHANG)
        except ChildProcessError:
            return
        if pid == 0:
            return


def _wait_gone(pids_of, seconds: float) -> list[int]:
    deadline = time.monotonic() + seconds
    while True:
        _collect_children()
        left = pids_of()
        if not left or time.monotonic() >= deadline:
            return left
        time.sleep(0.05)


def _signal_all(pids, sig) -> None:
    for pid in pids:
        try:
            os.kill(pid, sig)
        except (ProcessLookupError, PermissionError):
            pass


def reap(marker: str, pgid: int | None = None,
         term_wait_s: float = TERM_WAIT_S) -> dict:
    """End the group, then everything that carries the marker; return when
    a scan finds none. -> {"group_stragglers": pids the group kill found
    alive, "strays": pids outside the group that only the scan found}."""
    def in_group() -> list[int]:
        return [p for p in marked(marker) if _pgid_of(p) == pgid]

    stragglers: list[int] = []
    if pgid is not None:
        stragglers = in_group()
        try:
            os.killpg(pgid, signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            pass
        if _wait_gone(in_group, term_wait_s):
            try:
                os.killpg(pgid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            _wait_gone(in_group, KILL_WAIT_S)
    strays = marked(marker)
    if strays:
        _signal_all(strays, signal.SIGTERM)
        left = _wait_gone(lambda: marked(marker), term_wait_s)
        _signal_all(left, signal.SIGKILL)
        left = _wait_gone(lambda: marked(marker), KILL_WAIT_S)
        if left:
            raise RuntimeError(f"processes {left} survived SIGKILL")
    _collect_children()
    return {"group_stragglers": stragglers, "strays": strays}


def _pgid_of(pid: int):
    try:
        return os.getpgid(pid)
    except (ProcessLookupError, PermissionError):
        return None


def stop_children(procs, term_wait_s: float = TERM_WAIT_S) -> None:
    """A launcher's own clean stop: SIGTERM each, wait, SIGKILL what is
    left and WAIT AGAIN until each has ended (the wait after kill() is
    what tools/tcp_pool.py lacks)."""
    live = [p for p in procs if p is not None and p.poll() is None]
    for p in live:
        p.send_signal(signal.SIGTERM)
    deadline = time.monotonic() + term_wait_s
    for p in live:
        try:
            p.wait(timeout=max(0.0, deadline - time.monotonic()))
        except Exception:
            p.kill()
    for p in live:
        p.wait()
