"""(f) No process outlives a run: the real tcp_service launcher with the
service:cpu inner at a tiny size and a child that ignores SIGTERM, ended
by normal exit, by an exception in the cell process and by SIGTERM to the
parent; after each, no process carries the run's marker."""
import json
import os
import signal
import subprocess
import sys
import time
import uuid

import pytest

from bench_paths import ROOT
from benchmarks import reaper

HERE = os.path.dirname(os.path.abspath(__file__))
LIMIT_S = 150           # this file's own time limit, per scenario
PARENT = ("import sys; sys.path.insert(0, {root!r}); "
          "from benchmarks import run; "
          "sys.exit(run.main(['--workload', 'tcp_service.write_steady', "
          "'--seed', '1', '--seconds', '1', '--trace', '0', '--rehearse-cpu',"
          " '--scenario', {scenario!r}, '--ready', {ready!r}], "
          "cell_script={cell!r}, marker={marker!r}, term_wait_s=1.5))")


def run_parent(scenario: str, tmp_path, sigterm_when_ready: bool = False):
    marker = uuid.uuid4().hex
    ready = str(tmp_path / "ready")
    env = dict(os.environ, TMPDIR=str(tmp_path), JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-c", PARENT.format(
            root=ROOT, scenario=scenario, ready=ready, marker=marker,
            cell=os.path.join(HERE, "leaky_cell.py"))],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        start_new_session=True)
    try:
        if sigterm_when_ready:
            deadline = time.monotonic() + LIMIT_S
            while not os.path.exists(ready):
                assert time.monotonic() < deadline and proc.poll() is None
                time.sleep(0.1)
            # the pool is up: the marker is on the nodes and the service
            assert len(reaper.marked(marker)) >= 6
            proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=LIMIT_S)
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
        left = reaper.marked(marker)
        for pid in left:                # never leak into the other tests
            os.kill(pid, signal.SIGKILL)
    assert left == [], f"{scenario}: processes {left} outlived the run"
    return proc.returncode, out.strip().splitlines(), err


def test_normal_exit_reaps_a_pool_the_cell_never_stopped(tmp_path):
    rc, lines, err = run_parent("normal", tmp_path)
    assert rc == 0, err
    last = json.loads(lines[-1])
    assert last["correct"] is True
    reaped = [json.loads(x) for x in lines[:-1] if "reaped_from_group" in x]
    assert reaped and len(reaped[0]["reaped_from_group"]) >= 6


def test_exception_in_the_cell_process_prints_no_result(tmp_path):
    rc, lines, err = run_parent("exception", tmp_path)
    assert rc != 0 and "no result" in err
    assert not any(x.startswith('{"correct"') for x in lines)


def test_sigterm_to_the_parent_reaps_and_prints_no_result(tmp_path):
    rc, lines, err = run_parent("hang", tmp_path, sigterm_when_ready=True)
    assert rc != 0 and "no result" in err
    assert not any(x.startswith('{"correct"') for x in lines)


def test_a_process_only_the_scan_finds_makes_the_run_incorrect(tmp_path):
    rc, lines, err = run_parent("stray", tmp_path)
    assert rc == 0, err
    assert json.loads(lines[-1])["correct"] is False
    found = [json.loads(x)["compared"] for x in lines[:-1]
             if "processes.found_by_scan" in x]
    assert found and found[0]["got"] == 1 and not found[0]["ok"]


def test_stop_children_waits_after_kill():
    deaf = subprocess.Popen([sys.executable, "-c", (
        "import signal, time; signal.signal(signal.SIGTERM, signal.SIG_IGN);"
        " print('up', flush=True); time.sleep(600)")],
        stdout=subprocess.PIPE)
    deaf.stdout.readline()
    t0 = time.monotonic()
    reaper.stop_children([deaf, None], term_wait_s=0.5)
    assert deaf.returncode == -signal.SIGKILL
    assert time.monotonic() - t0 < 10


def test_without_the_program_there_is_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    files: non-zero, and nothing printed."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "cohosted.write_flood", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=LIMIT_S)
    assert proc.returncode != 0 and proc.stdout == ""
