"""The rest of a run, driven on the CPU at a size a test can hold (the
harness's look for a chip is what --rehearse-cpu skips): a sound run comes
out correct, the control (acknowledge on the first reply) and a timed path
broken underneath (interior Merkle hashes altered where the ledger produces them)
come out not correct. Labelled rehearsals: no metric is written."""
import json

import pytest

import bench_paths  # noqa: F401
from benchmarks import cell


def run_cell(tmp_path, capsys, *extra):
    result = tmp_path / "result.json"
    rc = cell.main(["--workload", "cohosted.write_flood", "--seed", "3",
                    "--seconds", "1.0", "--trace", "0", "--rehearse-cpu",
                    "--run-dir", str(tmp_path), "--result", str(result),
                    *extra])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    return rc, json.loads(result.read_text()), lines


def test_sound_run_is_correct_and_the_control_is_not(tmp_path, capsys):
    rc, result, lines = run_cell(tmp_path, capsys, "--check", "2")
    assert rc == 0 and result["correct"] is True
    assert result["metrics"] == {} and "rehearsal" in result
    assert result["attempted"] > 0 and result["failed"] == 0
    compared = [x["compared"] for x in lines if "compared" in x]
    assert len(compared) >= 10 and all(r["ok"] for r in compared)
    assert all("limit" in r and "got" in r for r in compared)
    second = [x for x in lines if "check_window" in x]
    assert len(second) == 1 and second[0]["correct"] is True
    controls = [x for x in lines if "control" in x]
    assert len(controls) == 3
    for c in controls:
        assert c["correct"] is False
        assert [r["check"] for r in c["failed_checks"]] == \
            ["acks.least_matching_replies"]


def test_a_broken_timed_path_is_not_correct(tmp_path, capsys, monkeypatch):
    from plenum_tpu.ledger.tree_hasher import TreeHasher
    plain = TreeHasher.hash_children
    monkeypatch.setattr(TreeHasher, "hash_children",
                        lambda self, left, right: plain(self, right, left))
    rc, result, lines = run_cell(tmp_path, capsys)
    assert rc == 0 and result["correct"] is False
    failed = [x["compared"]["check"] for x in lines
              if "compared" in x and not x["compared"]["ok"]]
    assert "reference.root_mismatches" in failed
