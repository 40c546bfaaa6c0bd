"""The one generator: every stream of every cell, made from --seed.

A traffic mix is a data file (traffic/<mix>.json) whose `kind` names a
module of generators/; a drive (workloads/<cell>.json) names a module of
drives/ by its `drive`. Both are found by name, as readers/ and
topologies/ are: a later PR adds a mix or a cell as data files, and a new
kind of either as one more module.

Every seed gets the SAME multiset of operation kinds, payload sizes and
arrival gaps, in another order, so that the seed changes which request
comes when and never how much work a run holds. This module makes plans
(tuples and numbers); signing them into requests is the topology's job."""
from __future__ import annotations

import hashlib
import importlib
import json
from typing import NamedTuple


class Op(NamedTuple):
    kind: str           # "NYM" | "ATTRIB"
    signer: int         # index of a preloaded DID; -1 = the trustee
    target: int         # NYM: index of the new DID; ATTRIB: the signer
    raw: str            # ATTRIB payload (JSON), else ""


def did_seed(seed: int, tag: str, i: int) -> bytes:
    """Key material is derived, never stored."""
    return hashlib.sha256(b"plenum-bench/%d/%s/%d"
                          % (seed, tag.encode(), i)).digest()


def attrib_raw(seed: int, serial: int, size: int) -> str:
    """An endpoint-style attribute of exactly `size` bytes of JSON, unique
    per (seed, serial) and of the same length whatever their digits."""
    tag = hashlib.sha256(b"%d/%d" % (seed, serial)).hexdigest()[:10]
    body = {"endpoint": {"ha": "10.%03d.%03d.1:9700" % (
        serial % 256, serial // 256 % 256), "k": tag, "p": ""}}
    short = len(json.dumps(body, separators=(",", ":")))
    if short > size:
        raise SystemExit(f"traffic: an attribute needs {short} B, "
                         f"{size} asked")
    body["endpoint"]["p"] = "x" * (size - short)
    return json.dumps(body, separators=(",", ":"))


def _kind(package: str, name: str):
    try:
        return importlib.import_module(f"benchmarks.{package}.{name}")
    except ModuleNotFoundError:
        raise SystemExit(f"traffic: no {package}/{name}.py")


def plan(mix: dict, seed: int, n: int, preload: int,
         serial_base: int = 0) -> list[Op]:
    """n operations of the mix, by the generator kind its file names."""
    return _kind("generators", mix["kind"]).plan(mix, seed, n, preload,
                                                 serial_base)


def stream_length(drive: dict, seconds: float) -> int:
    """How many requests set-up signs for a window of `seconds`."""
    return _kind("drives", drive["drive"]).stream_length(drive, seconds)


def schedule(drive: dict, seed: int, seconds: float) -> dict:
    """What accounting.Feeder releases requests by: {"due": [offsets]}
    for an open loop, {"in_flight": n} for a closed one."""
    return _kind("drives", drive["drive"]).schedule(drive, seed, seconds)
