"""A validator that owns its chip (tools/start_node.py --backend jax): it
builds its ring through make_crypto_pipeline, pins before it serves, and
accounts for its plane in VALIDATOR_INFO; `service` and `cpu` nodes build
what they built before.

The ring's ladder starts at 64 lanes and one verify shape costs minutes to
compile on the CPU, so a host double stands behind the ring here (steered
in the test, not by an option of the program): the ring pads, dispatches,
pins and counts for it exactly as for a device."""
import asyncio
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time

import pytest

pytest.importorskip(
    "cryptography",
    reason="build_node stands up the TCP stack, which needs cryptography")

from plenum_tpu.crypto import ed25519  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRUSTEE_SEED = b"ring-trustee".ljust(32, b"\0")


class HostVerifier(ed25519.JaxEd25519Verifier):
    def submit_batch(self, items):
        return ed25519.CpuEd25519Verifier().verify_batch(items)

    def collect_batch(self, token, wait=True):
        return token

    def preload(self, waves):
        return []


# `python -m plenum_tpu.tools.start_node` with the double behind the ring
STAND_IN = f"""
import sys
sys.path.insert(0, {os.path.dirname(os.path.abspath(__file__))!r})
import test_start_node_ring as t
t.ed25519.JaxEd25519Verifier = t.HostVerifier
from plenum_tpu.tools import start_node
start_node.main()
"""


@pytest.mark.parametrize("backend, verifier_chain, ring", [
    ("jax", ["PipelineVerifier", "SupervisedVerifier", "HostVerifier"],
     True),
    ("service", ["SupervisedVerifier", "ServiceEd25519Verifier"], False),
    ("cpu", ["CpuEd25519Verifier"], False),
])
def test_build_node_hands_a_device_owner_its_ring(tmp_path, monkeypatch,
                                                  backend, verifier_chain,
                                                  ring):
    from plenum_tpu.parallel.pipeline import CryptoPipeline
    from plenum_tpu.tools.start_node import build_node, warm_ring
    from plenum_tpu.tools.tcp_pool import setup_pool_dir
    monkeypatch.setattr(ed25519, "JaxEd25519Verifier", HostVerifier)
    monkeypatch.delenv("PLENUM_CONFIG_JSON", raising=False)
    # a `service` node connects when it is built: something must listen
    sock_dir = tempfile.mkdtemp(prefix="ring")     # short: a unix path
    listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    listener.bind(os.path.join(sock_dir, "s"))
    listener.listen(4)
    monkeypatch.setenv("PLENUM_CRYPTO_SOCKET", os.path.join(sock_dir, "s"))
    base = str(tmp_path)
    setup_pool_dir(base, ["N1", "N2", "N3", "N4"], TRUSTEE_SEED)
    _prodable, node, _reg = build_node("N1", base, backend=backend,
                                       kv="memory")
    try:
        chain, obj = [], node.c.authenticator.core_authenticator.verifier
        while obj is not None:
            chain.append(type(obj).__name__)
            obj = obj.__dict__.get("_inner") or obj.__dict__.get("_device")
        assert chain == verifier_chain
        pipe = node.c.pipeline
        if not ring:
            assert pipe is None
            assert node.validator_info()["plane"] is None
            return
        # THE ring of the co-hosted pool, built through the one seam
        assert type(pipe) is CryptoPipeline and pipe._bucketed
        assert pipe._ed_inner._device._min_batch == 1
        assert pipe._sha_device and pipe.config.PIPELINE_MAX_BUCKET == 4096
        assert not pipe.pinned
        warmed = warm_ring(pipe)
        # one validator's receive quota (100) fits the second bucket
        assert warmed["buckets"] == pipe.quota_buckets() == [64, 128]
        assert warmed["shapes"] == pipe.ed_shapes() == [[64, 64], [128, 64]]
        assert pipe.pinned and warmed["pinned"]
        assert warmed["device"]["platform"] == "cpu"    # JAX_PLATFORMS
        # client-auth, the BLS batch check and the tree hasher ride it
        assert pipe.verifier()._pipeline is pipe
        plane = node.validator_info()["plane"]
        assert plane["ring"]["pinned"] and plane["ring"]["unpinned_shapes"] == 0
        assert plane["ring"]["compiled_shapes"] >= 2 + 4   # ed + cmt ladder
        (sup,) = plane["supervisors"]
        assert sup["device_batches"] == 2 and sup["breaker_state"] == "closed"
        assert set(plane["compile"]) >= {"executables", "aot_loads", "traces"}
        assert plane["device"]["count"] >= 1
        assert "memory_peak_bytes" in plane["device"]
        json.dumps(node.validator_info()["plane"])      # goes over the wire
    finally:
        node.c.db.close()
        listener.close()
        shutil.rmtree(sock_dir, ignore_errors=True)


def _ask(addr, request, timeout=30.0) -> dict:
    from plenum_tpu.common.serialization import pack, unpack

    async def run():
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection(*addr, limit=1 << 24), 10.0)
        try:
            payload = pack(request.to_dict())
            writer.write(len(payload).to_bytes(4, "big") + payload)
            await writer.drain()
            while True:
                hdr = await asyncio.wait_for(reader.readexactly(4), timeout)
                msg = unpack(await asyncio.wait_for(reader.readexactly(
                    int.from_bytes(hdr, "big")), timeout))
                if isinstance(msg, dict) and msg.get("op") in (
                        "REPLY", "REQNACK", "REJECT"):
                    return msg
        finally:
            writer.close()
    return asyncio.run(run())


def test_a_validator_pins_its_ring_before_it_serves(tmp_path):
    """The process itself: `{"ring"` (pinned) comes before `{"started"`,
    VALIDATOR_INFO over the client port carries the plane, SIGTERM ends
    it with 143."""
    from plenum_tpu.common.request import Request
    from plenum_tpu.execution.action_manager import VALIDATOR_INFO_ACTION
    from plenum_tpu.tools.tcp_pool import setup_pool_dir
    base = str(tmp_path)
    specs = setup_pool_dir(base, ["N1", "N2", "N3", "N4"], TRUSTEE_SEED)
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("PLENUM_CONFIG_JSON", None)
    proc = subprocess.Popen(
        [sys.executable, "-c", STAND_IN, "--name", "N1", "--base-dir", base,
         "--kv", "memory", "--backend", "jax"],
        env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        lines = []
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                break
            if line.startswith(b"{"):
                lines.append(json.loads(line))
                if "started" in lines[-1]:
                    break
        assert [next(iter(ln)) for ln in lines] == ["ring", "started"], lines
        ring = lines[0]["ring"]
        assert ring["pinned"] and ring["buckets"] == [64, 128]
        assert ring["compile"]["executables"] == 0      # the double's

        signer = ed25519.Ed25519Signer(seed=TRUSTEE_SEED)
        req = Request(signer.identifier, 1, {"type": VALIDATOR_INFO_ACTION})
        req.signature = signer.sign_b58(req.signing_bytes())
        msg = _ask((specs[0][1], specs[0][3]), req)
        assert msg["op"] == "REPLY", msg
        plane = msg["result"]["data"]["plane"]
        assert plane["ring"]["pinned"]
        # the request's own signature went through this validator's ring
        assert plane["ring"]["verify_items"] >= 1
        assert plane["ring"]["dispatched_items"] >= 1
        assert plane["supervisors"][0]["device_batches"] >= 3
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=20) == 143
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
