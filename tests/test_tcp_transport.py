"""Real-socket transport tests: handshake, allowlist, batching, reconnect,
and a full 4-node pool ordering a NYM over localhost TCP.

Reference test model: stp_zmq tests (connect/auth) + the pool e2e NYM flow
(SURVEY.md §4). Everything runs in one asyncio loop — real sockets, no OS
process per node.
"""
from __future__ import annotations

import asyncio
import hashlib
import time

import pytest

pytest.importorskip(
    "cryptography",
    reason="the TCP node stack's handshake needs the cryptography package")

from plenum_tpu.common.node_messages import InstanceChange
from plenum_tpu.common.event_bus import ExternalBus
from plenum_tpu.common.serialization import pack, unpack
from plenum_tpu.network.tcp_stack import ClientStack, NodeRegistry, TcpStack


def _seed(name: str) -> bytes:
    return hashlib.sha256(b"tcp-test-" + name.encode()).digest()


def _vk(seed: bytes) -> bytes:
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey)
    sk = Ed25519PrivateKey.from_private_bytes(seed)
    return sk.public_key().public_bytes(
        serialization.Encoding.Raw, serialization.PublicFormat.Raw)


async def _make_pair(names=("Alpha", "Beta")):
    reg = NodeRegistry()
    stacks = {}
    for n in names:
        stacks[n] = TcpStack(n, "127.0.0.1", 0, reg, _seed(n))
        port = await stacks[n].bind()
        reg.set(n, "127.0.0.1", port, stacks[n].verkey)
    for n in names:
        await stacks[n].start()
    return reg, stacks


async def _wait(cond, timeout=5.0, interval=0.01):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if cond():
            return True
        await asyncio.sleep(interval)
    return cond()


def test_handshake_and_message_roundtrip():
    async def main():
        reg, stacks = await _make_pair()
        a, b = stacks["Alpha"], stacks["Beta"]
        assert await _wait(lambda: a.connected == {"Beta"}
                           and b.connected == {"Alpha"})

        got = []
        b.bus.subscribe(InstanceChange,
                        lambda msg, frm: got.append((msg, frm)))
        a.bus.send(InstanceChange(view_no=3, reason=0), "Beta")
        assert await _wait(lambda: b.drain() + len(got) and got)
        msg, frm = got[0]
        assert isinstance(msg, InstanceChange) and msg.view_no == 3
        assert frm == "Alpha"

        # and the reverse direction (acceptor -> dialer)
        got_a = []
        a.bus.subscribe(InstanceChange,
                        lambda msg, frm: got_a.append((msg, frm)))
        b.bus.send(InstanceChange(view_no=7, reason=0), "Alpha")
        assert await _wait(lambda: a.drain() + len(got_a) and got_a)
        assert got_a[0][0].view_no == 7 and got_a[0][1] == "Beta"

        # Connected events reached the bus subscribers
        assert a.bus.connecteds == {"Beta"}
        await a.stop()
        await b.stop()

    asyncio.run(main())


def test_allowlist_rejects_unknown_verkey():
    async def main():
        reg, stacks = await _make_pair()
        a, b = stacks["Alpha"], stacks["Beta"]
        assert await _wait(lambda: a.connected == {"Beta"})

        # an impostor dialing Beta with a key not in the registry: the
        # acceptor must refuse (ZAP allowlist, zstack.py:322)
        evil_reg = NodeRegistry()
        evil_reg.set("Beta", "127.0.0.1", b.port, b.verkey)
        evil = TcpStack("AAAevil", "127.0.0.1", 0, evil_reg,
                        _seed("not-in-registry"))
        await evil.bind()
        evil.maintain_connections()
        await asyncio.sleep(0.5)
        assert evil.connected == set()
        assert b.stats["rejected"] >= 1
        assert b.connected == {"Alpha"}      # honest session unaffected
        await evil.stop()
        await a.stop()
        await b.stop()

    asyncio.run(main())


def test_outbox_batching_one_frame_per_flush():
    async def main():
        reg, stacks = await _make_pair()
        a, b = stacks["Alpha"], stacks["Beta"]
        assert await _wait(lambda: a.connected == {"Beta"})
        base = a.stats["sent_frames"]
        got = []
        b.bus.subscribe(InstanceChange, lambda m, f: got.append(m))
        for v in range(50):
            a.bus.send(InstanceChange(view_no=v, reason=0), "Beta")
        assert await _wait(lambda: (b.drain(), len(got))[1] >= 50)
        # 50 messages coalesced into one encrypted frame (batched.py:20)
        assert a.stats["sent_frames"] == base + 1
        assert [m.view_no for m in got] == list(range(50))
        await a.stop()
        await b.stop()

    asyncio.run(main())


def test_queued_outbox_flushes_after_reconnect():
    async def main():
        reg = NodeRegistry()
        a = TcpStack("Alpha", "127.0.0.1", 0, reg, _seed("Alpha"))
        await a.bind()
        reg.set("Alpha", "127.0.0.1", a.port, a.verkey)
        # Beta is registered but not yet listening: messages queue
        beta_seed = _seed("Beta")
        reg.set("Beta", "127.0.0.1", 1, _vk(beta_seed))  # dead port
        await a.start()
        a.bus.send(InstanceChange(view_no=9, reason=0), "Beta")
        await asyncio.sleep(0.3)
        assert a.connected == set()

        # now Beta comes up on a real port; update registry; dialer retries
        b = TcpStack("Beta", "127.0.0.1", 0, reg, beta_seed)
        port = await b.bind()
        reg.set("Beta", "127.0.0.1", port, b.verkey)
        await b.start()
        got = []
        b.bus.subscribe(InstanceChange, lambda m, f: got.append(m))
        assert await _wait(lambda: (b.drain(), len(got))[1] >= 1, timeout=8.0)
        assert got[0].view_no == 9           # queued message survived
        await a.stop()
        await b.stop()

    asyncio.run(main())


def test_session_supersede_on_peer_restart():
    async def main():
        reg, stacks = await _make_pair()
        a, b = stacks["Alpha"], stacks["Beta"]
        assert await _wait(lambda: a.connected == {"Beta"})
        b_port = b.port
        await b.stop()
        assert await _wait(lambda: a.connected == set(), timeout=5.0)

        # Beta restarts on the SAME port with the same identity
        b2 = TcpStack("Beta", "127.0.0.1", b_port, reg, _seed("Beta"))
        await b2.start()
        assert await _wait(lambda: a.connected == {"Beta"}
                           and b2.connected == {"Alpha"}, timeout=8.0)
        got = []
        b2.bus.subscribe(InstanceChange, lambda m, f: got.append(m))
        a.bus.send(InstanceChange(view_no=4, reason=0), "Beta")
        assert await _wait(lambda: (b2.drain(), len(got))[1] >= 1)
        await a.stop()
        await b2.stop()

    asyncio.run(main())


# --- full pool over real sockets -----------------------------------------

def _build_tcp_pool(n_nodes=4):
    """Nodes + TCP stacks + client stacks in one loop; returns the parts."""
    from plenum_tpu.common.node_messages import DOMAIN_LEDGER_ID
    from plenum_tpu.common.timer import QueueTimer
    from plenum_tpu.config import Config
    from plenum_tpu.node import Node, NodeBootstrap
    from plenum_tpu.node.looper import Looper, Prodable
    from plenum_tpu.tools.local_pool import build_genesis

    names = [f"Node{i + 1}" for i in range(n_nodes)]
    genesis, trustee = build_genesis(names)
    reg = NodeRegistry()
    config = Config(Max3PCBatchWait=0.005,
                    STATE_FRESHNESS_UPDATE_INTERVAL=600.0)
    looper = Looper(prod_interval=0.002)
    nodes, node_stacks, client_stacks = {}, {}, {}

    async def setup():
        for name in names:
            stack = TcpStack(name, "127.0.0.1", 0, reg, _seed(name))
            await stack.bind()
            reg.set(name, "127.0.0.1", stack.port, stack.verkey)
            node_stacks[name] = stack
        for name in names:
            components = NodeBootstrap(name, genesis_txns=genesis).build()
            timer = QueueTimer(time.perf_counter)
            cstack = ClientStack(name, "127.0.0.1", 0, on_request=None)
            node = Node(name, timer, node_stacks[name].bus, components,
                        client_send=cstack.send, config=config)
            cstack._on_request = node.handle_client_message
            nodes[name] = node
            client_stacks[name] = cstack
            looper.add(Prodable(node, node_stacks[name], cstack, timer))

    return names, reg, looper, nodes, client_stacks, setup, trustee


@pytest.mark.slow
def test_pool_orders_nym_over_tcp():
    from plenum_tpu.common.node_messages import DOMAIN_LEDGER_ID
    from plenum_tpu.common.request import Request
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.execution.txn import NYM

    (names, reg, looper, nodes, client_stacks,
     setup, trustee) = _build_tcp_pool()

    async def main():
        await setup()
        async with looper:
            # all nodes fully meshed
            ok = await looper.run_until(
                lambda: all(len(n.node_bus.connecteds) == 3
                            for n in nodes.values()), timeout=10.0)
            assert ok, "pool never meshed over TCP"

            # a real TCP client submits a signed NYM to every node
            user = Ed25519Signer(seed=b"tcp-pool-user".ljust(32, b"\0"))
            req = Request(trustee.identifier, 1,
                          {"type": NYM, "dest": user.identifier,
                           "verkey": user.verkey_b58})
            req.signature = trustee.sign_b58(req.signing_bytes())
            replies = []

            async def submit(name):
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", client_stacks[name].port)
                data = pack(req.to_dict())
                writer.write(len(data).to_bytes(4, "big") + data)
                await writer.drain()
                try:
                    while True:
                        hdr = await asyncio.wait_for(
                            reader.readexactly(4), timeout=15.0)
                        frame = await reader.readexactly(
                            int.from_bytes(hdr, "big"))
                        msg = unpack(frame)
                        replies.append(msg)
                        if msg.get("op") == "REPLY":
                            break
                except (asyncio.TimeoutError, asyncio.IncompleteReadError):
                    pass
                writer.close()

            await asyncio.gather(*(submit(n) for n in names))
            assert any(m.get("op") == "REPLY" for m in replies), replies

            sizes = {nodes[n].c.db.get_ledger(DOMAIN_LEDGER_ID).size
                     for n in names}
            assert sizes == {2}, sizes       # genesis NYM + the new one

    asyncio.run(main())


@pytest.mark.slow
def test_primary_crash_recovers_within_disconnect_timeout():
    """Kill the primary (stop prodding + close its sockets): survivors see
    the TCP disconnect, vote PRIMARY_DISCONNECTED after
    PRIMARY_DISCONNECT_TIMEOUT, complete a view change, and order a
    pending NYM — with the stall/freshness watchdogs configured far too
    slow (600s) to be the cause (ref primary_connection_monitor_service)."""
    from plenum_tpu.common.node_messages import DOMAIN_LEDGER_ID
    from plenum_tpu.common.request import Request
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.execution.txn import NYM

    (names, reg, looper, nodes, client_stacks,
     setup, trustee) = _build_tcp_pool()

    async def main():
        await setup()
        # only the disconnect fast path may fire inside this test's window
        for node in nodes.values():
            node.config.PRIMARY_DISCONNECT_TIMEOUT = 2.0
            node.config.ORDERING_PROGRESS_TIMEOUT = 600.0
            node.config.STATE_FRESHNESS_UPDATE_INTERVAL = 600.0
        async with looper:
            ok = await looper.run_until(
                lambda: all(len(n.node_bus.connecteds) == 3
                            for n in nodes.values()), timeout=10.0)
            assert ok, "pool never meshed over TCP"

            primary = nodes[names[0]].master_replica.data.primary_name
            survivors = [n for n in names if n != primary]
            victim = next(p for p in looper._prodables
                          if p.node is nodes[primary])
            victim.prod = lambda: 0          # the process is "dead"
            await victim.stop()              # sockets close underneath peers

            user = Ed25519Signer(seed=b"tcp-crash-user".ljust(32, b"\0"))
            req = Request(trustee.identifier, 1,
                          {"type": NYM, "dest": user.identifier,
                           "verkey": user.verkey_b58})
            req.signature = trustee.sign_b58(req.signing_bytes())

            async def submit(name):
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", client_stacks[name].port)
                data = pack(req.to_dict())
                writer.write(len(data).to_bytes(4, "big") + data)
                await writer.drain()
                writer.close()

            await asyncio.gather(*(submit(n) for n in survivors))
            t0 = time.perf_counter()
            ok = await looper.run_until(
                lambda: all(
                    nodes[n].master_replica.view_no >= 1
                    and nodes[n].c.db.get_ledger(DOMAIN_LEDGER_ID).size == 2
                    for n in survivors),
                timeout=25.0)
            elapsed = time.perf_counter() - t0
            for n in survivors:
                assert nodes[n].master_replica.view_no >= 1, \
                    f"{n} never left view 0 (after {elapsed:.1f}s)"
                assert nodes[n].c.db.get_ledger(
                    DOMAIN_LEDGER_ID).size == 2, f"{n} did not order"
            # sanity: recovery rode the 2s disconnect vote, not the 600s
            # watchdogs (generous bound for slow CI)
            assert elapsed < 25.0

    asyncio.run(main())


def test_client_connection_flood_is_bounded():
    """Client-stack connection budget (ref plenum/config.py:285-292):
    a connection flood is capped at max_connections with the overflow
    rejected; sweeping reclaims slots from idle connections so live
    clients still get served after the flood."""
    import time as _time

    async def scenario():
        stack = ClientStack("srv", "127.0.0.1", 0, on_request=None,
                            max_connections=8, idle_timeout=0.5)
        seen = []
        stack._on_request = lambda msg, cid: seen.append((msg, cid))
        port = await stack.bind()

        # flood: 30 connections, each sending one frame to prove liveness
        floods = []
        for i in range(30):
            try:
                r, w = await asyncio.open_connection("127.0.0.1", port)
                from plenum_tpu.common.serialization import pack
                payload = pack({"op": "NOOP", "i": i})
                w.write(len(payload).to_bytes(4, "big") + payload)
                await w.drain()
                floods.append((r, w))
            except OSError:
                pass
        await asyncio.sleep(0.3)
        assert len(stack._conns) <= 8            # bounded, not 30
        assert stack.rejected_connections >= 20

        # flood connections go idle; a NEW client connects after the
        # idle window and must be admitted via the sweep
        await asyncio.sleep(0.6)
        r2, w2 = await asyncio.open_connection("127.0.0.1", port)
        from plenum_tpu.common.serialization import pack as _pack
        payload = _pack({"op": "LIVE"})
        w2.write(len(payload).to_bytes(4, "big") + payload)
        await w2.drain()
        await asyncio.sleep(0.3)
        stack.drain()
        assert any(m.get("op") == "LIVE" for m, _ in seen)
        assert len(stack._conns) <= 8

        for _, w in floods:
            try:
                w.close()
            except Exception:
                pass
        w2.close()
        await stack.stop()

    asyncio.run(scenario())


# --- when a message leaves, when a frame is read (PR 40) -------------------

class _Relay:
    """Stands where the Node does in a Prodable: nothing of its own to do;
    what it casts, it casts in the handlers the node stack's drain runs."""

    def prod(self) -> int:
        return 0


def _readable(stack: TcpStack, peer: str, timeout: float = 1.0) -> bool:
    """Bytes wait on `stack`'s socket to `peer`, asked of the KERNEL:
    no turn of the event loop is taken."""
    import select
    sock = stack._sessions[peer].conn.transport.get_extra_info("socket")
    return bool(select.select([sock.fileno()], [], [], timeout)[0])


def test_vote_cast_inside_prod_has_left_when_prod_returns():
    """Beta answers each InstanceChange it drains with one of its own (a
    PREPARE answering a PRE-PREPARE, in small). When Prodable.prod()
    returns, before any await, the answer is on Alpha's socket."""
    from plenum_tpu.node.looper import Prodable

    async def main():
        reg, stacks = await _make_pair()
        a, b = stacks["Alpha"], stacks["Beta"]
        assert await _wait(lambda: a.connected == {"Beta"}
                           and b.connected == {"Alpha"})
        b.bus.subscribe(
            InstanceChange,
            lambda m, f: b.bus.send(
                InstanceChange(view_no=m.view_no + 100, reason=0), f))
        prodable = Prodable(_Relay(), b)
        a.bus.send(InstanceChange(view_no=1, reason=0), "Beta")
        assert await _wait(lambda: len(b._inbound) == 1)
        assert not _readable(a, "Beta", timeout=0)
        base = dict(b.stats["flushes"]), b.stats["sent_frames"]
        # ---- no await from here ...
        assert prodable.prod() == 1
        assert b.stats["sent_frames"] == base[1] + 1
        assert b.stats["flushes"]["in_cycle"] == base[0]["in_cycle"] + 1
        assert b.stats["flushes"]["scheduled"] == base[0]["scheduled"]
        assert b._scheduled_flush is None      # and nothing left to run
        assert _readable(a, "Beta")
        # ---- ... to here
        got = []
        a.bus.subscribe(InstanceChange, lambda m, f: got.append(m.view_no))
        assert await _wait(lambda: (a.drain(), got)[1] == [101])
        hold = b.stats["tx_hold"]
        assert hold["count"] == b.stats["sent_frames"]
        assert 0 < hold["sum_s"] < 1.0
        assert a.stats["rx_hold"]["count"] == 1 == b.stats["rx_hold"]["count"]
        await a.stop()
        await b.stop()

    asyncio.run(main())


def test_fifty_votes_in_one_phase_are_one_frame():
    """Coalescing per flush point: what ONE drain casts to a peer is one
    encrypted frame, written by the drain's own flush."""
    async def main():
        reg, stacks = await _make_pair()
        a, b = stacks["Alpha"], stacks["Beta"]
        assert await _wait(lambda: a.connected == {"Beta"}
                           and b.connected == {"Alpha"})

        def fifty(m, f):
            for v in range(50):
                b.bus.send(InstanceChange(view_no=v, reason=0), f)
        b.bus.subscribe(InstanceChange, fifty)
        got = []
        a.bus.subscribe(InstanceChange, lambda m, f: got.append(m.view_no))
        a.bus.send(InstanceChange(view_no=0, reason=0), "Beta")
        assert await _wait(lambda: len(b._inbound) == 1)
        base = b.stats["sent_frames"]
        assert b.drain() == 1
        assert b.stats["sent_frames"] == base + 1
        assert b.stats["flushes"]["in_cycle"] == 1
        assert await _wait(lambda: (a.drain(), len(got))[1] >= 50)
        assert got == list(range(50))
        assert a.stats["recv_frames"] == 1
        await a.stop()
        await b.stop()

    asyncio.run(main())


def test_send_outside_any_cycle_leaves_through_the_scheduled_flush():
    """Nobody calls flush(): a handshake's tail, a reconnect, a test. The
    send schedules one for the loop's next turn, as before."""
    async def main():
        reg, stacks = await _make_pair()
        a, b = stacks["Alpha"], stacks["Beta"]
        assert await _wait(lambda: a.connected == {"Beta"}
                           and b.connected == {"Alpha"})
        base = a.stats["sent_frames"]
        a.bus.send(InstanceChange(view_no=5, reason=0), "Beta")
        a.bus.send(InstanceChange(view_no=6, reason=0), "Beta")
        assert a.stats["sent_frames"] == base  # queued, not written
        assert a._scheduled_flush is not None
        await asyncio.sleep(0)                 # the turn the flush rides
        assert a.stats["sent_frames"] == base + 1
        assert a.stats["flushes"] == {"in_cycle": 0, "scheduled": 1}
        assert a._scheduled_flush is None
        assert _readable(b, "Alpha")
        await a.stop()
        await b.stop()

    asyncio.run(main())


def test_write_hwm_still_drops_a_peer_that_stopped_reading(monkeypatch):
    from plenum_tpu.network import tcp_stack
    monkeypatch.setattr(tcp_stack, "WRITE_HWM", 256 * 1024)

    async def main():
        reg, stacks = await _make_pair()
        a, b = stacks["Alpha"], stacks["Beta"]
        assert await _wait(lambda: a.connected == {"Beta"}
                           and b.connected == {"Alpha"})
        b._sessions["Alpha"].conn.transport.pause_reading()
        blob = {"op": "BLOB", "data": b"\x00" * (512 * 1024)}
        for _ in range(64):                    # 32 MiB at a deaf peer
            a.bus.send(blob, "Beta")
            a.flush()
            if a.stats["dropped_sessions"]:
                break
        assert a.stats["dropped_sessions"] == 1
        assert a.stats["dropped_frames"] == 1
        assert "Beta" not in a._sessions
        assert a.bus.connecteds == set()
        await a.stop()
        await b.stop()

    asyncio.run(main())


def test_client_request_is_in_the_inbox_in_the_turn_that_read_it():
    """ClientStack cuts frames in data_received too: the turn after the
    selector saw the bytes the request is queued (a StreamReader task
    took one turn more), and the looper's arrival event is set."""
    async def main():
        stack = ClientStack("srv", "127.0.0.1", 0, on_request=None)
        stack.arrival = asyncio.Event()
        port = await stack.bind()
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        assert await _wait(lambda: len(stack._conns) == 1)
        payload = pack({"op": "NOOP"})
        frame = len(payload).to_bytes(4, "big") + payload
        # one frame and a half in one segment, the rest in the next
        writer.write(frame + frame[:5])
        await asyncio.sleep(0)     # the selector is polled behind this step
        await asyncio.sleep(0)     # ... and its callback has run before this
        assert len(stack._inbound) == 1
        assert stack.arrival.is_set()
        writer.write(frame[5:])
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert [m for m, _ in stack._inbound] == [{"op": "NOOP"}] * 2
        writer.close()
        await stack.stop()

    asyncio.run(main())


class _FakeTransport:
    def __init__(self):
        self.closed = False

    def is_closing(self):
        return self.closed

    def close(self):
        self.closed = True


@pytest.mark.parametrize("chunks, frames, closed", [
    # two frames and the head of a third in one segment, then its tail
    ([b"\x00\x00\x00\x01a\x00\x00\x00\x02bc\x00\x00", b"\x00\x03de", b"f"],
     [b"a", b"bc", b"def"], False),
    # a header split across segments; an empty frame
    ([b"\x00", b"\x00\x00", b"\x02xy\x00\x00\x00\x00"], [b"xy", b""], False),
    # a length over MAX_FRAME closes the connection, nothing delivered
    ([b"\x7f\xff\xff\xff", b"zz"], [], True),
    # a frame the session refuses (does not decrypt) closes it; what was
    # behind it in the segment is not delivered
    ([b"\x00\x00\x00\x01a\x00\x00\x00\x03BAD\x00\x00\x00\x01b"],
     [b"a"], True),
])
def test_conn_cuts_frames_as_the_bytes_arrive(chunks, frames, closed):
    from plenum_tpu.network.tcp_stack import _Conn
    got = []

    def on_frame(frame):
        if frame == b"BAD":
            raise ValueError("does not decrypt")
        got.append(frame)

    conn = _Conn()
    conn.connection_made(_FakeTransport())
    conn.deliver_frames(on_frame, lambda: None)
    for chunk in chunks:
        conn.data_received(chunk)
    assert got == frames
    assert conn.transport.closed is closed


def test_frame_behind_the_handshake_in_one_segment_is_delivered():
    """The dialer may flush its queued outbox the instant its handshake
    ends, so the acceptor can read the handshake's last bytes and the
    first frame in ONE segment: deliver_frames cuts what is buffered."""
    from plenum_tpu.network.tcp_stack import _Conn
    got = []

    async def main():
        conn = _Conn()
        conn.connection_made(_FakeTransport())
        conn.data_received(b"hand" + b"\x00\x00\x00\x02hi")
        assert await conn.readexactly(4) == b"hand"
        conn.deliver_frames(got.append, lambda: None)
        assert got == [b"hi"]
        # a connection lost mid-handshake ends readexactly, not hangs it
        lost = _Conn()
        lost.connection_made(_FakeTransport())
        lost.data_received(b"ha")
        asyncio.get_running_loop().call_soon(lost.connection_lost, None)
        with pytest.raises(asyncio.IncompleteReadError):
            await lost.readexactly(4)

    asyncio.run(main())
