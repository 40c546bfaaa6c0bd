"""The Looper's drive and what it promises the transport (PR 40): a busy
cycle lets the selector's callbacks run before the next one starts, so a
frame that is on the socket when a cycle ends is drained by the NEXT cycle;
an idle looper is woken by the arrival; every other task still gets a turn
per cycle. And the node's own flush points, in the order of a cycle.

Real sockets, one asyncio loop, no OS process per node; the pool test at
the end runs four whole nodes over TCP.
"""
from __future__ import annotations

import asyncio
import time

import pytest

pytest.importorskip(
    "cryptography",
    reason="the TCP node stack's handshake needs the cryptography package")

from plenum_tpu.common.node_messages import InstanceChange
from plenum_tpu.common.serialization import pack, unpack
from plenum_tpu.node.looper import Looper, Prodable
from tests.test_tcp_transport import _build_tcp_pool, _make_pair, _wait


async def _connected_pair():
    _, stacks = await _make_pair()
    a, b = stacks["Alpha"], stacks["Beta"]
    assert await _wait(lambda: a.connected == {"Beta"}
                       and b.connected == {"Alpha"})
    return a, b


class _Work:
    """Stands where the Node does in a Prodable: prod() runs `each`, burns
    `burn` seconds and reports `busy` work, and counts its cycles."""

    def __init__(self, busy=1, burn=0.0, each=None):
        self.busy, self.burn, self.each = busy, burn, each
        self.cycles = 0

    def prod(self) -> int:
        self.cycles += 1
        if self.each is not None:
            self.each(self.cycles)
        end = time.perf_counter() + self.burn
        while time.perf_counter() < end:
            pass
        return self.busy


class _Counted(Prodable):
    """Counts its cycles where one starts: before the stacks are drained."""
    cycle = 0

    def prod(self) -> int:
        self.cycle += 1
        return super().prod()


def test_frame_on_the_socket_is_drained_by_the_next_busy_cycle():
    """Beta's looper is busy (3 ms a cycle, never idle). Alpha's frame is
    written to the socket DURING Beta's cycle j, so it is readable when j
    ends: the handler must run in cycle j+1. The parent's drive (one
    sleep(0), a StreamReader task) ran j+1 and j+2 blind and handed the
    frame over in j+3."""
    async def main():
        a, b = await _connected_pair()
        sent_in, seen_in = {}, {}

        def each(_):
            # Alpha writes from INSIDE Beta's cycle: the bytes are on
            # Beta's socket before this cycle ends
            if prodable.cycle % 5 == 0 and len(sent_in) < 20:
                view = len(sent_in)
                a.bus.send(InstanceChange(view_no=view, reason=0), "Beta")
                a.flush()
                sent_in[view] = prodable.cycle

        work = _Work(busy=1, burn=0.003, each=each)
        prodable = _Counted(work, b)
        b.bus.subscribe(
            InstanceChange,
            lambda m, f: seen_in.setdefault(m.view_no, prodable.cycle))
        looper = Looper(prod_interval=0.002)
        # Beta was started by _make_pair: drive it without start()ing again
        looper._running = True
        task = asyncio.get_running_loop().create_task(looper._drive(prodable))
        assert await _wait(lambda: len(seen_in) == 20, timeout=10.0)
        looper._running = False
        await task
        blind = [seen_in[v] - sent_in[v] - 1 for v in sorted(sent_in)]
        assert blind == [0] * 20, blind
        assert prodable.wakes["busy"] >= work.cycles - 1
        assert prodable.wakes["interval"] == prodable.wakes["arrival"] == 0
        await a.stop()
        await b.stop()

    asyncio.run(main())


def test_idle_looper_is_woken_by_an_arrival_not_by_its_interval():
    async def main():
        a, b = await _connected_pair()
        got = []
        b.bus.subscribe(InstanceChange,
                        lambda m, f: got.append(time.perf_counter()))
        work = _Work(busy=0)
        looper = Looper(prod_interval=1.0)
        prodable = Prodable(work, b)
        looper._running = True
        task = asyncio.get_running_loop().create_task(looper._drive(prodable))
        await asyncio.sleep(0.05)              # Beta's looper is asleep now
        waits = []
        for view in range(5):
            t0 = time.perf_counter()
            a.bus.send(InstanceChange(view_no=view, reason=0), "Beta")
            assert await _wait(lambda: len(got) == view + 1, interval=0.001)
            waits.append(got[-1] - t0)
            await asyncio.sleep(0.02)
        looper._running = False
        task.cancel()
        # well under the 1 s interval, which the parent slept out (its
        # waits read 0.9 s and more here); a quarter of it leaves room
        # for a loaded test machine
        assert max(waits) < 0.25, waits
        assert prodable.wakes["arrival"] >= 5
        await a.stop()
        await b.stop()

    asyncio.run(main())


def test_idle_looper_still_services_timers_every_interval():
    """No arrival at all: the looper runs a cycle each prod_interval."""
    async def main():
        work = _Work(busy=0)
        looper = Looper(prod_interval=0.005)
        prodable = Prodable(work)
        looper.add(prodable)
        async with looper:
            await asyncio.sleep(0.2)
        assert 10 <= work.cycles <= 45, work.cycles
        assert prodable.wakes["interval"] >= work.cycles - 1
        assert prodable.wakes["arrival"] == prodable.wakes["busy"] == 0

    asyncio.run(main())


def test_busy_looper_lets_every_other_task_run_once_a_cycle():
    """Dial loops, accepts, the status task and the SIGTERM poll are tasks
    on the same loop: a node that is never idle must not starve them."""
    async def main():
        work = _Work(busy=1, burn=0.0005)
        looper = Looper(prod_interval=0.002)
        looper.add(Prodable(work))
        turns = 0

        async def other():
            nonlocal turns
            while True:
                await asyncio.sleep(0)
                turns += 1

        async with looper:
            side = asyncio.get_running_loop().create_task(other())
            start = work.cycles
            await asyncio.sleep(0.15)
            cycles = work.cycles - start
            side.cancel()
        assert cycles > 20
        assert turns >= cycles - 1, (turns, cycles)

    asyncio.run(main())


def test_node_prod_flushes_a_fresh_preprepare_before_the_commit():
    """Node.prod's flush points, in the order of a cycle: what
    replicas.service_all cast leaves BEFORE _service_ordered commits the
    previous batch and fans out its replies, and what the propagator
    queued leaves as the cycle ends. On the sim fabric both are no-ops."""
    from tests.test_pool import Pool

    pool = Pool()
    node = pool.nodes[pool.names[0]]
    assert node.transport_report is None
    assert node.validator_info()["transport"] is None
    node.node_bus.flush()                      # the sim fabric's: nothing
    order = []
    node.node_bus.flush = lambda: order.append("flush")
    node.replicas.service_all = lambda: order.append("replicas")
    node._service_ordered = lambda: order.append("ordered") or 0
    node.propagator.flush_outbox = lambda: order.append("propagates")
    node.prod()
    assert order == ["replicas", "flush", "ordered", "propagates", "flush"]


# --- four whole nodes over real sockets ------------------------------------

def test_tcp_pool_orders_and_replies_with_equal_roots():
    """Four nodes on TcpStack + ClientStack under one Looper order 12 NYMs
    sent over real client sockets: every node answers every write, the
    replies for one write are equal across the four (the roots and the
    audit path a REPLY carries included), all four end on one domain
    ledger root, state root and audit root, and the frames that carried
    the 3PC round left from the cycles' flush points."""
    from plenum_tpu.common.node_messages import (AUDIT_LEDGER_ID,
                                                 DOMAIN_LEDGER_ID)
    from plenum_tpu.common.request import Request
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.execution.txn import NYM

    (names, reg, looper, nodes, client_stacks,
     setup, trustee) = _build_tcp_pool()
    n_writes = 12

    def signed(i):
        user = Ed25519Signer(seed=f"looper-user-{i}".encode().ljust(32, b"\0"))
        req = Request(trustee.identifier, i + 1,
                      {"type": NYM, "dest": user.identifier,
                       "verkey": user.verkey_b58})
        req.signature = trustee.sign_b58(req.signing_bytes())
        return req

    async def main():
        await setup()
        async with looper:
            assert await looper.run_until(
                lambda: all(len(n.node_bus.connecteds) == 3
                            for n in nodes.values()), timeout=10.0)
            reqs = [signed(i) for i in range(n_writes)]
            replies = {name: {} for name in names}

            async def client(name):
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", client_stacks[name].port)
                for req in reqs:
                    data = pack(req.to_dict())
                    writer.write(len(data).to_bytes(4, "big") + data)
                while len(replies[name]) < n_writes:
                    hdr = await asyncio.wait_for(reader.readexactly(4), 20.0)
                    msg = unpack(await reader.readexactly(
                        int.from_bytes(hdr, "big")))
                    if msg.get("op") == "REPLY":
                        meta = msg["result"]["txn"]["metadata"]
                        replies[name][meta["reqId"]] = msg["result"]
                writer.close()

            await asyncio.gather(*(client(n) for n in names))
            for req_id in range(1, n_writes + 1):
                results = [replies[name][req_id] for name in names]
                assert all(r == results[0] for r in results), req_id

            def roots(node):
                db = node.c.db
                return (db.get_ledger(DOMAIN_LEDGER_ID).size,
                        db.get_ledger(DOMAIN_LEDGER_ID).root_hash,
                        db.get_state(DOMAIN_LEDGER_ID).committed_head_hash,
                        db.get_ledger(AUDIT_LEDGER_ID).root_hash)
            assert await looper.run_until(
                lambda: len({roots(n) for n in nodes.values()}) == 1,
                timeout=10.0)
            assert roots(nodes[names[0]])[0] == 1 + n_writes
            for prodable in looper._prodables:
                s = prodable.node_stack.stats
                assert s["flushes"]["in_cycle"] + s["flushes"]["scheduled"] \
                    == s["sent_frames"]
                # a scheduled flush is the path of a send made outside
                # any cycle; the 3PC round's frames are all in_cycle
                assert s["flushes"]["in_cycle"] >= 0.95 * s["sent_frames"] \
                    > 0, s["flushes"]
                assert s["tx_hold"]["count"] == s["sent_frames"]
                assert s["rx_hold"]["count"] == sum(
                    c for c, _ in s["rx_msgs"].values())

    asyncio.run(main())
