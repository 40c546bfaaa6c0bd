"""The benchmark's client over the nodes' client ports: one connection and
one reader per node, every reply handed to a Tracker. A copy of the idea of
plenum_tpu/client/pipelined.py, with a schedule (open loop from due times,
or closed loop), latency from the due time, and the generator's lag."""
from __future__ import annotations

import asyncio
import time

from benchmarks.accounting import Feeder
from plenum_tpu.common.serialization import pack, unpack


NACKS = ("REQNACK", "REJECT", "LOAD_SHED")


class PoolConnections:
    def __init__(self, addrs: dict):
        self.addrs = dict(addrs)
        self.conns: dict = {}
        self.readers: list = []
        self.tracker = None
        self.progress = None        # asyncio.Event, set on every ack

    async def connect(self) -> None:
        self.progress = asyncio.Event()
        for name, (host, port) in self.addrs.items():
            self.conns[name] = await asyncio.wait_for(
                asyncio.open_connection(host, port, limit=1 << 22), 10.0)
        self.readers = [asyncio.ensure_future(self._read(n))
                        for n in self.conns]

    async def close(self) -> None:
        for t in self.readers:
            t.cancel()
        await asyncio.gather(*self.readers, return_exceptions=True)
        for _, writer in self.conns.values():
            writer.close()
        self.conns.clear()

    async def _read(self, name: str) -> None:
        reader, _ = self.conns[name]
        try:
            while True:
                hdr = await reader.readexactly(4)
                msg = unpack(await reader.readexactly(
                    int.from_bytes(hdr, "big")))
                if isinstance(msg, dict):
                    self._on_message(name, msg)
        except (asyncio.IncompleteReadError, OSError):
            return

    def _on_message(self, name: str, msg: dict) -> None:
        tracker = self.tracker
        if tracker is None:
            return
        now = time.perf_counter()
        if msg.get("op") == "REPLY":
            result = msg.get("result", {})
            meta = result.get("txn", {}).get("metadata", {})
            key = (meta.get("from"), meta.get("reqId"))
            if tracker.on_reply(key, name, result, now):
                self.progress.set()
        elif msg.get("op") in NACKS and "req_id" in msg:
            tracker.on_nack((msg.get("identifier"), msg["req_id"]), name,
                            f"{msg.get('op')}: {msg.get('reason')}")
            self.progress.set()

    def write(self, request) -> None:
        payload = pack(request.to_dict())
        frame = len(payload).to_bytes(4, "big") + payload
        for _, writer in self.conns.values():
            writer.write(frame)

    async def flush(self) -> None:
        await asyncio.gather(*(asyncio.wait_for(w.drain(), 10.0)
                               for _, w in self.conns.values()))

    async def drive(self, requests, schedule: dict, seconds, tracker,
                    drain_s: float, actions=()) -> dict:
        """One window on the schedule accounting.Feeder keeps. actions:
        [(offset, callable)], each called once when the window reaches its
        offset. -> window times."""
        self.tracker = tracker
        actions = sorted(actions, key=lambda a: a[0])
        feeder = Feeder(requests, schedule, seconds, tracker,
                        time.perf_counter())
        a = 0
        while True:
            now = time.perf_counter()
            while a < len(actions) and now - feeder.t_open >= actions[a][0]:
                actions[a][1]()
                a += 1
            batch = feeder.take(now)
            for request in batch:
                self.write(request)
            if batch:
                await self.flush()
            if feeder.over(now):
                break
            if feeder.due is not None:
                await asyncio.sleep(max(
                    0.0, feeder.next_due() - time.perf_counter()))
            else:
                self.progress.clear()
                try:
                    await asyncio.wait_for(self.progress.wait(), 0.005)
                except asyncio.TimeoutError:
                    pass
        t_drained = feeder.close(time.perf_counter(), drain_s)
        while tracker.open and time.perf_counter() < t_drained:
            await asyncio.sleep(0.01)
        return feeder.times(t_drained, time.perf_counter())


async def ask(addr: tuple, request, timeout: float = 30.0) -> dict:
    """One request to ONE node on a connection of its own -> its reply."""
    reader, writer = await asyncio.wait_for(
        asyncio.open_connection(*addr, limit=1 << 24), 10.0)
    try:
        payload = pack(request.to_dict())
        writer.write(len(payload).to_bytes(4, "big") + payload)
        await writer.drain()
        deadline = time.monotonic() + timeout
        while True:
            hdr = await asyncio.wait_for(reader.readexactly(4),
                                         deadline - time.monotonic())
            msg = unpack(await asyncio.wait_for(reader.readexactly(
                int.from_bytes(hdr, "big")), deadline - time.monotonic()))
            if isinstance(msg, dict) and msg.get("op") in (
                    "REPLY", "REQNACK", "REJECT"):
                return msg
    finally:
        writer.close()
