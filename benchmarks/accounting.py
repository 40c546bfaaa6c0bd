"""From replies to the end-to-end numbers: the f+1 matching-reply tracker
and the arithmetic on (due, acknowledged) pairs. Pure functions of what a
drive observed; imports nothing of the program."""
from __future__ import annotations

import hashlib
import json
import math


def content_key(result) -> str:
    """Replies 'match' when their whole result is equal; a Byzantine
    node's different reply lands in its own bucket."""
    return hashlib.sha256(json.dumps(
        result, sort_keys=True, separators=(",", ":"),
        default=repr).encode()).hexdigest()


class Tracker:
    """due/sent/ack times of every request of one window, and the quorum
    each acknowledgement rested on."""

    def __init__(self, quorum: int):
        self.quorum = quorum
        self.due: dict = {}
        self.sent: dict = {}
        self.acked: dict = {}       # key -> time of the quorum-th equal reply
        self.results: dict = {}     # key -> the agreed result
        self.nacked: dict = {}      # key -> reason
        self._votes: dict = {}
        self.open = 0               # expected, not yet acknowledged or nacked

    def expect(self, key, due: float, sent: float | None) -> None:
        """sent None: due inside the window but never sent (the generator
        fell behind): attempted, and failed."""
        self.due[key] = due
        if sent is not None:
            self.sent[key] = sent
            self.open += 1

    def on_reply(self, key, node: str, result, now: float) -> bool:
        """-> True when this reply completed the quorum."""
        if key in self.acked or key not in self.due:
            return False
        voters = self._votes.setdefault(key, {}).setdefault(
            content_key(result), set())
        voters.add(node)
        if len(voters) < self.quorum:
            return False
        self.acked[key] = now
        self.results[key] = result
        if key not in self.nacked:
            self.open -= 1
        return True

    def on_nack(self, key, node: str, reason) -> None:
        if key in self.due and key not in self.acked \
                and key not in self.nacked:
            self.nacked[key] = str(reason)
            self.open -= 1

    def quorum_of(self, key) -> int:
        """Most equal replies seen for an acknowledged request."""
        return max((len(v) for v in self._votes.get(key, {}).values()),
                   default=0)


class Feeder:
    """Which requests a drive sends now: the one copy of the schedule that
    the served and the co-hosted drive loops share. An open loop
    ({"due": [offsets]}) releases request i when its due time has come,
    whatever has been acknowledged; a closed loop ({"in_flight": n}) keeps
    n unanswered and sends nothing past the close. seconds None = until
    every request is answered (the warm-up)."""

    def __init__(self, requests, schedule: dict, seconds, tracker: Tracker,
                 t_open: float):
        self.requests, self.tracker, self.t_open = requests, tracker, t_open
        self.due, self.cap = schedule.get("due"), schedule.get("in_flight")
        self.until_answered = seconds is None
        self.t_close = float("inf") if seconds is None else t_open + seconds
        self.sent = 0

    def _release(self, due: float, now: float):
        req = self.requests[self.sent]
        self.tracker.expect((req.identifier, req.req_id), due, now)
        self.sent += 1
        return req

    def take(self, now: float) -> list:
        """The requests to send at `now`, each already expected. What was
        due before the close is released even when this pass woke just
        past it."""
        out, n = [], len(self.requests)
        if self.due is not None:
            limit = min(now, self.t_close)
            while self.sent < n \
                    and self.t_open + self.due[self.sent] <= limit:
                out.append(self._release(
                    self.t_open + self.due[self.sent], now))
        elif now < self.t_close:
            while self.sent < n and self.tracker.open < self.cap:
                out.append(self._release(now, now))
        return out

    def over(self, now: float) -> bool:
        return now >= self.t_close or (
            self.until_answered and self.sent >= len(self.requests)
            and self.tracker.open == 0)

    def next_due(self) -> float:
        """When an open loop has to wake next."""
        if self.sent < len(self.requests):
            return min(self.t_open + self.due[self.sent], self.t_close)
        return self.t_close

    def close(self, now: float, drain_s: float) -> float:
        """The window has closed: what was due and never sent is attempted
        and failed. -> when the drain ends."""
        if self.until_answered:
            self.t_close = now
        for j in range(self.sent,
                       len(self.requests) if self.due is not None else 0):
            req = self.requests[j]
            self.tracker.expect((req.identifier, req.req_id),
                                self.t_open + self.due[j], None)
        return self.t_close + drain_s

    def times(self, t_drained: float, now: float) -> dict:
        return {"t_open": self.t_open, "t_close": self.t_close,
                "t_drained": min(t_drained, max(self.t_close, now)),
                "sent": self.sent}


def quantile(values, q: float) -> float:
    """Nearest-rank quantile: the ceil(q*n)-th smallest."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("quantile of nothing")
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def window_numbers(due: dict, acked: dict, t_open: float, t_close: float,
                   t_drained: float) -> dict:
    """attempted = operations due in [t_open, t_close). An operation not
    acknowledged by t_drained has failed (a later acknowledgement is a
    failure too) and enters the latency tail at t_drained - due, the least
    it can have taken. Throughput counts acknowledgements inside the
    window over the window's seconds."""
    keys = [k for k, t in due.items() if t_open <= t < t_close]
    ok = [k for k in keys if k in acked and acked[k] <= t_drained]
    done = set(ok)
    lat = [acked[k] - due[k] for k in ok] \
        + [t_drained - due[k] for k in keys if k not in done]
    in_window = sum(1 for k in ok if acked[k] <= t_close)
    out = {"attempted": len(keys), "failed": len(keys) - len(ok),
           "acked_in_window": in_window,
           "per_s": in_window / (t_close - t_open)}
    if lat:
        out["latency_p50_ms"] = quantile(lat, 0.50) * 1e3
        out["latency_p95_ms"] = quantile(lat, 0.95) * 1e3
        out["latency_samples"] = len(lat)
    return out
