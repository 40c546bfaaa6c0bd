"""From a jax.profiler trace to device busy/idle and per-program time.

Stage 1 (`xplane_events`) reads the .xplane.pb with jax.profiler.ProfileData
and yields (plane, line, name, start_ns, duration_ns). Stage 2 (`reduce`)
turns any such stream into numbers and is checked against the recorded
fixture (fixtures/trace_events.json, cut from a real TPU trace). No figure
here comes from a host clock."""
from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"     # one event per program execution
OP_LINE = "XLA Ops"             # one event per device operation, nested
WINDOW_EVENT = "bench_trace_window"     # the benchmark's own host span
TOP = 10


def hold_trace(log_dir: str, seconds: float) -> dict:
    """One jax.profiler trace of `seconds`, held by the calling thread of
    the process that owns the chip. A TraceAnnotation named WINDOW_EVENT
    spans it on the trace's own clock, so the traced window's length comes
    from the trace too. Blocks until the trace is written out; -> how long
    starting and stopping took (host clock, for PERF.md's cost notes)."""
    import time

    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # no Python call stacks: size, cost
    opts.enable_hlo_proto = False       # the verify programs are 20 MB each
    t0 = time.perf_counter()
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    t1 = time.perf_counter()
    with jax.profiler.TraceAnnotation(WINDOW_EVENT):
        time.sleep(seconds)
    t2 = time.perf_counter()
    jax.profiler.stop_trace()
    return {"start_s": t1 - t0, "held_s": t2 - t1,
            "stop_s": time.perf_counter() - t2}


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def xplane_events(path: str):
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                yield (plane.name, line.name, ev.name,
                       int(ev.start_ns), int(ev.duration_ns))


class _Union:
    """Total length of a union of intervals fed roughly in start order."""

    def __init__(self):
        self.spans: list = []
        self.cur = None
        self.total = 0

    def add(self, start: int, end: int) -> None:
        if self.cur is None:
            self.cur = [start, end]
        elif start < self.cur[0]:       # out of order: settle later
            self.spans.append((start, end))
        elif start <= self.cur[1]:
            self.cur[1] = max(self.cur[1], end)
        else:
            self.spans.append(tuple(self.cur))
            self.cur = [start, end]

    def merged(self) -> list:
        spans = sorted(self.spans + ([tuple(self.cur)] if self.cur else []))
        out: list = []
        for s, e in spans:
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return out


def reduce(events, device_prefix: str = DEVICE_PLANE_PREFIX,
           module_line: str = MODULE_LINE, op_line: str = OP_LINE) -> dict:
    """-> {"window_s", "busy_s" (mean over device planes), "chips",
    "programs": {name: {"count", "time_s"}} (whole executions inside the
    window, summed over chips),
    "device_ops": [[name, s]], "idle_gaps": [[what follows, s]]}.

    busy is the union of the op line's intervals where the plane has one
    (else of the module line's), inside the window. The window is the
    benchmark's own WINDOW_EVENT span where the trace holds it, else the
    extent of every event of every plane, host threads included, so idle
    time at either end of the trace counts as idle. An op's time includes
    the ops nested in it (a while loop spans its body)."""
    lo, hi, span = None, None, None
    planes: dict = {}
    for plane, line, name, start, dur in events:
        end = start + dur
        lo = start if lo is None or start < lo else lo
        hi = end if hi is None or end > hi else hi
        if not plane.startswith(device_prefix):
            if name == WINDOW_EVENT:
                span = (start, end)
            continue
        p = planes.setdefault(plane, {"ops": _Union(), "mods": _Union(),
                                      "op_time": {}, "mod_events": []})
        if line == module_line:
            p["mods"].add(start, end)
            p["mod_events"].append((start, end, name))
        elif line == op_line:
            p["ops"].add(start, end)
            op = name.split(" = ", 1)[0]        # the HLO instruction's name
            p["op_time"][op] = p["op_time"].get(op, 0) + dur
    if span is not None:
        lo, hi = span
    if lo is None or not planes:
        return {"window_s": 0.0, "busy_s": 0.0, "chips": 0, "programs": {},
                "device_ops": [], "idle_gaps": []}
    busy, programs, op_time, gaps = [], {}, {}, []
    for p in planes.values():
        spans = p["ops"].merged() or p["mods"].merged()
        busy.append(sum(max(0, min(e, hi) - max(s, lo)) for s, e in spans))
        for start, end, name in p["mod_events"]:
            if start < lo or end > hi:
                continue        # cut by the window's edge: not a whole run
            agg = programs.setdefault(name, {"count": 0, "time_s": 0.0})
            agg["count"] += 1
            agg["time_s"] += (end - start) / 1e9
        for name, dur in p["op_time"].items():
            op_time[name] = op_time.get(name, 0) + dur
        mods = sorted(p["mod_events"])
        prev_end = lo
        for start, end, name in mods:
            if start > prev_end:
                gaps.append((start - prev_end, "before " + name))
            prev_end = max(prev_end, end)
        if hi > prev_end:
            gaps.append((hi - prev_end, "after the last program"))
    ops = op_time or {n: int(v["time_s"] * 1e9) for n, v in programs.items()}
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "chips": len(planes),
        "programs": programs,
        "device_ops": [[n, t / 1e9] for n, t in sorted(
            ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[what, g / 1e9] for g, what in sorted(
            gaps, reverse=True)[:TOP]],
    }


def reduce_dir(log_dir: str) -> dict:
    return reduce(xplane_events(find_xplane(log_dir)))
