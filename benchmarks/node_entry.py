"""The benchmark's entry to a validator that owns its chip.

Runs plenum_tpu.tools.start_node.main unchanged, beside one control thread
(service_entry.py's pattern, with one command more) that lets the benchmark reach
into the only process that can: hold a jax.profiler trace of this node's
chip, read the device and its peak memory as JAX reports them here, and put
a sample of signatures through this validator's own ring. Commands are files
in --ctl: the benchmark writes `<cmd>`, the thread answers `<cmd>.done`.

    trace     body: "<log directory>\n<seconds>"  -> trace_reduce.hold_trace
    report                                        -> base.device_report()
    verdicts  body: path of a JSON list of [msg, sig, verkey] in hex
              -> ring_verdicts(): {"verdicts", "ring", "supervisor"}

`verdicts` asks the ring the node itself was built with
(`node.c.pipeline.verifier()`, the face client-auth stages through): the
timed plane, its verdict cache, its wave packing and scatter, its pinned
programs. The ring belongs to the node's loop and has no lock, so the
request is handed to that loop and runs on the main thread between two of
its turns (`watch_build_node`): start_node.main is run as it is, and the
`build_node` it calls is wrapped to keep the node and open that door.

--host-verifier (rehearsals on the CPU only) puts a host double behind the
ring before start_node builds it: the same ring, ladder, pin and counters,
with no multi-minute compile of the verify kernel."""
from __future__ import annotations

import json
import os
import queue
import sys
import threading
import time


def host_double():
    """A JaxEd25519Verifier whose dispatch is answered on the host. The
    ring pads to its buckets for it as for a device; it has no program to
    preload, so nothing compiles."""
    from plenum_tpu.crypto import ed25519

    class HostVerifier(ed25519.JaxEd25519Verifier):
        def submit_batch(self, items):
            return ed25519.CpuEd25519Verifier().verify_batch(items)

        def collect_batch(self, token, wait=True):
            return token

        def preload(self, waves):
            return []

    return HostVerifier


_node = None
_jobs: queue.SimpleQueue = queue.SimpleQueue()


def watch_build_node(start_node) -> None:
    """Wrap start_node.build_node: keep the node it builds, and let its
    prodable take jobs from `_jobs` at the top of each turn of the loop,
    on the main thread, where the node itself uses its ring."""
    build = start_node.build_node

    def build_node(*args, **kwargs):
        global _node
        prodable, node, registry = build(*args, **kwargs)
        _node, prod = node, prodable.prod

        def prod_after_jobs() -> int:
            done = 0
            while not _jobs.empty():
                _jobs.get_nowait()()
                done += 1
            return done + prod()
        prodable.prod = prod_after_jobs
        return prodable, node, registry
    start_node.build_node = build_node


def on_node_loop(fn, timeout: float = 120.0):
    """fn() on the node's main thread; its result, or its error, here."""
    done, box = threading.Event(), {}

    def job():
        try:
            box["got"] = fn()
        except Exception as e:
            box["error"] = f"{type(e).__name__}: {e}"
        done.set()
    _jobs.put(job)
    if not done.wait(timeout):
        raise TimeoutError(f"the node's loop took no job in {timeout} s")
    if "error" in box:
        raise RuntimeError(box["error"])
    return box["got"]


def ring_verdicts(ring, items) -> dict:
    """The sample through `ring` as client-auth goes: one batch staged,
    packed into waves with whatever else is staged, answered from the
    ring's verdict cache or its device. -> the verdicts, how the ring's
    counters grew over the call, its supervisor before and after."""
    def counts():
        got = ring.summary()
        return {k: got[k] for k in ("dispatched_items", "verdict_cache_hits",
                                    "dispatches", "unpinned_shapes")}
    sup = ring.supervisors()[0]
    before, sup0 = counts(), sup.supervisor_stats()
    verdicts = ring.verifier().verify_batch(items)
    after = counts()
    return {"verdicts": [bool(v) for v in verdicts],
            "ring": {k: after[k] - before[k] for k in after},
            "supervisor": {"before": sup0, "after": sup.supervisor_stats()}}


def owner_verdicts(path: str) -> dict:
    with open(path) as fh:
        items = [tuple(bytes.fromhex(part) for part in item)
                 for item in json.load(fh)]
    if _node is None or _node.c.pipeline is None:
        raise RuntimeError("this node was built with no ring of its own")
    return on_node_loop(lambda: ring_verdicts(_node.c.pipeline, items))


def control_loop(ctl: str) -> None:
    """service_entry.control_loop with `verdicts` (that file's loop takes
    no further command, and this PR may not edit it)."""
    from benchmarks.service_entry import _answer
    while True:
        time.sleep(0.01)
        for cmd in ("trace", "report", "verdicts"):
            path = os.path.join(ctl, cmd)
            if not os.path.exists(path):
                continue
            with open(path) as fh:
                arg = fh.read().strip()
            os.unlink(path)
            try:
                if cmd == "trace":
                    from benchmarks.trace_reduce import hold_trace
                    log_dir, seconds = arg.split("\n")
                    body = hold_trace(log_dir, float(seconds))
                elif cmd == "report":
                    from benchmarks.topologies.base import device_report
                    body = device_report()
                else:
                    body = owner_verdicts(arg)
            except Exception as e:      # the benchmark reads the error
                body = {"error": f"{type(e).__name__}: {e}"}
            _answer(path, body)


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    ctl = argv[argv.index("--ctl") + 1]
    del argv[argv.index("--ctl"):argv.index("--ctl") + 2]
    if "--host-verifier" in argv:
        argv.remove("--host-verifier")
        from plenum_tpu.crypto import ed25519
        ed25519.JaxEd25519Verifier = host_double()
    threading.Thread(target=control_loop, args=(ctl,), daemon=True).start()
    from plenum_tpu.tools import start_node
    watch_build_node(start_node)
    start_node.main(argv)


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
