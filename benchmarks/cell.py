"""The cell process: one run of one cell, start to result file.

Started by run.py in a session of its own. Loads the cell's data files by
name, picks the launcher by the configuration's `topology`, sets up, warms,
measures for --seconds, compares the outcome with the plain reference, and
writes the result as JSON to --result. Everything it prints is an earlier
line; the last line is run.py's."""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import (accounting, correctness, manifest, readers,  # noqa: E402
                        reference, topologies, traffic, trace_reduce)
from benchmarks.topologies.base import Split  # noqa: E402

DRAIN_S = 3.0           # after the window closes, for what was due inside
SETTLE_S = 10.0         # for the four nodes to reach one view, after drain
WARM_REQUESTS = 64
READ_BACKS = 32
LEDGER_SAMPLE = 256
# One execution of a verify program leaves ~110 000 op events, and writing
# a trace out costs ~60 s a loaded program plus ~10 s a traced execution,
# under every tpu_trace_mode that has a device plane at all (PERF.md
# section 5). So the trace is a SAMPLE of a few executions (the
# configuration's `trace_seconds`), good for the contract's `device` keys
# and the breakdown and for no metric. It sits at the window's start:
# writing it out overlaps the rest of the window, the drain and the
# comparison, and a traced run keeps inside its 360 s.
TRACE_AT = 0.05
REHEARSAL = "cpu: proves nothing about the chip"


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def window_failures(label: str, before: list, after: list,
                    must_stay_zero: dict) -> list[str]:
    """The no-fallback rule over one window (copied from chip_smoke.py):
    every lane's device_batches grew, no supervisor.FALLBACK_COUNTERS entry
    did, every breaker is closed, and the counters that live elsewhere
    (unpinned shapes, cmt host fallbacks, executables, worker errors) did
    not move."""
    from plenum_tpu.parallel.supervisor import fallback_growth
    problems = []
    if not after or len(before) != len(after):
        problems.append(f"{label}: no supervised device plane to judge")
    for i, (b, a) in enumerate(zip(before, after)):
        lane = a.get("label") or f"lane{i}"
        if a["device_batches"] <= b["device_batches"]:
            problems.append(f"{label}/{lane}: no device batch in the window")
        grew = fallback_growth(b, a)
        if grew:
            problems.append(f"{label}/{lane}: not answered by the device: "
                            f"{grew}")
        if a["breaker_state"] != "closed":
            problems.append(f"{label}/{lane}: breaker {a['breaker_state']}")
    problems += [f"{label}: {name} grew by {delta} in the window"
                 for name, delta in must_stay_zero.items() if delta]
    return problems


class Run:
    def __init__(self, args):
        self.args = args
        cell = manifest.cell(args.workload)
        self.config = cell["config"]
        self.drive_spec = dict(cell["workload"])
        if args.rehearse_cpu:
            self.drive_spec.update(self.drive_spec.get("rehearsal", {}))
        self.mix = cell["traffic"]
        self.topo = topologies.load(self.config["topology"]).Launcher(
            self.config, args.run_dir, args.seed, args.rehearse_cpu)
        self.quorum = self.topo.f + 1
        self.known: dict = {}       # seqNo -> txn, from agreed replies
        self.req_id = 1000
        self.serial = 0

    # --- streams ------------------------------------------------------------

    def stream(self, seed: int, n: int):
        ops = traffic.plan(self.mix, seed, n, self.topo.sizes["preload_dids"],
                           serial_base=self.serial)
        requests = self.topo.ids.sign(ops, seed, self.req_id)
        self.req_id += n
        self.serial += n
        return ops, requests

    def absorb(self, tracker) -> None:
        for result in tracker.results.values():
            seq = (result.get("txnMetadata") or {}).get("seqNo")
            if seq is not None:
                self.known[seq] = ledger_txn(result)

    # --- one window and its comparison ----------------------------------------

    def window(self, seed: int, seconds: float, trace_dir, quorum: int,
               requests=None, ops=None) -> dict:
        topo = self.topo
        if requests is None:
            ops, requests = self.stream(
                seed, traffic.stream_length(self.drive_spec, seconds))
        tracker = accounting.Tracker(quorum)
        actions = []
        if trace_dir:
            actions = [(TRACE_AT * seconds,
                        lambda: topo.trace_start(
                            trace_dir, self.config["trace_seconds"]))]
        before, sup0 = topo.snapshot()
        times = topo.drive(requests,
                           traffic.schedule(self.drive_spec, seed, seconds),
                           seconds, tracker, DRAIN_S, actions)
        after, sup1 = topo.snapshot()
        numbers = accounting.window_numbers(
            tracker.due, tracker.acked, times["t_open"], times["t_close"],
            times["t_drained"])
        numbers["nacked"] = len(tracker.nacked)
        lag = [tracker.sent.get(k, times["t_close"]) - tracker.due[k]
               for k in tracker.due]
        self.absorb(tracker)
        numbers["requests_left"] = len(requests) - times["sent"]
        problems = window_failures(
            self.args.workload, sup0, sup1,
            topo.must_stay_zero(before, after)) if topo.on_device else []
        return {"seed": seed, "numbers": numbers, "tracker": tracker,
                "ops": ops, "requests": requests, "lag": lag,
                "counters": {"before": before, "after": after},
                "trace": None, "fallback_problems": problems}

    def collect_trace(self, win: dict, trace_dir: str) -> None:
        """Wait until the chip's owner has written the trace out (the
        comparison has run meanwhile), then reduce it."""
        t_wait = time.perf_counter()
        cost = self.topo.trace_wait()
        t_reduce = time.perf_counter()
        win["trace"] = trace_reduce.reduce_dir(trace_dir)
        say(trace_cost=dict(cost, waited_s=t_reduce - t_wait,
                            reduce_s=time.perf_counter() - t_reduce))

    def compare(self, win: dict, extra_problems=()) -> correctness.Checks:
        """Outside the window: the five comparisons, and the quorum."""
        topo, tracker, seed = self.topo, win["tracker"], win["seed"]
        checks = correctness.Checks()
        rng = random.Random(seed * 1_000_003 + 53)

        deadline = time.monotonic() + SETTLE_S
        while True:
            states = topo.node_states()
            if len({(s["domain_size"], s["domain_root"])
                    for s in states}) == 1 or time.monotonic() > deadline:
                break
            time.sleep(0.2)
        correctness.nodes_agree(checks, states)

        genesis = topo.genesis_domain
        size = states[0]["domain_size"]
        acked = tracker.results
        sample = rng.sample(sorted(acked), min(LEDGER_SAMPLE, len(acked)))
        sample_seqs = [acked[k]["txnMetadata"]["seqNo"] for k in sample]
        holes = [s for s in range(len(genesis) + 1, size + 1)
                 if s not in self.known]
        fetched = topo.fetch_txns(sorted(set(sample_seqs) | set(holes)))
        for s in holes:
            self.known[s] = fetched[s]
        correctness.acknowledged_in_ledger(
            checks, states, len(genesis), len(self.known) - len(holes),
            {k: acked[k] for k in sample}, fetched)
        txns = genesis + [self.known[s]
                          for s in range(len(genesis) + 1, size + 1)]
        ref_root, ref_state = reference.replay(txns)
        correctness.reference_agrees(checks, states, ref_root, len(txns))

        by_key = {(r.identifier, r.req_id): (op, r)
                  for op, r in zip(win["ops"], win["requests"])}
        picked = rng.sample(sorted(acked), min(READ_BACKS, len(acked)))
        read_ops, wanted = [], []
        for key in picked:
            op, req = by_key[key]
            dest = req.operation["dest"]
            if op.kind == "NYM":
                read_ops.append(("GET_NYM", dest))
                wanted.append(ref_state.verkeys.get(dest))
            else:
                read_ops.append(("GET_ATTR", dest))
                wanted.append(ref_state.attrs.get((dest, "endpoint")))
        reads = topo.verified_reads(
            [read_request(kind, dest, 3 * 10 ** 9 + self.req_id + i)
             for i, (kind, dest) in enumerate(read_ops)])
        self.req_id += len(read_ops)
        correctness.reads_agree(checks, reads, wanted)

        items, corrupted = verdict_sample(win["requests"], topo.ids, rng,
                                          seed)
        from plenum_tpu.crypto.ed25519 import CpuEd25519Verifier
        cpu = [bool(v) for v in CpuEd25519Verifier().verify_batch(items)]
        correctness.verdicts_agree(checks, topo.device_verdicts(items), cpu,
                                   corrupted)

        correctness.no_fallback(checks, list(win["fallback_problems"])
                                + list(extra_problems))
        least = min((tracker.quorum_of(k) for k in acked), default=0)
        correctness.quorum_held(checks, least, self.topo.f + 1)
        if self.drive_spec["drive"] == "closed_loop":
            # a closed loop that ran out of signed requests measured the
            # stream's length, not the pool
            checks.add("drive.requests_left", win["numbers"]["requests_left"],
                       1, win["numbers"]["requests_left"] >= 1)
        return checks


def ledger_txn(result: dict) -> dict:
    """The transaction as the ledger holds it, from a write's REPLY: the
    reply adds the proof of inclusion to it and nothing else."""
    return {k: v for k, v in result.items()
            if k not in ("rootHash", "auditPath", "ledgerSize",
                         "merkle_proof", "state_proof")}


def read_request(kind: str, dest: str, req_id: int):
    from plenum_tpu.common.request import Request
    from plenum_tpu.execution.txn import GET_ATTR, GET_NYM
    body = {"type": GET_NYM, "dest": dest} if kind == "GET_NYM" else \
        {"type": GET_ATTR, "dest": dest, "attr_name": "endpoint"}
    return Request("bench-reader", req_id, body)


def verdict_sample(requests, ids, rng, seed: int):
    """32 of the window's signatures, 8 fresh genuine ones and 8 corrupted
    copies -> ([(message, signature, verkey)], number corrupted)."""
    signer_of = {ids.trustee.identifier: ids.trustee}
    for i in range(ids.preload):
        if len(signer_of) > 4096:
            break
        signer_of[ids.did(i).identifier] = ids.did(i)
    items = []
    pool = [r for r in requests if r.identifier in signer_of]
    for req in rng.sample(pool, min(32, len(pool))):
        s = signer_of[req.identifier]
        msg = req.signing_bytes()
        items.append((msg, s.sign(msg), s.verkey))
    for i in range(8):
        s = ids.did(rng.randrange(ids.preload))
        msg = b"plenum-bench fresh %d/%d" % (seed, i)
        items.append((msg, s.sign(msg), s.verkey))
    corrupted = 0
    for n, (msg, sig, vk) in enumerate(list(items[-8:])):
        if n % 3 == 0:              # a flipped bit in R
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        elif n % 3 == 1:            # a flipped bit in S
            sig = sig[:40] + bytes([sig[40] ^ 4]) + sig[41:]
        else:                       # a good signature on another message
            msg += b"!"
        items.append((msg, sig, vk))
        corrupted += 1
    return items, corrupted


def metrics(name: str, group: str, obs: dict) -> dict:
    """The cell's metrics of the manifest's `group`, each read by the
    reader its own file names; one that finds nothing is left out."""
    out = {}
    for m in manifest.metrics_of(name, group):
        value = readers.read(manifest.metric_spec(group, m["name"]), obs)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--run-dir", required=True)
    ap.add_argument("--result", required=True)
    ap.add_argument("--t0", type=float, default=time.time())
    ap.add_argument("--check", type=int, default=0,
                    help="builder's output check: this many sound windows "
                         "on successive seeds behind one set-up, then the "
                         "control on three")
    ap.add_argument("--sweep", default="",
                    help="builder's knee sweep: comma-separated rates, one "
                         "open-loop window each behind one set-up")
    args = ap.parse_args(argv)

    run = Run(args)
    topo = run.topo
    split = Split()
    result: dict = {"correct": False, "attempted": 0, "failed": 0,
                    "metrics": {}, "device": {}}
    try:
        topo.start(split)
        ops, requests = run.stream(
            args.seed, traffic.stream_length(run.drive_spec, args.seconds))
        _, warm = run.stream(args.seed ^ 0x5A5A5A, WARM_REQUESTS)
        split.mark("sign_requests")
        warm_tracker = accounting.Tracker(run.quorum)
        topo.drive(warm, {"kind": "closed", "in_flight": 32}, None,
                   warm_tracker, 60.0)
        if len(warm_tracker.acked) != len(warm):
            raise RuntimeError(f"warm-up: {len(warm_tracker.acked)}/"
                               f"{len(warm)} acknowledged; nacks "
                               f"{list(warm_tracker.nacked.values())[:3]}")
        run.absorb(warm_tracker)
        split.mark("warm_traffic")
        setup_s = time.time() - args.t0
        say(setup_split=split.parts, setup_s=setup_s)

        if args.sweep:
            return sweep(run, args)

        trace_dir = os.path.join(args.run_dir, "trace") if args.trace \
            else None
        win = run.window(args.seed, args.seconds, trace_dir, run.quorum,
                         requests, ops)
        say(window=win["numbers"], counters=win["counters"],
            lag_p95_ms=accounting.quantile(win["lag"], 0.95) * 1e3
            if win["lag"] else None)
        windows = [win]
        for k in range(1, args.check):
            windows.append(run.window(args.seed + k, args.seconds, None,
                                      run.quorum))
        controls = [run.window(args.seed + 100 + k, args.seconds, None, 1)
                    for k in range(3)] if args.check else []
        all_checks = [run.compare(w) for w in windows + controls]
        topo.quiesce()
        if trace_dir:
            run.collect_trace(win, trace_dir)
        device = topo.device()      # after the trace: one control thread
        node_side = topo.node_side_problems() if topo.on_device else []
        checks = all_checks[0]
        correctness.no_fallback(checks, node_side, "nodes.fallback_problems")
        checks.say()
        correct = checks.correct
        for w, c in zip(windows[1:], all_checks[1:len(windows)]):
            say(check_window=w["seed"], correct=c.correct,
                numbers=w["numbers"],
                failed_checks=[r for r in c.rows if not r["ok"]])
            correct = correct and c.correct
        for w, c in zip(controls, all_checks[len(windows):]):
            say(control="ack_on_first_reply", seed=w["seed"],
                correct=c.correct,
                failed_checks=[r for r in c.rows if not r["ok"]])
            correct = correct and not c.correct

        samples, totals = topo.samples()
        samples["drive.lag_s"] = win["lag"]
        numbers = win["numbers"]
        obs = {"numbers": dict(numbers, setup_s=setup_s),
               "counters": win["counters"], "samples": samples,
               "trace": win["trace"]}
        obs["counters"]["after"].update(totals)
        if args.trace:
            trace = win["trace"]
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            say(trace={k: trace[k] for k in ("window_s", "busy_s", "chips",
                                             "programs")})
            rates(run.config, device, obs)
            result["breakdown"] = {"device_ops": trace["device_ops"],
                                   "idle_gaps": trace["idle_gaps"]}
            reported = metrics(args.workload, "per_layer", obs)
        else:
            reported = metrics(args.workload, "end_to_end", obs)
        if args.rehearse_cpu:
            say(rehearsal=REHEARSAL, numbers=reported)
            reported = {}
            result["rehearsal"] = REHEARSAL
        result.update(correct=correct, attempted=numbers["attempted"],
                      failed=numbers["failed"], metrics=reported,
                      device=device)
    finally:
        topo.stop()
        with open(args.result + ".tmp", "w") as fh:
            json.dump(result, fh)
        os.replace(args.result + ".tmp", args.result)
    return 0


def rates(config: dict, device: dict, obs: dict) -> None:
    """Achieved int32 op/s and HBM bytes/s of the verify program, as rates
    on an earlier line. No roofline share: peaks.json holds no int32 VPU
    peak (PERF.md, Open questions)."""
    from benchmarks import kernel_cost
    trace = obs["trace"]
    progs = {n: p for n, p in trace["programs"].items()
             if kernel_cost.VERIFY_PROGRAM in n}
    time_s = sum(p["time_s"] for p in progs.values())
    count = sum(p["count"] for p in progs.values())
    if not time_s:
        return
    lanes = kernel_cost.lanes_per_execution(config)
    say(verify_kernel_rates={
        "device_kind": device.get("kind"),
        "published_peaks": manifest.peaks(device["kind"])
        if device.get("platform") == "tpu" else None,
        "executions": count, "device_s": time_s, "padded_lanes": lanes,
        "int32_ops_per_s": kernel_cost.ops_per_sig() * lanes * count / time_s,
        "hbm_bytes_per_s": kernel_cost.bytes_per_sig(lanes) * lanes * count
        / time_s})


def sweep(run: Run, args) -> int:
    """The knee: one set-up, then one open-loop window per rate."""
    for rate in [float(r) for r in args.sweep.split(",")]:
        run.drive_spec = dict(run.drive_spec, drive="open_loop",
                              rate_per_s=rate)
        win = run.window(args.seed + int(rate), args.seconds, None,
                         run.quorum)
        tracker = win["tracker"]
        mid = min(tracker.due.values()) + args.seconds / 2
        halves = []
        for lo, hi in ((0, mid), (mid, float("inf"))):
            lat = [tracker.acked[k] - tracker.due[k] for k in tracker.acked
                   if lo <= tracker.due[k] < hi]
            halves.append(accounting.quantile(lat, 0.95) * 1e3
                          if lat else None)
        say(sweep_rate=rate, numbers=win["numbers"],
            p95_first_half_ms=halves[0], p95_second_half_ms=halves[1],
            lag_p95_ms=accounting.quantile(win["lag"], 0.95) * 1e3,
            fallback_problems=win["fallback_problems"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
