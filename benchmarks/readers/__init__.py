"""Metric readers, one kind a file. A reader takes a metric's spec
(end_to_end/<name>.json or layer_metrics/<name>.json) and the run's
observations:

    {"numbers": accounting.window_numbers' dict plus setup_s,
     "counters": {"before": {...}, "after": {...}},   # window growth
     "samples": {name: [seconds]},
     "trace": the dict trace_reduce.reduce gives, or None}

and returns a number, or None when it finds nothing to read (the harness
then leaves the metric out of the line)."""
import importlib


def read(spec: dict, obs: dict):
    mod = importlib.import_module(f"benchmarks.readers.{spec['kind']}")
    return mod.read(spec, obs)


def growth(obs: dict, names) -> float | None:
    before, after = obs["counters"]["before"], obs["counters"]["after"]
    if any(n not in after for n in names):
        return None
    return sum(after[n] - before.get(n, 0) for n in names)
