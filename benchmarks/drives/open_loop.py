"""Poisson arrivals at the cell's fixed `rate_per_s`. n = rate x seconds
exponential gaps are drawn ONCE (ARRIVAL_SEED), scaled to fill the window
exactly, and only their ORDER comes from --seed: every seed offers the
same number of requests with the same multiset of gaps. Due times do not
depend on any acknowledgement."""
import math
import random

ARRIVAL_SEED = 7


def stream_length(drive: dict, seconds: float) -> int:
    return int(math.floor(drive["rate_per_s"] * seconds))


def due_times(drive: dict, seed: int, seconds: float) -> list[float]:
    """Seconds from window open; the first request is due as it opens."""
    fixed = random.Random(ARRIVAL_SEED)
    gaps = [fixed.expovariate(1.0)
            for _ in range(stream_length(drive, seconds))]
    scale = seconds / sum(gaps) if gaps else 0.0
    random.Random(seed * 1_000_003 + 41).shuffle(gaps)
    due, t = [], 0.0
    for g in gaps:
        due.append(t)
        t += g * scale
    return due


def schedule(drive: dict, seed: int, seconds: float) -> dict:
    return {"due": due_times(drive, seed, seconds)}
