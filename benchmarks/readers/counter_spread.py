"""The largest of the named counters' growths over the window, over their
mean: how unevenly a quantity fell on the owners that share it."""
from benchmarks.readers import growth


def read(spec: dict, obs: dict):
    grown = [growth(obs, [name]) for name in spec["counters"]]
    if any(g is None for g in grown) or not sum(grown):
        return None
    return max(grown) * len(grown) / sum(grown)
