"""The `q` quantile of the named samples (seconds), times `scale`."""
from benchmarks.accounting import quantile


def read(spec: dict, obs: dict):
    values = obs["samples"].get(spec["samples"]) or []
    if not values:
        return None
    return quantile(values, spec["q"]) * spec.get("scale", 1.0)
