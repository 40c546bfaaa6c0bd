"""growth(numerator) / growth(denominator) over the window, times `scale`;
`one_minus` gives the complement (a share of work avoided)."""
from benchmarks.readers import growth


def read(spec: dict, obs: dict):
    num = growth(obs, spec["numerator"])
    den = growth(obs, spec["denominator"])
    if num is None or not den:
        return None
    ratio = num / den
    if spec.get("one_minus"):
        ratio = 1.0 - ratio
    return ratio * spec.get("scale", 1.0)
