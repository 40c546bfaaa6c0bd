"""One of the window's own numbers (accounting.window_numbers, plus
`setup_s`) by its key, times `scale`: what the end-to-end metrics read."""


def read(spec: dict, obs: dict):
    value = (obs.get("numbers") or {}).get(spec["number"])
    return None if value is None else value * spec.get("scale", 1.0)
