"""`in_flight` requests kept unanswered until the window closes. Set-up
signs `max_rate_per_s` x seconds of them: a run that used them all up
measured the stream's length, not the pool (cell.py compares
`drive.requests_left`)."""
import math


def stream_length(drive: dict, seconds: float) -> int:
    return int(math.ceil(drive["max_rate_per_s"] * seconds)) \
        + drive["in_flight"]


def schedule(drive: dict, seed: int, seconds: float) -> dict:
    return {"in_flight": drive["in_flight"]}
