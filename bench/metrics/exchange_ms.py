"""Time per step in which a collective (the gradient exchange) was in
flight on the device that had the most of it, from the trace."""


def read(run):
    return run["trace"].get("exchange_ms")
