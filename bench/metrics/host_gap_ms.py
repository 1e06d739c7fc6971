"""Device idle time between one run of the step program and the next,
summed over the traced window and divided by its steps."""


def read(run):
    return run["trace"].get("host_gap_ms")
