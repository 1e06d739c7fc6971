"""The part of ``exchange_ms`` in which no other operation ran on that
device: exchange time the step waits for, per step, worst device."""


def read(run):
    return run["trace"].get("exchange_exposed_ms")
