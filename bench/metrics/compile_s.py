"""Seconds of set-up spent getting programs ready: tracing, lowering, the
backend compile and reads of the persistent compilation cache."""


def read(run):
    return run.get("compile_s")
