"""Median device time of one run of the engine's jitted program (the
compiled network, ``jit_run``), from the trace."""
import statistics


def read(run):
    runs = run.trace.module_s("jit_run")
    return statistics.median(runs) * 1e3 if runs else None
