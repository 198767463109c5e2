"""Images per served batch over the window, from the server's counters."""


def read(run):
    c = run.counters
    return c["completed"] / c["batches"] if c["batches"] else None
