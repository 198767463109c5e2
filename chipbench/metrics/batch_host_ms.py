"""Median over the window's batches of the drain thread's host work on one
batch: ``server.pad`` + ``server.dispatch`` + ``server.debatch`` spans of
the program's span log.  None where the run carries no spans."""


def read(run):
    spans = getattr(run, "spans", None)
    return None if spans is None else spans.batch_host_ms()
