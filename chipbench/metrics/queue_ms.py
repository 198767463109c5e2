"""Median time a request of the window waited in the batcher's queue, from
its enqueue to the pop that took it into a batch (``batcher.queue`` spans
of the program's span log).  None where the run carries no spans."""


def read(run):
    spans = getattr(run, "spans", None)
    return None if spans is None else spans.queue_ms()
