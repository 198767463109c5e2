"""Share of the batches' slots that were padding, from the server's
counters."""


def read(run):
    c = run.counters
    slots = c["padded_slots"] + c["completed"]
    return 100.0 * c["padded_slots"] / slots if slots else None
