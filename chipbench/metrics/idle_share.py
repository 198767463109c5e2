"""Share of the traced window in which no op ran on the device, averaged
over the cell's chips; read for ``idle_share.saturate`` and
``idle_share.stream``."""


def read(run):
    idle = run.trace.idle_share()
    return None if idle is None else 100.0 * idle
