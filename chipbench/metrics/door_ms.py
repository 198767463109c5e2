"""Median over the window's requests of the front door's host time for one
request: the sum of its ``door.read``, ``door.decode``, ``door.submit``,
``door.encode`` and ``door.write`` spans from the program's span log; read
for ``door_ms.stream`` and ``door_ms.saturate``.  None where the run
carries no spans."""


def read(run):
    spans = getattr(run, "spans", None)
    return None if spans is None else spans.door_ms()
