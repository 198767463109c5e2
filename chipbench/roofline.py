"""A kernel's share of its roofline over a traced window."""
from __future__ import annotations


def share(work, ops_per_s: float, bytes_per_s: float, seconds: float):
    """(percent, bound) for calls ``work`` = [(operations, bytes), ...] that
    took ``seconds`` of device time.  The least time a call can take is the
    larger of its operations over the peak rate and its bytes over the
    memory bandwidth; ``bound`` names the side that bounds most of it."""
    t_ops = t_bytes = t_min = 0.0
    for ops, nbytes in work:
        a, b = ops / ops_per_s, nbytes / bytes_per_s
        t_min += max(a, b)
        if a >= b:
            t_ops += a
        else:
            t_bytes += b
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * t_min / seconds, bound
