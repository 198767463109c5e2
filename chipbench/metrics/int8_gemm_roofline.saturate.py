"""``int8_gemm``'s share of its roofline: the least time its calls in the
traced window could take at one chip's int8 peak and memory bandwidth, over
the device time they took, summed over chips.  The calls' slots and batches come from the server's
counters over the same window."""
from chipbench.roofline import share


def read(run):
    t = run.trace.kernel_s("int8_gemm")
    if not t:
        return None
    c = run.counters
    work = run.cost("int8_gemm").work(
        run.cfg, c["completed"] + c["padded_slots"], c["batches"])
    pct, bound = share(work, run.peak["int8_ops_s"],
                       run.peak["hbm_bytes_s"], t)
    run.note(f"int8_gemm: {t:.6f} device s in the window, {bound}-bound")
    return pct
