"""``fused_chain``'s share of its roofline.  The chain computes in float32
on the matrix unit, whose ceiling is the bf16 peak; the calls' slots and
batches come from the server's counters over the traced window."""
from chipbench.roofline import share


def read(run):
    t = run.trace.kernel_s("fused_chain")
    if not t:
        return None
    c = run.counters
    work = run.cost("fused_chain").work(
        run.cfg, c["completed"] + c["padded_slots"], c["batches"])
    pct, bound = share(work, run.peak["bf16_flops_s"],
                       run.peak["hbm_bytes_s"], t)
    run.note(f"fused_chain: {t:.6f} device s in the window, {bound}-bound")
    return pct
