"""The whole step's share of the chips' bf16 peak: the network's FLOPs per
image (``costs/<architecture>.py``, else ``costs/conv_net.py``) times the
images completed per second in the traced window."""


def read(run):
    if not run.trace.window_s:
        return None
    flops = run.cost(run.cfg["architecture"],
                     "conv_net").flops_per_image(run.cfg)
    rate = run.counters["completed"] / run.trace.window_s
    return 100.0 * flops * rate / (run.chips * run.peak["bf16_flops_s"])
