"""FLOPs of one image through a convolutional network of the reference
(``chipbench.reference``), counted from the graph's shapes: two per
multiply-accumulate of every convolution and fully connected layer."""
from __future__ import annotations

from chipbench.reference import macs_per_image


def flops_per_image(cfg: dict) -> float:
    return 2.0 * macs_per_image(cfg)
