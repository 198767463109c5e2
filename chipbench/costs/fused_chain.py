"""Operations and bytes of the ``fused_chain`` kernel's calls.

One call per chain of the configuration's ``fused_chain`` list and per
served batch.  A chain is [pw 1x1 ->] dw 3x3 (stride s) -> pw 1x1 on a
float32 map of the batch; its intermediates stay in on-chip memory, so the
bytes are the input and output maps and the weights, read once per call.
"""
from __future__ import annotations

from chipbench.reference import modules, spatial


def work(cfg: dict, slots: int, batches: int) -> list[tuple[float, float]]:
    mods = {m["name"]: m for m in modules(cfg)}
    sides = spatial(list(mods.values()), int(cfg["resolution"]))
    out = []
    for module, *chain in cfg["kernels"].get("fused_chain", ()):
        nodes = {x["name"]: x for x in mods[module]["nodes"]}
        ops = weights = 0.0
        for name in chain:
            n = nodes[name]
            _, h = sides[(module, name)]
            if n["op"] == "dwconv":
                ops += 2.0 * h * h * 9 * n["cout"]
                weights += 9 * n["cout"] + n["cout"]
            else:
                ops += 2.0 * h * h * n["cin"] * n["cout"]
                weights += n["cin"] * n["cout"] + n["cout"]
        first, last = nodes[chain[0]], nodes[chain[-1]]
        h_in, _ = sides[(module, chain[0])]
        _, h_out = sides[(module, chain[-1])]
        per_image = 4.0 * (h_in * h_in * first["cin"]
                           + h_out * h_out * last["cout"])
        out.append((slots * ops, slots * per_image + batches * 4.0 * weights))
    return out
