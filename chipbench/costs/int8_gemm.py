"""Operations and bytes of the ``int8_gemm`` kernel's calls.

One call per (module, node) of the configuration's ``int8_gemm`` list and
per served batch: x (M, K) int8 @ w (K, N) int8, a float32 scale per row of
x and per column of w, out (M, N) float32.  For a k x k convolution the
rows are the output pixels and K = k*k*C_in (im2col).  M counts the batch's
slots, padding included: the kernel is handed them and computes them; the
kernel's own padding of M and N to its tiles is not work.
"""
from __future__ import annotations

from chipbench.reference import modules, spatial


def shapes(cfg: dict) -> list[tuple[int, int, int]]:
    """(rows per image, K, N) of each call site."""
    mods = {m["name"]: m for m in modules(cfg)}
    sides = spatial(list(mods.values()), int(cfg["resolution"]))
    out = []
    for module, node in cfg["kernels"].get("int8_gemm", ()):
        n = next(x for x in mods[module]["nodes"] if x["name"] == node)
        _, h = sides[(module, node)]
        rows = 1 if n["op"] == "fc" else h * h
        out.append((rows, n["k"] ** 2 * n["cin"], n["cout"]))
    return out


def work(cfg: dict, slots: int, batches: int) -> list[tuple[float, float]]:
    """(operations, bytes) per call site, summed over ``batches`` calls
    that held ``slots`` images between them."""
    out = []
    for rows, k, n in shapes(cfg):
        m = slots * rows
        ops = 2.0 * m * k * n
        nbytes = m * k + 4.0 * m + 4.0 * m * n + batches * (k * n + 4.0 * n)
        out.append((ops, nbytes))
    return out
