"""MobileNetV2 (arXiv:1801.04381, Table 2) as a list of modules.

Each module is ``{"name", "kind", "nodes", "output", "residual"}``; each
node is ``{"name", "op", "cin", "cout", "k", "s", "inputs", "act"}`` with
``op`` one of conv, dwconv, pwconv, fc, gap.  Batch norm is folded into the
convolutions' biases.  Node order matters: weights are drawn per module,
one key per node in this order.
"""
from __future__ import annotations


def make_divisible(v: float, d: int = 8) -> int:
    out = max(d, int(v + d / 2) // d * d)
    if out < 0.9 * v:
        out += d
    return out


def node(name, op, cin, cout, k=1, s=1, inputs=("in",), act="relu6"):
    return {"name": name, "op": op, "cin": cin, "cout": cout, "k": k,
            "s": s, "inputs": list(inputs), "act": act}


def modules(cfg: dict) -> list[dict]:
    width = float(cfg["width_multiplier"])
    d = int(cfg["round_nearest"])
    c_stem = make_divisible(cfg["stem_channels"] * width, d)
    mods = [{"name": "stem", "kind": "stem", "output": "conv1",
             "residual": False,
             "nodes": [node("conv1", "conv", 3, c_stem, k=3, s=2)]}]
    c_in, idx = c_stem, 0
    for t, c, n, s in cfg["inverted_residual_setting"]:
        c_out = make_divisible(c * width, d)
        for i in range(n):
            stride = s if i == 0 else 1
            hidden = c_in * t
            nodes, src = [], "in"
            if t != 1:
                nodes.append(node("pw_exp", "pwconv", c_in, hidden))
                src = "pw_exp"
            nodes.append(node("dw", "dwconv", hidden, hidden, k=3, s=stride,
                              inputs=(src,)))
            nodes.append(node("pw_proj", "pwconv", hidden, c_out,
                              inputs=("dw",), act="none"))
            mods.append({"name": f"bneck{idx}", "kind": "bottleneck",
                         "nodes": nodes, "output": "pw_proj",
                         "residual": stride == 1 and c_in == c_out})
            c_in = c_out
            idx += 1
    c_last = make_divisible(cfg["last_channels"] * max(1.0, width), d)
    mods.append({"name": "head", "kind": "head", "output": "fc",
                 "residual": False, "nodes": [
                     node("conv_last", "pwconv", c_in, c_last),
                     node("gap", "gap", c_last, c_last,
                          inputs=("conv_last",), act="none"),
                     node("fc", "fc", c_last, cfg["num_classes"],
                          inputs=("gap",), act="none")]})
    return mods
