"""ShuffleNetV2 (arXiv:1807.11164, Table 5) as a list of modules, in the
form ``mobilenetv2.modules`` gives.  Extra ops: maxpool (3x3, stride 2),
split (the second half of the channels goes through the branch, the first
half is the identity), concat, and the channel shuffle of two groups.
"""
from __future__ import annotations

from .mobilenetv2 import node


def down_unit(name: str, cin: int, c: int) -> dict:
    half = c // 2
    return {"name": name, "kind": "shuffle_unit_down", "output": "shuffle",
            "residual": False, "nodes": [
                node("b1_dw", "dwconv", cin, cin, k=3, s=2, act="none"),
                node("b1_pw", "pwconv", cin, half, inputs=("b1_dw",),
                     act="relu"),
                node("b2_pw1", "pwconv", cin, half, act="relu"),
                node("b2_dw", "dwconv", half, half, k=3, s=2,
                     inputs=("b2_pw1",), act="none"),
                node("b2_pw2", "pwconv", half, half, inputs=("b2_dw",),
                     act="relu"),
                node("cat", "concat", c, c, inputs=("b1_pw", "b2_pw2"),
                     act="none"),
                node("shuffle", "shuffle", c, c, inputs=("cat",),
                     act="none")]}


def basic_unit(name: str, c: int) -> dict:
    half = c // 2
    return {"name": name, "kind": "shuffle_unit", "output": "shuffle",
            "residual": False, "nodes": [
                node("split", "split", c, half, act="none"),
                node("b2_pw1", "pwconv", half, half, inputs=("split",),
                     act="relu"),
                node("b2_dw", "dwconv", half, half, k=3,
                     inputs=("b2_pw1",), act="none"),
                node("b2_pw2", "pwconv", half, half, inputs=("b2_dw",),
                     act="relu"),
                node("cat", "concat", c, c, inputs=("split", "b2_pw2"),
                     act="none"),
                node("shuffle", "shuffle", c, c, inputs=("cat",),
                     act="none")]}


def modules(cfg: dict) -> list[dict]:
    c0, *stages, c_last = cfg["stage_out_channels"]
    mods = [{"name": "stem", "kind": "stem", "output": "pool1",
             "residual": False, "nodes": [
                 node("conv1", "conv", 3, c0, k=3, s=2, act="relu"),
                 node("pool1", "maxpool", c0, c0, k=3, s=2,
                      inputs=("conv1",), act="none")]}]
    c_in = c0
    for si, (c, reps) in enumerate(zip(stages, cfg["stage_repeats"])):
        mods.append(down_unit(f"stage{si + 2}_down", c_in, c))
        for i in range(reps - 1):
            mods.append(basic_unit(f"stage{si + 2}_u{i + 1}", c))
        c_in = c
    mods.append({"name": "head", "kind": "head", "output": "fc",
                 "residual": False, "nodes": [
                     node("conv5", "pwconv", c_in, c_last, act="relu"),
                     node("gap", "gap", c_last, c_last, inputs=("conv5",),
                          act="none"),
                     node("fc", "fc", c_last, cfg["num_classes"],
                          inputs=("gap",), act="none")]})
    return mods
