"""The plain reference of the served networks, independent of the program.

It imports nothing from ``repro`` and takes nothing the program made.  From
the configuration it rebuilds the network (``<architecture>.modules``, the
module of this package named by the configuration's ``architecture``),
draws the weights from the seed the way the configuration states (per
module ``fold_in(key, crc32(name))``, one key per node, normal /
sqrt(fan_in), zero biases), and runs the forward pass in ``jax.numpy`` at
the numerics the configuration states (``numerics``):

* ``reference``: as stated.  Activations are stored in ``dtype``; a
  convolution or a fully connected layer is one matrix product (a k x k
  convolution over its k*k SAME-padded taps) whose operands are rounded to
  ``matmul_operands`` and whose products are summed in float32; a
  depthwise convolution is the sum of its k*k taps in ``dtype``.  On the
  nodes the configuration puts on ``int8_gemm`` the paper's fixed point:
  symmetric per-output-channel weights and one activation scale per image,
  the product taken on the integers and scaled after.  A ``fused_chain``
  quantizes the chain's input and weights only; its intermediate stays in
  ``dtype`` and its products round their operands to
  ``fused_chain_operands``.
* ``control_bf16``: the same with bfloat16 storage where the
  configuration states float32; ``control_int4``: 4-bit where it states
  8-bit.  The comparison that decides ``correct`` must fail each.
"""
from __future__ import annotations

import importlib
import json
import zlib
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

WEIGHT_OPS = ("conv", "dwconv", "pwconv", "fc")
# one step below each stated precision
LOWER = {"control_bf16": ("dtype", {"float32": "bfloat16"}),
         "control_int4": ("fixed_point_bits", {8: 4})}
CONTROLS = tuple(LOWER)


def numerics(cfg: dict, mode: str = "reference") -> tuple:
    """(storage dtype, matmul operand dtype, fused-chain operand dtype,
    fixed-point bits) of ``mode``: the configuration's ``numerics``, or one
    of them a step lower for a control."""
    n = dict(cfg["numerics"])
    if mode != "reference":
        key, step = LOWER[mode]
        n[key] = step[n[key]]
    return (jnp.dtype(n["dtype"]), jnp.dtype(n["matmul_operands"]),
            jnp.dtype(n["fused_chain_operands"]),
            int(n["fixed_point_bits"]))


def modules(cfg: dict) -> list[dict]:
    arch = importlib.import_module(f"{__name__}.{cfg['architecture']}")
    return arch.modules(cfg)


def weight_shape(n: dict):
    if n["op"] == "dwconv":
        return (n["k"], n["k"], 1, n["cout"])
    if n["op"] in ("conv", "pwconv"):
        return (n["k"], n["k"], n["cin"], n["cout"])
    if n["op"] == "fc":
        return (n["cin"], n["cout"])
    return None


def spatial(mods, res: int) -> dict:
    """(input side, output side) of every (module, node) at input ``res``:
    a strided node halves its side, rounding up, as SAME padding does."""
    out, side = {}, res
    for m in mods:
        sides = {"in": side}
        for n in m["nodes"]:
            h_in = sides[n["inputs"][0]]
            if n["op"] == "gap":
                h_out = 1
            elif n["op"] in WEIGHT_OPS + ("maxpool",):
                h_out = -(-h_in // n["s"])
            else:
                h_out = h_in
            out[(m["name"], n["name"])] = (h_in, h_out)
            sides[n["name"]] = h_out
        side = sides[m["output"]]
    return out


def macs_per_image(cfg: dict) -> float:
    """Multiply-accumulates of one image's forward pass, from the shapes."""
    mods = modules(cfg)
    sides = spatial(mods, int(cfg["resolution"]))
    total = 0
    for m in mods:
        for n in m["nodes"]:
            _, h = sides[(m["name"], n["name"])]
            if n["op"] in ("conv", "pwconv"):
                total += h * h * n["k"] ** 2 * n["cin"] * n["cout"]
            elif n["op"] == "dwconv":
                total += h * h * n["k"] ** 2 * n["cout"]
            elif n["op"] == "fc":
                total += n["cin"] * n["cout"]
    return float(total)


def weight_seed(seed: int) -> int:
    """The seed the weights are drawn from (a PRNG key takes 31 bits)."""
    return int(seed) % 2**31


def _init(mods, seed):
    key = jax.random.PRNGKey(seed)
    params = {}
    for m in mods:
        keys = jax.random.split(
            jax.random.fold_in(key, zlib.crc32(m["name"].encode()) % 2**31),
            len(m["nodes"]))
        params[m["name"]] = {}
        for n, k in zip(m["nodes"], keys):
            shape = weight_shape(n)
            if shape is None:
                continue
            fan_in = int(np.prod(shape[:-1]))
            params[m["name"]][n["name"]] = {
                "w": jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan_in),
                "b": jnp.zeros((n["cout"],), jnp.float32)}
    return params


def quantize(x, axis: int, bits: int):
    """(integers, scale): symmetric fixed point with one scale per index
    of ``axis``, the integers held in ``x``'s dtype."""
    qmax = 2.0 ** (bits - 1) - 1
    red = tuple(i for i in range(x.ndim) if i != axis % x.ndim)
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=red, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    q = jnp.clip(jnp.round(x32 / scale), -qmax, qmax)
    return q.astype(x.dtype), scale.astype(x.dtype)


def fake_quant(x, axis: int, bits: int):
    q, scale = quantize(x, axis, bits)
    return q * scale


def _act(x, kind: str):
    if kind == "relu":
        return jnp.maximum(x, 0)
    if kind == "relu6":
        return jnp.clip(x, 0, 6)
    return x


def quantized_sites(kernels: dict) -> dict:
    """{(module, node): site} for a configuration's ``kernels``: ``int8``
    for an ``int8_gemm`` node, ``chain_in`` for the first node of a
    ``fused_chain``, ``chain`` for the rest of it."""
    sites = {(module, node): "int8"
             for module, node in kernels.get("int8_gemm", ())}
    for module, first, *rest in kernels.get("fused_chain", ()):
        sites[(module, first)] = "chain_in"
        sites.update(((module, n), "chain") for n in rest)
    return sites


def taps(x, k: int, s: int) -> list:
    """The k*k shifted, strided windows of SAME-padded ``x`` (NHWC), the
    padding split low = total // 2 as XLA's SAME does."""
    h, w = x.shape[1], x.shape[2]
    ho, wo = -(-h // s), -(-w // s)
    ph, pw = max((ho - 1) * s + k - h, 0), max((wo - 1) * s + k - w, 0)
    xp = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                     (pw // 2, pw - pw // 2), (0, 0)))
    return [xp[:, dy:dy + (ho - 1) * s + 1:s, dx:dx + (wo - 1) * s + 1:s]
            for dy in range(k) for dx in range(k)]


def _columns(n, x):
    """``x`` as the rows of a matrix product: (B, C) for a fully connected
    layer, (B, Ho, Wo, k*k*C) for a k x k convolution."""
    if n["op"] == "fc":
        return x.reshape(x.shape[0], -1)
    if n["k"] == 1 and n["s"] == 1:
        return x
    return jnp.concatenate(taps(x, n["k"], n["s"]), axis=-1)


def _matmul(a, b, operands, dtype):
    """``a @ b`` on operands rounded to ``operands``, the products summed
    in float32 and the result stored in ``dtype``."""
    return jnp.dot(a.astype(operands), b.astype(operands),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32).astype(dtype)


def _weighted(n, p, x, site, num):
    dtype, operands, chain_operands, bits = num
    w, b = p["w"].astype(dtype), p["b"].astype(dtype)
    if site == "chain_in":
        x = fake_quant(x, 0, bits)
    if n["op"] == "dwconv":
        if site:
            w = fake_quant(w, -1, bits)
        y = None
        for t, wt in zip(taps(x, n["k"], n["s"]),
                         w.reshape(n["k"] ** 2, n["cout"])):
            y = t * wt if y is None else y + t * wt
        return _act(y + b, n["act"])
    w = w.reshape(-1, n["cout"])
    if site == "int8":
        qx, sx = quantize(x, 0, bits)
        qw, sw = quantize(w, -1, bits)
        cols = _columns(n, qx)
        y = _matmul(cols, qw, operands, dtype)
        y = y * sx.reshape((-1,) + (1,) * (y.ndim - 1)) * sw.reshape(-1)
    else:
        if site:
            w = fake_quant(w, -1, bits)
        y = _matmul(_columns(n, x), w,
                    chain_operands if site else operands, dtype)
    return _act(y + b, n["act"])


def _module(m, params, x, sites, num):
    vals = {"in": x}
    for n in m["nodes"]:
        op = n["op"]
        xs = [vals[i] for i in n["inputs"]]
        if op in WEIGHT_OPS:
            y = _weighted(n, params[n["name"]], xs[0],
                          sites.get((m["name"], n["name"])), num)
        elif op == "maxpool":
            y = jax.lax.reduce_window(xs[0], -jnp.inf, jax.lax.max,
                                      (1, n["k"], n["k"], 1),
                                      (1, n["s"], n["s"], 1), "SAME")
        elif op == "gap":
            y = xs[0].mean(axis=(1, 2), keepdims=True)
        elif op == "split":
            vals["identity"] = x[..., :n["cout"]]
            y = x[..., n["cout"]:]
        elif op == "concat":
            first = vals["identity"] if n["inputs"][0] == "split" else xs[0]
            y = jnp.concatenate([first, xs[1]], axis=-1)
        elif op == "shuffle":
            b, h, w_, c = xs[0].shape
            y = (xs[0].reshape(b, h, w_, 2, c // 2)
                 .transpose(0, 1, 2, 4, 3).reshape(b, h, w_, c))
        else:
            raise ValueError(f"unknown op {op!r}")
        vals[n["name"]] = y
    out = vals[m["output"]]
    return out + x if m["residual"] else out


def forward(mods, sites, num, params, x):
    """Logits (B, classes) of images ``x`` (B, H, W, 3) at numerics
    ``num`` (what ``numerics`` returns), in its storage dtype: widened
    inside the same program, bfloat16 logits may keep the float32 bits the
    compiler is allowed to leave in them.  ``sites`` is what
    ``quantized_sites`` returns."""
    x = x.astype(num[0])
    for m in mods:
        x = _module(m, params[m["name"]], x, sites, num)
    return x.reshape(x.shape[0], -1)


@lru_cache(maxsize=None)
def _compiled(cfg_json: str, mode: str):
    """(every weight from a seed, on the device in one jitted call; the
    jitted forward pass in ``mode``) of a configuration, shared by every
    ``Reference`` of it."""
    cfg = json.loads(cfg_json)
    mods = modules(cfg)
    return (jax.jit(partial(_init, mods)),
            jax.jit(partial(forward, mods, quantized_sites(cfg["kernels"]),
                            numerics(cfg, mode))))


class Reference:
    """One configuration's network, its weights from one seed, and its
    forward pass run in fixed blocks of images (one compile per mode and
    configuration)."""

    def __init__(self, cfg: dict, seed: int, block: int = 16):
        self.cfg_json = json.dumps(cfg, sort_keys=True)
        self.block = block
        init, _ = _compiled(self.cfg_json, "reference")
        self.params = init(jnp.int32(weight_seed(seed)))

    def __call__(self, images: np.ndarray, mode: str = "reference"
                 ) -> np.ndarray:
        _, fn = _compiled(self.cfg_json, mode)
        out = []
        for i in range(0, len(images), self.block):
            xb = images[i:i + self.block]
            pad = self.block - len(xb)
            if pad:
                xb = np.concatenate([xb, np.zeros((pad, *xb.shape[1:]),
                                                  xb.dtype)])
            out.append(np.asarray(fn(self.params, xb)).astype(np.float32)
                       [:self.block - pad])
        return np.concatenate(out)


def rel_errors(rows: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per row: the norm of the difference over the norm of the reference
    row, in float64."""
    rows = rows.astype(np.float64)
    ref = ref.astype(np.float64)
    return (np.linalg.norm(rows - ref, axis=1)
            / np.maximum(np.linalg.norm(ref, axis=1), 1e-30))
