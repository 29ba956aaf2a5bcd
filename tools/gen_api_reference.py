#!/usr/bin/env python
"""Regenerate docs/API_REFERENCE.md — the public-symbol inventory.

Usage:  JAX_PLATFORMS=cpu python tools/gen_api_reference.py
"""
import os
import sys
import types
import warnings

warnings.filterwarnings("ignore")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import paddle_tpu as paddle  # noqa: E402


def _mod(name):
    return __import__("paddle_tpu." + name, fromlist=["x"])


SECTIONS = [
    ("paddle", paddle),
    ("paddle.nn", paddle.nn),
    ("paddle.nn.functional", paddle.nn.functional),
    ("paddle.nn.initializer", paddle.nn.initializer),
    ("paddle.nn.utils", paddle.nn.utils),
    ("paddle.nn.quant", paddle.nn.quant),
    ("paddle.tensor (method surface)", None),
    ("paddle.linalg", paddle.linalg),
    ("paddle.fft", paddle.fft),
    ("paddle.signal", paddle.signal),
    ("paddle.optimizer", paddle.optimizer),
    ("paddle.optimizer.lr", paddle.optimizer.lr),
    ("paddle.autograd", paddle.autograd),
    ("paddle.amp", paddle.amp),
    ("paddle.io", paddle.io),
    ("paddle.static", _mod("static")),
    ("paddle.static.nn", _mod("static.nn")),
    ("paddle.static.amp", _mod("static.amp")),
    ("paddle.jit", paddle.jit),
    ("paddle.distributed", paddle.distributed),
    ("paddle.distributed.fleet", paddle.distributed.fleet),
    ("paddle.distributed.fleet.meta_parallel",
     paddle.distributed.fleet.meta_parallel),
    ("paddle.distributed.fleet.utils", paddle.distributed.fleet.utils),
    ("paddle.distributed.sharding", paddle.distributed.sharding),
    ("paddle.distributed.checkpoint", paddle.distributed.checkpoint),
    ("paddle.distributed.rpc", paddle.distributed.rpc),
    ("paddle.distributed.communication",
     paddle.distributed.communication),
    ("paddle.distributed.passes", paddle.distributed.passes),
    ("paddle.vision.models", paddle.vision.models),
    ("paddle.vision.datasets", paddle.vision.datasets),
    ("paddle.vision.transforms", paddle.vision.transforms),
    ("paddle.vision.ops", paddle.vision.ops),
    ("paddle.text", paddle.text),
    ("paddle.audio", paddle.audio),
    ("paddle.metric", paddle.metric),
    ("paddle.hapi (paddle.Model)", _mod("hapi")),
    ("paddle.callbacks", paddle.callbacks),
    ("paddle.distribution", paddle.distribution),
    ("paddle.sparse", paddle.sparse),
    ("paddle.quantization", paddle.quantization),
    ("paddle.incubate", paddle.incubate),
    ("paddle.incubate.nn", paddle.incubate.nn),
    ("paddle.incubate.nn.functional", paddle.incubate.nn.functional),
    ("paddle.geometric", paddle.geometric),
    ("paddle.profiler", paddle.profiler),
    ("paddle.device", paddle.device),
    ("paddle.inference", paddle.inference),
    ("paddle.onnx", paddle.onnx),
    ("paddle.hub", paddle.hub),
    ("paddle.utils", paddle.utils),
]


def public(mod):
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in dir(mod) if not n.startswith("_")]
    return [n for n in sorted(set(names))
            if not isinstance(getattr(mod, n, None), types.ModuleType)]


def main():
    lines = ["# paddle_tpu API reference (generated)",
             "",
             "Auto-generated public-symbol inventory, one section per",
             "namespace (regenerate: `python tools/gen_api_reference.py`).",
             "The upstream surface this mirrors is PaddlePaddle 2.5/2.6.",
             ""]
    total = 0
    body = []
    for title, mod in SECTIONS:
        if mod is None:
            from paddle_tpu.framework.core import Tensor
            syms = sorted(n for n in dir(Tensor) if not n.startswith("_"))
        else:
            syms = public(mod)
        total += len(syms)
        unit = "methods" if mod is None else "symbols"
        body.append(f"## {title} — {len(syms)} {unit}\n")
        body.append(", ".join(f"`{s}`" for s in syms) + "\n")
    lines.append(f"**Total: {total} public symbols.**")
    lines.append("")
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "API_REFERENCE.md")
    with open(out, "w") as f:
        f.write("\n".join(lines + body))
    print(f"wrote {out}: {total} symbols across {len(SECTIONS)} namespaces")


if __name__ == "__main__":
    main()
