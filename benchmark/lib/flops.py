"""Operations and bytes a step needs, computed from the layers' shapes.

The utilization numerator of the benchmark.  It is taken from the layer
graph and never from the compiler, so it does not move when a lowering
does: a convolution or inner product costs one multiply-accumulate per
weight per output position, a training step costs the forward pass three
times over (forward, gradient with respect to the input, gradient with
respect to the weights) except in a layer fed by the data alone, whose
input needs no gradient and which costs it twice, and nothing else is
counted.  Shape inference is this file's own (Caffe's rules: floor for
convolutions, ceil for pools).
"""

from __future__ import annotations

import math


def _pair(p, key: str, default: int, h_key: str | None = None,
          w_key: str | None = None) -> tuple[int, int]:
    if h_key and (p.has(h_key) or p.has(w_key)):
        return int(p.get(h_key, default)), int(p.get(w_key, default))
    vals = [int(v) for v in p.get_all(key)]
    if not vals:
        return default, default
    return (vals[0], vals[0]) if len(vals) == 1 else (vals[0], vals[1])


def conv_geometry(lp) -> dict:
    p = lp.sub("convolution_param")
    kh, kw = _pair(p, "kernel_size", 0, "kernel_h", "kernel_w")
    sh, sw = _pair(p, "stride", 1, "stride_h", "stride_w")
    ph, pw = _pair(p, "pad", 0, "pad_h", "pad_w")
    dh, dw = _pair(p, "dilation", 1)
    return {"kh": kh, "kw": kw, "sh": sh, "sw": sw, "ph": ph, "pw": pw,
            "dh": dh, "dw": dw, "num_output": int(p.get("num_output")),
            "group": int(p.get("group", 1)),
            "bias": bool(p.get("bias_term", True))}


def pool_geometry(lp, h: int, w: int) -> dict:
    p = lp.sub("pooling_param")
    if p.get("global_pooling", False):
        kh, kw = h, w
    else:
        kh, kw = _pair(p, "kernel_size", 0, "kernel_h", "kernel_w")
    sh, sw = _pair(p, "stride", 1, "stride_h", "stride_w")
    ph, pw = _pair(p, "pad", 0, "pad_h", "pad_w")

    def out(n, k, s, pad):
        o = int(math.ceil((n + 2 * pad - k) / s)) + 1
        if pad and (o - 1) * s >= n + pad:      # last window starts in pad
            o -= 1
        return o

    return {"kh": kh, "kw": kw, "sh": sh, "sw": sw, "ph": ph, "pw": pw,
            "oh": out(h, kh, sh, ph), "ow": out(w, kw, sw, pw),
            "pool": str(p.get("pool", "MAX")).upper()}


def _input_shapes(lp) -> list[tuple[int, ...]] | None:
    """Shapes of a host-fed data layer's tops, or None for other layers."""
    if lp.type == "JavaData":
        p = lp.sub("java_data_param")
        shapes = [tuple(int(d) for d in p.get("shape").get_all("dim"))]
        if p.has("label_shape"):
            shapes.append(tuple(
                int(d) for d in p.get("label_shape").get_all("dim")))
        return shapes
    if lp.type == "Input":
        return [tuple(int(d) for d in s.get_all("dim"))
                for s in lp.sub("input_param").get_all("shape")]
    return None


_SAME_SHAPE = {"ReLU", "Dropout", "LRN", "Softmax"}
_SCALAR_OUT = {"SoftmaxWithLoss", "Accuracy"}


def layer_table(net_param) -> list[dict]:
    """One row per layer of an already phase-filtered ``NetParameter``:
    name, type, bottom and top shapes, ``macs``, the
    multiply-accumulates of one forward pass over the whole batch the
    net declares (0 for everything but Convolution and InnerProduct),
    and ``from_data``, true where every bottom is a data layer's top."""
    shapes: dict[str, tuple[int, ...]] = {}
    fed_blobs: set[str] = set()
    rows = []
    for lp in net_param.layer:
        ins = [shapes[b] for b in lp.bottom]
        macs = 0
        fed = _input_shapes(lp)
        if fed is not None:
            outs = fed
            fed_blobs.update(lp.top)
        elif lp.type == "Convolution":
            g = conv_geometry(lp)
            n, c, h, w = ins[0]
            oh = (h + 2 * g["ph"] - g["dh"] * (g["kh"] - 1) - 1) // g["sh"] + 1
            ow = (w + 2 * g["pw"] - g["dw"] * (g["kw"] - 1) - 1) // g["sw"] + 1
            outs = [(n, g["num_output"], oh, ow)]
            macs = (n * g["num_output"] * oh * ow
                    * (c // g["group"]) * g["kh"] * g["kw"])
        elif lp.type == "Pooling":
            n, c, h, w = ins[0]
            g = pool_geometry(lp, h, w)
            outs = [(n, c, g["oh"], g["ow"])]
        elif lp.type == "InnerProduct":
            p = lp.sub("inner_product_param")
            n = ins[0][0]
            k = math.prod(ins[0][1:])
            m = int(p.get("num_output"))
            outs = [(n, m)]
            macs = n * m * k
        elif lp.type == "Concat":
            axis = int(lp.sub("concat_param").get("axis", 1))
            out = list(ins[0])
            out[axis] = sum(s[axis] for s in ins)
            outs = [tuple(out)]
        elif lp.type in _SAME_SHAPE:
            outs = [ins[0]]
        elif lp.type in _SCALAR_OUT:
            outs = [()] * max(len(lp.top), 1)
        else:
            raise ValueError(
                f"layer {lp.name!r}: type {lp.type!r} has no shape rule in "
                f"benchmark/lib/flops.py")
        for t, s in zip(lp.top, outs):
            shapes[t] = s
        rows.append({"name": lp.name, "type": lp.type, "in": ins,
                     "out": outs, "macs": macs,
                     "from_data": bool(lp.bottom) and all(
                         b in fed_blobs for b in lp.bottom)})
    return rows


def _batch(rows: list[dict]) -> int:
    return next(r["out"][0][0] for r in rows if not r["in"])


def forward_macs_per_image(net_param) -> float:
    """Multiply-accumulates of one forward pass, per image."""
    rows = layer_table(net_param)
    return sum(r["macs"] for r in rows) / _batch(rows)


def train_flops_per_image(net_param) -> float:
    """Floating-point operations one training step requires per image:
    2 per multiply-accumulate, forward plus the backward products (two,
    or one where the layer's input is the data and needs no gradient)."""
    rows = layer_table(net_param)
    return sum(2 * r["macs"] * (2 if r["from_data"] else 3)
               for r in rows) / _batch(rows)


def lrn_min_bytes_per_step(net_param, itemsize: int) -> dict:
    """The least bytes the LRN layers of one training step must move:
    forward reads the input and writes the output, backward reads the
    input and the output's gradient and writes the input's gradient.
    Returns ``{"layers": n, "fwd": bytes, "bwd": bytes}`` over the batch
    the net declares."""
    elems = [math.prod(r["in"][0]) for r in layer_table(net_param)
             if r["type"] == "LRN"]
    return {"layers": len(elems),
            "fwd": 2 * sum(elems) * itemsize,
            "bwd": 3 * sum(elems) * itemsize}


def widths(net_param) -> list[tuple]:
    """Every size of a phase-filtered net that a layer's cost depends on,
    one row per sized layer, in order: what a configuration file states
    ``as_built`` and the harness checks the program's builder against.
    Convolution: (name, type, num_output, kernel h, w, stride, pad,
    group); Pooling: (name, type, pool, kernel h, w, stride, pad);
    InnerProduct: (name, type, num_output); LRN: (name, type,
    local_size); data layers: (name, type, channels, height, width)."""
    rows: list[tuple] = []
    for lp, r in zip(net_param.layer, layer_table(net_param)):
        if lp.type == "Convolution":
            g = conv_geometry(lp)
            rows.append((lp.name, lp.type, g["num_output"], g["kh"], g["kw"],
                         g["sh"], g["ph"], g["group"]))
        elif lp.type == "Pooling":
            g = pool_geometry(lp, *r["in"][0][2:])
            rows.append((lp.name, lp.type, g["pool"], g["kh"], g["kw"],
                         g["sh"], g["ph"]))
        elif lp.type == "InnerProduct":
            rows.append((lp.name, lp.type, r["out"][0][1]))
        elif lp.type == "LRN":
            rows.append((lp.name, lp.type,
                         int(lp.sub("lrn_param").get("local_size", 5))))
        elif not r["in"]:
            rows.append((lp.name, lp.type, *r["out"][0][1:]))
    return rows
