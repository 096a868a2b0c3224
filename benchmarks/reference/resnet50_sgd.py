"""Plain reference for ``resnet50_sgd``: ResNet-50 v1.5 (He et al.,
arXiv:1512.03385; the stride on the 3x3, as torchvision and apex's
imagenet example train it), softmax cross-entropy, and SGD with
momentum, in straightforward ``jax.numpy``/``lax`` float32 at
``highest`` convolution precision.  No amp, no buckets, nothing
imported from the program.

Parameter names follow the flax modules of ``apex_tpu.models.resnet``
(``Conv_0``, ``BatchNorm_0``, ``Bottleneck_<n>/...``, ``Dense_0``), so
that one tree of seeded weights serves both.  BatchNorm normalises by
the batch's own statistics (training mode, biased variance, epsilon
1e-5); the running statistics are not part of the comparison.  SGD is
torch.optim.SGD's: weight decay added to the gradient, the momentum
buffer seeded with the first gradient, no dampening, no Nesterov.

Memory: each bottleneck is a ``jax.checkpoint``, so the backward pass
keeps block inputs only.

``precision`` other than ``"f32"`` is for the control: the operands of
every convolution and of the dense layer are rounded to that type (with
a per-tensor scale for fp8) before a float32 product.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.common import (HIGHEST, as_floats, diff_norms, norms,
                                         rounder, unzip)

BN_EPS = 1e-5
STAGES = (3, 4, 6, 3)


def _blocks(sizes):
    """(name, in channels, filters, stride, projects) of each block."""
    out, cin, n = [], sizes["width"], 0
    for i, count in enumerate(sizes.get("stage_sizes", STAGES)):
        f = sizes["width"] * 2 ** i
        for j in range(count):
            stride = 2 if i > 0 and j == 0 else 1
            out.append((f"Bottleneck_{n}", cin, f, stride, cin != 4 * f))
            cin, n = 4 * f, n + 1
    return out


def param_spec(sizes: dict) -> dict:
    def conv(k, i, o):
        return {"kernel": ((k, k, i, o),
                           ("normal", math.sqrt(2.0 / (k * k * i))))}

    def bn(c):
        return {"bias": ((c,), ("zeros",)), "scale": ((c,), ("ones",))}

    w = sizes["width"]
    spec = {"BatchNorm_0": bn(w), "Conv_0": conv(7, 3, w)}
    cin = w
    for name, cin, f, _, projects in _blocks(sizes):
        blk = {"Conv_0": conv(1, cin, f), "BatchNorm_0": bn(f),
               "Conv_1": conv(3, f, f), "BatchNorm_1": bn(f),
               "Conv_2": conv(1, f, 4 * f), "BatchNorm_2": bn(4 * f)}
        if projects:
            blk["Conv_3"] = conv(1, cin, 4 * f)
            blk["BatchNorm_3"] = bn(4 * f)
        spec[name] = blk
        cin = 4 * f
    spec["Dense_0"] = {
        "bias": ((sizes["num_classes"],), ("zeros",)),
        "kernel": ((cin, sizes["num_classes"]),
                   ("normal", math.sqrt(1.0 / cin)))}
    return spec


def _conv(x, p, stride, pad, rnd):
    return jax.lax.conv_general_dilated(
        rnd(x), rnd(p["kernel"]), (stride, stride), [(pad, pad)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _bn(x, p):
    mu = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean((x - mu) ** 2, axis=(0, 1, 2))
    return (x - mu) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def _bottleneck(p, x, *, stride, rnd):
    y = jax.nn.relu(_bn(_conv(x, p["Conv_0"], 1, 0, rnd), p["BatchNorm_0"]))
    y = jax.nn.relu(_bn(_conv(y, p["Conv_1"], stride, 1, rnd),
                        p["BatchNorm_1"]))
    y = _bn(_conv(y, p["Conv_2"], 1, 0, rnd), p["BatchNorm_2"])
    if "Conv_3" in p:
        x = _bn(_conv(x, p["Conv_3"], stride, 0, rnd), p["BatchNorm_3"])
    return jax.nn.relu(y + x)


def loss(params, images, labels, *, sizes, precision="f32"):
    rnd = rounder(precision)
    x = _conv(images, params["Conv_0"], 2, 3, rnd)
    x = jax.nn.relu(_bn(x, params["BatchNorm_0"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])
    for name, _, _, stride, _ in _blocks(sizes):
        x = jax.checkpoint(functools.partial(
            _bottleneck, stride=stride, rnd=rnd))(params[name], x)
    x = jnp.mean(x, axis=(1, 2))
    logits = jnp.matmul(rnd(x), rnd(params["Dense_0"]["kernel"]),
                        precision=HIGHEST) + params["Dense_0"]["bias"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


@functools.partial(jax.jit, donate_argnums=(0, 2))
def _sgd(p, g, buf, first, hyper):
    def leaf(p, g, buf):
        g = g + hyper["weight_decay"] * p
        buf = jnp.where(first, g, hyper["momentum"] * buf + g)
        return p - hyper["lr"] * buf, buf

    ps, treedef = jax.tree_util.tree_flatten(p)
    outs = [leaf(*x) for x in zip(ps, jax.tree_util.tree_leaves(g),
                                  jax.tree_util.tree_leaves(buf))]
    return unzip(treedef, outs, 2)


def follow(params: dict, batches, sizes: dict, optimizer: dict,
           precision: str = "f32") -> dict:
    """Train from ``params`` (float32, consumed) over ``batches``
    (``(images, labels)`` each, images float32 NHWC) and return what
    the comparison reads: each step's loss, the first gradient as the
    optimizer got it (with its weight decay: the momentum buffer after
    one step) and the parameters' change after the last step, both as
    norms by leaf."""
    hyper = {k: jnp.float32(optimizer[k]) for k in
             ("lr", "momentum", "weight_decay")}
    step = jax.jit(jax.value_and_grad(functools.partial(
        loss, sizes=sizes, precision=precision)))
    start = jax.tree_util.tree_map(jnp.copy, params)
    buf = jax.tree_util.tree_map(jnp.zeros_like, params)
    out = {"losses": []}
    for t, (images, labels) in enumerate(batches, start=1):
        value, grads = step(params, jnp.asarray(images, jnp.float32),
                            jnp.asarray(labels))
        out["losses"].append(float(value))
        params, buf = _sgd(params, grads, buf, t == 1, hyper)
        if t == 1:
            out["grad1"] = as_floats(norms(buf))
    out["change"] = as_floats(diff_norms(params, start))
    return out
