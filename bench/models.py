"""The plain reference of the paper's two vision models, and their
weights made from a seed.

Written from the published descriptions, not from the program: LeNet-5
(two 5x5 VALID convolutions with ReLU and 2x2 max-pooling, then three
dense layers, FedDPC §5.2.1) and ResNet-18 (He et al. 2016,
arXiv:1512.03385: a 3x3 stem, eight basic blocks of two 3x3
convolutions at widths w, w, 2w, 2w, 4w, 4w, 8w, 8w with strides 1, 1,
2, 1, 2, 1, 2, 1 and a 1x1 projection where the shape changes, global
average pooling, one dense head) with GroupNorm (Wu & He 2018,
arXiv:1803.08494: per-sample statistics over each group of channels and
all positions, eps 1e-5, a per-channel scale and bias) in place of
BatchNorm. Images are NHWC, kernels HWIO.

The weights are laid out as the tree the program's ``loss_fn`` takes,
which is what a configuration fixes; the values are drawn here, on the
device, in one jitted call (He-normal kernels, unit GroupNorm scales,
zero biases).

``forward`` takes the precision to compute in: float32 at the
configurations' default precision for the reference, bfloat16 for the
control, float32 at ``highest`` for a witness.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

STRIDES = (1, 1, 2, 1, 2, 1, 2, 1)
GN_EPS = 1e-5


def _widths(w: int) -> List[int]:
    return [w, w, 2 * w, 2 * w, 4 * w, 4 * w, 8 * w, 8 * w]


def _he(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * math.sqrt(2.0 / fan_in)


def _gn(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def _init_lenet5(m: Dict[str, Any], key):
    ks = jax.random.split(key, 5)
    c, n = m["channels"], m["num_classes"]
    flat = ((m["image_size"] // 4 - 3) ** 2) * 16
    return {
        "c1": _he(ks[0], (5, 5, c, 6), 25 * c),
        "c2": _he(ks[1], (5, 5, 6, 16), 25 * 6),
        "f1": {"w": _he(ks[2], (flat, 120), flat), "b": jnp.zeros(120)},
        "f2": {"w": _he(ks[3], (120, 84), 120), "b": jnp.zeros(84)},
        "f3": {"w": _he(ks[4], (84, n), 84), "b": jnp.zeros(n)},
    }


def _init_resnet18(m: Dict[str, Any], key):
    w, c = m["width"], m["channels"]
    ks = jax.random.split(key, 10)
    blocks, cin = [], w
    for i, (cout, s) in enumerate(zip(_widths(w), STRIDES)):
        kb = jax.random.split(ks[1 + i], 3)
        b = {"conv1": _he(kb[0], (3, 3, cin, cout), 9 * cin),
             "gn1": _gn(cout),
             "conv2": _he(kb[1], (3, 3, cout, cout), 9 * cout),
             "gn2": _gn(cout)}
        if s != 1 or cin != cout:
            b["proj"] = _he(kb[2], (1, 1, cin, cout), cin)
            b["gn_proj"] = _gn(cout)
        blocks.append(b)
        cin = cout
    return {"stem": _he(ks[0], (3, 3, c, w), 9 * c), "gn_stem": _gn(w),
            "blocks": blocks,
            "head": {"w": _he(ks[9], (8 * w, m["num_classes"]), 8 * w),
                     "b": jnp.zeros(m["num_classes"])}}


INIT = {"lenet5": _init_lenet5, "resnet18": _init_resnet18}


def init_params(model: Dict[str, Any], seed_word: int, device=None):
    """The configuration's weights from a seed, made on ``device`` (the
    default device when None) in one jitted call, float32."""
    fn = jax.jit(lambda k: INIT[model["family"]](model, k), device=device)
    return fn(jax.random.key(seed_word))


def param_count(model: Dict[str, Any]) -> int:
    shapes = jax.eval_shape(lambda: INIT[model["family"]](
        model, jax.random.key(0)))
    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))


# ---- the plain forward pass ----

def _conv(x, w, stride, padding, precision):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _conv_patches(x, w, precision):
    """A VALID convolution of stride 1 as one product of the image's
    patches with the kernel: the same sums as ``_conv``. LeNet-5 takes
    this form because the TPU's compiler spends minutes on the weight
    gradient of its 5x5 convolution over 3 channels at batch 256 written
    as a convolution, and seconds on this."""
    kh, kw, c, o = w.shape
    oh, ow = x.shape[1] - kh + 1, x.shape[2] - kw + 1
    cols = jnp.concatenate([x[:, i:i + oh, j:j + ow, :]
                            for i in range(kh) for j in range(kw)], axis=-1)
    return jnp.dot(cols, w.astype(x.dtype).reshape(kh * kw * c, o),
                   precision=precision)


def _dense(x, p, precision):
    return jnp.dot(x, p["w"].astype(x.dtype),
                   precision=precision) + p["b"].astype(x.dtype)


def _group_norm(p, x, groups):
    b, h, w, c = x.shape
    g = x.reshape(b, h, w, groups, c // groups)
    mean = g.mean(axis=(1, 2, 4), keepdims=True)
    var = ((g - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    g = (g - mean) / jnp.sqrt(var + GN_EPS)
    return (g.reshape(b, h, w, c) * p["scale"].astype(x.dtype)
            + p["bias"].astype(x.dtype))


def _pool2(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def _lenet5(m, p, x, precision):
    h = _pool2(jax.nn.relu(_conv_patches(x, p["c1"], precision)))
    h = _pool2(jax.nn.relu(_conv_patches(h, p["c2"], precision)))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(_dense(h, p["f1"], precision))
    h = jax.nn.relu(_dense(h, p["f2"], precision))
    return _dense(h, p["f3"], precision)


def _resnet18(m, p, x, precision):
    g = m["groups"]
    h = jax.nn.relu(_group_norm(p["gn_stem"],
                                _conv(x, p["stem"], 1, "SAME", precision), g))
    for b, s in zip(p["blocks"], STRIDES):
        y = jax.nn.relu(_group_norm(
            b["gn1"], _conv(h, b["conv1"], s, "SAME", precision), g))
        y = _group_norm(b["gn2"], _conv(y, b["conv2"], 1, "SAME", precision),
                        g)
        if "proj" in b:
            h = _group_norm(b["gn_proj"],
                            _conv(h, b["proj"], s, "SAME", precision), g)
        h = jax.nn.relu(y + h)
    return _dense(h.mean(axis=(1, 2)), p["head"], precision)


FORWARD = {"lenet5": _lenet5, "resnet18": _resnet18}


def loss(model: Dict[str, Any], params, images, labels, dtype, precision):
    """Mean softmax cross-entropy of one batch, every tensor in ``dtype``
    and the log-sum-exp in float32."""
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    logits = FORWARD[model["family"]](model, p, images.astype(dtype),
                                      precision).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return (jax.nn.logsumexp(logits, axis=-1) - gold).mean()
