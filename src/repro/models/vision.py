"""The paper's own models: LeNet5 (CIFAR-10) and ResNet18 + GroupNorm
(CIFAR-100 / TinyImageNet), in pure JAX (NHWC).

These are what the faithful FedDPC reproduction trains (FedDPC §5.2.1).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import group_norm, init_group_norm


@dataclass(frozen=True)
class VisionConfig:
    name: str
    family: str                 # lenet5 | resnet18
    image_size: int = 32
    channels: int = 3
    num_classes: int = 10
    width: int = 64             # resnet stem width
    groups: int = 8             # groupnorm groups
    arch_type: str = "vision"


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    return jax.random.normal(key, (kh, kw, cin, cout)) * math.sqrt(2.0 / fan_in)


def _conv(x, w, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


# ---------------- LeNet5 ----------------

def init_lenet5(cfg: VisionConfig, key):
    ks = jax.random.split(key, 5)
    flat = ((cfg.image_size // 4 - 3) ** 2) * 16    # 32 -> 5*5*16 = 400
    return {
        "c1": _conv_init(ks[0], 5, 5, cfg.channels, 6),
        "c2": _conv_init(ks[1], 5, 5, 6, 16),
        "f1": {"w": jax.random.normal(ks[2], (flat, 120)) * math.sqrt(2.0 / flat),
               "b": jnp.zeros(120)},
        "f2": {"w": jax.random.normal(ks[3], (120, 84)) * math.sqrt(2.0 / 120),
               "b": jnp.zeros(84)},
        "f3": {"w": jax.random.normal(ks[4], (84, cfg.num_classes)) * math.sqrt(2.0 / 84),
               "b": jnp.zeros(cfg.num_classes)},
    }


def lenet5_forward(cfg: VisionConfig, p, x):
    """x: (B, H, W, C) -> logits (B, classes)."""
    h = jax.nn.relu(_conv(x, p["c1"], padding="VALID"))
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                              (1, 2, 2, 1), "VALID")
    h = jax.nn.relu(_conv(h, p["c2"], padding="VALID"))
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                              (1, 2, 2, 1), "VALID")
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ p["f1"]["w"] + p["f1"]["b"])
    h = jax.nn.relu(h @ p["f2"]["w"] + p["f2"]["b"])
    return h @ p["f3"]["w"] + p["f3"]["b"]


# ---------------- ResNet18 + GroupNorm ----------------

def _init_block(key, cin, cout, stride, groups):
    ks = jax.random.split(key, 3)
    p = {
        "conv1": _conv_init(ks[0], 3, 3, cin, cout),
        "gn1": init_group_norm(groups, cout),
        "conv2": _conv_init(ks[1], 3, 3, cout, cout),
        "gn2": init_group_norm(groups, cout),
    }
    if stride != 1 or cin != cout:
        p["proj"] = _conv_init(ks[2], 1, 1, cin, cout)
        p["gn_proj"] = init_group_norm(groups, cout)
    return p


def _block(p, x, stride, groups):
    h = _conv(x, p["conv1"], stride)
    h = jax.nn.relu(group_norm(p["gn1"], h, groups))
    h = _conv(h, p["conv2"], 1)
    h = group_norm(p["gn2"], h, groups)
    if "proj" in p:
        x = group_norm(p["gn_proj"], _conv(x, p["proj"], stride), groups)
    return jax.nn.relu(h + x)


def init_resnet18(cfg: VisionConfig, key):
    w = cfg.width
    ks = jax.random.split(key, 10)
    widths = [w, w, 2 * w, 2 * w, 4 * w, 4 * w, 8 * w, 8 * w]
    strides = [1, 1, 2, 1, 2, 1, 2, 1]
    blocks = []
    cin = w
    for i in range(8):
        blocks.append(_init_block(ks[1 + i], cin, widths[i], strides[i], cfg.groups))
        cin = widths[i]
    return {
        "stem": _conv_init(ks[0], 3, 3, cfg.channels, w),
        "gn_stem": init_group_norm(cfg.groups, w),
        "blocks": blocks,
        "head": {"w": jax.random.normal(ks[9], (8 * w, cfg.num_classes))
                 * math.sqrt(2.0 / (8 * w)),
                 "b": jnp.zeros(cfg.num_classes)},
    }


def resnet18_forward(cfg: VisionConfig, p, x):
    strides = [1, 1, 2, 1, 2, 1, 2, 1]
    h = jax.nn.relu(group_norm(p["gn_stem"], _conv(x, p["stem"], 1), cfg.groups))
    for bp, s in zip(p["blocks"], strides):
        h = _block(bp, h, s, cfg.groups)
    h = h.mean(axis=(1, 2))
    return h @ p["head"]["w"] + p["head"]["b"]


# ---------------- unified ----------------

def init_vision(cfg: VisionConfig, key):
    if cfg.family == "lenet5":
        return init_lenet5(cfg, key)
    return init_resnet18(cfg, key)


def vision_forward(cfg: VisionConfig, p, x):
    if cfg.family == "lenet5":
        return lenet5_forward(cfg, p, x)
    return resnet18_forward(cfg, p, x)


def vision_loss_fn(cfg: VisionConfig, p, batch):
    """batch: {images (B,H,W,C), labels (B,)} -> mean CE (paper §5.2.2)."""
    logits = vision_forward(cfg, p, batch["images"]).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][:, None], axis=-1)[:, 0]
    return (logz - gold).mean()


EVAL_CHUNK = 1000


def vision_accuracy(cfg: VisionConfig, p, images, labels):
    """Top-1 accuracy, forwarding EVAL_CHUNK images at a time: the whole
    CIFAR test set in one forward pass of the full-width ResNet-18 needs
    about 8 GB of activations on a TPU, half a v5e's HBM, while the
    trainer's async eval overlaps the next round. Padded rows carry
    label -1 and never count as correct."""
    n = images.shape[0]
    chunk = min(EVAL_CHUNK, n)
    pad = (-n) % chunk
    x = jnp.pad(images, ((0, pad),) + ((0, 0),) * (images.ndim - 1))
    y = jnp.pad(labels, (0, pad), constant_values=-1)

    def correct(xy):
        pred = jnp.argmax(vision_forward(cfg, p, xy[0]), axis=-1)
        return jnp.sum(pred == xy[1])

    hits = jax.lax.map(correct, (x.reshape(-1, chunk, *images.shape[1:]),
                                 y.reshape(-1, chunk)))
    return hits.sum() / n
