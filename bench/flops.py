"""Operations and bytes the work requires, computed from shapes alone.

``train_flops_per_sample`` counts the multiply-adds (two operations
each) of the convolutions and dense layers of one image's forward pass,
where a convolution's count is of the kernel taps that fall inside the
image (taps on the zero padding of a SAME convolution are no work the
model requires, and XLA's own count leaves them out as well),
and twice that for the backward pass (the gradients of the weights and
of the layer's input), less the input gradient of the first layer,
which nothing needs. Normalisation, activations, pooling and the loss
are left out: they are a few per cent of the total, and the model
FLOP/s utilisation is a share of the matrix units' peak.

``epilogue_bytes`` is what FedDPC's server epilogue has to move in one
round whatever implements it: read the K client pseudo-gradients, the
previous global update and the weights, and write the weights and the
new global update, all float32. ``epilogue_flops`` is what it has to
compute once the per-client scalars are known: for each client and
weight the residual d - c * prev (two operations), its scale and the
sum over clients (two more); then the mean and the step, w - eta_g *
mean (three).
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

F32 = 4


def _taps(size: int, k: int, stride: int, same: bool) -> int:
    """Kernel taps inside the input, summed over one axis's outputs."""
    if not same:
        return (size - k + 1) * k
    out = -(-size // stride)
    lo = max((out - 1) * stride + k - size, 0) // 2
    return sum(1 for o in range(out) for t in range(k)
               if 0 <= o * stride - lo + t < size)


def _conv(size: int, k: int, cin: int, cout: int, stride: int = 1,
          same: bool = True) -> int:
    return 2 * _taps(size, k, stride, same) ** 2 * cin * cout


def layer_flops(model: Dict[str, Any]) -> List[Tuple[str, int]]:
    """(layer, forward FLOPs per image), in forward order."""
    s, c, n = model["image_size"], model["channels"], model["num_classes"]
    if model["family"] == "lenet5":
        h2 = (s - 4) // 2               # 5x5 VALID, 2x2 pool
        flat = ((h2 - 4) // 2) ** 2 * 16
        return [("c1", _conv(s, 5, c, 6, same=False)),
                ("c2", _conv(h2, 5, 6, 16, same=False)),
                ("f1", 2 * flat * 120), ("f2", 2 * 120 * 84),
                ("f3", 2 * 84 * n)]
    if model["family"] == "resnet18":
        w0 = model["width"]
        out = [("stem", _conv(s, 3, c, w0))]
        cin, h = w0, s
        for i, (cout, st) in enumerate(zip(
                [w0, w0, 2 * w0, 2 * w0, 4 * w0, 4 * w0, 8 * w0, 8 * w0],
                [1, 1, 2, 1, 2, 1, 2, 1])):
            ho = -(-h // st)
            out.append((f"b{i}.conv1", _conv(h, 3, cin, cout, st)))
            out.append((f"b{i}.conv2", _conv(ho, 3, cout, cout)))
            if st != 1 or cin != cout:
                out.append((f"b{i}.proj", _conv(h, 1, cin, cout, st)))
            cin, h = cout, ho
        out.append(("head", 2 * cin * n))
        return out
    raise ValueError(f"no FLOP count for family {model['family']!r}")


def forward_flops_per_sample(model: Dict[str, Any]) -> int:
    return sum(f for _, f in layer_flops(model))


def train_flops_per_sample(model: Dict[str, Any]) -> int:
    layers = layer_flops(model)
    return 3 * sum(f for _, f in layers) - layers[0][1]


def epilogue_bytes(num_params: int, clients: int) -> int:
    return (clients + 4) * num_params * F32


def epilogue_flops(num_params: int, clients: int) -> int:
    return (4 * clients + 3) * num_params
