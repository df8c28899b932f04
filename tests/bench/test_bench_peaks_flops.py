"""The benchmark's yardstick: the peak table keyed by device kind, and
the FLOP and byte counts computed from shapes."""
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "bench"))

import flops  # noqa: E402
import models  # noqa: E402
import peaks  # noqa: E402


def test_v5e_peaks_as_published():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["ici_bits_per_s"] == 1600e9
    assert "cloud.google.com/tpu/docs/v5e" in json.loads(
        peaks.TABLE.read_text())["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice, match="TPU v9 imaginary"):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")


def _config(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", ["resnet18-gn-cifar100", "lenet5-cifar10"])
def test_param_count_as_configured(name):
    cfg = _config(name)
    assert models.param_count(cfg["model"]) == cfg["params"]


# XLA's count adds what bench/flops.py leaves out on purpose: the
# normalisation, activation, pooling and loss arithmetic, a few per cent
XLA_EXTRA = 0.03


@pytest.mark.parametrize("name", ["resnet18-gn-cifar100", "lenet5-cifar10"])
def test_flops_against_xla_cost_analysis(name, monkeypatch):
    """Forward and forward+backward FLOPs per image from the layer shapes,
    against XLA's cost analysis of the reference model on the CPU at a
    batch of 2, every convolution written as one: never above it, and
    under it by at most XLA_EXTRA."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setattr(models, "_conv_patches", lambda x, w, precision:
                        models._conv(x, w, 1, "VALID", precision))
    model = _config(name)["model"]
    params = jax.eval_shape(lambda: models.INIT[model["family"]](
        model, jax.random.key(0)))
    b, s, c = 2, model["image_size"], model["channels"]
    x = jax.ShapeDtypeStruct((b, s, s, c), jnp.float32)
    y = jax.ShapeDtypeStruct((b,), jnp.int32)

    def loss(p, x, y):
        return models.loss(model, p, x, y, jnp.float32, None)

    def xla(fn):
        cost = jax.jit(fn).lower(params, x, y).compile().cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        return cost["flops"] / b

    fwd, train = xla(loss), xla(jax.grad(loss))
    for mine, theirs in ((flops.forward_flops_per_sample(model), fwd),
                         (flops.train_flops_per_sample(model), train)):
        assert mine <= theirs <= mine * (1 + XLA_EXTRA), (mine, theirs)


def test_resnet18_flops_by_hand():
    """The stem and the first block's convolution of ResNet-18 at 32x32:
    a 3x3 SAME convolution has 30 rows of three taps and 2 edge rows of
    two taps inside the image along each axis."""
    model = _config("resnet18-gn-cifar100")["model"]
    layers = dict(flops.layer_flops(model))
    taps = (30 * 3 + 2 * 2) ** 2
    assert layers["stem"] == 2 * taps * 3 * 64
    assert layers["b0.conv1"] == 2 * taps * 64 * 64
    assert layers["head"] == 2 * 512 * 100


def test_epilogue_bytes():
    # K deltas read, delta_prev and the weights read, the weights and the
    # new update written: (K + 4) float32 words per parameter
    assert flops.epilogue_bytes(11_220_132, 10) == 14 * 11_220_132 * 4


def test_patch_convolution_equals_convolution():
    """LeNet-5's reference convolutions, written as one product of
    patches, give the sums of a VALID convolution."""
    import jax
    import jax.numpy as jnp
    k1, k2 = jax.random.split(jax.random.key(3))
    x = jax.random.normal(k1, (2, 12, 12, 3))
    w = jax.random.normal(k2, (5, 5, 3, 4))
    hi = jax.lax.Precision.HIGHEST
    want = models._conv(x, w, 1, "VALID", hi)
    got = models._conv_patches(x, w, hi)
    assert got.shape == want.shape == (2, 8, 8, 4)
    assert jnp.allclose(got, want, rtol=1e-5, atol=1e-5)
