"""Every FedDPC Pallas kernel, compiled ahead of time for a described
TPU v5e chip (no chip attached) at ResNet-18 widths, with
``interpret=False``. This is the check interpret mode cannot make: the
Mosaic lowering refuses row blocks that are not a whole number of the
dtype's sublane tile, scalars held as VMEM vectors, and unaligned
output blocks. A compile that passes is not a chip run.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.feddpc_project import kernel as fp_kernel
from repro.kernels.feddpc_project import ops as fp_ops

# ResNet-18's largest leaf: a 3x3x512x512 conv kernel, (18432, 128)
M = 3 * 3 * 512 * 512 // fp_kernel.LANE


@pytest.fixture(scope="module")
def one_chip():
    """Sharding on device 0 of a described v5e:2x2 host, with JAX's
    persistent compilation cache off (an entry compiled for a described
    chip cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile_text(fn, sharding, *shapes):
    """Compiled HLO text of jit(fn) over ShapeDtypeStructs on `sharding`."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32 = jnp.float32


def _dots(name):
    fn = getattr(fp_kernel, name)
    return (lambda d, p: fn(d, p, interpret=False),
            [((M, 128), F32), ((M, 128), F32)])


def _fused_epilogue():
    return (lambda d, p, c, s: fp_kernel.fused_epilogue(d, p, c, s,
                                                        interpret=False),
            [((M, 128), F32), ((M, 128), F32), ((), F32), ((), F32)])


def _cohort(name, k, qdtype=F32):
    """(fn, shapes) for the K-stacked epilogues/folds; the dequant pair
    takes a quantized stack plus per-client dequant scale/zero."""
    fn = getattr(fp_kernel, name)
    dequant = name.startswith("dequant")
    fold = name.endswith("fold")
    shapes = [((k, M, 128), qdtype), ((M, 128), F32), ((M, 128), F32),
              ((k,), F32), ((k,), F32)]
    if fold:
        shapes.append(((k,), F32))                # staleness weights
    shapes.append(((), F32))                      # eta_g
    if dequant:
        shapes += [((k,), F32), ((k,), F32)]      # dequant scale, zero
    return (lambda *a: fn(*a, interpret=False)), shapes


CASES = {
    "fused_dots": lambda: _dots("fused_dots"),
    "guard_dots": lambda: _dots("guard_dots"),
    "fused_epilogue": _fused_epilogue,
}
for _k in (10, 16):
    CASES[f"batched_epilogue-K{_k}"] = (
        lambda k=_k: _cohort("batched_epilogue", k))
    CASES[f"buffer_fold-B{_k}"] = lambda k=_k: _cohort("buffer_fold", k)
    for _q in ("int8", "bfloat16"):
        CASES[f"dequant_batched_epilogue-K{_k}-{_q}"] = (
            lambda k=_k, q=_q: _cohort("dequant_batched_epilogue", k,
                                       jnp.dtype(q)))
        CASES[f"dequant_buffer_fold-B{_k}-{_q}"] = (
            lambda k=_k, q=_q: _cohort("dequant_buffer_fold", k,
                                       jnp.dtype(q)))


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes = CASES[case]()
    text = _compile_text(fn, one_chip, *shapes)
    assert "tpu_custom_call" in text, case


def _resnet18_shapes():
    from repro.configs.paper_resnet18 import CONFIG
    from repro.models.vision import init_vision
    return jax.eval_shape(lambda: init_vision(CONFIG, jax.random.PRNGKey(0)))


@pytest.mark.parametrize("wrapper", ["batched_server_epilogue",
                                     "buffered_server_fold",
                                     "dequant_batched_server_epilogue",
                                     "dequant_buffered_server_fold"])
def test_server_fold_over_resnet18_compiles_for_v5e(wrapper, one_chip):
    """The pytree wrappers the round calls, over every leaf of the paper's
    ResNet-18-GN (K=10): each leaf's padding must give aligned blocks,
    down to the 64-element GroupNorm scales."""
    k = 10
    params = _resnet18_shapes()
    on = lambda s, dt=F32: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    p_sh = jax.tree.map(lambda x: on(x.shape), params)
    stack = lambda dt: jax.tree.map(lambda x: on((k,) + x.shape, dt), params)
    per_client = on((k,))
    fn = getattr(fp_ops, wrapper)
    if wrapper.startswith("dequant"):
        first = {"q": stack(jnp.int8),
                 "scale": jax.tree.map(lambda _: per_client, params),
                 "zero": jax.tree.map(lambda _: per_client, params)}
    else:
        first = stack(F32)
    extra = (per_client,) if wrapper.endswith("fold") else ()
    call = lambda x, p, w, c, s, e, *wt: fn(x, p, w, c, s, *wt, e,
                                            interpret=False)
    text = jax.jit(call).lower(first, p_sh, p_sh, per_client, per_client,
                               on(()), *extra).compile().as_text()
    n_leaves = len(jax.tree.leaves(params))
    assert text.count("tpu_custom_call") >= n_leaves, wrapper
