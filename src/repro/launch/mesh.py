"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state, so tests/benches see the default 1-device CPU while the
dry-run (which sets XLA_FLAGS *before* importing jax) sees 512 placeholder
host devices.

Target hardware: TPU v5e. 256 chips/pod in a (16, 16) twisted torus;
multi-pod = 2 pods over DCN. Axis meaning (DESIGN.md §2):
  pod    across-datacenter replica/client axis (DCN-linked)
  data   within-pod batch / FL-client / expert axis
  model  tensor-parallel axis (Megatron sharding)
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link (~ per-device collective bw)
DCN_BW = 6.25e9                   # B/s per host across pods (50 Gb/s)
HBM_BYTES = 16e9                  # v5e HBM capacity


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_cohort_mesh(num_devices: int = None, axis: str = "clients",
                     model: int = 1):
    """Mesh over the local devices for the simulation trainer's cohort
    sharding (DESIGN.md §2).

    ``model == 1`` (default): the historical 1-D ``(axis,)`` mesh — the
    fused cohort round's (K, M, ...) batch stack is data-parallel over
    ``axis`` while params / server state replicate.

    ``model > 1``: a two-axis ``(devices // model, model)`` mesh over
    ``(axis, "model")`` — each client slice holds a model-parallel
    replica whose params / server state shard over ``model`` with the §8
    per-leaf rules (sharding/rules.cohort_param_specs), the layout for
    models larger than one device's HBM. ``model`` must divide the
    device count; anything else fails loudly here rather than producing
    a silently lopsided mesh.

    MULTI-PROCESS (DESIGN.md §15): ``jax.devices()`` is the GLOBAL
    device list once ``launch/distributed.maybe_initialize()`` has run,
    so the same call builds a process-spanning clients mesh with no
    changes — the axis enumerates every host's devices in process order
    (process 0's local devices first), which is what makes each host's
    contiguous client-row slice land on its own local devices
    (sharding/rules.local_row_range). ``num_devices`` must then be the
    GLOBAL count (or None).

    On CPU CI both shapes are exercised with
    XLA_FLAGS=--xla_force_host_platform_device_count=8."""
    n = num_devices or len(jax.devices())
    if model <= 1:
        return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,))
    if n % model:
        raise ValueError(
            f"model={model} does not divide the {n} available devices; "
            f"a ({axis}, model) mesh needs clients x model == devices")
    return jax.make_mesh((n // model, model), (axis, "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_info(mesh) -> dict:
    return {"axis_names": tuple(mesh.axis_names),
            "shape": tuple(mesh.devices.shape),
            "num_devices": int(mesh.devices.size)}
