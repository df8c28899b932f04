"""Subprocess body for the cross-regime equivalence test matrix.

Runs on 8 FORCED host devices (the XLA flag must be set before jax
initializes, which is why this lives in its own process rather than the
main pytest interpreter). Every cell of the matrix

    algorithm x sampler x execution regime x {prefetch on/off}

must reproduce the serial reference (vectorize=False, prefetch=False)
round for round: identical client schedule, allclose params / server
state / per-round losses / diagnostics. The axes come from the LIVE
registries — ``repro.core.baselines.ALGORITHM_NAMES``,
``repro.core.samplers.sampler_matrix`` and ``repro.core.api.
EXEC_REGIMES`` — so a newly registered algorithm, sampler, or execution
regime auto-enrolls without touching this file.

On the 8-device harness ``sharded2d`` is the (2 clients x 4 model) mesh
of the acceptance criteria; the model dims of the toy task (16, 4) are
4-divisible so the model axis genuinely partitions the leaves.

Invoked by tests/test_regime_matrix.py with either
    --cells algo:sampler:regime:{P|N}[,...]     matrix cells to check
    --cross-mesh-resume                         save on 2-axis, resume 1-D
    --kernel-fallback                           use_kernel under sharded1d/2d
"""
import argparse
import os
import sys
import tempfile

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8"
                           ).strip()

import numpy as np          # noqa: E402
import jax                  # noqa: E402
import jax.numpy as jnp     # noqa: E402

from repro.core.api import (AlgoConfig, EXEC_REGIMES,       # noqa: E402
                            ExecConfig, FederatedTrainer)
from repro.core.baselines import default_hyper              # noqa: E402
from repro.core.samplers import sampler_matrix              # noqa: E402
from _tree_assert import assert_trees_close                 # noqa: E402
# the toy task lives in _matrix_task.py (no env side effects) so the
# multi-process worker (tests/_multihost_worker.py) shares it verbatim
from _matrix_task import (NUM_CLIENTS, K, ROUNDS,           # noqa: E402
                          batch_fn, loss_fn, make_params)


def run_cell(algo: str, sampler_name: str, regime: str, prefetch: bool,
             use_kernel: bool = False, codec=None,
             server_opt=None) -> FederatedTrainer:
    kw = dict(EXEC_REGIMES[regime])
    if codec is not None:
        kw["codec"] = codec
    if server_opt is not None:
        kw["server_opt"] = server_opt
    cfg = ExecConfig(rounds=ROUNDS, clients_per_round=K, seed=5,
                     eval_every=10 ** 9, prefetch=prefetch, **kw)
    with FederatedTrainer(
            loss_fn, make_params(), NUM_CLIENTS, batch_fn, cfg,
            algo=AlgoConfig(name=algo, eta_l=0.05, eta_g=0.1,
                            hyper=default_hyper(algo,
                                                use_kernel=use_kernel)),
            sampler=sampler_matrix(NUM_CLIENTS, K)[sampler_name]) as tr:
        tr.run()
    return tr


_ref_cache = {}


def reference(algo: str, sampler_name: str, codec=None,
              server_opt=None) -> FederatedTrainer:
    key = (algo, sampler_name, codec, server_opt)
    if key not in _ref_cache:
        _ref_cache[key] = run_cell(algo, sampler_name, "serial", False,
                                   codec=codec, server_opt=server_opt)
    return _ref_cache[key]


# Documented quantization-drift bounds for the LOSSY codec regimes vs
# the NO-codec serial reference (DESIGN.md §13). Strict regime
# equivalence (vectorized / async / 2-axis mesh vs serial) is still
# checked at the default tolerances — against a serial reference run
# with the SAME codec, so the only slack granted here is the codec's
# own quantization error, never an execution-regime divergence.
CODEC_TOL = {
    "bf16": dict(rtol=5e-2, atol=5e-3),
    "int8": dict(rtol=1e-1, atol=2e-2),
    "int8_sym": dict(rtol=1e-1, atol=2e-2),
    "int8_sr": dict(rtol=2e-1, atol=5e-2),
}


def check_cell(cell: str):
    algo, sampler_name, regime, pf = cell.split(":")
    prefetch = {"P": True, "N": False}[pf]
    if regime == "serial" and not prefetch:
        # this IS the reference configuration: run it into the cache
        reference(algo, sampler_name)
        print(f"[matrix] {cell} is the reference OK")
        return
    tr = run_cell(algo, sampler_name, regime, prefetch)
    codec = EXEC_REGIMES[regime].get("codec")
    sopt = EXEC_REGIMES[regime].get("server_opt")
    lossy = codec is not None and codec != "identity"
    plain = reference(algo, sampler_name, server_opt=sopt)
    if lossy:
        # the documented drift bound vs the uncompressed run
        tol = CODEC_TOL[codec]
        assert_trees_close(tr.params, plain.params, **tol)
        for rv, rs in zip(tr.history, plain.history):
            assert np.isclose(rv.train_loss, rs.train_loss,
                              rtol=tol["rtol"], atol=tol["atol"]), cell
    # strict regime equivalence: same-codec (and same-server-opt)
    # serial reference
    ref = plain if not lossy else reference(algo, sampler_name, codec,
                                            server_opt=sopt)
    for a, b in zip(ref.schedule[:ROUNDS], tr.schedule[:ROUNDS]):
        assert (np.asarray(a) == np.asarray(b)).all(), (cell, a, b)
    assert_trees_close(tr.params, ref.params)
    assert_trees_close(tr.server_state, ref.server_state)
    for rv, rs in zip(tr.history, ref.history):
        assert np.isclose(rv.train_loss, rs.train_loss,
                          rtol=1e-4, atol=1e-6), cell
        assert rv.diagnostics.keys() == rs.diagnostics.keys(), cell
        for key in rv.diagnostics:
            assert np.isclose(rv.diagnostics[key], rs.diagnostics[key],
                              rtol=1e-3, atol=1e-4), (cell, key)
    if regime == "sharded2d":
        assert tr.mesh is not None and tr.mesh.devices.shape == (2, 4)
        # the model axis must actually partition a param leaf
        from jax.sharding import PartitionSpec as P
        assert tr.params["w1"].sharding.spec == P(None, "model"), \
            tr.params["w1"].sharding
    if sopt is not None:
        # optimizer state must exist and (on the two-axis mesh) the
        # moments must CO-LOCATE with their param leaves (DESIGN.md §14)
        assert tr._opt_state is not None, cell
        if regime.endswith("_2d"):
            for mom in ("m", "v"):
                for leaf, pleaf in zip(
                        jax.tree_util.tree_leaves(tr._opt_state[mom]),
                        jax.tree_util.tree_leaves(tr.params)):
                    assert leaf.sharding == pleaf.sharding, \
                        (cell, mom, leaf.sharding, pleaf.sharding)
    print(f"[matrix] {cell} == serial reference OK")


def check_cross_mesh_resume():
    """Save a 2-axis run mid-stream, resume it on a 1-D client mesh: the
    checkpoint holds full host arrays, so a mesh-shape change across
    save/resume works (allclose — mesh shape changes the reduction
    order, so bitwise equality only holds for same-mesh resume, which
    tests/test_resume.py covers)."""
    algo = AlgoConfig(name="feddpc", eta_l=0.05, eta_g=0.1)

    def cfg(**kw):
        return ExecConfig(rounds=ROUNDS, clients_per_round=K, seed=5,
                          eval_every=10 ** 9, **kw)

    with tempfile.TemporaryDirectory() as d:
        tr = FederatedTrainer(loss_fn, make_params(), NUM_CLIENTS, batch_fn,
                              cfg(shard_clients=True, shard_model=4),
                              algo=algo)
        with tr:
            tr.run_round(0)
            tr.run_round(1)
            tr.save(d)
            tr.run_round(2)
        tr2 = FederatedTrainer.resume(d, loss_fn, make_params(), NUM_CLIENTS,
                                      batch_fn, cfg(shard_clients=True),
                                      algo=algo)
        assert tr2.start_round == 2, tr2.start_round
        assert tr2.mesh.devices.shape == (8,)
        with tr2:
            tr2.run()
    assert_trees_close(tr.params, tr2.params)
    assert_trees_close(tr.server_state, tr2.server_state)
    print("[matrix] cross-mesh (2x4 -> 8) resume OK")


def check_codec_identity_bitwise():
    """codec=identity must be BITWISE identical to no-codec — the
    encode/decode hooks return the SAME arrays, so every regime's round
    math is untouched down to the last ulp (acceptance criterion)."""
    for regime in ("serial", "vectorized", "sharded2d", "async_buffer"):
        base = run_cell("feddpc", "uniform", regime, True)
        idt = run_cell("feddpc", "uniform", regime, True, codec="identity")
        for which, a, b in (("params", base.params, idt.params),
                            ("state", base.server_state, idt.server_state)):
            la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
            assert len(la) == len(lb)
            for x, y in zip(la, lb):
                assert np.array_equal(np.asarray(x), np.asarray(y),
                                      equal_nan=True), (regime, which)
        for rb, ri in zip(base.history, idt.history):
            assert rb.train_loss == ri.train_loss, regime
        print(f"[matrix] identity bitwise == no-codec under {regime} OK")


def check_kernel_fallback():
    """FedDPCHyper(use_kernel=True) on a partitioned round — the 1-D
    client mesh and the two-axis mesh — must fall back to the reference
    epilogue (a Mosaic kernel cannot be partitioned) and still match the
    serial reference."""
    ref = reference("feddpc", "uniform")
    for regime in ("sharded1d", "sharded2d"):
        tr = run_cell("feddpc", "uniform", regime, True, use_kernel=True)
        assert_trees_close(tr.params, ref.params)
        assert_trees_close(tr.server_state, ref.server_state)
        print(f"[matrix] use_kernel fallback under {regime} OK")


def main():
    assert len(jax.devices()) == 8, jax.devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="")
    ap.add_argument("--cross-mesh-resume", action="store_true")
    ap.add_argument("--kernel-fallback", action="store_true")
    ap.add_argument("--codec-identity-bitwise", action="store_true")
    args = ap.parse_args()
    for cell in [c for c in args.cells.split(",") if c]:
        check_cell(cell)
    if args.cross_mesh_resume:
        check_cross_mesh_resume()
    if args.kernel_fallback:
        check_kernel_fallback()
    if args.codec_identity_bitwise:
        check_codec_identity_bitwise()
    print("ALL OK")


if __name__ == "__main__":
    main()
