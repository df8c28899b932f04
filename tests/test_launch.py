"""Launch drivers: serve loop smoke, train CLI smoke, FL round step, and
the train-step microbatching equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.configs.shapes import InputShape
from repro.launch import steps as steps_mod
from repro.launch.serve import serve_encdec, serve_lm


def test_serve_lm_smoke():
    cfg = get_config("starcoder2-3b", smoke=True)
    tokens, stats = serve_lm(cfg, batch=2, prompt_len=8, gen=4)
    assert tokens.shape == (2, 4)
    assert not jnp.isnan(tokens).any()
    assert stats["tok_per_s"] > 0


def test_serve_ssm_smoke():
    cfg = get_config("falcon-mamba-7b", smoke=True)
    tokens, _ = serve_lm(cfg, batch=2, prompt_len=8, gen=4)
    assert tokens.shape == (2, 4)


def test_serve_encdec_smoke():
    cfg = get_config("whisper-base", smoke=True)
    tokens, _ = serve_encdec(cfg, batch=2, gen=4)
    assert tokens.shape == (2, 4)


@pytest.mark.slow
def test_train_cli_smoke(tmp_path, monkeypatch):
    from repro.launch.compile_cache import ENV_VAR
    from repro.launch.train import main
    # a cache placed from outside: main() then leaves JAX's (off) cache
    # setting alone, instead of caching this worker's later compiles
    # into the checkout
    monkeypatch.setenv(ENV_VAR, str(tmp_path / "jax_cache"))
    rc = main(["--model", "lenet5", "--rounds", "2", "--clients", "6",
               "--participation", "0.5", "--samples-per-class", "20",
               "--batch-size", "16", "--eval-every", "1",
               "--out", str(tmp_path / "hist.json")])
    assert rc == 0
    assert (tmp_path / "hist.json").exists()


@pytest.mark.parametrize("placed", [None, "/elsewhere/cache"])
def test_compile_cache_directory(placed, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to the checkout's fixed .jax_cache directory."""
    from pathlib import Path

    from repro.launch import compile_cache
    if placed is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, placed)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        now = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if placed is None:
        repo = Path(__file__).resolve().parents[1]
        assert got == now == str(repo / ".jax_cache")
    else:
        assert got == placed and now == before


def test_microbatched_train_step_equivalence(rng):
    """microbatches=4 must produce the same update as microbatches=1 when
    the loss is a mean over examples (linear in the batch split)."""
    cfg = get_config("starcoder2-3b", smoke=True)
    shape = InputShape("t", 16, 8, "train")
    step1 = steps_mod.make_train_step(cfg, lr=0.01, microbatches=1,
                                      remat="none")
    step4 = steps_mod.make_train_step(cfg, lr=0.01, microbatches=4,
                                      remat="none")
    from repro.models import transformer as tf
    params = tf.init_lm(cfg, rng, jnp.float32)
    toks = jax.random.randint(rng, (8, 17), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    p1, l1 = jax.jit(step1)(params, batch)
    p4, l4 = jax.jit(step4)(params, batch)
    # losses are means over valid tokens; equal-size microbatches with no
    # padding -> identical means
    assert np.isclose(float(l1), float(l4), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_fl_round_step_learns(rng):
    from repro.core.round import make_fl_round_step
    from repro.models import transformer as tf
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    loss_fn = lambda p, b: tf.loss_fn(cfg, p, b)
    step = jax.jit(make_fl_round_step(loss_fn, 0.05, 0.05))
    params = tf.init_lm(cfg, rng, jnp.float32)
    delta = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), params)
    toks = jax.random.randint(rng, (3, 2, 2, 17), 0, cfg.vocab_size)
    batches = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
    losses = []
    for _ in range(3):
        params, delta, m = step(params, delta, batches)
        losses.append(float(m["train_loss"]))
    assert losses[-1] < losses[0]
