"""The benchmark's float32 reference against the program's, at a tiny size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.arch import dense_gqa as arch
from chipbench.bench import program_config
from chipbench_tiny import tiny_cell

CELL = tiny_cell("qwen3-32b-serve.chat")


@pytest.fixture(scope="module")
def model_and_params():
    from repro.models.model import build_model
    cfg = program_config(CELL.config)
    model = build_model(cfg, remat="none")
    params = arch.make_params(model.abstract_params(jnp.float32), 123,
                              jnp.float32)
    return cfg, model, params


def test_weights_are_seeded_and_scaled(model_and_params):
    cfg, model, params = model_and_params
    again = arch.make_params(model.abstract_params(jnp.float32), 123,
                             jnp.float32)
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(params), jax.tree.leaves(again)))
    wq = np.asarray(params["layers"]["attn"]["wq"])
    assert abs(wq.std() * np.sqrt(cfg.d_model) - 1) < 0.1
    assert np.all(np.asarray(params["layers"]["ln1"]["scale"]) == 1)


def test_logits_match_the_programs_reference(model_and_params):
    from repro.models.reference import dense_logits_f32
    cfg, _, params = model_and_params
    toks = np.random.default_rng(0).integers(2, cfg.vocab_size, 40)
    rows = list(range(10, 40))
    mine = arch.logits(params, CELL.config, toks, rows, q_block=16)
    theirs = dense_logits_f32(params, cfg, toks, rows, q_block=16)
    np.testing.assert_allclose(mine, theirs, rtol=1e-4, atol=1e-4)


def test_control_is_a_lower_precision(model_and_params):
    cfg, _, params = model_and_params
    toks = np.random.default_rng(1).integers(2, cfg.vocab_size, 24)
    ref = arch.logits(params, CELL.config, toks, range(24))
    low = arch.logits(params, CELL.config, toks, range(24), low=True)
    err = np.sqrt(np.mean((low - ref) ** 2) / np.mean(ref ** 2))
    assert 1e-3 < err < 0.2


def test_loss_matches_the_programs_loss(model_and_params):
    cfg, model, params = model_and_params
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(2, cfg.vocab_size, (1, 32)), jnp.int32)
    labels = jnp.asarray(rng.integers(2, cfg.vocab_size, (1, 32)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        mine = float(arch.loss(params, tokens, labels,
                               arch.Spec(CELL.config), q_block=16))
    theirs, _ = model.loss(params, {"tokens": tokens, "labels": labels})
    assert abs(mine - float(theirs)) < 0.02 * mine       # bf16 compute


def test_control_gradient_is_the_gradient_in_fp8(model_and_params):
    """The control's backward carries the gradient (rounded, not lost):
    every leaf's norm stays near the reference's and the direction
    agrees, while the values differ by more than float32 round-off."""
    cfg, _, params = model_and_params
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(2, cfg.vocab_size, (1, 32)), jnp.int32)
    labels = jnp.asarray(rng.integers(2, cfg.vocab_size, (1, 32)), jnp.int32)
    spec = arch.Spec(CELL.config)
    with jax.default_matmul_precision("highest"):
        ref = jax.grad(arch.loss)(params, tokens, labels, spec, False, 16)
        low = jax.grad(arch.loss)(params, tokens, labels, spec, True, 16)
    for g, c in zip(jax.tree.leaves(ref), jax.tree.leaves(low)):
        g, c = np.asarray(g).ravel(), np.asarray(c).ravel()
        if not np.any(g):
            continue
        cos = g @ c / (np.linalg.norm(g) * np.linalg.norm(c))
        assert 0.9 < cos < 1 - 1e-7, cos
        assert 0.8 < np.linalg.norm(c) / np.linalg.norm(g) < 1.25
