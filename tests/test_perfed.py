"""Eq. (5)/(7) meta-gradient correctness against the autodiff oracle."""
import jax
import jax.numpy as jnp
import pytest

from repro.config import ModelConfig
from repro.core import perfed
from repro.models import build_model
from repro.utils import tree_norm, tree_sub


def _quadratic_model():
    """f(w; x, y) = mean((x·w1 + b − y)^2) — analytically tractable."""
    class M:
        def init(self, rng):
            k1, k2 = jax.random.split(rng)
            return {"w": jax.random.normal(k1, (5, 3)),
                    "b": jax.random.normal(k2, (3,))}

        def loss(self, params, batch, rng=None):
            pred = batch["x"] @ params["w"] + params["b"]
            return jnp.mean(jnp.square(pred - batch["y"])), {}
    return M()


@pytest.fixture
def setup():
    model = _quadratic_model()
    rng = jax.random.PRNGKey(1)
    params = model.init(rng)
    kx, ky = jax.random.split(rng)
    batch = {"x": jax.random.normal(kx, (32, 5)),
             "y": jax.random.normal(ky, (32, 3))}
    return model, params, batch


def test_perfed_grad_matches_autodiff_oracle(setup):
    """With identical D_in = D_o = D_h, Eq. (7) must equal d/dw f(w−α∇f(w))."""
    model, params, batch = setup
    alpha = 0.05
    batches = {"inner": batch, "outer": batch, "hessian": batch}
    got = perfed.perfed_grad(model.loss, params, batches, alpha)
    want = perfed.perfed_grad_exact(model.loss, params, batch, alpha)
    err = float(tree_norm(tree_sub(got, want)) / tree_norm(want))
    assert err < 1e-5, err


def test_perfed_grad_on_neural_model():
    """Same identity through a real nonconvex model (2-layer DNN)."""
    cfg = ModelConfig(name="mnist_dnn", family="small", d_model=16,
                      vocab_size=10, dtype="float32")
    model = build_model(cfg)
    rng = jax.random.PRNGKey(2)
    params = model.init(rng)
    kx, ky = jax.random.split(jax.random.fold_in(rng, 1))
    batch = {"x": jax.random.normal(kx, (8, 28, 28)),
             "y": jax.random.randint(ky, (8,), 0, 10)}
    batches = {"inner": batch, "outer": batch, "hessian": batch}
    got = perfed.perfed_grad(model.loss, params, batches, 0.03)
    want = perfed.perfed_grad_exact(model.loss, params, batch, 0.03)
    err = float(tree_norm(tree_sub(got, want)) / tree_norm(want))
    assert err < 1e-4, err


def test_first_order_drops_hessian(setup):
    model, params, batch = setup
    batches = {"inner": batch, "outer": batch, "hessian": batch}
    fo = perfed.perfed_grad(model.loss, params, batches, 0.05,
                            first_order=True)
    w_ad = perfed.adapt(model.loss, params, batch, 0.05)
    want = jax.grad(lambda p: model.loss(p, batch)[0])(w_ad)
    err = float(tree_norm(tree_sub(fo, want)))
    assert err < 1e-6

    full = perfed.perfed_grad(model.loss, params, batches, 0.05)
    assert float(tree_norm(tree_sub(full, fo))) > 1e-4  # Hessian term matters


def test_adapt_reduces_loss(setup):
    model, params, batch = setup
    l0 = float(model.loss(params, batch)[0])
    adapted = perfed.adapt(model.loss, params, batch, 0.05)
    l1 = float(model.loss(adapted, batch)[0])
    assert l1 < l0


def test_perfed_loss_value(setup):
    model, params, batch = setup
    batches = {"inner": batch, "outer": batch}
    got = float(perfed.perfed_loss(model.loss, params, batches, 0.05))
    adapted = perfed.adapt(model.loss, params, batch, 0.05)
    want = float(model.loss(adapted, batch)[0])
    assert abs(got - want) < 1e-6


@pytest.mark.parametrize("first_order", [False, True])
def test_perfed_value_and_grad(setup, first_order):
    """One Eq.-(7) pass gives F̃ and ∇̃F: the gradient is ``perfed_grad``'s
    and the oracle's, the value is ``perfed_loss``'s on distinct batches."""
    model, params, batch = setup
    alpha = 0.05
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    distinct = {role: {"x": jax.random.normal(keys[2 * i], (32, 5)),
                       "y": jax.random.normal(keys[2 * i + 1], (32, 3))}
                for i, role in enumerate(("inner", "outer", "hessian"))}
    value, grad = perfed.perfed_value_and_grad(
        model.loss, params, distinct, alpha, first_order=first_order)
    want = perfed.perfed_grad(model.loss, params, distinct, alpha,
                              first_order=first_order)
    assert float(tree_norm(tree_sub(grad, want))) == 0.0
    loss = perfed.perfed_loss(model.loss, params, distinct, alpha)
    assert abs(float(value) - float(loss)) < 1e-6

    same = {"inner": batch, "outer": batch, "hessian": batch}
    _, grad = perfed.perfed_value_and_grad(
        model.loss, params, same, alpha, first_order=first_order)
    if first_order:
        w_ad = perfed.adapt(model.loss, params, batch, alpha)
        oracle = jax.grad(lambda p: model.loss(p, batch)[0])(w_ad)
    else:
        oracle = perfed.perfed_grad_exact(model.loss, params, batch, alpha)
    err = float(tree_norm(tree_sub(grad, oracle)) / tree_norm(oracle))
    assert err < 1e-5, err


def test_alpha_zero_recovers_plain_gradient(setup):
    model, params, batch = setup
    batches = {"inner": batch, "outer": batch, "hessian": batch}
    got = perfed.perfed_grad(model.loss, params, batches, 0.0)
    want = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    assert float(tree_norm(tree_sub(got, want))) < 1e-6
