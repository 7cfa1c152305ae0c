"""Optimizers, schedules, ZeRO-1 specs, int8 gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.optim import adagrad, adamw, sgd, clip_by_global_norm, cosine_schedule
from repro.optim.compression import (
    compressed_grad_transform,
    init_error_feedback,
    int8_compress,
    int8_decompress,
)
from repro.optim.optimizers import moment_specs, zero1_specs


def test_sgd_momentum_reference():
    opt = sgd(momentum=0.9)
    p = {"w": jnp.asarray([1.0, 2.0])}
    s = opt.init(p)
    g = {"w": jnp.asarray([0.5, -0.5])}
    p1, s1 = opt.update(g, s, p, 0.1)
    np.testing.assert_allclose(np.asarray(p1["w"]), [0.95, 2.05])
    p2, _ = opt.update(g, s1, p1, 0.1)
    # m = 0.9*0.5 + 0.5 = 0.95
    np.testing.assert_allclose(np.asarray(p2["w"]), [0.95 - 0.095, 2.05 + 0.095])


def test_adamw_matches_manual():
    opt = adamw(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0)
    p = {"w": jnp.asarray([1.0])}
    s = opt.init(p)
    g = {"w": jnp.asarray([0.1])}
    p1, s1 = opt.update(g, s, p, 0.01)
    # bias-corrected first step: update = g/|g| -> p - lr
    np.testing.assert_allclose(np.asarray(p1["w"]), [1.0 - 0.01], rtol=1e-4)


def test_adamw_weight_decay_decoupled():
    opt = adamw(weight_decay=0.1)
    p = {"w": jnp.asarray([2.0])}
    s = opt.init(p)
    g = {"w": jnp.asarray([0.0])}
    p1, _ = opt.update(g, s, p, 0.01)
    np.testing.assert_allclose(np.asarray(p1["w"]), [2.0 - 0.01 * 0.1 * 2.0], rtol=1e-5)


def test_clip_by_global_norm():
    g = {"a": jnp.asarray([3.0]), "b": jnp.asarray([4.0])}
    clipped, norm = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(norm), 5.0)
    total = np.sqrt(sum(float((x**2).sum()) for x in jax.tree.leaves(clipped)))
    np.testing.assert_allclose(total, 1.0, rtol=1e-5)


def test_cosine_schedule_shape():
    lr = cosine_schedule(1e-3, warmup=10, total=100)
    assert float(lr(0)) == 0.0
    np.testing.assert_allclose(float(lr(10)), 1e-3, rtol=1e-5)
    assert float(lr(100)) < 1.3e-4
    assert float(lr(5)) == pytest.approx(5e-4)


def test_zero1_specs_extend_over_data():
    specs = {"w": P(None, "model"), "e": P("data", None, "model")}
    shapes = {"w": jax.ShapeDtypeStruct((64, 32), jnp.float32),
              "e": jax.ShapeDtypeStruct((16, 8, 4), jnp.float32)}
    z = zero1_specs(specs, shapes, dp_axis="data", dp_size=16)
    assert z["w"] == P("data", "model")  # largest free dim gets dp
    assert z["e"] == P("data", None, "model")  # already uses data: untouched


def test_moment_specs_structure():
    specs = {"w": P(None, "model")}
    shapes = {"w": jax.ShapeDtypeStruct((64, 32), jnp.float32)}
    m = moment_specs("adamw", specs, shapes, dp_size=16)
    assert set(m) == {"m", "v", "t"}
    assert m["t"] == P()


@given(st.integers(0, 2**32 - 1))
@settings(max_examples=20, deadline=None)
def test_int8_roundtrip_bounded(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, rng.uniform(0.01, 10), 257).astype(np.float32))
    q, scale = int8_compress(x)
    back = int8_decompress(q, scale)
    err = float(jnp.abs(back - x).max())
    assert err <= float(scale) * 0.5 + 1e-9  # half-ULP of the int8 grid


def test_error_feedback_removes_bias():
    """Constant gradient: with error feedback the AVERAGE applied gradient
    converges to the true one even when a single step misquantizes."""
    g = {"w": jnp.full((64,), 0.31)}
    err = init_error_feedback(g)
    applied = []
    for _ in range(50):
        dq, err = compressed_grad_transform(g, err)
        applied.append(np.asarray(dq["w"]))
    mean = np.mean(applied, axis=0)
    np.testing.assert_allclose(mean, 0.31, rtol=1e-3)


def test_compression_preserves_convergence():
    """SGD on a quadratic with int8+EF reaches the optimum."""
    w = jnp.asarray([5.0, -3.0, 2.0])
    target = jnp.asarray([1.0, 1.0, 1.0])
    err = init_error_feedback({"w": w})
    for _ in range(300):
        g = {"w": w - target}
        dq, err = compressed_grad_transform(g, err)
        w = w - 0.1 * dq["w"]
    np.testing.assert_allclose(np.asarray(w), np.asarray(target), atol=1e-2)


def test_adagrad_matches_hand_update():
    """a += g**2; p -= lr * g / (sqrt(a) + eps), element-wise on every leaf,
    from the initial accumulator; a zero gradient leaves its element."""
    opt = adagrad(eps=1e-3, initial_accumulator=0.5)
    params = {"w": jnp.array([[1.0, -2.0], [0.5, 3.0]]), "b": jnp.array([0.1, 0.2, 0.3])}
    grads = [{"w": jnp.array([[0.3, 0.0], [-1.0, 2.0]]), "b": jnp.array([1.0, -0.5, 0.0])},
             {"w": jnp.array([[-0.2, 0.4], [0.1, 0.0]]), "b": jnp.array([0.0, 0.5, 2.0])}]
    state = opt.init(params)
    p_np = {k: np.asarray(v, np.float64) for k, v in params.items()}
    a_np = {k: np.full(v.shape, 0.5) for k, v in p_np.items()}
    for g in grads:
        params, state = opt.update(g, state, params, 0.1)
        for k in p_np:
            gk = np.asarray(g[k], np.float64)
            a_np[k] += gk * gk
            p_np[k] -= 0.1 * gk / (np.sqrt(a_np[k]) + 1e-3)
    for k in p_np:
        np.testing.assert_allclose(np.asarray(params[k]), p_np[k], rtol=1e-6)
        np.testing.assert_allclose(np.asarray(state["a"][k]), a_np[k], rtol=1e-6)
