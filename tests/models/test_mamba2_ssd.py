"""Mamba2 SSD: chunked algorithm vs sequential-recurrence oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ModelConfig
from repro.models.mamba2 import (
    init_ssm_state,
    mamba_init,
    ssd_forward,
    ssd_reference,
    ssm_decode_step,
)


def _cfg(chunk=8, state=16, head_dim=16, d_model=32):
    return ModelConfig(
        name="t", family="ssm", num_layers=1, d_model=d_model, vocab_size=64,
        ssm_state=state, ssm_head_dim=head_dim, ssm_chunk=chunk,
        param_dtype="float32", compute_dtype="float32",
    )


@pytest.mark.parametrize("S,chunk", [(24, 8), (32, 32), (16, 4), (64, 16)])
def test_ssd_equals_recurrence(rng, S, chunk):
    cfg = _cfg(chunk=chunk)
    p = mamba_init(jax.random.key(1), cfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, S, 32)) * 0.5, jnp.float32)
    y_ssd, _ = ssd_forward(cfg, p, x)
    y_ref = ssd_reference(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y_ssd), np.asarray(y_ref), atol=2e-3, rtol=1e-3)


def test_final_state_continues_generation(rng):
    """State after ssd_forward must equal state after stepping the prompt."""
    cfg = _cfg()
    p = mamba_init(jax.random.key(2), cfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((1, 16, 32)) * 0.5, jnp.float32)
    _, final = ssd_forward(cfg, p, x)
    state = init_ssm_state(cfg, 1)
    for t in range(16):
        _, state = ssm_decode_step(cfg, p, state, x[:, t : t + 1])
    np.testing.assert_allclose(
        np.asarray(final["h"]), np.asarray(state["h"]), atol=2e-3, rtol=1e-3
    )
    # conv window continues exactly as well
    np.testing.assert_allclose(
        np.asarray(final["conv"]), np.asarray(state["conv"]), atol=2e-3, rtol=1e-3
    )


def test_decay_bounds(rng):
    """A < 0 guarantees the recurrence is stable (decay in (0,1))."""
    cfg = _cfg()
    p = mamba_init(jax.random.key(3), cfg, jnp.float32)
    A = -jnp.exp(p["A_log"])
    assert bool((A < 0).all())


def test_conv_cache_consistency(rng):
    """Decode conv window must reproduce the causal conv of the full pass."""
    cfg = _cfg(chunk=4)
    p = mamba_init(jax.random.key(4), cfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((1, 8, 32)) * 0.5, jnp.float32)
    y_full, _ = ssd_forward(cfg, p, x)
    state = init_ssm_state(cfg, 1)
    ys = []
    for t in range(8):
        y, state = ssm_decode_step(cfg, p, state, x[:, t : t + 1])
        ys.append(y)
    y_steps = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_steps), atol=2e-3, rtol=1e-3)


def _long_decay(chunk, S):
    """A layer whose log-decay spans far more than 88 inside a chunk:
    dt_bias 1.0 makes dt about 1.3, so with A in [-16, -1] the unmasked
    upper triangle's exp(seg_t - seg_s) overflows f32."""
    cfg = _cfg(chunk=chunk)
    p = mamba_init(jax.random.key(5), cfg, jnp.float32)
    p["dt_bias"] = jnp.ones_like(p["dt_bias"])
    x = jax.random.normal(jax.random.key(6), (2, S, 32)) * 0.5
    w = jax.random.normal(jax.random.key(7), (2, S, 32))
    dt = jax.nn.softplus(x @ p["w_dt"] + p["dt_bias"]) * jnp.exp(p["A_log"])
    assert float(jnp.max(jnp.sum(dt.reshape(2, S // chunk, chunk, -1), axis=2))) > 88
    return cfg, p, x, w


def _grads(fn, cfg, p, x, w):
    return jax.jit(jax.grad(lambda p: jnp.sum(fn(cfg, p, x) * w)))(p)


@pytest.mark.parametrize("check,chunk,S", [("finite", 64, 128),
                                           ("equals_recurrence", 16, 48)])
def test_ssd_gradient_past_a_long_decay_span(check, chunk, S):
    cfg, p, x, w = _long_decay(chunk, S)
    g = _grads(lambda c, q, u: ssd_forward(c, q, u)[0], cfg, p, x, w)
    if check == "finite":
        for name, leaf in g.items():
            assert bool(jnp.all(jnp.isfinite(leaf))), name
        return
    want = _grads(ssd_reference, cfg, p, x, w)
    for name in g:
        scale = float(jnp.max(jnp.abs(want[name]))) + 1e-6
        np.testing.assert_allclose(np.asarray(g[name]), np.asarray(want[name]),
                                   atol=2e-3 * scale, rtol=2e-3, err_msg=name)


def test_zamba2_hybrid_gradients_are_finite_past_a_long_decay_span():
    from repro.configs import get_config
    from repro.models.zoo import build

    cfg = get_config("zamba2-1.2b", smoke=True).with_overrides(ssm_chunk=64)
    model = build(cfg)
    params = model.init(jax.random.key(0))
    params["blocks"]["ssm"]["dt_bias"] = jnp.ones_like(params["blocks"]["ssm"]["dt_bias"])
    toks = jax.random.randint(jax.random.key(1), (2, 65), 0, cfg.vocab_size)
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    g = jax.jit(jax.grad(lambda p: model.loss(p, batch)[0]))(params)
    for path, leaf in jax.tree_util.tree_leaves_with_path(g):
        assert bool(jnp.all(jnp.isfinite(leaf))), jax.tree_util.keystr(path)


def test_ssd_scope_names_forward_and_backward_operations():
    """The device trace tells the SSD's operations apart by their op name."""
    cfg, p, x, w = _long_decay(16, 32)
    fwd = jax.jit(lambda p: ssd_forward(cfg, p, x)[0]).lower(p).as_text(debug_info=True)
    bwd = jax.jit(jax.grad(lambda p: jnp.sum(ssd_forward(cfg, p, x)[0] * w))).lower(
        p).as_text(debug_info=True)
    assert "/ssd/" in fwd
    assert "transpose(jvp(ssd))" in bwd


@pytest.mark.parametrize("arch", ["mamba2-130m", "zamba2-1.2b"])
def test_residual_stream_is_carried_in_float32(arch):
    """bf16 parameters, f32 residual (``residual_in_fp32``): every layer
    scan carries an f32 stream, and the head gets the parameters' dtype."""
    from repro.configs import get_config
    from repro.models import hybrid
    from repro.models.zoo import build

    cfg = get_config(arch, smoke=True).with_overrides(param_dtype="bfloat16")
    params = jax.eval_shape(build(cfg).init, jax.random.key(0))
    toks = jax.ShapeDtypeStruct((2, cfg.ssm_chunk), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, t: hybrid.hidden_forward(cfg, p, t))(params, toks)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert scans
    for e in scans:
        carry = e.outvars[: e.params["num_carry"]]
        assert [v.aval.dtype for v in carry] == [jnp.float32], arch
    h, _ = jax.eval_shape(lambda p, t: hybrid.hidden_forward(cfg, p, t), params, toks)
    assert h.dtype == jnp.bfloat16
