"""Chunked CE vs dense oracle; AdamW/schedules/clipping; int8 error-feedback
compression convergence."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.nn.losses import chunked_softmax_xent, softmax_xent_dense
from repro.optim.adamw import (AdamWConfig, adamw_init, adamw_update,
                               bias_correction, clip_by_global_norm,
                               cosine_schedule, global_norm, linear_warmup)
from repro.optim.compression import (compress_decompress, ef_init,
                                     quantize_int8, dequantize_int8)


@pytest.mark.parametrize("softcap", [None, 25.0])
@pytest.mark.parametrize("z_loss", [0.0, 1e-3])
@pytest.mark.parametrize("chunk", [5, 8, 24])
def test_chunked_ce_matches_dense(softcap, z_loss, chunk):
    B, S, d, V = 3, 24, 16, 50
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d, V)) * 0.2
    y = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, V)
    mask = (jax.random.uniform(jax.random.PRNGKey(3), (B, S)) > 0.25
            ).astype(jnp.float32)
    l1, m1 = softmax_xent_dense(x, w, y, mask=mask, z_loss=z_loss,
                                logit_softcap=softcap)
    l2, m2 = chunked_softmax_xent(x, w, y, mask=mask, chunk=chunk,
                                  z_loss=z_loss, logit_softcap=softcap)
    np.testing.assert_allclose(l1, l2, atol=1e-5)
    np.testing.assert_allclose(m1["accuracy"], m2["accuracy"], atol=1e-6)
    g1 = jax.grad(lambda x, w: softmax_xent_dense(
        x, w, y, mask=mask, z_loss=z_loss, logit_softcap=softcap)[0],
        argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda x, w: chunked_softmax_xent(
        x, w, y, mask=mask, chunk=chunk, z_loss=z_loss,
        logit_softcap=softcap)[0], argnums=(0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_adamw_converges_quadratic():
    """AdamW must drive a quadratic bowl to ~0."""
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, grad_clip=None)
    state = adamw_init(params)
    for _ in range(300):
        g = jax.grad(lambda p: ((p["w"] - target) ** 2).sum())(params)
        params, state, _ = adamw_update(cfg, g, state, params)
    np.testing.assert_allclose(params["w"], target, atol=1e-2)


def test_weight_decay_matrices_only():
    params = {"w": jnp.ones((2, 2)), "b": jnp.ones((2,))}
    zeros = jax.tree.map(jnp.zeros_like, params)
    cfg = AdamWConfig(lr=0.1, weight_decay=0.5, grad_clip=None)
    state = adamw_init(params)
    new, _, _ = adamw_update(cfg, zeros, state, params)
    assert float(jnp.abs(new["w"] - 1.0).max()) > 1e-3   # decayed
    np.testing.assert_allclose(new["b"], params["b"])     # not decayed


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, gn = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(global_norm(clipped), 1.0, atol=1e-5)
    assert float(gn) == pytest.approx(20.0)


def test_schedules():
    w = linear_warmup(10)
    assert float(w(jnp.int32(5))) == pytest.approx(0.5)
    c = cosine_schedule(10, 100, final_frac=0.1)
    assert float(c(jnp.int32(5))) == pytest.approx(0.5)
    assert float(c(jnp.int32(100))) == pytest.approx(0.1, abs=1e-3)
    assert float(c(jnp.int32(10))) == pytest.approx(1.0, abs=1e-2)


def test_int8_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(0), (128,)) * 3
    q, s = quantize_int8(x)
    err = dequantize_int8(q, s) - x
    assert float(jnp.abs(err).max()) <= float(s) * 0.51 + 1e-6


def test_error_feedback_convergence():
    """SGD + int8 EF compression still converges on a quadratic bowl —
    the residual accumulator corrects quantization bias over steps."""
    target = jnp.asarray([0.3, -1.7, 2.2, 0.01])
    w = jnp.zeros(4)
    e = jnp.zeros(4)
    for _ in range(400):
        g = 2 * (w - target)
        g_hat, e = compress_decompress(g, e)
        w = w - 0.05 * g_hat
    np.testing.assert_allclose(w, target, atol=5e-2)


def test_error_feedback_beats_plain_quantization():
    target = jnp.asarray([1e-3, 2e-3, -1e-3, 5.0])  # tiny + large components
    def run(use_ef):
        w = jnp.zeros(4)
        e = jnp.zeros(4)
        for _ in range(300):
            g = 2 * (w - target)
            if use_ef:
                g_hat, e = compress_decompress(g, e)
            else:
                q, s = quantize_int8(g)
                g_hat = dequantize_int8(q, s)
            w = w - 0.05 * g_hat
        return float(jnp.abs(w - target).max())
    assert run(True) <= run(False) + 1e-6


@pytest.mark.parametrize("beta", [0.0, 0.9, 0.95, 0.999, 0.9999])
def test_bias_correction_to_float32_precision(beta):
    """1 - beta**t against double precision over the first thousand steps:
    formed directly in float32 it cancels for beta near 1."""
    steps = np.arange(1, 1001)
    got = np.asarray(jax.jit(lambda t: bias_correction(beta, t))(
        jnp.asarray(steps, jnp.int32)), np.float64)
    want = 1.0 - beta ** steps.astype(np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-6)
