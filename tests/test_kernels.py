"""Pallas kernels vs dense oracles (interpret mode on the CPU mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels import flash_attention, fused_layernorm
from deeplearning4j_tpu.parallel.ring_attention import dense_attention


def _qkv(b=2, h=2, t=48, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, h, t, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal, 16, 16)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_ragged_blocks():
    # T=50 not a multiple of the 16-wide blocks: exercises padding+mask
    q, k, v = _qkv(t=50)
    out = flash_attention(q, k, v, True, 16, 16)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_grad_matches_dense_grad():
    q, k, v = _qkv(t=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 16, 16) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_flash_bf16_runs():
    q, k, v = _qkv(t=32)
    out = flash_attention(*(x.astype(jnp.bfloat16) for x in (q, k, v)))
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


def _ln_ref(x, g, b, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def test_layernorm_matches_ref():
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (3, 7, 24), jnp.float32)
    g = jnp.linspace(0.5, 1.5, 24)
    b = jnp.linspace(-1.0, 1.0, 24)
    out = fused_layernorm(x, g, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ln_ref(x, g, b)),
                               atol=1e-5, rtol=1e-5)


def test_layernorm_grads():
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (5, 16), jnp.float32)
    g = jnp.ones(16) * 1.3
    b = jnp.zeros(16)

    def loss_fused(x, g, b):
        return jnp.sum(jnp.sin(fused_layernorm(x, g, b)))

    def loss_ref(x, g, b):
        return jnp.sum(jnp.sin(_ln_ref(x, g, b)))

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x, g, b)
    for a, bb in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=1e-5, rtol=1e-4)


def test_flash_under_jit():
    q, k, v = _qkv(t=32)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, True, 16, 16))
    out = f(q, k, v)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_kernel_noncausal_and_causal(causal):
    """The round-2 Pallas backward (dQ + dK/dV kernels) vs dense VJP,
    with an asymmetric cotangent so dq/dk/dv are all nontrivial."""
    q, k, v = _qkv(t=48, seed=3)
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)

    _, vjp_f = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, causal, 16, 16), q, k, v)
    _, vjp_d = jax.vjp(
        lambda q, k, v: dense_attention(q, k, v, causal=causal), q, k, v)
    for a, b, name in zip(vjp_f(g), vjp_d(g), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4, err_msg=name)


def test_flash_bwd_ragged_T():
    """T not a multiple of the block: padded rows/cols must contribute
    ZERO gradient (padding bugs show up here)."""
    q, k, v = _qkv(t=50, seed=4)
    g = jax.random.normal(jax.random.PRNGKey(10), q.shape, jnp.float32)
    _, vjp_f = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, True, 16, 16), q, k, v)
    _, vjp_d = jax.vjp(
        lambda q, k, v: dense_attention(q, k, v, causal=True), q, k, v)
    for a, b, name in zip(vjp_f(g), vjp_d(g), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4, err_msg=name)


def test_flash_bwd_bf16():
    q, k, v = _qkv(t=32, seed=5)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, False, 16, 16)
                       .astype(jnp.float32) ** 2)

    gb = jax.grad(loss, argnums=(0, 1, 2))(qb, kb, vb)
    gd = jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gb, gd):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   atol=0.15, rtol=0.15)


def test_flash_bwd_under_jit_grad_of_mean():
    """Whole train-step shape: jit(grad(scalar loss over flash attn))."""
    q, k, v = _qkv(t=32, seed=6)

    @jax.jit
    def gradfn(q, k, v):
        return jax.grad(
            lambda q, k, v: jnp.mean(
                flash_attention(q, k, v, True, 16, 16)),
            argnums=(0, 1, 2))(q, k, v)

    gf = gradfn(q, k, v)
    gd = jax.grad(
        lambda q, k, v: jnp.mean(dense_attention(q, k, v, causal=True)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-4)


# ---------------------------------------------------------------------------
# masked flash attention (round-3: per-example padding masks in the kernels)
# ---------------------------------------------------------------------------
def _dense_masked(q, k, v, mask, causal=False):
    """Oracle: dense masked attention; padded QUERY rows zeroed (the masked
    flash contract)."""
    t = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) \
        / (q.shape[-1] ** 0.5)
    m = mask[:, None, None, :] > 0
    if causal:
        m = m & (jnp.arange(t)[:, None] >= jnp.arange(t)[None, :])[None, None]
    s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return jnp.where(mask[:, None, :, None] > 0, o, 0.0).astype(q.dtype)


def _length_mask(t, lengths):
    return (jnp.arange(t)[None, :] < jnp.asarray(lengths)[:, None]) \
        .astype(jnp.int32)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_masked_fwd_matches_dense(causal):
    q, k, v = _qkv(t=48)
    mask = _length_mask(48, [31, 48])
    out = flash_attention(q, k, v, causal, 16, 16, mask=mask)
    ref = _dense_masked(q, k, v, mask, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_masked_random_mask():
    # arbitrary (non-contiguous) validity pattern, T not block-aligned
    q, k, v = _qkv(t=40)
    mask = jax.random.bernoulli(jax.random.PRNGKey(7), 0.7, (2, 40)) \
        .astype(jnp.int32)
    out = flash_attention(q, k, v, False, 16, 16, mask=mask)
    ref = _dense_masked(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_masked_grads_match_dense(causal):
    q, k, v = _qkv(t=32)
    mask = _length_mask(32, [21, 32])

    def lf(q, k, v):
        return jnp.sum(jnp.sin(
            flash_attention(q, k, v, causal, 16, 16, mask=mask)))

    def ld(q, k, v):
        return jnp.sum(jnp.sin(_dense_masked(q, k, v, mask, causal=causal)))

    gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(ld, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_flash_masked_no_grad_leak_to_padding():
    # gradients w.r.t. padded positions of q/k/v must be exactly zero
    q, k, v = _qkv(t=24)
    mask = _length_mask(24, [13, 24])

    def lf(q, k, v):
        return jnp.sum(flash_attention(q, k, v, False, 16, 16, mask=mask) ** 2)

    gq, gk, gv = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    pad = np.asarray(mask) == 0
    for g in (gq, gk, gv):
        assert np.all(np.asarray(g)[pad[:, None, :, None]
                                    .repeat(2, 1).repeat(16, 3)] == 0)


def test_flash_masked_under_jit():
    q, k, v = _qkv(t=32)
    mask = _length_mask(32, [20, 30])

    @jax.jit
    def f(q, k, v, mask):
        return flash_attention(q, k, v, mask=mask)

    out = f(q, k, v, mask)
    ref = _dense_masked(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# fused 1x1-conv + BatchNorm kernels (kernels/pointwise_conv.py)
# ---------------------------------------------------------------------------
def _bn_ref(y, gamma, beta, eps):
    yf = y.astype(jnp.float32)
    mu = jnp.mean(yf, axis=0)
    var = jnp.mean(yf * yf, axis=0) - mu * mu
    r = jax.lax.rsqrt(var + eps)
    return ((yf - mu) * r * gamma + beta).astype(y.dtype), mu, var


def _fused_ref(x, w, gamma, beta, eps, act):
    y = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
    z, mu, var = _bn_ref(y, gamma, beta, eps)
    if act == "relu":
        z = jnp.maximum(z, 0)
    return z, mu, var


@pytest.mark.parametrize("act", ["identity", "relu"])
@pytest.mark.parametrize("m", [256, 250])  # exact block and ragged-pad M
def test_fused_conv1x1_bn_forward(act, m):
    from deeplearning4j_tpu.kernels.pointwise_conv import fused_conv1x1_bn
    k, n = 16, 24
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32) * 0.2
    gamma = jax.random.uniform(kg, (n,), jnp.float32, 0.5, 1.5)
    beta = jnp.linspace(-1, 1, n)
    z, mu, var = fused_conv1x1_bn(x, w, gamma, beta, 1e-5, act, True)
    zr, mur, varr = _fused_ref(x, w, gamma, beta, 1e-5, act)
    np.testing.assert_allclose(np.asarray(mu), np.asarray(mur),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(var), np.asarray(varr),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(z), np.asarray(zr),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("act", ["identity", "relu"])
def test_fused_conv1x1_bn_grads_match_unfused(act):
    from deeplearning4j_tpu.kernels.pointwise_conv import fused_conv1x1_bn
    m, k, n = 250, 8, 12
    kx, kw, kg, kt = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32) * 0.3
    gamma = jax.random.uniform(kg, (n,), jnp.float32, 0.5, 1.5)
    beta = jnp.linspace(-0.5, 0.5, n)
    t = jax.random.normal(kt, (m, n), jnp.float32)

    def loss_fused(x, w, g, b):
        z, _, _ = fused_conv1x1_bn(x, w, g, b, 1e-5, act, True)
        return jnp.sum(z * t)

    def loss_ref(x, w, g, b):
        z, _, _ = _fused_ref(x, w, g, b, 1e-5, act)
        return jnp.sum(z * t)

    gf = jax.grad(loss_fused, (0, 1, 2, 3))(x, w, gamma, beta)
    gr = jax.grad(loss_ref, (0, 1, 2, 3))(x, w, gamma, beta)
    for a, b_, name in zip(gf, gr, "x w gamma beta".split()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-3, rtol=2e-3, err_msg=name)


def test_fused_conv1x1_bn_bf16():
    from deeplearning4j_tpu.kernels.pointwise_conv import fused_conv1x1_bn
    m, k, n = 128, 8, 16
    x = jax.random.normal(jax.random.PRNGKey(2), (m, k), jnp.bfloat16)
    w = (jax.random.normal(jax.random.PRNGKey(3), (k, n), jnp.float32)
         * 0.2).astype(jnp.bfloat16)
    gamma = jnp.ones((n,), jnp.float32)
    beta = jnp.zeros((n,), jnp.float32)
    z, mu, var = fused_conv1x1_bn(x, w, gamma, beta, 1e-5, "relu", True)
    assert z.dtype == jnp.bfloat16
    zr, _, _ = _fused_ref(x, w, gamma, beta, 1e-5, "relu")
    np.testing.assert_allclose(np.asarray(z, np.float32),
                               np.asarray(zr, np.float32), atol=0.1)


# -- cross-length (Tq != Tk) flash attention (VERDICT r3 #8) ----------------
def _qkv_cross(b=2, h=2, tq=24, tk=56, d=16, seed=3):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (b, h, tq, d), jnp.float32),
            jax.random.normal(kk, (b, h, tk, d), jnp.float32),
            jax.random.normal(kv, (b, h, tk, d), jnp.float32))


def _dense_cross(q, k, v, kv_mask=None, q_mask=None):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (d ** 0.5)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :] > 0, s, -1e30)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    if q_mask is not None:
        o = jnp.where(q_mask[:, None, :, None] > 0, o, 0.0)
    return o


def test_flash_cross_length_matches_dense():
    q, k, v = _qkv_cross()
    out = flash_attention(q, k, v, False, 16, 16)
    ref = _dense_cross(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_cross_length_kv_mask():
    q, k, v = _qkv_cross(tq=20, tk=44)
    kv_mask = _length_mask(44, [29, 44])
    out = flash_attention(q, k, v, False, 16, 16, kv_mask=kv_mask)
    ref = _dense_cross(q, k, v, kv_mask=kv_mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_cross_length_both_masks():
    q, k, v = _qkv_cross(tq=28, tk=36)
    q_mask = _length_mask(28, [19, 28])
    kv_mask = _length_mask(36, [25, 36])
    out = flash_attention(q, k, v, False, 16, 16, mask=q_mask,
                          kv_mask=kv_mask)
    ref = _dense_cross(q, k, v, kv_mask=kv_mask, q_mask=q_mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_cross_length_grads_match_dense():
    q, k, v = _qkv_cross(tq=16, tk=40)
    kv_mask = _length_mask(40, [27, 40])

    def lf(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, False, 16, 16, kv_mask=kv_mask)))

    def ld(q, k, v):
        return jnp.sum(jnp.sin(_dense_cross(q, k, v, kv_mask=kv_mask)))

    gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(ld, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_flash_cross_length_no_grad_leak_to_padded_keys():
    q, k, v = _qkv_cross(tq=16, tk=32)
    kv_mask = _length_mask(32, [17, 32])

    def lf(q, k, v):
        return jnp.sum(flash_attention(q, k, v, False, 16, 16,
                                       kv_mask=kv_mask) ** 2)

    _, gk, gv = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    pad = np.asarray(kv_mask) == 0
    for g in (gk, gv):
        assert np.all(np.asarray(g)[pad[:, None, :, None]
                                    .repeat(2, 1).repeat(16, 3)] == 0)


def test_flash_cross_length_validation():
    q, k, v = _qkv_cross(tq=16, tk=32)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, True, 16, 16)
    with pytest.raises(ValueError, match="cross-attention"):
        flash_attention(q, k, v, False, 16, 16,
                        mask=jnp.ones((2, 16), jnp.int32))
    with pytest.raises(ValueError, match="kv_mask length"):
        flash_attention(q, k, v, False, 16, 16,
                        kv_mask=jnp.ones((2, 16), jnp.int32))


def test_flash_cross_length_under_jit():
    q, k, v = _qkv_cross(tq=24, tk=48)
    kv_mask = _length_mask(48, [31, 48])

    @jax.jit
    def f(q, k, v, m):
        return flash_attention(q, k, v, False, 16, 16, kv_mask=m)

    out = f(q, k, v, kv_mask)
    ref = _dense_cross(q, k, v, kv_mask=kv_mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_cross_length_all_padded_kv_example():
    """An example with NO valid keys: zeroed outputs, zero grads — no
    leak into fully-padded K/V."""
    q, k, v = _qkv_cross(tq=16, tk=24)
    kv_mask = jnp.stack([jnp.zeros(24, jnp.int32),
                         jnp.ones(24, jnp.int32)])
    out = flash_attention(q, k, v, False, 16, 16, kv_mask=kv_mask)
    assert np.all(np.asarray(out)[0] == 0)
    ref1 = _dense_cross(q[1:], k[1:], v[1:])
    np.testing.assert_allclose(np.asarray(out)[1], np.asarray(ref1)[0],
                               atol=2e-5, rtol=2e-5)

    def lf(q, k, v):
        return jnp.sum(flash_attention(q, k, v, False, 16, 16,
                                       kv_mask=kv_mask) ** 2)

    gq, gk, gv = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    for g in (gq, gk, gv):
        assert np.all(np.asarray(g)[0] == 0)
        assert np.any(np.asarray(g)[1] != 0)
