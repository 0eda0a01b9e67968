"""Per-kernel interpret-mode validation: shape/dtype sweeps vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


_TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("B,H,KV,S,hd", [
    (2, 4, 2, 256, 64), (1, 4, 4, 200, 32), (2, 8, 2, 192, 64),
    (1, 2, 1, 128, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, KV, S, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B * S + H), 3)
    q = _rand(ks[0], (B, H, S, hd), dtype)
    k = _rand(ks[1], (B, KV, S, hd), dtype)
    v = _rand(ks[2], (B, KV, S, hd), dtype)
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64,
                              interpret=True)
    expected = ref.flash_attention_ref(q.astype(jnp.float32),
                                       k.astype(jnp.float32),
                                       v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=_TOL[dtype], rtol=_TOL[dtype])


@pytest.mark.parametrize("window", [32, 96])
def test_flash_attention_swa(window):
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (2, 4, 256, 32), jnp.float32)
    k = _rand(ks[1], (2, 2, 256, 32), jnp.float32)
    v = _rand(ks[2], (2, 2, 256, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, window=window, block_q=64,
                              block_k=64, interpret=True)
    expected = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,H,KV,hd,NP,page,MP", [
    (2, 4, 2, 32, 16, 16, 4), (3, 8, 4, 64, 32, 8, 6), (1, 2, 1, 16, 8, 4, 3),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode(B, H, KV, hd, NP, page, MP, dtype):
    ks = jax.random.split(jax.random.PRNGKey(NP + MP), 5)
    q = _rand(ks[0], (B, H, hd), dtype)
    kp = _rand(ks[1], (NP, KV, page, hd), dtype)
    vp = _rand(ks[2], (NP, KV, page, hd), dtype)
    table = jax.random.randint(ks[3], (B, MP), 0, NP)
    lengths = jax.random.randint(ks[4], (B,), 1, MP * page + 1)
    out = ops.paged_decode_attention(q, kp, vp, table, lengths,
                                     interpret=True)
    expected = ref.paged_decode_attention_ref(
        q.astype(jnp.float32), kp.astype(jnp.float32),
        vp.astype(jnp.float32), table, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=_TOL[dtype], rtol=_TOL[dtype])


@pytest.mark.parametrize("T,D,E,C", [(64, 32, 8, 12), (100, 16, 4, 40),
                                     (32, 8, 2, 4), (128, 64, 16, 8)])
def test_moe_dispatch(T, D, E, C):
    ks = jax.random.split(jax.random.PRNGKey(T + E), 2)
    toks = _rand(ks[0], (T, D), jnp.float32)
    eids = jax.random.randint(ks[1], (T,), 0, E)
    oh = jax.nn.one_hot(eids, E, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(oh, 0), eids[:, None], 1)[:, 0] - 1
    out = ops.moe_dispatch(toks, eids, pos, E, C, interpret=True)
    expected = ref.moe_dispatch_ref(toks, eids, pos, E, C)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expected))


@pytest.mark.parametrize("B,T,D,N,bd", [(2, 16, 8, 4, 8), (1, 32, 16, 4, 16),
                                        (3, 8, 32, 8, 8)])
def test_linear_scan(B, T, D, N, bd):
    ks = jax.random.split(jax.random.PRNGKey(B * T), 3)
    a = jax.random.uniform(ks[0], (B, T, D, N), jnp.float32, 0.5, 1.0)
    b = _rand(ks[1], (B, T, D, N), jnp.float32)
    h0 = _rand(ks[2], (B, D, N), jnp.float32)
    hs, hl = ops.linear_scan(a, b, h0, block_d=bd, interpret=True)
    rhs, rhl = ref.linear_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(rhs), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(rhl), atol=1e-5)


@pytest.mark.parametrize("B,S,H,hd,chunk", [(2, 64, 2, 8, 16),
                                            (1, 50, 3, 16, 32),
                                            (2, 33, 1, 8, 8)])
def test_wkv6(B, S, H, hd, chunk):
    ks = jax.random.split(jax.random.PRNGKey(B * S * H), 6)
    r = _rand(ks[0], (B, S, H, hd), jnp.float32)
    k = _rand(ks[1], (B, S, H, hd), jnp.float32)
    v = _rand(ks[2], (B, S, H, hd), jnp.float32)
    logw = -jnp.exp(jnp.clip(jax.random.normal(ks[3], (B, S, H, hd)),
                             -8, 0.5))
    u = _rand(ks[4], (H, hd), jnp.float32) * 0.1
    s0 = _rand(ks[5], (B, H, hd, hd), jnp.float32) * 0.1
    y, s = ops.wkv6_chunked(r, k, v, logw, u, s0, chunk=chunk,
                            interpret=True)
    ry, rs = ref.wkv6_ref(r, k, v, logw, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(rs), atol=2e-5)


@pytest.mark.parametrize("B,H,hd", [(2, 2, 8), (1, 3, 16), (4, 1, 8)])
def test_wkv6_decode(B, H, hd):
    ks = jax.random.split(jax.random.PRNGKey(B * H * hd), 6)
    r = _rand(ks[0], (B, H, hd), jnp.float32)
    k = _rand(ks[1], (B, H, hd), jnp.float32)
    v = _rand(ks[2], (B, H, hd), jnp.float32)
    w = jnp.exp(-jnp.exp(jnp.clip(jax.random.normal(ks[3], (B, H, hd)),
                                  -8, 0.5)))
    u = _rand(ks[4], (H, hd), jnp.float32) * 0.1
    s0 = _rand(ks[5], (B, H, hd, hd), jnp.float32) * 0.1
    y, s = ops.wkv6_decode(r, k, v, w, u, s0, interpret=True)
    ry, rs = ref.wkv6_decode_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(rs), atol=1e-5)
    # one decode step == the t=1 column of the chunked scan
    cy, cs = ops.wkv6_chunked(r[:, None], k[:, None], v[:, None],
                              jnp.log(w)[:, None], u, s0, chunk=1,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(cy[:, 0]), np.asarray(y),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(cs), np.asarray(s), atol=2e-5)


@pytest.mark.parametrize("B,Di,N,bd", [(2, 8, 4, 8), (1, 32, 8, 16),
                                       (3, 16, 4, 16)])
def test_ssm_decode_step(B, Di, N, bd):
    ks = jax.random.split(jax.random.PRNGKey(B * Di * N), 5)
    h = _rand(ks[0], (B, Di, N), jnp.float32)
    dA = jax.random.uniform(ks[1], (B, Di, N), jnp.float32, 0.5, 1.0)
    dtx = _rand(ks[2], (B, Di), jnp.float32)
    B_ssm = _rand(ks[3], (B, N), jnp.float32)
    C_ssm = _rand(ks[4], (B, N), jnp.float32)
    y, hn = ops.ssm_decode_step(h, dA, dtx, B_ssm, C_ssm, block_d=bd,
                                interpret=True)
    ry, rhn = ref.ssm_decode_step_ref(h, dA, dtx, B_ssm, C_ssm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hn), np.asarray(rhn), atol=1e-5)
    # one decode step == the T=1 slice of the linear_scan recurrence
    shs, shl = ops.linear_scan(dA[:, None], (dtx[..., None]
                               * B_ssm[:, None, :])[:, None], h,
                               block_d=bd, interpret=True)
    np.testing.assert_allclose(np.asarray(shl), np.asarray(hn), atol=1e-5)
