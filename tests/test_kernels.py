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


def _paged(q, kp, vp, table, lengths, ppb, monkeypatch, interpret=True):
    """The Pallas kernel in interpret mode; ``ppb`` set, the VMEM budget
    is cut to that many pages' tile, so the shape rule derives it."""
    if ppb is None and interpret is True:
        return ops.paged_decode_attention(q, kp, vp, table, lengths,
                                          interpret=True)
    from repro.kernels import paged_attention as pa
    _, KV, page, hd = kp.shape
    if ppb is not None:
        monkeypatch.setattr(pa, "BLOCK_BYTES", ppb * KV * page * hd
                            * kp.dtype.itemsize)
        assert pa.pages_per_block(KV, page, hd, kp.dtype.itemsize,
                                  table.shape[1]) == ppb
    return pa.paged_decode_attention(q, kp, vp, table, lengths,
                                     backend="pallas", interpret=interpret)


@pytest.mark.parametrize("B,H,KV,hd,NP,page,MP,ppb,lens", [
    (2, 4, 2, 32, 16, 16, 4, None, None),
    (3, 8, 4, 64, 32, 8, 6, None, None),
    (1, 2, 1, 16, 8, 4, 3, None, None),
    # MP not a multiple of ppb: the tail block stops at MP
    (3, 4, 4, 32, 24, 8, 3, 2, None),
    # G=4: one block exactly (4 pages x 4), one token, the whole table
    (3, 16, 4, 32, 40, 4, 6, 4, (16, 1, 24)),
    # G=1 with KV=8, a page per block
    (2, 8, 8, 16, 16, 4, 3, 1, (12, 5)),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode(B, H, KV, hd, NP, page, MP, ppb, lens, dtype,
                      monkeypatch):
    ks = jax.random.split(jax.random.PRNGKey(NP + MP), 5)
    q = _rand(ks[0], (B, H, hd), dtype)
    kp = _rand(ks[1], (NP, KV, page, hd), dtype)
    vp = _rand(ks[2], (NP, KV, page, hd), dtype)
    table = jax.random.randint(ks[3], (B, MP), 0, NP)
    lengths = (jax.random.randint(ks[4], (B,), 1, MP * page + 1)
               if lens is None else jnp.asarray(lens, jnp.int32))
    out = _paged(q, kp, vp, table, lengths, ppb, monkeypatch)
    expected = ref.paged_decode_attention_ref(
        q.astype(jnp.float32), kp.astype(jnp.float32),
        vp.astype(jnp.float32), table, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=_TOL[dtype], rtol=_TOL[dtype])


@pytest.mark.parametrize("ppb", [None, 4, 1])
def test_paged_decode_never_reads_dead_pages(ppb, monkeypatch):
    """Dead table entries: id 0, stale ids past a slot's live pages, and
    a whole slot of length 0 (outside the batch). First every page that
    only dead entries name is NaN, as are the rows past each length in a
    slot's last page: the output still equals the reference on the clean
    pool, so nothing dead is mixed in (the length-0 slot reads zeros).
    Then the dead entries name pages past the pool, and the TPU
    interpreter raises on any read out of bounds: none is fetched."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.default_rng(7)
    B, KV, G, hd, NP, page, MP = 5, 2, 2, 16, 40, 4, 6
    lengths = np.array([5, 17, 24, 1, 0], np.int32)
    n_live = -(-lengths // page)
    ids = rng.permutation(np.arange(1, NP))
    table = np.zeros((B, MP), np.int32)
    live = []
    for b, n in enumerate(n_live):
        table[b, :n] = ids[:n]
        live += list(ids[:n])
        ids = ids[n:]
    dead = ids[:8]                                 # stale ids, never live
    table[0, 3:5] = dead[:2]
    table[1, 5] = dead[2]
    table[4, :3] = dead[3:6]
    kp = rng.normal(size=(NP, KV, page, hd)).astype(np.float32)
    vp = rng.normal(size=(NP, KV, page, hd)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(B, KV * G, hd)), jnp.float32)
    expected = np.asarray(ref.paged_decode_attention_ref(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
        jnp.asarray(lengths)))

    poisoned = np.setdiff1d(np.arange(NP), live)   # id 0 among them
    assert 0 in poisoned and set(dead) <= set(poisoned)
    kn, vn = kp.copy(), vp.copy()
    kn[poisoned] = np.nan
    vn[poisoned] = np.nan
    for b in range(B):
        if lengths[b] % page:
            last = table[b, lengths[b] // page]
            kn[last, :, lengths[b] % page:] = np.nan
            vn[last, :, lengths[b] % page:] = np.nan
    out = np.asarray(_paged(q, jnp.asarray(kn), jnp.asarray(vn),
                            jnp.asarray(table), jnp.asarray(lengths), ppb,
                            monkeypatch))
    np.testing.assert_allclose(out[:4], expected[:4], atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(out[4], np.zeros_like(out[4]))

    cols = np.arange(MP)[None, :]
    past = np.where(cols >= n_live[:, None], NP + cols, table)
    out = np.asarray(_paged(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(past),
        jnp.asarray(lengths), ppb, monkeypatch,
        interpret=pltpu.InterpretParams(out_of_bounds_reads="raise")))
    np.testing.assert_allclose(out[:4], expected[:4], atol=2e-5, rtol=2e-5)


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
