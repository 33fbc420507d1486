"""Pallas-kernel sweeps: shapes × dtypes, assert_allclose vs the ref.py
pure-jnp oracles (interpret=True executes the kernel body on CPU)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.quant import quantize_rows
from repro.kernels import decode_attention as da
from repro.kernels import ops
from repro.kernels import ref


def _dq(payload, scale):
    return payload.astype(jnp.float32) * scale


def _rand(key, shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)
    return x.astype(dtype)


_TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("B,S,H,hd", [(1, 128, 2, 32), (2, 256, 4, 64),
                                      (1, 512, 1, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, S, H, hd, dtype, causal):
    q = _rand(0, (B, S, H, hd), dtype)
    k = _rand(1, (B, S, H, hd), dtype)
    v = _rand(2, (B, S, H, hd), dtype)
    o = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    r = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = _TOL[dtype]
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)


def test_flash_attention_softcap():
    q = _rand(0, (2, 128, 2, 64), jnp.float32)
    k = _rand(1, (2, 128, 2, 64), jnp.float32)
    v = _rand(2, (2, 128, 2, 64), jnp.float32)
    o = ops.flash_attention(q, k, v, causal=True, softcap=30.0,
                            block_q=64, block_k=64)
    r = ref.flash_attention_ref(q, k, v, causal=True, softcap=30.0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,S,KH,G,hd", [(2, 256, 2, 2, 32),
                                         (1, 512, 1, 4, 64),
                                         (3, 128, 4, 1, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, S, KH, G, hd, dtype):
    H = KH * G
    q = _rand(0, (B, H, hd), dtype)
    k = _rand(1, (B, S, KH, hd), dtype)
    v = _rand(2, (B, S, KH, hd), dtype)
    lengths = jnp.asarray([S // 2 + 7 * i % (S // 2) + 1
                           for i in range(B)], jnp.int32)
    o = ops.decode_attention(q, k, v, lengths, block_s=64)
    r = ref.decode_attention_ref(q, k, v, lengths)
    tol = _TOL[dtype]
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)


def _paged_case(B, KH, G, hd, bs, nmax, tracks, dtype):
    """q, pools and a shuffled table over a shared pool for the paged
    kernel, with ragged lengths: the full capacity, then 0 (an inactive
    lane), 1, an exact compute-block boundary and a mid-block length,
    as far as B goes.  Table entries past a slot's length are garbage
    (``table``); ``clean`` holds valid ids there, for the oracle.
    ``tracks`` > 0 adds a leading axis of that many tracks to q and the
    pools, as PT's track vmap holds them."""
    lead = (tracks,) if tracks else ()
    N = B * nmax + 1                     # pool with spare blocks + trash
    q = _rand(0, lead + (B, KH * G, hd), dtype)
    k_pool = _rand(1, lead + (N, bs, KH, hd), dtype)
    v_pool = _rand(2, lead + (N, bs, KH, hd), dtype)
    rng = np.random.default_rng(7)
    # each row draws distinct blocks from the shared pool, shuffled
    clean = rng.permutation(N - 1)[:B * nmax].reshape(B, nmax) + 1
    cap = nmax * bs
    block = min(nmax, da.MAX_PAGES,
                da.BLOCK_COLS // (max(tracks, 1) * KH * bs)) * bs
    lengths = np.asarray([cap, 0, 1, block, cap // 2 + 3][:B], np.int32)
    live = np.arange(nmax)[None] * bs < lengths[:, None]
    table = np.where(live, clean, rng.integers(-9, 10 ** 6, clean.shape))
    return (q, k_pool, v_pool, jnp.asarray(table, jnp.int32),
            jnp.asarray(clean, jnp.int32), jnp.asarray(lengths))


def _check_paged(o, r, lengths, tol):
    """Lanes with KV match the oracle; a lane of length 0 reads nothing
    and returns zeros (the oracle's softmax over no key is undefined)."""
    o, r = np.asarray(o, np.float32), np.asarray(r, np.float32)
    on = np.asarray(lengths) > 0
    np.testing.assert_allclose(o[..., on, :, :], r[..., on, :, :],
                               rtol=tol, atol=tol)
    assert not o[..., ~on, :, :].any()


def _paged_call(tracks, table, lengths, **scales):
    """The kernel and its oracle, vmapped over tracks as
    ``core.track._track_layers`` calls them (table and lengths shared)."""
    def call(q, k, v, *sc):
        kw = dict(zip(("k_scale", "v_scale"), sc))
        return ops.paged_decode_attention(q, k, v, table, lengths, **kw)
    return jax.vmap(call) if tracks else call


# (B, KH, G, hd, bs, nmax, tracks): small GQA shapes; PT's tracks folded
# into the head axis (8 tracks x 1 KV head, G 4, hd 128, under the
# track vmap); the dense-6b layout (8 KV heads).  The last two span
# three compute blocks of 256 tokens a slot.
PAGED_CASES = [(2, 2, 2, 32, 16, 4, 0), (1, 1, 4, 64, 8, 8, 0),
               (3, 4, 1, 128, 32, 2, 0), (5, 1, 4, 128, 16, 40, 8),
               (5, 8, 4, 128, 16, 40, 0)]


@pytest.mark.parametrize("B,KH,G,hd,bs,nmax,tracks", PAGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_sweep(B, KH, G, hd, bs, nmax, tracks, dtype):
    """The paged kernel streams KV blocks through a scalar-prefetched
    block table; outputs must match the gather-then-dense oracle for
    random (shuffled, shared-pool) tables, ragged lengths and garbage
    table entries past each slot's length."""
    q, k_pool, v_pool, table, clean, lengths = _paged_case(
        B, KH, G, hd, bs, nmax, tracks, dtype)
    o = _paged_call(tracks, table, lengths)(q, k_pool, v_pool)
    oracle = functools.partial(ref.paged_decode_attention_ref,
                               block_table=clean, lengths=lengths)
    r = (jax.vmap(oracle) if tracks else oracle)(q, k_pool, v_pool)
    _check_paged(o, r, lengths, _TOL[dtype])


@pytest.mark.parametrize("B,S,KH,G,hd", [(2, 256, 2, 2, 32),
                                         (1, 512, 1, 4, 64)])
def test_decode_attention_int8_sweep(B, S, KH, G, hd):
    """int8 K/V with per-token-per-head scales, dequant fused into the
    online-softmax loop: must match the fp oracle run on the explicitly
    dequantized cache (identical math, fp32 accumulation both sides)."""
    H = KH * G
    q = _rand(0, (B, H, hd), jnp.float32)
    k = _rand(1, (B, S, KH, hd), jnp.float32)
    v = _rand(2, (B, S, KH, hd), jnp.float32)
    kq, ks = quantize_rows(k)
    vq, vs = quantize_rows(v)
    lengths = jnp.asarray([S // 2 + 7 * i % (S // 2) + 1
                           for i in range(B)], jnp.int32)
    o = ops.decode_attention(q, kq, vq, lengths, block_s=64,
                             k_scale=ks, v_scale=vs)
    r = ref.decode_attention_ref(q, _dq(kq, ks), _dq(vq, vs), lengths)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,KH,G,hd,bs,nmax,tracks",
                         PAGED_CASES[:2] + PAGED_CASES[3:])
def test_paged_decode_attention_int8_sweep(B, KH, G, hd, bs, nmax, tracks):
    """int8 block pools + scale pools riding the same scalar-prefetched
    block table: matches the oracle on the dequantized pool, for ragged
    lengths and garbage table entries past each slot's length."""
    q, k_pool, v_pool, table, clean, lengths = _paged_case(
        B, KH, G, hd, bs, nmax, tracks, jnp.float32)
    kq, ks = quantize_rows(k_pool)
    vq, vs = quantize_rows(v_pool)
    o = _paged_call(tracks, table, lengths)(q, kq, vq, ks, vs)
    oracle = functools.partial(ref.paged_decode_attention_ref,
                               block_table=clean, lengths=lengths)
    r = (jax.vmap(oracle) if tracks else oracle)(q, _dq(kq, ks), _dq(vq, vs))
    _check_paged(o, r, lengths, 2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("quantized", [False, True])
def test_paged_matches_dense_long_nonaligned(dtype, quantized):
    """Paged vs dense decode attention on longer sequences with lengths
    that do NOT land on block boundaries, at bf16 and int8: both kernels
    read the same bytes through different address paths, so they must
    agree to fp32-accumulation tolerance."""
    B, S, KH, G, hd, bs = 2, 1024, 2, 2, 64, 16
    q = _rand(0, (B, KH * G, hd), dtype)
    k = _rand(1, (B, S, KH, hd), dtype)
    v = _rand(2, (B, S, KH, hd), dtype)
    lengths = jnp.asarray([1000, 513], jnp.int32)   # mid-block boundaries
    kw = {}
    if quantized:
        kq, ks = quantize_rows(k.astype(jnp.float32))
        vq, vs = quantize_rows(v.astype(jnp.float32))
        k, v = kq, vq
        kw = {"k_scale": ks, "v_scale": vs}
        pk_s = ks.reshape(B * S // bs, bs, KH, 1)
        pv_s = vs.reshape(B * S // bs, bs, KH, 1)
    pools_k = k.reshape(B * S // bs, bs, KH, hd)
    pools_v = v.reshape(B * S // bs, bs, KH, hd)
    table = jnp.arange(B * S // bs, dtype=jnp.int32).reshape(B, S // bs)
    pkw = ({"k_scale": pk_s, "v_scale": pv_s} if quantized else {})
    o_paged = ops.paged_decode_attention(q, pools_k, pools_v, table,
                                         lengths, **pkw)
    o_dense = ops.decode_attention(q, k, v, lengths, block_s=64, **kw)
    tol = 2e-5 if quantized else _TOL[dtype]
    np.testing.assert_allclose(np.asarray(o_paged, np.float32),
                               np.asarray(o_dense, np.float32),
                               rtol=tol, atol=tol)


def test_int8_matmul_vs_dequant_oracle():
    """Fused int8-weight matmul: int8 payload x fp activations with the
    per-column rescale applied to the fp32 accumulator must equal the
    explicit dequantize-then-matmul oracle."""
    M, K, N = 48, 96, 160
    x = _rand(0, (M, K), jnp.float32)
    w = _rand(1, (K, N), jnp.float32)
    from repro.common.quant import quantize
    qt = quantize(w, axes=-2)              # per-output-column scales
    scale = qt.scale.reshape(1, N)
    o = ops.int8_matmul(x, qt.payload, scale)
    r = ref.int8_matmul_ref(x, qt.payload, scale)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=1e-5, atol=1e-5)


def test_paged_matches_contiguous_identity_table():
    """With the identity table the paged kernel IS the dense kernel."""
    B, S, KH, G, hd, bs = 2, 128, 2, 2, 64, 32
    q = _rand(0, (B, KH * G, hd), jnp.float32)
    k = _rand(1, (B, S, KH, hd), jnp.float32)
    v = _rand(2, (B, S, KH, hd), jnp.float32)
    lengths = jnp.asarray([37, 101], jnp.int32)
    pools_k = k.reshape(B * S // bs, bs, KH, hd)
    pools_v = v.reshape(B * S // bs, bs, KH, hd)
    table = jnp.arange(B * S // bs, dtype=jnp.int32).reshape(B, S // bs)
    o_paged = ops.paged_decode_attention(q, pools_k, pools_v, table, lengths)
    o_dense = ops.decode_attention(q, k, v, lengths, block_s=bs)
    np.testing.assert_allclose(np.asarray(o_paged), np.asarray(o_dense),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_max_len_skips_dead_blocks():
    """Blocks past a slot's length are neither copied nor computed: NaN
    in every dead block of the cache leaves the result equal to the
    oracle's on the clean cache."""
    B, S, KH, G, hd = 2, 512, 2, 2, 32
    q = _rand(0, (B, KH * G, hd), jnp.float32)
    k = _rand(1, (B, S, KH, hd), jnp.float32)
    v = _rand(2, (B, S, KH, hd), jnp.float32)
    lengths = jnp.asarray([9, 70], jnp.int32)
    live_blocks = -(-lengths // 64) * 64
    dead = jnp.arange(S)[None, :, None, None] >= live_blocks[:, None,
                                                              None, None]
    trunc = ops.decode_attention(q, jnp.where(dead, jnp.nan, k),
                                 jnp.where(dead, jnp.nan, v), lengths,
                                 block_s=64)
    r = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(trunc), np.asarray(r),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,S,di,ds", [(2, 64, 32, 4), (1, 256, 128, 16),
                                       (2, 128, 64, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_scan_sweep(B, S, di, ds, dtype):
    # a in (0,1) for stability, like exp(dt*A)
    a = jax.nn.sigmoid(_rand(0, (B, S, di, ds), jnp.float32)).astype(dtype)
    b = _rand(1, (B, S, di, ds), dtype)
    h0 = _rand(2, (B, di, ds), jnp.float32)
    h, hl = ops.ssm_scan(a, b, h0, chunk=32, block_d=min(di, 32))
    rh, rhl = ref.ssm_scan_ref(a, b, h0)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(h), np.asarray(rh),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(rhl),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", [(4, 64), (2, 16, 128), (8, 3, 5, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = _rand(0, shape, dtype)
    scale = _rand(1, shape[-1:], jnp.float32) * 0.1
    o = ops.rmsnorm(x, scale)
    r = ref.rmsnorm_ref(x, scale)
    tol = _TOL[dtype]
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)


def test_flash_matches_model_attention_path():
    """The kernel agrees with the model's chunked-jnp attention path."""
    from repro.models.attention import blockwise_attention
    q = _rand(0, (2, 128, 4, 32), jnp.float32)
    k = _rand(1, (2, 128, 4, 32), jnp.float32)
    v = _rand(2, (2, 128, 4, 32), jnp.float32)
    o1 = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    o2 = blockwise_attention(q, k, v, causal=True, chunk_q=64, chunk_k=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)
