"""Pallas-kernel sweeps: shapes × dtypes, assert_allclose vs the ref.py
pure-jnp oracles (interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.quant import quantize_rows
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


@pytest.mark.parametrize("B,KH,G,hd,bs,nmax", [(2, 2, 2, 32, 16, 4),
                                               (1, 1, 4, 64, 8, 8),
                                               (3, 4, 1, 128, 32, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_sweep(B, KH, G, hd, bs, nmax, dtype):
    """The paged kernel streams KV blocks through a scalar-prefetched
    block table; outputs must match the gather-then-dense oracle for
    random (shuffled, shared-pool) tables and ragged lengths."""
    H = KH * G
    N = B * nmax + 1                     # pool with spare blocks + trash
    q = _rand(0, (B, H, hd), dtype)
    k_pool = _rand(1, (N, bs, KH, hd), dtype)
    v_pool = _rand(2, (N, bs, KH, hd), dtype)
    rng = np.random.default_rng(7)
    # each row draws distinct blocks from the shared pool, shuffled
    perm = rng.permutation(N - 1)[:B * nmax].reshape(B, nmax) + 1
    table = jnp.asarray(perm, jnp.int32)
    lengths = jnp.asarray(
        [1 + (11 * i + 5) % (nmax * bs) for i in range(B)], jnp.int32)
    o = ops.paged_decode_attention(q, k_pool, v_pool, table, lengths)
    r = ref.paged_decode_attention_ref(q, k_pool, v_pool, table, lengths)
    tol = _TOL[dtype]
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)
    # max_len truncates the block sweep without changing results
    ml = int(lengths.max())
    o2 = ops.paged_decode_attention(q, k_pool, v_pool, table, lengths,
                                    max_len=ml)
    np.testing.assert_allclose(np.asarray(o2, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)


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


@pytest.mark.parametrize("B,KH,G,hd,bs,nmax", [(2, 2, 2, 32, 16, 4),
                                               (1, 1, 4, 64, 8, 8)])
def test_paged_decode_attention_int8_sweep(B, KH, G, hd, bs, nmax):
    """int8 block pools + scale pools riding the same scalar-prefetched
    block table: matches the oracle on the dequantized pool, with and
    without the max_len sweep bound."""
    H = KH * G
    N = B * nmax + 1
    q = _rand(0, (B, H, hd), jnp.float32)
    k_pool = _rand(1, (N, bs, KH, hd), jnp.float32)
    v_pool = _rand(2, (N, bs, KH, hd), jnp.float32)
    kq, ks = quantize_rows(k_pool)
    vq, vs = quantize_rows(v_pool)
    rng = np.random.default_rng(7)
    perm = rng.permutation(N - 1)[:B * nmax].reshape(B, nmax) + 1
    table = jnp.asarray(perm, jnp.int32)
    lengths = jnp.asarray(
        [1 + (11 * i + 5) % (nmax * bs) for i in range(B)], jnp.int32)
    o = ops.paged_decode_attention(q, kq, vq, table, lengths,
                                   k_scale=ks, v_scale=vs)
    r = ref.paged_decode_attention_ref(q, _dq(kq, ks), _dq(vq, vs),
                                       table, lengths)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=2e-5, atol=2e-5)
    o2 = ops.paged_decode_attention(q, kq, vq, table, lengths,
                                    k_scale=ks, v_scale=vs,
                                    max_len=int(lengths.max()))
    np.testing.assert_allclose(np.asarray(o2), np.asarray(r),
                               rtol=2e-5, atol=2e-5)


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
    """Truncating the sequential grid to the max valid length must not
    change the result (the skipped blocks are fully masked anyway)."""
    B, S, KH, G, hd = 2, 512, 2, 2, 32
    q = _rand(0, (B, KH * G, hd), jnp.float32)
    k = _rand(1, (B, S, KH, hd), jnp.float32)
    v = _rand(2, (B, S, KH, hd), jnp.float32)
    lengths = jnp.asarray([9, 70], jnp.int32)
    full = ops.decode_attention(q, k, v, lengths, block_s=64)
    trunc = ops.decode_attention(q, k, v, lengths, block_s=64, max_len=70)
    np.testing.assert_allclose(np.asarray(trunc), np.asarray(full),
                               rtol=2e-5, atol=2e-5)
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
