"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# ---------------------------------------------------------------------------
# staging_pack
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape,tile", [
    ((256, 128), (256, 128)),
    ((512, 256), (256, 128)),
    ((64, 384), (8, 128)),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("out_dtype", [None, jnp.int8, jnp.bfloat16])
def test_staging_pack_vs_ref(shape, tile, dtype, out_dtype):
    from repro.kernels.staging_pack import kernel, ref
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    bp, sp = kernel.pack_blocks(x, tile=tile, out_dtype=out_dtype,
                                interpret=True)
    br, sr = ref.pack_blocks_ref(x, tile=tile, out_dtype=out_dtype)
    assert bp.dtype == br.dtype and bp.shape == br.shape
    if out_dtype == jnp.int8:
        # amax reduction order may differ by 1 ulp -> round-half ties can
        # flip by one quantization step
        diff = np.abs(np.asarray(bp, np.int32) - np.asarray(br, np.int32))
        assert diff.max() <= 1 and (diff != 0).mean() < 1e-3
    else:
        np.testing.assert_array_equal(np.asarray(bp), np.asarray(br))
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr), rtol=1e-6)


def test_pack_roundtrip_lossless_and_quantized():
    from repro.kernels.staging_pack import ops
    y = jax.random.normal(jax.random.PRNGKey(1), (3, 1000, 7), jnp.float32)
    b, s = ops.pack(y, block_bytes=64 << 10, impl="xla")
    assert bool(jnp.array_equal(ops.unpack(b, s, y.shape), y))
    bq, sq = ops.pack(y, block_bytes=64 << 10, out_dtype=jnp.int8,
                      impl="pallas", interpret=True)
    yr = ops.unpack(bq, sq, y.shape)
    rel = float(jnp.max(jnp.abs(yr - y)) / jnp.max(jnp.abs(y)))
    assert rel < 1e-2


def test_unpack_respects_non_default_block_bytes():
    # regression: unpack hardcoded tc=128 instead of asking tile_for_block
    # for the dtype's lane width; round-trips must survive any block_bytes
    # (geometry is recovered from the packed shape, not the default knob)
    from repro.kernels.staging_pack import ops
    y = jax.random.normal(jax.random.PRNGKey(2), (64, 192), jnp.bfloat16)
    for block_bytes in (8 << 10, 16 << 10, 64 << 10):
        b, s = ops.pack(y, block_bytes=block_bytes, impl="xla")
        assert b.shape[1] * jnp.dtype(y.dtype).itemsize == block_bytes
        out = ops.unpack(b, s, y.shape, block_bytes=block_bytes)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(y))
        # unpack with a *different* block_bytes still round-trips: the
        # packed shape carries the real geometry
        out2 = ops.unpack(b, s, y.shape)
        np.testing.assert_array_equal(np.asarray(out2), np.asarray(y))
    # blocks whose width is not a multiple of the lane count are rejected
    with pytest.raises(ValueError):
        ops.unpack(jnp.zeros((2, 100), jnp.float32),
                   jnp.ones((2,), jnp.float32), (200,))


@pytest.mark.parametrize("n", [0, 1, 4096, 5000, 3 * 4096 + 17])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_blocks_bound_and_shapes(n, dtype):
    from repro.kernels.staging_pack import ops
    x = jax.random.normal(jax.random.PRNGKey(3), (n,), dtype) * 3.0
    q, s = ops.quantize_blocks(x, block_elems=4096, impl="xla")
    nb = -(-n // 4096)
    assert q.shape == (nb, 4096) and q.dtype == jnp.int8
    assert s.shape == (nb,) and s.dtype == jnp.float32
    back = ops.dequantize_blocks(q, s, n, dtype=dtype)
    xf = np.asarray(x, np.float32)
    err = np.abs(np.asarray(back, np.float32) - xf)
    # |x - dq| <= scale/2 per block (+ dtype rounding slack)
    bound = np.repeat(np.asarray(s), 4096)[:n] * 0.5 + \
        (1e-6 if dtype == jnp.float32 else 0.05)
    assert n == 0 or bool((err <= bound + np.abs(xf) * 0.01).all())


def test_quantize_blocks_pallas_matches_xla():
    from repro.kernels.staging_pack import ops
    x = jax.random.normal(jax.random.PRNGKey(4), (2 * 4096 + 100,),
                          jnp.float32)
    qx, sx = ops.quantize_blocks(x, impl="xla")
    qp, sp = ops.quantize_blocks(x, impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sx), rtol=1e-6)
    diff = np.abs(np.asarray(qp, np.int32) - np.asarray(qx, np.int32))
    assert diff.max() <= 1 and (diff != 0).mean() < 1e-3


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [
    dict(B=2, S=256, Hq=4, Hkv=2, D=64, window=0, cap=0.0, causal=True),
    dict(B=1, S=512, Hq=8, Hkv=1, D=128, window=0, cap=50.0, causal=True),
    dict(B=2, S=256, Hq=4, Hkv=4, D=64, window=128, cap=0.0, causal=True),
    dict(B=1, S=256, Hq=2, Hkv=2, D=64, window=0, cap=0.0, causal=False),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(cfg, dtype):
    from repro.kernels.flash_attention import ops
    B, S, Hq, Hkv, D = cfg["B"], cfg["S"], cfg["Hq"], cfg["Hkv"], cfg["D"]
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    kw = dict(softcap=cfg["cap"], causal=cfg["causal"], window=cfg["window"])
    o_ref = ops.gqa_attention_ref(q, k, v, **kw)
    o_pl = ops.gqa_attention(q, k, v, impl="pallas", block_q=128,
                             block_k=128, interpret=True, **kw)
    o_xla = ops.gqa_attention(q, k, v, impl="xla", block_q=128, block_k=128,
                              **kw)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o_pl, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(o_xla, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol)


# ---------------------------------------------------------------------------
# ssm_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,di,N,chunk,dtile", [
    (2, 64, 256, 16, 16, 128),
    (1, 100, 300, 8, 32, 128),     # padding paths
    (2, 128, 512, 16, 64, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_scan_vs_ref(B, S, di, N, chunk, dtile, dtype):
    from repro.kernels.ssm_scan import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    xi = jax.random.normal(ks[0], (B, S, di), dtype)
    dt = (jax.nn.softplus(jax.random.normal(ks[1], (B, S, di))) * 0.1).astype(dtype)
    Bm = jax.random.normal(ks[2], (B, S, N), dtype)
    Cm = jax.random.normal(ks[3], (B, S, N), dtype)
    A = -jnp.exp(jax.random.normal(ks[4], (di, N)) * 0.2)
    h0 = jax.random.normal(ks[5], (B, di, N), jnp.float32)
    y0, h_ref = ref.ssm_scan_ref(xi, dt, Bm, Cm, A, h0)
    yp, hp = ops.selective_scan(xi, dt, Bm, Cm, A, h0, chunk=chunk,
                                d_tile=dtile, impl="pallas", interpret=True)
    yx, hx = ops.selective_scan(xi, dt, Bm, Cm, A, h0, chunk=chunk,
                                impl="xla")
    tol = 5e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(yp, np.float32),
                               np.asarray(y0, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(hp), np.asarray(h_ref), atol=tol)
    np.testing.assert_allclose(np.asarray(yx, np.float32),
                               np.asarray(y0, np.float32), atol=tol)


# ---------------------------------------------------------------------------
# decode_matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("M,K,N,block_k,block_n", [
    (16, 256, 384, 256, 384),      # one tile
    (16, 512, 768, 128, 256),      # 4 x 3 tiles, all whole
    (5, 200, 300, 200, 300),       # dims off the 128 grid, whole
    (3, 700, 260, 128, 128),       # partial last K and N tiles
    (1, 384, 640, 384, 256),       # one row, partial N tile
])
def test_decode_matmul_vs_ref(M, K, N, block_k, block_n):
    """The float32 weight read in tiles and cast in VMEM gives the product
    with the weight cast first, to the rounding of a reordered sum."""
    from repro.kernels.decode_matmul import kernel, ref
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    x = jax.random.normal(ks[0], (M, K), jnp.bfloat16)
    w = jax.random.normal(ks[1], (3, K, N), jnp.float32)
    y = kernel.matmul_cast(x, w, jnp.int32(2), block_k=block_k,
                           block_n=block_n, interpret=True)
    assert y.shape == (M, N) and y.dtype == jnp.bfloat16
    r = np.asarray(ref.matmul_cast_ref(x, w[2]), np.float32)
    # float32 sums in another order can round to the neighbouring bf16,
    # and seldom do; products of the uncast weight differ in ~40% of them
    y32 = np.asarray(y, np.float32)
    np.testing.assert_allclose(y32, r, rtol=2 ** -7,
                               atol=1e-3 * np.abs(r).max())
    assert (y32 != r).mean() <= 0.01


def test_decode_matmul_unstacked_weight():
    """A plain (K, N) weight through the public wrapper, which picks the
    tiles, gives the product of its layer in a stack."""
    from repro.kernels.decode_matmul import ops
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(ks[0], (16, 640), jnp.bfloat16)
    w = jax.random.normal(ks[1], (2, 640, 384), jnp.float32)
    np.testing.assert_array_equal(np.asarray(ops.decode_matmul(x, w[1])),
                                  np.asarray(ops.decode_matmul(x, w, 1)))


@pytest.mark.parametrize("k,n,tiles", [
    (1536, 6144, (512, 2048)),
    (6144, 1536, (512, 1536)),
    (1536, 1536, (512, 1536)),
    (200, 300, (200, 300)),
    (1000, 8000, (512, 2048)),     # no 128-multiple divides either: the
                                   # last tiles are partial
])
def test_decode_matmul_tiles(k, n, tiles):
    from repro.kernels.decode_matmul import ops
    assert ops.tiles(k, n, 4) == tiles
