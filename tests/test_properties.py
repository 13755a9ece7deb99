"""Hypothesis property tests on system invariants."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.blocks import TransferCostModel, plan_blocks, vmem_tile
from repro.codec.int8block import dequantize, quantize
from repro.core.tars import TAR, Attribute, Dimension

settings.register_profile("ci", max_examples=50, deadline=None)
settings.load_profile("ci")


# ---------------------------------------------------------------------------
# block planner
# ---------------------------------------------------------------------------


@given(nbytes=st.integers(0, 1 << 24), block=st.integers(1, 1 << 22))
def test_plan_blocks_covers_exactly(nbytes, block):
    plan = plan_blocks(nbytes, block)
    assert sum(sz for _, sz in plan) == nbytes
    # contiguous, disjoint, ordered (FCFS over offsets)
    pos = 0
    for off, sz in plan:
        assert off == pos and sz > 0
        pos += sz
    if nbytes:
        assert max(sz for _, sz in plan) <= block


@given(nbytes=st.integers(1, 1 << 30),
       b1=st.sampled_from([1 << 21, 1 << 23, 1 << 25]),
       b2=st.sampled_from([1 << 26, 1 << 27, 1 << 28]))
def test_cost_model_monotone_in_block_size(nbytes, b1, b2):
    """Paper claim C1: larger blocks never slower (per-block costs amortize)."""
    m = TransferCostModel()
    assert m.predict(nbytes, b2) <= m.predict(nbytes, b1) + 1e-12


@given(elems=st.integers(128, 1 << 22),
       itemsize=st.sampled_from([1, 2, 4]))
def test_vmem_tile_alignment(elems, itemsize):
    rows, lanes = vmem_tile(elems, itemsize)
    assert lanes == 128
    assert rows % max(32 // itemsize, 1) == 0      # sublane packing
    assert rows * lanes <= max(elems, rows * lanes)  # never zero-sized


# ---------------------------------------------------------------------------
# quantization
# ---------------------------------------------------------------------------


def assert_within_bound(x, back, s, block):
    """The int8-block guarantee, per block: |x - dq| <= scale/2 +
    spacing(amax), the spacing covering the float32 rounding of x / scale
    and q * scale."""
    n = x.size
    pad = (-n) % block
    amax = np.abs(np.pad(x, (0, pad))).reshape(-1, block).max(axis=1)
    bound = np.repeat(s / 2 + np.spacing(amax), block)[:n]
    assert (np.abs(back - x) <= bound).all()


@given(st.integers(1, 5000), st.integers(0, 2 ** 32 - 1))
def test_int8_quant_error_bound(n, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(n).astype(np.float32) * rng.uniform(0.01, 100)
    block = 256
    s, q = quantize(x, block)
    back = dequantize(s, q, x.dtype, block)
    assert_within_bound(x, back, s, block)


@given(st.integers(1, 2000))
def test_quant_zero_block_is_exact(n):
    x = np.zeros(n, np.float32)
    s, q = quantize(x, 128)
    assert (dequantize(s, q, x.dtype, 128) == 0).all()


@given(n=st.integers(1, 1 << 14), block=st.integers(1, 4096),
       seed=st.integers(0, 2 ** 31 - 1))
def test_quant_roundtrip_any_size_any_block(n, block, seed):
    """Round trip holds for every (size, block) pairing: odd sizes, blocks
    larger than the input, and non-divisible blocks all pad correctly
    and dequantize back to the original size within the error bound."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(n).astype(np.float32)
    s, q = quantize(x, block)
    assert q.size == n                             # one int8 per element
    assert s.size == -(-n // block)                # one scale per block
    back = dequantize(s, q, x.dtype, block)
    assert back.shape == x.shape and back.dtype == x.dtype
    assert_within_bound(x, back, s, block)


@pytest.mark.parametrize("n,block", [
    (1, 4096),        # single element, giant block (all padding)
    (7, 8),           # odd size one short of the block
    (127, 64),        # odd size spanning two blocks
    (129, 64),        # one element into the third block
    (4095, 4096),     # the codec's block, one short
    (4097, 4096),     # the codec's block, one over
    (5000, 333),      # mutually indivisible
])
def test_quant_roundtrip_edge_sizes(n, block):
    rng = np.random.default_rng(n * 31 + block)
    x = (rng.standard_normal(n) * 10).astype(np.float32)
    s, q = quantize(x, block)
    back = dequantize(s, q, x.dtype, block)
    assert back.shape == x.shape
    assert_within_bound(x, back, s, block)


@given(n=st.integers(1, 2048), block=st.integers(1, 512))
def test_quant_zero_and_constant_blocks_nondivisible(n, block):
    """All-zero input stays exactly zero for every block size (the zero
    scale is replaced by 1.0, so padding never produces NaN/Inf), and a
    constant input is recovered exactly (it sits on a quantization level)."""
    z = np.zeros(n, np.float32)
    s, q = quantize(z, block)
    assert np.isfinite(s).all()
    assert (dequantize(s, q, z.dtype, block) == 0).all()
    c = np.full(n, 3.25, np.float32)
    s, q = quantize(c, block)
    back = dequantize(s, q, c.dtype, block)
    assert np.allclose(back, c, rtol=1e-6, atol=0)
    assert_within_bound(c, back, s, block)


# ---------------------------------------------------------------------------
# TARS
# ---------------------------------------------------------------------------


@st.composite
def tar_and_boxes(draw):
    nd = draw(st.integers(1, 3))
    dims = [draw(st.integers(2, 12)) for _ in range(nd)]
    n_sub = draw(st.integers(1, 4))
    subs = []
    for _ in range(n_sub):
        origin = tuple(draw(st.integers(0, d - 1)) for d in dims)
        shape = tuple(draw(st.integers(1, d - o)) for d, o in zip(dims, origin))
        subs.append((origin, shape))
    qlo = tuple(draw(st.integers(0, d - 1)) for d in dims)
    qhi = tuple(draw(st.integers(l, d - 1)) for d, l in zip(dims, qlo))
    return dims, subs, qlo, qhi


@given(tar_and_boxes(), st.integers(0, 2 ** 31 - 1))
def test_tars_select_matches_numpy(data, seed):
    """select() over overlapping subtars == last-write-wins dense array."""
    dims, subs, qlo, qhi = data
    rng = np.random.default_rng(seed)
    t = TAR("t", [Dimension(f"d{i}", 0, n - 1) for i, n in enumerate(dims)],
            [Attribute("v", "float64")])
    dense = np.zeros(dims)
    for origin, shape in subs:
        data_arr = rng.standard_normal(shape)
        t.load_subtar(origin, shape, {"v": data_arr})
        sl = tuple(slice(o, o + s) for o, s in zip(origin, shape))
        dense[sl] = data_arr
    sel = t.select("v", qlo, qhi)
    qsl = tuple(slice(l, h + 1) for l, h in zip(qlo, qhi))
    assert np.array_equal(sel, dense[qsl])
    # aggregates consistent with select
    assert np.isclose(t.aggregate("v", "sum", qlo, qhi), dense[qsl].sum())


@given(st.integers(1, 50), st.integers(2, 40))
def test_dimension_mapping_roundtrip(i, stride):
    d = Dimension("x", 0, 100, offset=3.5, stride=float(stride))
    assert d.to_index(float(d.to_coord(i))) == i


# ---------------------------------------------------------------------------
# FCFS queue ordering
# ---------------------------------------------------------------------------


@given(st.lists(st.integers(0, 100), min_size=1, max_size=30))
def test_fcfs_single_thread_preserves_order(items):
    from repro.core.queues import FCFSPool
    out = []
    pool = FCFSPool(1, "t")
    hs = [pool.submit(out.append, i, name=str(i)) for i in items]
    pool.sync(10)
    pool.stop()
    assert out == items
