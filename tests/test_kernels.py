"""Per-kernel allclose validation against the pure-jnp oracles.

Shape/dtype sweeps per the assignment contract: every Pallas kernel is
executed in interpret mode (Python emulation on CPU) and compared against
``ref.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.confidence import (LANES, MAX_CHUNKS, VMEM_LIMIT,
                                     confidence_fused, tiling, vmem_bytes)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import (attention_ref, confidence_ref,
                               selective_scan_ref)
from repro.kernels.selective_scan import selective_scan

# (leading shape, vocab, ties): each takes the tiling rule to an edge.
# ``ties`` lists (row, first, second) positions set to a shared maximum;
# "edge" places them on the two sides of the first vocab-block boundary.
CONF_CASES = {
    "rows-28-block-32-ragged-vocab": ((4, 7), 1000, ()),
    "one-lane-over-a-chunk": ((2, 3), 513, ()),
    "rows-8-vocab-exact": ((8,), 1024, ()),
    "rows-12-ragged-3-lanes": ((12,), 515, ()),
    "row-edge-block-ragged-vocab-block": ((264,), 4200, ()),
    "tie-across-vocab-block-edge": (
        (264,), 16640, ((0, "edge", "edge"), (7, 3, 3 + LANES),
                        (200, 5, 6), (263, "edge", "edge"))),
}


def _conf_logits(shape, vocab, ties, dtype):
    rows = int(np.prod(shape))
    rng = jax.random.PRNGKey(rows * 7919 + vocab)
    x = np.array(5 * jax.random.normal(rng, (rows, vocab)), np.float32)
    vb = tiling(rows, vocab, jnp.dtype(dtype).itemsize)[1]
    for row, first, second in ties:
        if first == "edge":
            first, second = vb - 1, vb
        x[row, [first, second]] = 40.0
    return jnp.asarray(x.reshape(shape + (vocab,))).astype(dtype)


@pytest.mark.parametrize("case", CONF_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_confidence_kernel_matches_ref(case, dtype):
    shape, vocab, ties = CONF_CASES[case]
    logits = _conf_logits(shape, vocab, ties, dtype)
    a, p, m, e = confidence_fused(logits)
    ra, rp, rm, re = confidence_ref(logits)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(ra))
    np.testing.assert_allclose(p, rp, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(m, rm, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(e, re, rtol=2e-3, atol=2e-4)
    flat_a, flat_m = np.asarray(a).reshape(-1), np.asarray(m).reshape(-1)
    vb = tiling(flat_a.size, vocab, jnp.dtype(dtype).itemsize)[1]
    for row, first, _ in ties:
        # equal maxima: the lower index wins and the margin is exactly 0
        assert flat_a[row] == (vb - 1 if first == "edge" else first)
        assert flat_m[row] == 0.0


def test_confidence_kernel_duplicate_max():
    """Ties for the top logit must give margin exactly 0."""
    logits = jnp.zeros((1, 8))  # all equal
    _, p, m, _ = confidence_fused(logits)
    np.testing.assert_allclose(m[0], 0.0, atol=1e-6)
    np.testing.assert_allclose(p[0], 1.0 / 8, rtol=1e-5)


def test_confidence_kernel_extreme_logits():
    """Large-magnitude logits: online softmax must not overflow."""
    logits = jnp.array([[1e4, -1e4, 0.0, 5.0] * 200])
    a, p, m, e = confidence_fused(logits)
    ra, rp, rm, re = confidence_ref(logits)
    assert int(a[0]) == int(ra[0])
    np.testing.assert_allclose(p, rp, rtol=1e-5)
    assert np.isfinite(np.asarray(e)).all()


@pytest.mark.parametrize("rows,vocab,itemsize", [
    (1024, 126464, 4), (1024, 126464, 2), (8, 126464, 4), (12, 1000, 2),
    (264, 65024, 4), (2048, 151936, 2), (512, 51865, 2), (3, 130, 4),
    (1, 8, 4), (40, 2000, 2),
])
def test_confidence_tiling_rule(rows, vocab, itemsize):
    rb, vb = tiling(rows, vocab, itemsize)
    assert rb % 8 == 0 and vb % LANES == 0 and vb <= MAX_CHUNKS * LANES
    assert rb <= -(-rows // 8) * 8           # never beyond the padded rows
    assert vb <= -(-vocab // LANES) * LANES  # nor the padded vocabulary
    assert vmem_bytes(rb, vb, itemsize) <= VMEM_LIMIT
    if vocab == 126464:
        # 128·988 has divisors near the block budget: no ragged block
        assert vocab % vb == 0
    if (rows, vocab, itemsize) == (1024, 126464, 4):
        steps = -(-rows // rb) * -(-vocab // vb)
        assert steps <= 300, steps
        assert vocab % vb == 0


ATTN_SHAPES = [
    (2, 100, 100, 2, 64, 0),
    (1, 256, 256, 1, 128, 0),
    (1, 300, 300, 2, 64, 50),     # banded + ragged
    (2, 128, 256, 1, 32, 0),      # cross lengths
    (1, 257, 257, 1, 64, 128),    # band wider than one tile
]


@pytest.mark.parametrize("b,lq,lk,h,d,w", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, lq, lk, h, d, w, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, lq, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, lk, h, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, lk, h, d)).astype(dtype)
    out = flash_attention(q, k, v, window=w)
    ref = attention_ref(q, k, v, window=w)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


SCAN_SHAPES = [
    (2, 300, 130, 16),    # ragged time + channel tiles
    (1, 256, 128, 8),     # exact tiles
    (2, 100, 64, 16),     # single partial tile
]


@pytest.mark.parametrize("b,l,di,n", SCAN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_selective_scan_kernel_matches_ref(b, l, di, n, dtype):
    ks = jax.random.split(jax.random.PRNGKey(l + di), 4)
    x = jax.random.normal(ks[0], (b, l, di)).astype(dtype)
    delta = jax.nn.softplus(
        jax.random.normal(ks[1], (b, l, di)) - 2).astype(dtype)
    bs = jax.random.normal(ks[2], (b, l, n)).astype(dtype)
    cs = jax.random.normal(ks[3], (b, l, n)).astype(dtype)
    a_log = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)
                    )[None].repeat(di, 0)
    y = selective_scan(x, delta, bs, cs, a_log)
    yr = selective_scan_ref(x, delta, bs, cs, a_log)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=tol, atol=tol)


def test_selective_scan_state_carries_across_tiles():
    """A constant drive with slow decay must accumulate monotonically far
    beyond one T_TILE boundary (state carried in scratch, not reset)."""
    b, l, di, n = 1, 600, 64, 4
    x = jnp.ones((b, l, di))
    delta = jnp.full((b, l, di), 0.01)
    bs = jnp.ones((b, l, n))
    cs = jnp.ones((b, l, n))
    a_log = jnp.full((di, n), -3.0)   # A ≈ -0.05: slow decay
    y = selective_scan(x, delta, bs, cs, a_log)
    assert float(y[0, 599, 0]) > float(y[0, 100, 0]) > float(y[0, 5, 0])


def test_flash_attention_band_excludes_far_tokens():
    """With window=1 every query attends only to itself."""
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    q = jax.random.normal(ks[0], (1, 140, 1, 16))
    v = jax.random.normal(ks[1], (1, 140, 1, 16))
    out = flash_attention(q, q, v, window=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(v),
                               rtol=1e-5, atol=1e-5)
