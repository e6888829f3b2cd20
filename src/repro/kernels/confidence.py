"""Fused decode-confidence Pallas kernel.

The decode-order hot spot: every sampler step reduces logits (B, L, V) —
V up to 152 k — to four per-position scalars (argmax token, max prob, top-2
margin, negative entropy).  A naive implementation materializes the full
softmax in HBM up to three times (softmax, top_k, entropy); at bf16 32 k × 152 k
logits that is ~28 GB of traffic per extra pass on a problem that is
strictly memory-bound (arithmetic intensity < 10 flops/byte « the 240
flop/byte v5e ridge point).

This kernel streams the vocab axis through VMEM **once**, maintaining
online-softmax accumulators per row:

    m   — running max logit          s  — Σ exp(l − m)
    u   — Σ l·exp(l − m)             (m₂, i₁) — top-2 value / argmax index

from which all four outputs are exact (no approximation):

    max_prob  = exp(m − m − log s)            = 1/s · exp(0)
    margin    = (exp(m−m) − exp(m₂−m)) / s
    neg_ent   = u/s − (m + log s)     since Σ p·log p = E[l] − logZ

Grid: (row_tiles, vocab_tiles) with the vocab axis innermost; accumulators
live in VMEM scratch and the outputs are written by the last vocab tile.
Block shapes are MXU/VPU aligned: (ROWS=8, VTILE=512) float32 ⇒ 16 KiB per
block, comfortably inside the ~16 MiB VMEM budget with double buffering.
The four outputs and the five accumulators are (ROWS, 1) columns, not
(ROWS,) vectors: Pallas TPU accepts a rank-1 block only when it spans the
whole array or a multiple of 128 lanes, and Mosaic refuses the 1-D int32
layout, while a 2-D block whose last dim equals the array's is legal.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8          # rows (positions) per block
VTILE = 512       # vocab lanes per block (128-multiple)
NEG = -3.4e38     # ~f32 lowest


def _confidence_kernel(logits_ref, argmax_ref, maxp_ref, margin_ref,
                       negent_ref, m_ref, s_ref, u_ref, m2_ref, i1_ref,
                       *, vocab: int, vtiles: int):
    vj = pl.program_id(1)

    @pl.when(vj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        u_ref[...] = jnp.zeros_like(u_ref)
        m2_ref[...] = jnp.full_like(m2_ref, NEG)
        i1_ref[...] = jnp.zeros_like(i1_ref)

    tile = logits_ref[...].astype(jnp.float32)            # (ROWS, VTILE)
    # mask lanes beyond the true vocab (ragged last tile)
    lane = vj * VTILE + jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    tile = jnp.where(lane < vocab, tile, NEG)

    # per-tile top-2 + argmax; every per-row value is a (ROWS, 1) column
    t1 = jnp.max(tile, axis=1, keepdims=True)
    ti = jnp.argmax(tile, axis=1, keepdims=True).astype(jnp.int32) \
        + vj * VTILE
    masked = jnp.where(tile >= t1, NEG, tile)             # drop (all) maxima
    t2 = jnp.max(masked, axis=1, keepdims=True)
    # duplicate maxima inside one tile: true top-2 equals the max
    dup = jnp.sum((tile >= t1).astype(jnp.int32), axis=1,
                  keepdims=True) > 1
    t2 = jnp.where(dup, t1, t2)

    m_old, s_old, u_old = m_ref[...], s_ref[...], u_ref[...]
    m2_old, i1_old = m2_ref[...], i1_ref[...]

    m_new = jnp.maximum(m_old, t1)
    # rescale old accumulators to the new max
    alpha = jnp.exp(m_old - m_new)                        # 0 when m_old=NEG
    ex = jnp.exp(tile - m_new)
    ex = jnp.where(lane < vocab, ex, 0.0)
    s_new = s_old * alpha + jnp.sum(ex, axis=1, keepdims=True)
    u_new = u_old * alpha + jnp.sum(tile * ex, axis=1, keepdims=True)
    # top-2 merge: candidates {m_old, m2_old, t1, t2} minus the new top-1
    take_new = t1 > m_old
    m2_new = jnp.where(take_new, jnp.maximum(m_old, t2),
                       jnp.maximum(m2_old, t1))
    i1_new = jnp.where(take_new, ti, i1_old)

    m_ref[...], s_ref[...], u_ref[...] = m_new, s_new, u_new
    m2_ref[...], i1_ref[...] = m2_new, i1_new

    @pl.when(vj == vtiles - 1)
    def _finish():
        logz = m_new + jnp.log(s_new)
        inv_s = 1.0 / s_new
        maxp = inv_s                                      # exp(m - m)/s
        p2 = jnp.exp(m2_new - m_new) * inv_s
        argmax_ref[...] = i1_new
        maxp_ref[...] = maxp
        margin_ref[...] = maxp - p2
        negent_ref[...] = u_new * inv_s - logz


@functools.partial(jax.jit, static_argnames=("interpret",))
def confidence_fused(logits: jnp.ndarray, interpret: bool = True
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                jnp.ndarray]:
    """(..., V) -> (argmax, max_prob, margin, neg_entropy), single HBM pass.

    ``interpret=True`` executes the kernel body in Python on CPU (this
    container's validation mode); on TPU pass ``interpret=False``.
    """
    shape = logits.shape
    v = shape[-1]
    rows = 1
    for d in shape[:-1]:
        rows *= d
    flat = logits.reshape(rows, v)
    pad_rows = (-rows) % ROWS
    if pad_rows:
        flat = jnp.pad(flat, ((0, pad_rows), (0, 0)))
    r = flat.shape[0]
    vtiles = -(-v // VTILE)

    kernel = functools.partial(_confidence_kernel, vocab=v, vtiles=vtiles)
    out_shape = [
        jax.ShapeDtypeStruct((r, 1), jnp.int32),    # argmax
        jax.ShapeDtypeStruct((r, 1), jnp.float32),  # max_prob
        jax.ShapeDtypeStruct((r, 1), jnp.float32),  # margin
        jax.ShapeDtypeStruct((r, 1), jnp.float32),  # neg_entropy
    ]
    row_spec = pl.BlockSpec((ROWS, 1), lambda i, j: (i, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(r // ROWS, vtiles),
        in_specs=[pl.BlockSpec((ROWS, VTILE), lambda i, j: (i, j))],
        out_specs=[row_spec] * 4,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((ROWS, 1), jnp.float32),  # m
            pltpu.VMEM((ROWS, 1), jnp.float32),  # s
            pltpu.VMEM((ROWS, 1), jnp.float32),  # u
            pltpu.VMEM((ROWS, 1), jnp.float32),  # m2
            pltpu.VMEM((ROWS, 1), jnp.int32),    # i1
        ],
        interpret=interpret,
    )(flat)
    argmax, maxp, margin, negent = outs

    def unflat(a):
        return a[:rows, 0].reshape(shape[:-1])

    return (unflat(argmax), unflat(maxp), unflat(margin), unflat(negent))
