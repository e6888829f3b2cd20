"""Fused decode-confidence Pallas kernel.

The decode-order hot spot: every sampler step reduces logits (B, L, V) —
V up to 152 k — to four per-position scalars (argmax token, max prob, top-2
margin, negative entropy).  A naive implementation materializes the full
softmax in HBM up to three times (softmax, top_k, entropy); at bf16 32 k × 152 k
logits that is ~28 GB of traffic per extra pass on a problem that is
strictly memory-bound (arithmetic intensity < 10 flops/byte « the 240
flop/byte v5e ridge point).

This kernel streams the vocab axis through VMEM **once**.  Each row keeps
online accumulators per lane (one of the 128 lanes of a vreg), carried in
VMEM scratch across the vocab blocks:

    a₁, c₁ — running max of the lane and the first 128-chunk holding it
    a₂     — running second value of the lane (ties count twice)
    s      — Σ exp(l − a₁)           u — Σ l·exp(l − a₁)

Per element that is five compares/selects for the top-2 and argmax and
four operations around one ``exp``; the cross-lane reductions run once per
row, after the last vocab block, where with M = max a₁ and w = exp(a₁ − M):

    argmax    = lowest c₁·128 + lane among the lanes with a₁ = M
    top-2     = max(max a₂, max of a₁ over the other lanes)
    S = Σ s·w,  U = Σ u·w,  log Z = M + log S
    max_prob  = 1/S,  margin = (1 − exp(top-2 − M))/S,  neg_ent = U/S − log Z

all exact (no approximation): equal maxima give margin 0, and the strict
``>`` of the running max keeps the lowest index, as ``jnp.argmax`` does.

Tiling (``tiling``) is a function of the operand's shape and dtype.  A
grid step has a fixed cost (a DMA issue and wait, the pipeline's
bookkeeping) of about a third of a microsecond on a v5e, so blocks are
large: up to 256 rows by a vocab block of about ``BLOCK_BYTES``, the vocab
block a multiple of 128 that divides the vocabulary where one does (only
the last block of a ragged vocabulary is masked).  At [1024, 126464] f32
that is 152 steps instead of the 31,616 of fixed (8, 512) blocks.  Inside
a block, rows are reduced ``ROW_GROUP`` at a time so that a group's
accumulators and one 128-lane chunk stay in vector registers.  A ragged
row edge is the grid's partial edge block: the logits are never padded or
copied in HBM, and the custom call's operand is the 2-D ``[rows, vocab]``
array the caller's logits flatten to.

The four outputs are (rows, 1) columns, not (rows,) vectors: Pallas TPU
accepts a rank-1 block only when it spans the whole array or a multiple of
128 lanes, and Mosaic refuses the 1-D int32 layout, while a 2-D block
whose last dim equals the array's is legal.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -3.4e38               # ~f32 lowest
LANES = 128
ROW_GROUP = 32              # rows reduced together in vector registers
MAX_ROW_BLOCK = 256
BLOCK_BYTES = 4 << 20       # target bytes of one logits block
MAX_CHUNKS = 64             # 128-lane chunks a block's unrolled body reads
VMEM_LIMIT = 32 << 20       # scoped VMEM the kernel asks for (v5e: 128 MiB)
N_STATE = 5                 # per-lane accumulators: a1, a2, c1, s, u
N_OUT = 4


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def tiling(rows: int, vocab: int, itemsize: int) -> Tuple[int, int]:
    """(row block, vocab block) for logits of shape (rows, vocab) whose
    elements take ``itemsize`` bytes.

    The row block is a multiple of 8: the rows rounded up to 8 while that
    is at most one ``ROW_GROUP``, else a multiple of ``ROW_GROUP`` up to
    ``MAX_ROW_BLOCK``.  The vocab block is a multiple of 128 of about
    ``BLOCK_BYTES`` per block and at most ``MAX_CHUNKS`` chunks of 128
    (the kernel body is unrolled over them): the largest that divides a
    vocabulary that is a multiple of 128 without falling below half that
    size, else the blocks are balanced and the last one is ragged."""
    rows8 = _cdiv(rows, 8) * 8
    if rows8 <= ROW_GROUP:
        row_block = rows8
    else:
        row_block = min(MAX_ROW_BLOCK, rows8 // ROW_GROUP * ROW_GROUP)
    chunks = _cdiv(vocab, LANES)
    budget = max(1, min(MAX_CHUNKS,
                        BLOCK_BYTES // (row_block * LANES * itemsize)))
    if chunks <= budget:
        return row_block, chunks * LANES
    if vocab % LANES == 0:
        for c in range(budget, budget // 2, -1):
            if chunks % c == 0:
                return row_block, c * LANES
    per_block = _cdiv(chunks, _cdiv(chunks, budget))
    return row_block, per_block * LANES


def vmem_bytes(row_block: int, vocab_block: int, itemsize: int) -> int:
    """VMEM the kernel holds at one tiling: the double-buffered logits
    block, the per-lane accumulators, and the double-buffered (rows, 1)
    outputs, each of which fills a 128-lane tile."""
    logits = 2 * row_block * vocab_block * itemsize
    state = N_STATE * row_block * LANES * 4
    outs = 2 * N_OUT * row_block * LANES * 4
    return logits + state + outs


def _confidence_kernel(x_ref, argmax_ref, maxp_ref, margin_ref, negent_ref,
                       a1_ref, a2_ref, c1_ref, s_ref, u_ref,
                       *, vocab: int, vocab_block: int, vocab_steps: int,
                       group: int):
    vj = pl.program_id(1)
    chunks = vocab_block // LANES
    tail = vocab - (vocab_steps - 1) * vocab_block   # valid lanes, last step

    @pl.when(vj == 0)
    def _init():
        a1_ref[...] = jnp.full_like(a1_ref, NEG)
        a2_ref[...] = jnp.full_like(a2_ref, NEG)
        c1_ref[...] = jnp.zeros_like(c1_ref)
        s_ref[...] = jnp.zeros_like(s_ref)
        u_ref[...] = jnp.zeros_like(u_ref)

    def block(valid: int, last: bool):
        """One vocab block whose first ``valid`` lanes hold logits."""
        n_chunks = _cdiv(valid, LANES)
        part = valid % LANES            # lanes of a ragged last chunk
        lane = jax.lax.broadcasted_iota(jnp.int32, (group, LANES), 1)

        def chunk(rows, k):
            x = x_ref[rows, pl.ds(k * LANES, LANES)].astype(jnp.float32)
            if part and k == n_chunks - 1:
                x = jnp.where(lane < part, x, NEG)
            return x

        def group_body(g, carry):
            rows = pl.ds(pl.multiple_of(g * group, group), group)
            a1_old = a1_ref[rows, :]
            a1, a2, c1 = a1_old, a2_ref[rows, :], c1_ref[rows, :]
            base = vj * chunks
            for k in range(n_chunks):
                x = chunk(rows, k)
                c1 = jnp.where(x > a1, base + k, c1)
                a2 = jnp.maximum(a2, jnp.minimum(a1, x))
                a1 = jnp.maximum(a1, x)
            f = jnp.exp(a1_old - a1)
            s, u = s_ref[rows, :] * f, u_ref[rows, :] * f
            for k in range(n_chunks):
                x = chunk(rows, k)
                e = jnp.exp(x - a1)
                if part and k == n_chunks - 1:
                    e = jnp.where(lane < part, e, 0.0)
                s = s + e
                u = u + x * e
            a1_ref[rows, :], a2_ref[rows, :], c1_ref[rows, :] = a1, a2, c1
            s_ref[rows, :], u_ref[rows, :] = s, u
            if last:
                top = jnp.max(a1, axis=1, keepdims=True)
                idx = c1 * LANES + lane
                i1 = jnp.min(jnp.where(a1 >= top, idx, jnp.int32(2**30)),
                             axis=1, keepdims=True)
                top2 = jnp.maximum(
                    jnp.max(a2, axis=1, keepdims=True),
                    jnp.max(jnp.where(idx == i1, NEG, a1), axis=1,
                            keepdims=True))
                w = jnp.exp(a1 - top)
                big_s = jnp.sum(s * w, axis=1, keepdims=True)
                big_u = jnp.sum(u * w, axis=1, keepdims=True)
                inv_s = 1.0 / big_s
                argmax_ref[rows, :] = i1
                maxp_ref[rows, :] = inv_s                # exp(M − M)/S
                margin_ref[rows, :] = inv_s - jnp.exp(top2 - top) * inv_s
                negent_ref[rows, :] = (big_u * inv_s
                                       - (top + jnp.log(big_s)))
            return carry

        n_groups = x_ref.shape[0] // group
        jax.lax.fori_loop(0, n_groups, group_body, 0)

    if vocab_steps > 1:
        pl.when(vj < vocab_steps - 1)(lambda: block(vocab_block, False))
    pl.when(vj == vocab_steps - 1)(lambda: block(tail, True))


@functools.partial(jax.jit, static_argnames=("interpret",))
def confidence_fused(logits: jnp.ndarray, interpret: bool = True
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                jnp.ndarray]:
    """(..., V) -> (argmax, max_prob, margin, neg_entropy), single HBM pass.

    ``interpret=True`` executes the kernel body in Python on CPU (the
    validation mode); on TPU pass ``interpret=False``.
    """
    shape = logits.shape
    v = shape[-1]
    rows = 1
    for d in shape[:-1]:
        rows *= d
    flat = logits.reshape(rows, v)
    row_block, vocab_block = tiling(rows, v, flat.dtype.itemsize)
    vocab_steps = _cdiv(v, vocab_block)
    kernel = functools.partial(_confidence_kernel, vocab=v,
                               vocab_block=vocab_block,
                               vocab_steps=vocab_steps,
                               group=min(ROW_GROUP, row_block))
    row_spec = pl.BlockSpec((row_block, 1), lambda i, j: (i, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(_cdiv(rows, row_block), vocab_steps),
        in_specs=[pl.BlockSpec((row_block, vocab_block),
                               lambda i, j: (i, j))],
        out_specs=[row_spec] * N_OUT,
        out_shape=[jax.ShapeDtypeStruct((rows, 1), dt) for dt in
                   (jnp.int32, jnp.float32, jnp.float32, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((row_block, LANES), dt) for dt in
                        (jnp.float32, jnp.float32, jnp.int32, jnp.float32,
                         jnp.float32)],                 # a1 a2 c1 s u
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(flat)
    return tuple(a[:, 0].reshape(shape[:-1]) for a in outs)
