"""Blocked Pallas TPU kernels for distance covariance (paper Eq. 1-3).

The O(n²) pairwise-distance computation is the paper's core compute. For
ORACLE-scale analyses (n = thousands of profiled configs) the n×n distance
matrices must not materialize in HBM. The kernel is batched over a column
set: given C 1-d samples stacked as (n, C), two passes over
(block_i × block_j) VMEM tiles shared across all columns:

  pass 1 (row sums):   r_c[i] = Σ_j |x_ci − x_cj| for every column c
  pass 2 (Gram):       G[c,c'] = Σ_ij A_c,ij · A_c',ij where
                       A_c,ij = a_c,ij − ā_c,i − ā_c,j + ā_c

The full C×C Gram matrix of ⟨A_c, A_c'⟩ sums falls out of one contraction
per tile (an MXU matmul over the flattened tile), so D settings × M metrics
correlation analyses cost one kernel launch instead of D·M pairwise ones.

Grid iteration on TPU is sequential over the minor axis, so accumulating
into the same output block across grid steps is the standard reduction
pattern (init at the first step).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Columns are padded to a multiple of the lane-friendly width; padded
# columns are all-zero → zero distance matrices → zero Gram rows, sliced
# away by the wrapper.
_COL_PAD = 8


# Canonical interpret-mode routing lives in repro.kernels.runtime; the
# names are re-exported here because this module hosted them first and
# benchmarks.common / tests still import them from this path.
from repro.kernels.runtime import (  # noqa: F401
    default_interpret,
    parse_interpret_env,
)


# VMEM budget for one (block, block, cpad) float32 intermediate, half of
# the 16 MiB scoped VMEM limit of a v5e core. Mosaic lays the last dim of
# that intermediate out on the 128 lanes, so a cpad=8 tile occupies 16×
# its logical bytes: block 256 asks for ~25 MiB of scoped VMEM and is
# refused, block 128 (8 MiB lane-padded) compiles.
_VMEM_BUDGET = 8 << 20
_LANES = 128


def _auto_block(n: int, cpad: int) -> int:
    """Largest power-of-two block edge whose lane-padded (b, b, cpad)
    float32 tile fits the VMEM budget (never larger than n, never
    smaller than 8). Callers that pass an explicit ``block`` keep it —
    this only drives the default, so window/grid sizes beyond one VMEM
    tile run the real blocked kernel instead of degrading to an
    oversized single tile."""
    lane_bytes = pl.cdiv(cpad, _LANES) * _LANES * 4
    block = 8
    while block * 2 <= max(n, 8) and (2 * block) ** 2 * lane_bytes <= _VMEM_BUDGET:
        block *= 2
    return block


def _row_sum_batch_kernel(ci_ref, cj_ref, rs_ref, *, n, b):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        rs_ref[...] = jnp.zeros_like(rs_ref)

    gi = i * b + jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    gj = j * b + jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
    mask = ((gi < n) & (gj < n)).astype(jnp.float32)  # (b, b)
    a = jnp.abs(ci_ref[...][:, None, :] - cj_ref[...][None, :, :])
    rs_ref[...] += (a * mask[:, :, None]).sum(axis=1)


def _gram_batch_kernel(
    ci_ref, cj_ref, rsi_ref, rsj_ref, g_ref, gram_ref, *, n, b
):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        gram_ref[...] = jnp.zeros_like(gram_ref)

    gi = i * b + jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    gj = j * b + jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
    mask = ((gi < n) & (gj < n)).astype(jnp.float32)
    inv_n = 1.0 / n
    grand = g_ref[...][0] * inv_n * inv_n  # (C,) per-column grand mean
    a = jnp.abs(ci_ref[...][:, None, :] - cj_ref[...][None, :, :])
    A = (
        a
        - rsi_ref[...][:, None, :] * inv_n
        - rsj_ref[...][None, :, :] * inv_n
        + grand[None, None, :]
    ) * mask[:, :, None]
    Af = A.reshape(b * b, A.shape[-1])
    gram_ref[...] += jnp.dot(Af.T, Af, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dcov_gram_pallas(
    cols, block: Optional[int] = None, interpret: Optional[bool] = None
):
    """Gram matrix of double-centered distance matrices for a column batch.

    cols: (n, C) float32 — C independent 1-d samples.
    returns: (C, C) where [c, c'] = Σ_ij A_c,ij · A_c',ij. Diagonal entries
    are the dVar sums; off-diagonals the dCov sums (both unnormalized — the
    caller divides by n² or cancels it in the dCor ratio).

    ``block=None`` picks the largest tile edge whose working set fits
    the VMEM budget for this column count (see ``_auto_block``), so
    ORACLE-scale n (thousands of rows) runs the real blocked kernel.
    """
    if interpret is None:
        interpret = default_interpret()
    n, c = cols.shape
    if block is None:
        block = _auto_block(n, pl.cdiv(c, _COL_PAD) * _COL_PAD)
    nb = pl.cdiv(n, block)
    npad = nb * block
    cpad = pl.cdiv(c, _COL_PAD) * _COL_PAD
    cp = jnp.pad(cols.astype(jnp.float32), ((0, npad - n), (0, cpad - c)))

    col = lambda i, j: (i, 0)
    row = lambda i, j: (j, 0)
    rs = pl.pallas_call(
        functools.partial(_row_sum_batch_kernel, n=n, b=block),
        grid=(nb, nb),
        in_specs=[
            pl.BlockSpec((block, cpad), col),
            pl.BlockSpec((block, cpad), row),
        ],
        out_specs=pl.BlockSpec((block, cpad), col),
        out_shape=jax.ShapeDtypeStruct((npad, cpad), jnp.float32),
        interpret=interpret,
    )(cp, cp)

    g = rs.sum(axis=0, keepdims=True)  # (1, C) per-column grand sums

    scalar = lambda i, j: (0, 0)
    gram = pl.pallas_call(
        functools.partial(_gram_batch_kernel, n=n, b=block),
        grid=(nb, nb),
        in_specs=[
            pl.BlockSpec((block, cpad), col),
            pl.BlockSpec((block, cpad), row),
            pl.BlockSpec((block, cpad), col),
            pl.BlockSpec((block, cpad), row),
            pl.BlockSpec((1, cpad), scalar),
        ],
        out_specs=pl.BlockSpec((cpad, cpad), scalar),
        out_shape=jax.ShapeDtypeStruct((cpad, cpad), jnp.float32),
        interpret=interpret,
    )(cp, cp, rs, rs, g)
    return gram[:c, :c]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dcov_sums_pallas(
    x, y, block: Optional[int] = None, interpret: Optional[bool] = None
):
    """Returns (Σ A·B, Σ A², Σ B²) for double-centered distance matrices.

    x, y: (n,) float32. Thin two-column wrapper over ``dcov_gram_pallas``.
    """
    cols = jnp.stack([x.astype(jnp.float32), y.astype(jnp.float32)], axis=1)
    gram = dcov_gram_pallas(cols, block=block, interpret=interpret)
    return gram[0, 1], gram[0, 0], gram[1, 1]
