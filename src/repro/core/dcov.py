"""Distance covariance / distance correlation (paper Eq. 1-4).

Székely & Rizzo (2009), "Brownian Distance Covariance". Given n paired
observations of a metric m and a hardware setting s:

    a_ij = ||m_i - m_j||,  b_ij = ||s_i - s_j||              (Eq. 1)
    A_ij = a_ij - ā_i. - ā_.j + ā_..   (double centering)    (Eq. 2)
    dCov²(m,s) = (1/n²) Σ_ij A_ij B_ij                        (Eq. 3)
    dCor(m,s)  = dCov(m,s) / sqrt(dCov(m,m)·dCov(s,s))        (Eq. 4)

dCor ∈ [0,1]; 0 iff statistically independent. The pure-jnp version below
is the reference; ``repro.kernels.dcov`` is the blocked Pallas TPU twin for
ORACLE-scale n (thousands of profiled configs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.contracts import check_dcor_state, contracts_enabled


def _mm(a: jax.Array, b: jax.Array) -> jax.Array:
    """Float32 contraction at full precision. A TPU runs float32 matmuls
    with bf16 passes by default; on a v5e that moved the 64-twin fleet's
    warm-start gain from 4.06 to 1.62. Pinned, the chip reproduces the
    CPU records exactly."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _pairwise_dist(x: jax.Array) -> jax.Array:
    """x: (n,) or (n,d) -> (n,n) euclidean distance matrix."""
    if x.ndim == 1:
        x = x[:, None]
    diff = x[:, None, :] - x[None, :, :]
    return jnp.sqrt(jnp.sum(diff.astype(jnp.float32) ** 2, axis=-1) + 0.0)


def _double_center(a: jax.Array) -> jax.Array:
    row = a.mean(axis=1, keepdims=True)
    col = a.mean(axis=0, keepdims=True)
    grand = a.mean()
    return a - row - col + grand


def dcov2(x: jax.Array, y: jax.Array) -> jax.Array:
    """Squared distance covariance (Eq. 3). Non-negative up to fp error."""
    A = _double_center(_pairwise_dist(x))
    B = _double_center(_pairwise_dist(y))
    return jnp.mean(A * B)


def dcor(x: jax.Array, y: jax.Array, eps: float = 1e-12) -> jax.Array:
    """Distance correlation (Eq. 4) in [0, 1]; 0 for degenerate inputs."""
    A = _double_center(_pairwise_dist(x))
    B = _double_center(_pairwise_dist(y))
    return dcor_from_sums(jnp.mean(A * B), jnp.mean(A * A), jnp.mean(B * B), eps)


@jax.jit
def dcor_jit(x: jax.Array, y: jax.Array) -> jax.Array:
    """Jitted scalar distance correlation of two (W,) samples."""
    return dcor(x, y)


def centered_distance_stack(cols: jax.Array, n_valid: jax.Array) -> jax.Array:
    """Double-centered distance matrices for every column at once.

    cols: (W, C) — C independent 1-d samples stacked column-wise; rows at
          index >= n_valid are padding and are masked out of every mean.
    returns: (W, W, C) stack of A matrices (Eq. 2), zero outside the valid
          n_valid × n_valid block, so any contraction over (i, j) equals the
          unpadded computation exactly.
    """
    w = cols.shape[0]
    valid = jnp.arange(w, dtype=jnp.int32) < n_valid
    mask = (valid[:, None] & valid[None, :]).astype(jnp.float32)
    d = jnp.abs(cols.astype(jnp.float32)[:, None, :] - cols[None, :, :])
    d = d * mask[:, :, None]
    inv_n = 1.0 / n_valid.astype(jnp.float32)
    row = d.sum(axis=1, keepdims=True) * inv_n
    col = d.sum(axis=0, keepdims=True) * inv_n
    grand = d.sum(axis=(0, 1)) * inv_n * inv_n
    return (d - row - col + grand[None, None, :]) * mask[:, :, None]


def dcor_from_sums(
    sab: jax.Array, saa: jax.Array, sbb: jax.Array, eps: float = 1e-12
) -> jax.Array:
    """dCor (Eq. 4) from ⟨A,B⟩ / ⟨A,A⟩ / ⟨B,B⟩ sums (broadcasting)."""
    denom = jnp.sqrt(jnp.maximum(saa * sbb, 0.0))
    val = jnp.sqrt(jnp.maximum(sab, 0.0) / jnp.maximum(denom, eps))
    return jnp.where(denom < eps, 0.0, jnp.clip(val, 0.0, 1.0))


@jax.jit
def dcor_all(settings: jax.Array, metrics: jax.Array, n_valid: jax.Array) -> jax.Array:
    """All (setting dim, metric dim) correlation weights in one device call.

    Each column's double-centered distance matrix is computed once and
    all D×M pairs fall out of ONE (C, C) Gram contraction over the
    flattened stack — replacing the per-pair loop that re-centered every
    column 2×D times per optimizer iteration. The op count matters as
    much as the FLOPs: the episode engine inlines this function into a
    ``lax.scan`` body, where every kernel launch is paid T times per
    episode, so the column means are computed once (|x_i − x_j| is
    symmetric, column means are row means transposed — bitwise, not just
    mathematically) and the three contraction groups collapse into a
    single small matmul.

    settings: (W, D) sliding window of D hardware parameters (padded to a
              fixed W so JIT compiles one shape; n_valid rows are real).
    metrics:  (W, M) matching window of M performance metrics.
    returns:  (D, M) dCor matrix — column 0 is α (throughput), column 1 is
              β (power) in the CORAL formulation (Eq. 9).
    """
    d = settings.shape[1]
    cols = jnp.concatenate(
        [settings.astype(jnp.float32), metrics.astype(jnp.float32)], axis=1
    )
    return dcor_all_cols(cols, n_valid, d)


def dcor_all_cols(cols: jax.Array, n_valid: jax.Array, d: int) -> jax.Array:
    """``dcor_all`` on a pre-stacked (W, D+M) column block — the episode
    engine stores its observation window in exactly this layout, so it
    skips the concatenation (and stays bitwise-aligned with the scalar
    path, which reaches the same block through ``dcor_all``)."""
    w, c = cols.shape
    cols = cols.astype(jnp.float32)
    n = jnp.asarray(n_valid)
    valid = jnp.arange(w, dtype=jnp.int32) < n
    mask = (valid[:, None] & valid[None, :]).astype(jnp.float32)
    dist = jnp.abs(cols[:, None, :] - cols[None, :, :]) * mask[:, :, None]
    inv_n = 1.0 / n.astype(jnp.float32)
    row = dist.sum(axis=1, keepdims=True) * inv_n
    col = jnp.swapaxes(row, 0, 1)
    grand = row.sum(axis=(0, 1)) * inv_n
    A = (dist - row - col + grand[None, None, :]) * mask[:, :, None]
    gram = _mm(A.reshape(w * w, c).T, A.reshape(w * w, c))
    diag = jnp.diagonal(gram)
    return dcor_from_sums(gram[:d, d:], diag[:d, None], diag[None, d:])


def dcor_numpy(x: np.ndarray, y: np.ndarray) -> float:
    """Convenience wrapper for host-side (optimizer-loop) use."""
    return float(dcor_jit(jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32)))


# ---------------------------------------------------------------------------
# Incremental windowed dCor (fleet hot path)
#
# ``dcor_all_cols`` rebuilds the full (W, W, C) distance stack every call
# — O(W²·C) per optimizer step. A sliding window only ever changes by one
# observation, and replacing ring slot k touches exactly row k and column
# k of every (symmetric) distance matrix, so the three sums dCor needs
# can be maintained instead of recomputed:
#
#     cross_ab = Σ_ij d^a_ij · d^b_ij          (C, C)
#     rows_i   = Σ_j  d_ij                     (W, C)   (row sums)
#     S        = Σ_ij d_ij = Σ_i rows_i        (C,)
#
# because for double-centered A (the masked Eq. 2 matrices):
#
#     Σ_ij A^a_ij A^b_ij
#       = cross_ab − (2/n)·Σ_i rows^a_i rows^b_i + S^a S^b / n²
#
# the standard dCov computing formula — every term is scale-consistent,
# and ``dcor_from_sums`` takes ratios, so the unnormalized sums feed it
# directly. One push is O(W·C) distance work plus two (W, C)ᵀ(W, C)
# matmuls: O(W·C²) total, independent of how the window got here. The
# (W, W, C) distance tensor rides along only so the *removed* row is
# available without recomputation.
# ---------------------------------------------------------------------------


def dcor_state_init(window: int, c: int) -> dict:
    """Empty incremental-dCor state for a (window, c)-shaped column
    block. Contract (core/contracts.py::DCOR_STATE_CONTRACT, enforced
    under REPRO_CONTRACTS=1): ``win: Float32[Array, "W C"]``, ``dist:
    Float32[Array, "W W C"]``, ``rows: Float32[Array, "W C"]``,
    ``cross: Float32[Array, "C C"]``."""
    f32 = jnp.float32
    state = {
        "win": jnp.zeros((window, c), f32),
        "dist": jnp.zeros((window, window, c), f32),
        "rows": jnp.zeros((window, c), f32),
        "cross": jnp.zeros((c, c), f32),
    }
    if contracts_enabled():  # trace-time check only
        check_dcor_state(state)
    return state


def dcor_state_from_window(cols: jax.Array, n_valid: jax.Array) -> dict:
    """Full O(W²·C) build — warm-start seeding and the test reference.

    cols: (W, C) column block; rows at index >= n_valid are padding.
    The result is bitwise what ``n_valid`` sequential pushes of the same
    rows into ``dcor_state_init`` produce (same masked |·| distances).
    """
    w, c = cols.shape
    cols = cols.astype(jnp.float32)
    valid = jnp.arange(w, dtype=jnp.int32) < n_valid
    mask = (valid[:, None] & valid[None, :]).astype(jnp.float32)
    dist = jnp.abs(cols[:, None, :] - cols[None, :, :]) * mask[:, :, None]
    flat = dist.reshape(w * w, c)
    state = {
        "win": cols * valid[:, None],
        "dist": dist,
        "rows": dist.sum(axis=1),
        "cross": _mm(flat.T, flat),
    }
    if contracts_enabled():  # trace-time check only
        check_dcor_state(state)
    return state


def dcor_state_push(state: dict, row: jax.Array, slot, n_filled) -> dict:
    """Replace ring slot ``slot`` with observation ``row`` — O(W·C²).

    ``n_filled`` is the number of filled slots *before* this push (the
    sequential ring discipline: slot = step mod W, n_filled = min(step,
    W), so the replaced slot is either the first empty one or the oldest
    filled one). Removing old row/column k subtracts its pair sums;
    adding the new one is a masked (W, C) distance row plus rank-1-style
    updates to the row sums and the (C, C) cross products.
    """
    w = state["win"].shape[0]
    idx = jnp.arange(w, dtype=jnp.int32)
    keep = ((idx < n_filled) & (idx != slot)).astype(jnp.float32)[:, None]
    old = state["dist"][slot]  # (W, C); zero at unfilled slots
    new = jnp.abs(row[None, :].astype(jnp.float32) - state["win"]) * keep
    cross = state["cross"] - 2.0 * _mm(old.T, old) + 2.0 * _mm(new.T, new)
    rows = state["rows"] - old + new
    rows = rows.at[slot].set(new.sum(axis=0))
    dist = state["dist"].at[slot].set(new)
    dist = dist.at[:, slot].set(new)
    out = {
        "win": state["win"].at[slot].set(row.astype(jnp.float32)),
        "dist": dist,
        "rows": rows,
        "cross": cross,
    }
    if contracts_enabled():  # trace-time check only
        check_dcor_state(out)
    return out


def dcor_state_corr(state: dict, n_valid: jax.Array, d: int) -> jax.Array:
    """The (D, M) dCor matrix from maintained sums — what ``dcor_all``
    returns for the same window contents, without touching (W, W)."""
    n = jnp.maximum(n_valid, 1).astype(jnp.float32)
    rows = state["rows"]
    grand = rows.sum(axis=0)
    sums = (
        state["cross"]
        - (2.0 / n) * _mm(rows.T, rows)
        + grand[:, None] * grand[None, :] / (n * n)
    )
    diag = jnp.diagonal(sums)
    return dcor_from_sums(sums[:d, d:], diag[:d, None], diag[None, d:])
