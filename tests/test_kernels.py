"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.dcov import dcor_pallas, dcor_ref
from repro.kernels.flash_attention import attention_ref, flash_attention_bhsd
from repro.kernels.ssd_scan import ssd, ssd_ref

RNG = np.random.default_rng(0)


# ------------------------------------------------------------------- dcov
@pytest.mark.parametrize("n", [5, 63, 128, 300])
@pytest.mark.parametrize("block", [64, 128])
def test_dcov_kernel_matches_ref(n, block):
    x = jnp.asarray(RNG.normal(size=n), jnp.float32)
    y = jnp.asarray(x**2 + RNG.normal(size=n) * 0.1, jnp.float32)
    a = float(dcor_pallas(x, y, block=block))
    b = float(dcor_ref(x, y))
    assert a == pytest.approx(b, abs=1e-5)


def test_dcov_kernel_matches_core_dcor():
    from repro.core.dcov import dcor

    x = jnp.asarray(RNG.normal(size=200), jnp.float32)
    y = jnp.asarray(np.sin(np.asarray(x)) + RNG.normal(size=200) * 0.05)
    assert float(dcor_pallas(x, y)) == pytest.approx(float(dcor(x, y)), abs=1e-5)


# --------------------------------------------------------- flash attention
@pytest.mark.parametrize(
    "b,hq,hkv,s,d,causal,window",
    [
        (1, 2, 2, 64, 32, True, None),
        (2, 4, 2, 96, 32, True, None),  # GQA
        (1, 4, 1, 128, 16, True, 24),  # MQA + sliding window
        (2, 2, 2, 80, 32, False, None),  # bidirectional (whisper encoder)
        (1, 8, 2, 72, 64, True, 16),
    ],
)
def test_flash_attention_matches_ref(b, hq, hkv, s, d, causal, window):
    q = jnp.asarray(RNG.normal(size=(b, hq, s, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    out = flash_attention_bhsd(q, k, v, causal=causal, window=window,
                               block_q=32, block_k=32)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = jnp.asarray(RNG.normal(size=(1, 2, 64, 32)), dtype)
    k = jnp.asarray(RNG.normal(size=(1, 2, 64, 32)), dtype)
    v = jnp.asarray(RNG.normal(size=(1, 2, 64, 32)), dtype)
    out = flash_attention_bhsd(q, k, v, block_q=32, block_k=32)
    ref = attention_ref(q, k, v)
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=atol
    )
    assert out.dtype == dtype


def test_flash_attention_unpadded_tail():
    """Sequence not a multiple of the block size."""
    q = jnp.asarray(RNG.normal(size=(1, 2, 70, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 2, 70, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 2, 70, 32)), jnp.float32)
    out = flash_attention_bhsd(q, k, v, block_q=32, block_k=32)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ----------------------------------------------------------------- ssd scan
@pytest.mark.parametrize(
    "b,s,nh,hd,n,chunk",
    [(1, 32, 2, 16, 8, 8), (2, 64, 4, 16, 16, 16), (1, 48, 1, 8, 4, 16)],
)
def test_ssd_kernel_matches_ref(b, s, nh, hd, n, chunk):
    x = jnp.asarray(RNG.normal(size=(b, s, nh, hd)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(b, s, nh)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2, size=(nh,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    y1, s1 = ssd(x, dt, A, Bm, Cm, chunk=chunk)
    y2, s2 = ssd_ref(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)


def test_ssd_kernel_initial_state_chaining():
    """Running two halves with carried state == running the whole sequence."""
    b, s, nh, hd, n, chunk = 1, 32, 2, 8, 4, 8
    x = jnp.asarray(RNG.normal(size=(b, s, nh, hd)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(b, s, nh)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2, size=(nh,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    y_full, s_full = ssd(x, dt, A, Bm, Cm, chunk=chunk)
    h = s // 2
    y1, st = ssd(x[:, :h], dt[:, :h], A, Bm[:, :h], Cm[:, :h], chunk=chunk)
    y2, s_end = ssd(x[:, h:], dt[:, h:], A, Bm[:, h:], Cm[:, h:], chunk=chunk,
                    initial_state=st)
    np.testing.assert_allclose(np.asarray(y_full[:, h:]), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_full), np.asarray(s_end), atol=1e-4)


def test_ssd_decode_step_consistent_with_scan():
    """The recurrent decode step must equal the chunked scan one token at a
    time (the serve path vs the train path)."""
    from repro.models.ssm import ssd_chunked

    b, s, nh, hd, n = 1, 6, 2, 8, 4
    x = jnp.asarray(RNG.normal(size=(b, s, nh, hd)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(b, s, nh)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2, size=(nh,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    y_scan, _ = ssd_chunked(x, dt, A, Bm, Cm, chunk=4)
    state = jnp.zeros((b, nh, hd, n), jnp.float32)
    ys = []
    for t in range(s):
        dA = jnp.exp(dt[:, t] * A[None, :])
        dBx = jnp.einsum("bn,bh,bhp->bhpn", Bm[:, t], dt[:, t], x[:, t])
        state = state * dA[:, :, None, None] + dBx
        ys.append(jnp.einsum("bn,bhpn->bhp", Cm[:, t], state))
    y_step = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_scan), np.asarray(y_step), atol=1e-4)


@pytest.mark.parametrize(
    "n,cpad,block",
    [
        (5, 8, 8),
        (100, 8, 64),
        (2048, 8, 128),  # 256 would need ~25 MiB of a v5e's 16 MiB VMEM
        (2048, 136, 64),  # 136 columns pad to 256 lanes
    ],
)
def test_dcov_auto_block_counts_lane_padding(n, cpad, block):
    from repro.kernels.dcov.dcov import _auto_block

    assert _auto_block(n, cpad) == block


def test_interpret_mode_follows_the_backend(monkeypatch):
    """Off a TPU the kernels interpret; an env value that asks for the
    other mode is an error, never a silent switch."""
    from repro.kernels.runtime import default_interpret

    monkeypatch.delenv("PALLAS_INTERPRET", raising=False)
    assert default_interpret() is True
    monkeypatch.setenv("PALLAS_INTERPRET", "1")
    assert default_interpret() is True
    monkeypatch.setenv("PALLAS_INTERPRET", "0")
    with pytest.raises(RuntimeError, match="PALLAS_INTERPRET"):
        default_interpret()
