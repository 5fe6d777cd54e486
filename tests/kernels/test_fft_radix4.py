"""Radix-4 fused panels + real-input kernels (interpret mode) vs oracles,
and the VMEM working-set accounting that gates the fused 2D path."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fft_radix2 import (
    fft2_fits_vmem,
    fft2_fused,
    fft_fused,
    pick_row_tile,
)
from repro.kernels.ops import (
    fft2_kernel,
    fft_kernel,
    hbm_traffic_model,
    irfft2_kernel,
    irfft_kernel,
    rfft2_kernel,
    rfft_kernel,
)

# ISSUE 2 acceptance sizes: radix-4 vs radix-2 vs the reference at these N.
PARITY_N = [8, 64, 1024]


@pytest.mark.parametrize("n", PARITY_N)
def test_radix4_fused_matches_jnp_fft(rng, n):
    """Radix-4 fused kernel ≤ 1e-4 max abs error vs jnp.fft.fft (scaled)."""
    x = (rng.standard_normal((4, n)) + 1j * rng.standard_normal((4, n))).astype(
        np.complex64
    )
    ref = np.asarray(jnp.fft.fft(jnp.asarray(x)))
    r4 = np.asarray(fft_kernel(jnp.asarray(x), radix=4, interpret=True))
    r2 = np.asarray(fft_kernel(jnp.asarray(x), radix=2, interpret=True))
    scale = max(1.0, np.max(np.abs(ref)))
    assert np.max(np.abs(r4 - ref)) / scale <= 1e-4
    assert np.max(np.abs(r4 - r2)) / scale <= 1e-4


# 1 << 15 is past the turned-block census: the four-step (N/128, 128) layout.
@pytest.mark.parametrize("n", [2, 4, 16, 32, 128, 512, 1 << 15])
def test_radix4_fused_all_parities(rng, n):
    """Odd log2(N) falls back to one radix-2 stage; every size stays exact."""
    x = (rng.standard_normal((2, n)) + 1j * rng.standard_normal((2, n))).astype(
        np.complex64
    )
    got = np.asarray(fft_kernel(jnp.asarray(x), radix=4, interpret=True))
    ref = np.fft.fft(np.asarray(x, np.complex128))
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)


@pytest.mark.parametrize("hw", [(8, 8), (16, 64), (128, 128)])
@pytest.mark.parametrize("radix", [2, 4])
def test_fused_2d_kernel_radix(rng, hw, radix):
    x = rng.standard_normal((2, *hw)).astype(np.float32)
    got = np.asarray(fft2_kernel(jnp.asarray(x), radix=radix, interpret=True))
    ref = np.fft.fft2(x)
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)


# 4096 and 1 << 15 take the four-step layout (complex kernel at full length).
@pytest.mark.parametrize("n", [2, 8, 64, 1024, 4096, 1 << 15])
@pytest.mark.parametrize("radix", [2, 4])
def test_rfft_kernel_matches_numpy(rng, n, radix):
    x = rng.standard_normal((3, n)).astype(np.float32)
    got = np.asarray(rfft_kernel(jnp.asarray(x), radix=radix, interpret=True))
    ref = np.fft.rfft(x)
    assert got.shape == ref.shape
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)
    rt = np.asarray(irfft_kernel(jnp.asarray(got), radix=radix, interpret=True))
    np.testing.assert_allclose(rt, x, atol=1e-4)


@pytest.mark.parametrize("hw", [(8, 8), (16, 64), (64, 16)])
@pytest.mark.parametrize("radix", [2, 4])
def test_rfft2_kernel_matches_numpy(rng, hw, radix):
    x = rng.standard_normal((2, *hw)).astype(np.float32)
    got = np.asarray(rfft2_kernel(jnp.asarray(x), radix=radix, interpret=True))
    ref = np.fft.rfft2(x)
    assert got.shape == ref.shape
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)
    rt = np.asarray(irfft2_kernel(jnp.asarray(got), radix=radix, interpret=True))
    np.testing.assert_allclose(rt, x, atol=1e-4)


# ------------------------- VMEM working-set accounting ---------------------


def test_fft2_fused_guard_counts_corner_turn_temporaries():
    """The budget census includes the transposed temporaries (8 frame-sized
    arrays), not just the 4 I/O panes the old guard assumed."""
    # 1024x512: 4 arrays fit the 8 MiB budget exactly, the true working set
    # (16 MiB) does not — exactly the silent-overflow regime the fix targets.
    assert 1024 * 512 * 4 * 4 <= 8 * 1024 * 1024
    assert not fft2_fits_vmem(1024, 512)
    with pytest.raises(ValueError, match="VMEM budget"):
        fft2_fused(jnp.zeros((1, 1024, 512)), jnp.zeros((1, 1024, 512)),
                   interpret=True)


def test_fft2_kernel_fails_over_to_unfused(rng):
    """Frames over budget route through the unfused row/turn/column path
    and stay correct instead of overflowing VMEM."""
    x = rng.standard_normal((1, 1024, 512)).astype(np.float32)
    assert not fft2_fits_vmem(1024, 512)
    got = np.asarray(fft2_kernel(jnp.asarray(x), interpret=True))
    ref = np.fft.fft2(x)
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)


def test_rfft2_kernel_fails_over_to_unfused(rng):
    x = rng.standard_normal((1, 512, 1024)).astype(np.float32)
    assert not fft2_fits_vmem(512, 1024, arrays=6)
    got = np.asarray(rfft2_kernel(jnp.asarray(x), interpret=True))
    ref = np.fft.rfft2(x)
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)
    rt = np.asarray(irfft2_kernel(jnp.asarray(got), interpret=True))
    np.testing.assert_allclose(rt, x, atol=1e-4)


def test_fft_fused_rejects_untileable_rows():
    """A row too long for even a 1-row VMEM tile raises instead of
    silently overflowing (the 1D kernels have no unfused failover)."""
    from repro.kernels.fft_radix2 import fft_fits_vmem

    n = 1 << 20
    assert not fft_fits_vmem(n)
    with pytest.raises(ValueError, match="VMEM budget"):
        fft_fused(jnp.zeros((1, n)), jnp.zeros((1, n)), interpret=True)


def test_fft2_kernel_failover_handles_untileable_rows(rng):
    """Rows too long for even a 1-row VMEM tile: the 2D failover composes
    the row pass with the jnp engine — a result, never an overflow."""
    from repro.kernels.fft_radix2 import fft_fits_vmem

    w = 1 << 19
    assert not fft_fits_vmem(w)
    x = rng.standard_normal((1, 2, w)).astype(np.float32)
    got = np.asarray(fft2_kernel(jnp.asarray(x), interpret=True))
    ref = np.fft.fft2(x)
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-4)
    gotr = np.asarray(rfft2_kernel(jnp.asarray(x), interpret=True))
    refr = np.fft.rfft2(x)
    np.testing.assert_allclose(gotr / scale, refr / scale, atol=1e-4)
    rt = np.asarray(irfft2_kernel(jnp.asarray(gotr), interpret=True))
    np.testing.assert_allclose(rt, x, atol=1e-3)


def test_irfft_discards_dc_and_nyquist_imag(rng):
    """np.fft.irfft parity: Im(Y[0]) and Im(Y[N/2]) are ignored."""
    from repro.core.rfft import irfft

    n = 16
    y = (rng.standard_normal((2, n // 2 + 1))
         + 1j * rng.standard_normal((2, n // 2 + 1))).astype(np.complex64)
    ref = np.fft.irfft(y, n=n)
    got = np.asarray(irfft(jnp.asarray(y)))
    np.testing.assert_allclose(got, ref, atol=1e-5)
    got_k = np.asarray(irfft_kernel(jnp.asarray(y), interpret=True))
    np.testing.assert_allclose(got_k, ref, atol=1e-5)


def test_pick_row_tile_counts_working_set():
    """Default census is 6 row-sized arrays (in+out+working), not 4."""
    t = pick_row_tile(1 << 20, 4096)
    assert t * 4096 * 4 * 6 <= 8 * 1024 * 1024
    # a caller declaring a smaller working set may tile larger
    assert pick_row_tile(1 << 20, 4096, arrays=4) >= t


def test_traffic_model_radix_and_realness():
    for n in (64, 1024, 4096):
        full = hbm_traffic_model(32, n, False)
        assert hbm_traffic_model(32, n, True) / full == 1 / np.log2(n)
        # radix-4 halves the staged pass count (ceil for odd log2 N)
        r4 = hbm_traffic_model(32, n, False, radix=4)
        assert r4 == full * np.ceil(np.log2(n) / 2) / np.log2(n)
        # the two-for-one real pack halves every pass
        assert hbm_traffic_model(32, n, False, real=True) == full // 2
