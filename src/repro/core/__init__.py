"""Core: the paper's area-efficient FFT engine as plain transform bodies.

The jnp engines' pass programs, their 2D compositions, the ping-pong
stream and the pencil program, each under a concrete variant. Nothing
here plans or picks an engine.
"""

from repro.core.fft1d import (
    bit_reversal_permutation,
    butterfly_counts,
    fft_routing_tables,
)
from repro.core.fft2d import fftshift2, ifftshift2

__all__ = [
    "bit_reversal_permutation",
    "butterfly_counts",
    "fft_routing_tables",
    "fftshift2",
    "ifftshift2",
]
