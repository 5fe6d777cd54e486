"""The repo's one spelling of a 64-bit scope."""

from __future__ import annotations

import jax


def enable_x64():
    """Context manager inside which 64-bit dtypes survive every jnp op
    (outside it jax canonicalizes them down to 32 bits)."""
    return jax.enable_x64(True)
