"""The one sanctioned 64-bit constant constructor.

Written against the installed JAX (0.9): version shims do not live
here — a symbol the installed JAX lacks is tpulint's api-compat finding
at the call site, not a branch in this module.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def wide_i64(value):
    """A genuinely-64-bit int constant for math on int64/float64 lanes.

    A bare ``jnp.int64(x)`` is a lie when x64 is disabled: it silently
    builds an int32, and any mask/shift arithmetic written for 64-bit
    lanes truncates without a whisper (tpulint's dtype-drift rule exists
    for exactly this). This helper asserts the intent instead: the
    caller is operating on a lane whose dtype IS 64-bit, which can only
    happen with x64 enabled — calling it in 32-bit mode is a programmer
    error surfaced at trace time, not a silent truncation at query time.
    """
    if not jax.config.jax_enable_x64:
        raise AssertionError(
            "wide_i64 used while x64 is disabled — a 64-bit lane cannot "
            "exist here; the surrounding dtype dispatch is wrong")
    return jnp.int64(value)  # tpulint: disable=dtype-drift -- the one sanctioned 64-bit constructor: guarded by the x64 assertion above
