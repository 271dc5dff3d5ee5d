"""Runtime complement to the static host-sync rule.

tpulint proves what it can from the AST; this guard catches the rest
at runtime. With ``PINOT_TPU_DEBUG_TRANSFERS=1`` every per-segment
execution runs under ``jax.transfer_guard_device_to_host("disallow")``:
the explicit, batched ``jax.device_get`` per combine still works
(explicit transfers are always allowed), while any silent device→host
pull — a stray ``.item()``, ``np.asarray`` on a device array, printing
a device value — raises at the offending call site instead of shipping
as a per-query stall. Set the env var to ``log`` to trace instead of
raise. Off (the default) this is a zero-cost nullcontext.
"""
from __future__ import annotations

import contextlib
import os

ENV_VAR = "PINOT_TPU_DEBUG_TRANSFERS"


_OFF = ("", "0", "false", "no", "off")
_ON = ("1", "true", "yes", "on")
_MODES = ("allow", "log", "disallow")


def debug_transfer_guard():
    """Context manager guarding implicit device→host transfers."""
    mode = os.environ.get(ENV_VAR, "").lower()
    if mode in _OFF:
        return contextlib.nullcontext()
    if mode in _ON:
        mode = "disallow"
    elif mode not in _MODES:
        raise ValueError(
            f"{ENV_VAR}={mode!r}: expected one of "
            f"{_OFF + _ON + _MODES}")
    import jax
    return jax.transfer_guard_device_to_host(mode)
