"""Where a Pallas kernel runs: compiled on a TPU, interpreted elsewhere.

Every kernel entry point takes ``interpret: Optional[bool] = None`` and
resolves it here, so a call on the chip never falls back to the
interpreter unless the caller asks for it explicitly.
"""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``None`` -> compile on a TPU backend, interpret on any other."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
