"""Where the Pallas kernels run: Mosaic on a TPU backend, the Pallas
interpreter elsewhere.  Every kernel resolves its `interpret` flag here,
so no kernel can fall back to the interpreter on the chip by default."""
from __future__ import annotations

from typing import Optional

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None (every kernel's default) compiles with Mosaic on a TPU backend
    and interprets elsewhere; an explicit bool is kept."""
    return not on_tpu() if interpret is None else bool(interpret)
