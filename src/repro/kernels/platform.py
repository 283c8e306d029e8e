"""Where Pallas kernels run: the one place that decides interpret mode.

On ``cpu`` the kernels execute through the Pallas interpreter (exact
semantics; the test and CPU path).  On ``tpu`` they compile to Mosaic.
Any other platform has no kernel path and raises rather than falling back
to the interpreter, which would hide the device.
"""

from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel's ``interpret`` flag.

    ``None`` (every caller's default) decides from the platform JAX runs
    on; an explicit bool is passed through — the chip-compiler tests use
    ``False`` to compile for a described TPU from a CPU process."""
    if interpret is not None:
        return interpret
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run on 'tpu' (compiled) or 'cpu' (interpreted), "
        f"not on {platform!r}")
