"""Where Pallas kernels run: compiled by Mosaic on a TPU, interpreted
everywhere else.

Every kernel wrapper and the serving engine ask :func:`pallas_interpret`
instead of deciding for themselves, so a caller that forgets the flag
still runs compiled on the chip, and the CPU test suite still runs the
kernels' bodies in the interpreter.  An ahead-of-time compile for a
described (not attached) TPU passes ``interpret=False`` explicitly: the
process's own backend is then the CPU.
"""
from __future__ import annotations

import jax


def pallas_interpret() -> bool:
    """True only off the TPU."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:
    """``None`` (the wrappers' default) -> :func:`pallas_interpret`."""
    return pallas_interpret() if interpret is None else bool(interpret)
