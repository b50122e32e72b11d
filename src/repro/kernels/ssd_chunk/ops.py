"""jit'd wrapper for the SSD intra-chunk kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.platform import resolve_interpret
from repro.kernels.ssd_chunk.ref import ssd_chunk_ref
from repro.kernels.ssd_chunk.ssd_chunk import ssd_chunk_pallas


@functools.partial(jax.jit, static_argnames=("interpret", "use_ref"))
def ssd_chunk(x, bm, cm, la, dt, *, interpret=None, use_ref: bool = False):
    if use_ref:
        return ssd_chunk_ref(x, bm, cm, la, dt)
    return tuple(ssd_chunk_pallas(x, bm, cm, la, dt,
                                  interpret=resolve_interpret(interpret)))
