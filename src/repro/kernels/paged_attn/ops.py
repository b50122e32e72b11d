"""jit'd wrapper + backend dispatch for paged decode attention.

Two entry points:

* :func:`paged_attention` — standalone jit'd call (kernel tests, ad-hoc use).
* :func:`paged_attention_call` — un-jit'd dispatch for composition inside a
  larger jitted program (the engine's donated decode step traces it under
  ``lax.scan`` over layers).

Backends: ``pallas`` is the TPU kernel (runs in interpret mode off-TPU —
correct but slow, kept for parity tests; ``interpret=None`` lets
:func:`repro.kernels.platform.pallas_interpret` decide); ``ref`` is the
pure-jnp oracle,
which XLA compiles well on CPU/GPU.  ``auto`` picks pallas on TPU and ref
everywhere else.  Both are lengths-bounded only up to the page-table width,
so callers shrink ``page_table.shape[1]`` to the live maximum (the engine
buckets it to a power of two to bound retraces).

**Mesh-sharded serving**: when a ``repro.launch.pspec`` policy is active at
trace time and its ``kv_heads`` rule divides the pool's head axis, the
Pallas backend is wrapped in ``shard_map`` over the tensor-parallel axis —
paged decode attention is embarrassingly parallel across kv-head shards
(each shard holds its heads' pages and its queries' head group; the page
table and lengths are replicated), so the kernel runs per-device with no
collectives.  The ref backend needs no wrapping: GSPMD partitions the
gather + einsum along the annotated head axes (see ``ref.py``).
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import PartitionSpec as P

from repro.kernels.paged_attn.paged_attn import paged_attention_pallas
from repro.kernels.paged_attn.ref import paged_attention_ref
from repro.kernels.platform import pallas_interpret, resolve_interpret
from repro.launch.pspec import axis_divides, current_policy


def resolve_backend(backend: str = "auto") -> str:
    """'pallas' | 'ref' | 'auto' → concrete backend for this process."""
    if backend != "auto":
        return backend
    return "ref" if pallas_interpret() else "pallas"


def head_shard_axis(hq: int, hkv: int):
    """Mesh axis the active policy maps heads to, if it evenly divides both
    the query and kv head counts (GQA group size is preserved per shard);
    None when unsharded or not divisible (same ``axis_divides`` rule as
    every other guard)."""
    pol = current_policy()
    if pol is None:
        return None, None
    mesh, rules = pol
    ax = rules.get("kv_heads")
    if (ax is None or rules.get("heads") != ax or isinstance(ax, tuple)
            or ax not in mesh.axis_names
            or not axis_divides(mesh, ax, hq)
            or not axis_divides(mesh, ax, hkv)):
        return None, None
    return mesh, ax


def paged_attention_call(q, k_pool, v_pool, page_table, lengths, *,
                         k_scale=None, v_scale=None,
                         window: int = 0, backend: str = "ref",
                         interpret=None):
    """Dispatch without jit — safe to trace inside scan/jit.

    ``k_scale``/``v_scale`` (P, Hkv) fp32 select the int8-pool path on
    both backends (the pools are then int8 pages; dequantization happens
    inside the kernel / oracle, never as a separate pass)."""
    if backend == "ref":
        return paged_attention_ref(q, k_pool, v_pool, page_table, lengths,
                                   k_scale, v_scale, window=window)
    mesh, ax = head_shard_axis(q.shape[1], k_pool.shape[2])
    fn = functools.partial(paged_attention_pallas, window=window,
                           interpret=resolve_interpret(interpret))
    args = (q, k_pool, v_pool, page_table, lengths)
    in_specs = (P(None, ax, None), P(None, None, ax, None),
                P(None, None, ax, None), P(None, None), P(None))
    if k_scale is not None:
        args += (k_scale, v_scale)
        # scale rows shard with their pages: kv heads on the TP axis
        in_specs += (P(None, ax), P(None, ax))
    if mesh is not None:
        # per-shard pallas: heads/pages split on the TP axis, table and
        # lengths replicated; every shard computes its own softmax (heads
        # never mix), so out_specs need no reduction
        fn = jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs,
            out_specs=P(None, ax, None), check_vma=False)
    return fn(*args)


@functools.partial(jax.jit,
                   static_argnames=("window", "interpret", "use_ref"))
def paged_attention(q, k_pool, v_pool, page_table, lengths, *,
                    k_scale=None, v_scale=None,
                    window: int = 0, interpret=None,
                    use_ref: bool = False):
    return paged_attention_call(
        q, k_pool, v_pool, page_table, lengths,
        k_scale=k_scale, v_scale=v_scale, window=window,
        backend="ref" if use_ref else "pallas", interpret=interpret)
