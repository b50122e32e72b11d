"""Paged KV pool (vLLM's PagedAttention adapted to TPU/XLA static shapes).

A fixed pool of pages per layer: ``(num_pages, page_size, Hkv, Dh)``.
Requests own page lists via a page table; lookup is gather-based (static
shapes, jit-friendly).  The pool backs the serving engine's per-request
caches and the paged decode-attention Pallas kernel.

Every pool write is a donated jit, so the engine reassigns ``pool.k/pool.v``
from the outputs and XLA updates the (aliased) buffers in place:
:func:`scatter_tokens` (dense-prefill splice-in), :func:`pool_link` (the
linker's ``link_paged`` placement and the engine's MRAG link: one donated
scatter per media segment, fed the library entry's own K/V at the
segment's exact length, with no padding), and the per-layer new-token
scatters inside the donated decode/prefill steps
(``models/transformer.decode_paged`` / ``selective_prefill_paged``).
Steady-state serving never copies the pool.

Mesh-sharded serving: construct with ``sharding=`` (kv heads on the
``model`` axis) and the buffers are committed to the mesh at creation
while every pool-owned write pins the same sharding on its outputs — the
pool never leaves the mesh, and reads (``gather``) stream only the local
kv-head slice per shard.

Int8 residency (``dtype="int8"``): the pages store int8 with one running
fp32 scale per ``(layer, page, kv_head)`` in sibling ``k_scale``/
``v_scale`` buffers (see :mod:`repro.cache.pagequant` for the write math
and the no-clip argument).  Every write path quantizes in its donated jit;
the paged attention kernels dequantize in-register from the same scale
buffers, so the pool is never materialized in fp — ~2x the warm tokens
per byte of a fp16 pool (scalar scales cost ``2*L*Hkv*4`` bytes per page
against ``2*L*ps*Hkv*Dh`` payload bytes).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import rope_relink

from .pagequant import quant_scatter


@dataclasses.dataclass
class PagedConfig:
    num_pages: int
    page_size: int
    num_layers: int
    num_kv_heads: int
    head_dim: int
    dtype: str = "bfloat16"

    @property
    def quantized(self) -> bool:
        return self.dtype == "int8"

    @property
    def page_nbytes(self) -> int:
        """HBM bytes one page costs (k + v payload, plus the per-page scale
        rows when int8) — the fixed-HBM benchmark's capacity denominator."""
        itemsize = {"int8": 1, "bfloat16": 2, "float16": 2}.get(self.dtype, 4)
        n = 2 * self.num_layers * self.page_size * self.num_kv_heads \
            * self.head_dim * itemsize
        if self.quantized:
            n += 2 * self.num_layers * self.num_kv_heads * 4
        return n


def _pool_link_impl(pool_k, pool_v, pages, offs, k_seg, v_seg, delta, *,
                    theta: float, relink: bool):
    """RoPE-relink one placed segment run on device and scatter it into the
    pool — the donated write shared by the engine's MRAG link and the
    linker's ``link_paged`` prefill placement (no dense intermediate)."""
    if relink:
        k_seg = rope_relink(k_seg, delta, theta)
    pool_k = pool_k.at[:, pages, offs].set(k_seg.astype(pool_k.dtype))
    pool_v = pool_v.at[:, pages, offs].set(v_seg.astype(pool_v.dtype))
    return pool_k, pool_v


def _scatter_tokens_impl(pool_k, pool_v, pages, offs, k_new, v_new):
    """Donated scatter of (L, S, H, Dh) tokens into (L, P, ps, H, Dh) pools.

    ``pages``/``offs`` are (S,) pool coordinates per token.  Duplicate
    targets (e.g. a shared scratch page absorbing padding writes) are legal
    scatter semantics — last write wins, and callers only ever point real
    tokens at unique slots.
    """
    pool_k = pool_k.at[:, pages, offs].set(k_new.astype(pool_k.dtype))
    pool_v = pool_v.at[:, pages, offs].set(v_new.astype(pool_v.dtype))
    return pool_k, pool_v


def _pool_link_q_impl(pool_k, pool_v, k_scale, v_scale, pages, offs,
                      k_seg, v_seg, delta, *, theta: float, relink: bool):
    """Quantized-pool variant of :func:`_pool_link_impl`: relink in fp,
    then quantize-on-write through the running page scales."""
    if relink:
        k_seg = rope_relink(k_seg, delta, theta)
    return quant_scatter(pool_k, pool_v, k_scale, v_scale, pages, offs,
                         k_seg, v_seg)


def _pool_link_q8_impl(pool_k, pool_v, k_scale, v_scale, pages, offs,
                       qk_seg, qk_scale, qv_seg, qv_scale, seg_ids, delta,
                       *, theta: float, relink: bool):
    """Spool→pool zero-copy link: the segments arrive as the library's int8
    bytes plus their per-segment spool scales, and are rescaled onto the
    page grid inside this one donated jit — no host dequantize→requantize
    round trip and no fp copy of the block ever leaves the device.

    ``qk_seg``/``qv_seg`` (L, S, H, Dh) int8; ``qk_scale``/``qv_scale``
    (L, nseg, H, Dh) fp32 whole-sequence spool scales; ``seg_ids`` (S,)
    maps each token to its segment's scale row.  RoPE relinking (K only)
    rotates channel pairs, so K goes through in-register fp either way; V
    is a pure rescale.
    """
    k_seg = qk_seg.astype(jnp.float32) * qk_scale[:, seg_ids]
    v_seg = qv_seg.astype(jnp.float32) * qv_scale[:, seg_ids]
    if relink:
        k_seg = rope_relink(k_seg, delta, theta)
    return quant_scatter(pool_k, pool_v, k_scale, v_scale, pages, offs,
                         k_seg, v_seg)


def _scatter_tokens_q_impl(pool_k, pool_v, k_scale, v_scale, pages, offs,
                           k_new, v_new):
    """Quantized-pool variant of :func:`_scatter_tokens_impl`."""
    return quant_scatter(pool_k, pool_v, k_scale, v_scale, pages, offs,
                         k_new, v_new)


def _reset_scales_impl(k_scale, v_scale, pages):
    """Zero the scale rows of freed pages so a new tenant's running amax
    starts fresh (and the first write's requantize pass wipes the stale
    int8 bytes — see :func:`repro.cache.pagequant._requant_pages`)."""
    return (k_scale.at[:, pages].set(0.0), v_scale.at[:, pages].set(0.0))


def _cow_copy_impl(pool_k, pool_v, src, dst):
    """Donated whole-page copy ``src[i] -> dst[i]`` — the copy-on-write
    resolution for forked sessions.  All reads gather from the *input*
    buffers before any scatter lands, so a batch of copies is order-free;
    padding pairs repeat ``(src[0], dst[0])`` — duplicate writes of the
    same value, safe under undefined scatter order."""
    pool_k = pool_k.at[:, dst].set(pool_k[:, src])
    pool_v = pool_v.at[:, dst].set(pool_v[:, src])
    return pool_k, pool_v


def _cow_copy_q_impl(pool_k, pool_v, k_scale, v_scale, src, dst):
    """Int8-pool CoW copy: the per-page fp32 scale rows travel with the
    page bytes, so the duplicate dequantizes to exactly the shared
    original."""
    pool_k = pool_k.at[:, dst].set(pool_k[:, src])
    pool_v = pool_v.at[:, dst].set(pool_v[:, src])
    k_scale = k_scale.at[:, dst].set(k_scale[:, src])
    v_scale = v_scale.at[:, dst].set(v_scale[:, src])
    return pool_k, pool_v, k_scale, v_scale


def _adopt_pages_impl(pool_k, pool_v, pages, k_pages, v_pages):
    """Donated whole-page restore for session thaw: ``k_pages``/``v_pages``
    (L, n, ps, H, Dh) land verbatim on ``pages``.  Duplicate entries (the
    scratch-page padding) all carry the caller's pad content for that page,
    so the undefined scatter winner cannot matter for real pages."""
    pool_k = pool_k.at[:, pages].set(k_pages.astype(pool_k.dtype))
    pool_v = pool_v.at[:, pages].set(v_pages.astype(pool_v.dtype))
    return pool_k, pool_v


def _adopt_pages_q_impl(pool_k, pool_v, k_scale, v_scale, pages,
                        qk_pages, k_rows, qv_pages, v_rows):
    """Int8 thaw restore: raw int8 page bytes plus their per-page scale
    rows are written back exactly as frozen — no dequantize→requantize
    round trip, so a thawed int8 session is bit-identical to the pool
    state at freeze time."""
    pool_k = pool_k.at[:, pages].set(qk_pages)
    pool_v = pool_v.at[:, pages].set(qv_pages)
    k_scale = k_scale.at[:, pages].set(k_rows)
    v_scale = v_scale.at[:, pages].set(v_rows)
    return pool_k, pool_v, k_scale, v_scale


# module-level (unsharded) jits — sharded pools build their own instance
# jits with pinned out_shardings, so the constraint never leaks into these
# shared compile caches
pool_link = functools.partial(jax.jit, donate_argnums=(0, 1),
                              static_argnames=("theta", "relink"))(
    _pool_link_impl)
scatter_tokens = functools.partial(
    jax.jit, donate_argnums=(0, 1))(_scatter_tokens_impl)
pool_link_q = functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3),
                                static_argnames=("theta", "relink"))(
    _pool_link_q_impl)
pool_link_q8 = functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3),
                                 static_argnames=("theta", "relink"))(
    _pool_link_q8_impl)
scatter_tokens_q = functools.partial(
    jax.jit, donate_argnums=(0, 1, 2, 3))(_scatter_tokens_q_impl)
reset_scales = functools.partial(
    jax.jit, donate_argnums=(0, 1))(_reset_scales_impl)
cow_copy = functools.partial(
    jax.jit, donate_argnums=(0, 1))(_cow_copy_impl)
cow_copy_q = functools.partial(
    jax.jit, donate_argnums=(0, 1, 2, 3))(_cow_copy_q_impl)
adopt_pages = functools.partial(
    jax.jit, donate_argnums=(0, 1))(_adopt_pages_impl)
adopt_pages_q = functools.partial(
    jax.jit, donate_argnums=(0, 1, 2, 3))(_adopt_pages_q_impl)


def _bucket_pow2(n: int) -> int:
    """Next power of two ≥ n — bounds the trace count of the per-free
    scale-reset jit the same way core.linker buckets prefill selections."""
    return 1 << max(n - 1, 0).bit_length()


class PagedKVPool:
    def __init__(self, cfg: PagedConfig, *, sharding=None,
                 scale_sharding=None):
        """``sharding``: optional :class:`jax.sharding.NamedSharding` for
        the pool buffers (kv heads on the mesh's ``model`` axis — see
        ``repro.serving.sharding.ServingSharding.pool``).  When set, the
        buffers are committed to it at construction and every pool-owned
        donated write pins the same sharding on its outputs, so the pool
        stays resident and partitioned across devices for the whole
        serving lifetime.  ``scale_sharding`` is the (L, P, Hkv) analogue
        for an int8 pool's scale buffers
        (``ServingSharding.pool_scale``)."""
        self.cfg = cfg
        self.quantized = cfg.quantized
        dt = jnp.int8 if self.quantized else {
            "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}.get(cfg.dtype, jnp.float32)
        shape = (cfg.num_layers, cfg.num_pages, cfg.page_size,
                 cfg.num_kv_heads, cfg.head_dim)
        self.sharding = sharding
        self.scale_sharding = scale_sharding
        # allocate straight into the sharded layout: a sharded pool must
        # never materialize unsharded on one device first — at production
        # scale the whole point is that the pool exceeds a single chip's HBM
        self.k = jnp.zeros(shape, dt, device=sharding)
        self.v = jnp.zeros(shape, dt, device=sharding)
        # int8 pools carry one running fp32 scale per (layer, page, kv head)
        # beside the pages; zero means "never written since (re)alloc"
        self.k_scale = self.v_scale = None
        if self.quantized:
            sshape = (cfg.num_layers, cfg.num_pages, cfg.num_kv_heads)
            self.k_scale = jnp.zeros(sshape, jnp.float32,
                                     device=scale_sharding)
            self.v_scale = jnp.zeros(sshape, jnp.float32,
                                     device=scale_sharding)
        if sharding is not None:
            out_sh = (sharding, sharding)
            out_qsh = out_sh + (scale_sharding, scale_sharding)
            self._link_jit = jax.jit(
                _pool_link_impl, donate_argnums=(0, 1),
                static_argnames=("theta", "relink"), out_shardings=out_sh)
            self._scatter_jit = jax.jit(
                _scatter_tokens_impl, donate_argnums=(0, 1),
                out_shardings=out_sh)
            self._link_q_jit = jax.jit(
                _pool_link_q_impl, donate_argnums=(0, 1, 2, 3),
                static_argnames=("theta", "relink"), out_shardings=out_qsh)
            self._link_q8_jit = jax.jit(
                _pool_link_q8_impl, donate_argnums=(0, 1, 2, 3),
                static_argnames=("theta", "relink"), out_shardings=out_qsh)
            self._scatter_q_jit = jax.jit(
                _scatter_tokens_q_impl, donate_argnums=(0, 1, 2, 3),
                out_shardings=out_qsh)
            self._reset_jit = jax.jit(
                _reset_scales_impl, donate_argnums=(0, 1),
                out_shardings=(scale_sharding, scale_sharding))
            self._cow_jit = jax.jit(
                _cow_copy_impl, donate_argnums=(0, 1), out_shardings=out_sh)
            self._cow_q_jit = jax.jit(
                _cow_copy_q_impl, donate_argnums=(0, 1, 2, 3),
                out_shardings=out_qsh)
            self._adopt_jit = jax.jit(
                _adopt_pages_impl, donate_argnums=(0, 1),
                out_shardings=out_sh)
            self._adopt_q_jit = jax.jit(
                _adopt_pages_q_impl, donate_argnums=(0, 1, 2, 3),
                out_shardings=out_qsh)
        else:
            self._link_jit = pool_link
            self._scatter_jit = scatter_tokens
            self._link_q_jit = pool_link_q
            self._link_q8_jit = pool_link_q8
            self._scatter_q_jit = scatter_tokens_q
            self._reset_jit = reset_scales
            self._cow_jit = cow_copy
            self._cow_q_jit = cow_copy_q
            self._adopt_jit = adopt_pages
            self._adopt_q_jit = adopt_pages_q
        self._free: List[int] = list(range(cfg.num_pages - 1, -1, -1))
        self._owned: Dict[str, List[int]] = {}
        # session CoW bookkeeping: a page's refcount is the number of owner
        # lists it appears on (absent == free).  ``fork`` bumps it, ``free``
        # decrements, and only a zero-ref page returns to the free stack;
        # ``make_exclusive`` resolves a write into a shared page by copying
        # it first.  ``cow_copies``/``pages_shared`` are the cumulative
        # counters the session benchmarks and KVLibrary.stats() surface.
        self._refs: Dict[int, int] = {}
        self.cow_copies = 0
        self.pages_shared = 0

    # -- allocation --------------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.cfg.page_size)

    def owned_pages(self, req_id: str) -> int:
        return len(self._owned.get(req_id, []))

    def capacity(self, req_id: str) -> int:
        """Tokens the request's current page list can hold."""
        return self.owned_pages(req_id) * self.cfg.page_size

    def page_ref(self, page: int) -> int:
        """Current refcount of one page (0 == free / unknown)."""
        return self._refs.get(page, 0)

    def alloc(self, req_id: str, n_tokens: int) -> Optional[np.ndarray]:
        need = self.pages_for(n_tokens)
        if need > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(need)]
        for p in pages:
            self._refs[p] = 1
        self._owned.setdefault(req_id, []).extend(pages)
        return np.asarray(self._owned[req_id], np.int32)

    def extend(self, req_id: str, n_more_tokens: int, cur_tokens: int
               ) -> Optional[np.ndarray]:
        have = self.owned_pages(req_id)
        need = self.pages_for(cur_tokens + n_more_tokens) - have
        if need > len(self._free):
            return None
        for _ in range(max(need, 0)):
            p = self._free.pop()
            self._refs[p] = 1
            self._owned.setdefault(req_id, []).append(p)
        return np.asarray(self._owned[req_id], np.int32)

    def free(self, req_id: str) -> None:
        """Drop a request's hold on its pages.  Idempotent: a second
        ``free`` (or one for an unknown request) is a no-op, never a
        double-release.  A page shared with a forked sibling (refcount
        > 1) merely loses one reference; only the last hold returns it to
        the free stack.  On an int8 pool the *released* pages' scale rows
        are zeroed (one donated jit, pow2-bucketed page count) so the next
        tenant's running amax starts fresh instead of inheriting a stale
        large scale."""
        pages = self._owned.pop(req_id, [])
        released = []
        for p in pages:
            r = self._refs.get(p, 1) - 1
            if r <= 0:
                self._refs.pop(p, None)
                released.append(p)
            else:
                self._refs[p] = r
        self._free.extend(released)
        if released and self.quantized:
            n = _bucket_pow2(len(released))
            padded = released + [released[0]] * (n - len(released))
            arr = jnp.asarray(np.asarray(padded, np.int32))
            self.k_scale, self.v_scale = self._reset_jit(
                self.k_scale, self.v_scale, arr)

    # -- session fork / copy-on-write --------------------------------------
    def fork(self, parent_req: str, child_reqs: List[str]) -> None:
        """Register every child as a co-owner of the parent's page list.

        Zero pages move and zero bytes copy: each child's page table is the
        parent's, with every page's refcount bumped.  The first *write* a
        child makes into a still-shared page goes through
        :meth:`make_exclusive`, which duplicates just that page.  Children
        must not already own pages (their tables would be clobbered)."""
        pages = self._owned.get(parent_req)
        if pages is None:
            raise KeyError(f"fork: unknown parent request {parent_req!r}")
        for child in child_reqs:
            if child in self._owned:
                raise ValueError(f"fork: child {child!r} already owns pages")
            self._owned[child] = list(pages)
            for p in pages:
                self._refs[p] = self._refs.get(p, 0) + 1
        self.pages_shared += len(pages) * len(child_reqs)

    def make_exclusive(self, req_id: str, first_token: int,
                       n_tokens: int = 1) -> Optional[np.ndarray]:
        """Guarantee the pages covering ``[first_token, first_token +
        n_tokens)`` are exclusively owned before a write lands in them.

        Shared pages (refcount > 1) are duplicated through one donated
        ``cow_copy`` jit (page bytes, plus the scale rows on an int8 pool)
        and swapped into this request's table; the sibling keeps the
        original.  Returns the request's (possibly updated) page array, or
        ``None`` when the pool cannot supply the copies — the caller treats
        that exactly like an ``extend`` failure.  A request with nothing
        shared in range pays two dict probes per covered page and no
        device work."""
        pages = self._owned.get(req_id)
        if pages is None:
            return None
        ps = self.cfg.page_size
        lo = first_token // ps
        hi = min((first_token + max(n_tokens, 1) - 1) // ps, len(pages) - 1)
        shared = [i for i in range(lo, hi + 1)
                  if self._refs.get(pages[i], 1) > 1]
        if not shared:
            return np.asarray(pages, np.int32)
        if len(shared) > len(self._free):
            return None
        src, dst = [], []
        for i in shared:
            old = pages[i]
            new = self._free.pop()
            src.append(old)
            dst.append(new)
            self._refs[old] -= 1
            self._refs[new] = 1
            pages[i] = new
        n = _bucket_pow2(len(src))
        src_arr = jnp.asarray(np.asarray(
            src + [src[0]] * (n - len(src)), np.int32))
        dst_arr = jnp.asarray(np.asarray(
            dst + [dst[0]] * (n - len(dst)), np.int32))
        if self.quantized:
            self.k, self.v, self.k_scale, self.v_scale = self._cow_q_jit(
                self.k, self.v, self.k_scale, self.v_scale, src_arr, dst_arr)
        else:
            self.k, self.v = self._cow_jit(self.k, self.v, src_arr, dst_arr)
        self.cow_copies += len(src)
        return np.asarray(pages, np.int32)

    # -- data movement -----------------------------------------------------
    def link_write(self, pages, offs, k_seg, v_seg, delta, *, theta: float,
                   relink: bool) -> None:
        """Relink + scatter one placed run through the pool-owned donated
        jit (sharding-preserving on sharded pools; quantize-on-write on an
        int8 pool)."""
        if self.quantized:
            self.k, self.v, self.k_scale, self.v_scale = self._link_q_jit(
                self.k, self.v, self.k_scale, self.v_scale, pages, offs,
                k_seg, v_seg, delta, theta=theta, relink=relink)
        else:
            self.k, self.v = self._link_jit(self.k, self.v, pages, offs,
                                            k_seg, v_seg, delta, theta=theta,
                                            relink=relink)

    def link_write_q8(self, pages, offs, qk_seg, qk_scale, qv_seg, qv_scale,
                      seg_ids, delta, *, theta: float,
                      relink: bool) -> None:
        """Spool→pool fast path: link already-quantized segments (library
        int8 bytes + their per-segment spool scales) by rescaling onto the
        page grid inside one donated jit.  Only valid on an int8 pool."""
        if not self.quantized:
            raise ValueError("link_write_q8 requires an int8 pool")
        self.k, self.v, self.k_scale, self.v_scale = self._link_q8_jit(
            self.k, self.v, self.k_scale, self.v_scale, pages, offs,
            qk_seg, qk_scale, qv_seg, qv_scale, seg_ids, delta,
            theta=theta, relink=relink)

    def write_tokens(self, page_table: np.ndarray, slot0: int,
                     k_new: jnp.ndarray, v_new: jnp.ndarray) -> None:
        """Scatter (L, S, H, Dh) tokens into the pool starting at ``slot0``."""
        s = k_new.shape[1]
        ps = self.cfg.page_size
        slots = slot0 + np.arange(s)
        pages = jnp.asarray(np.asarray(page_table)[slots // ps], jnp.int32)
        offs = jnp.asarray(slots % ps, jnp.int32)
        if self.quantized:
            self.k, self.v, self.k_scale, self.v_scale = self._scatter_q_jit(
                self.k, self.v, self.k_scale, self.v_scale, pages, offs,
                k_new, v_new)
        else:
            self.k, self.v = self._scatter_jit(self.k, self.v, pages, offs,
                                               k_new, v_new)

    def gather(self, page_table: np.ndarray, n_tokens: int):
        """Contiguous (L, n_tokens, H, Dh) view of a request's cache.
        An int8 pool hands back the dequantized fp32 view — gather is the
        debug/inspection path, not the serving read (the kernels read the
        int8 pages + scales directly)."""
        ps = self.cfg.page_size
        slots = np.arange(n_tokens)
        pages = np.asarray(page_table)[slots // ps]
        offs = slots % ps
        k = self.k[:, pages, offs]
        v = self.v[:, pages, offs]
        if self.quantized:
            k = k.astype(jnp.float32) * self.k_scale[:, pages][..., None]
            v = v.astype(jnp.float32) * self.v_scale[:, pages][..., None]
        return k, v

    # -- session freeze / thaw ---------------------------------------------
    def export_session(self, page_table: np.ndarray, n_tokens: int) -> dict:
        """Snapshot a request's live KV for the session store.

        fp pools return ``{"k", "v"}`` trimmed to ``n_tokens`` (stale
        bytes past the live length must never leave the pool — they can
        belong to a previous tenant).  Int8 pools return the *raw* page
        bytes ``{"qk", "qv"}`` (L, npages·ps, H, Dh) with the tail beyond
        ``n_tokens`` zeroed, plus ``{"k_scale", "v_scale"}`` per-page rows
        (L, npages, H) — re-adopting those via :meth:`adopt_session`
        restores the pool bit-identically, so a thawed int8 session
        decodes exactly like one that was never frozen."""
        ps = self.cfg.page_size
        npages = self.pages_for(n_tokens)
        pages = np.asarray(page_table)[:npages]
        if not self.quantized:
            k, v = self.gather(page_table, n_tokens)
            return {"k": np.asarray(k), "v": np.asarray(v)}
        L = self.cfg.num_layers
        qk = np.array(self.k[:, pages])          # (L, npages, ps, H, Dh)
        qv = np.array(self.v[:, pages])
        qk = qk.reshape(L, npages * ps, *qk.shape[3:])
        qv = qv.reshape(L, npages * ps, *qv.shape[3:])
        qk[:, n_tokens:] = 0
        qv[:, n_tokens:] = 0
        return {"qk": qk, "qv": qv,
                "k_scale": np.asarray(self.k_scale[:, pages]),
                "v_scale": np.asarray(self.v_scale[:, pages])}

    def adopt_session(self, page_table: np.ndarray, snap: dict,
                      scratch_page: int) -> None:
        """Write an :meth:`export_session` snapshot back into this
        request's pages through one donated jit (whole-page restore; the
        page count pads to its pow2 bucket with writes to the scratch
        page, which absorbs garbage by design).  Int8 snapshots restore
        raw bytes + scale rows — no dequantize→requantize round trip."""
        ps = self.cfg.page_size
        if self.quantized:
            qk, qv = snap["qk"], snap["qv"]
            npages = qk.shape[1] // ps
            pages = list(np.asarray(page_table)[:npages])
            n = _bucket_pow2(npages)
            pad = n - npages
            idx = jnp.asarray(np.asarray(pages + [scratch_page] * pad,
                                         np.int32))
            def _pages(a):   # (L, npages*ps, H, Dh) -> padded (L, n, ps, ...)
                a = np.asarray(a).reshape(a.shape[0], npages, ps, *a.shape[2:])
                if pad:
                    a = np.concatenate(
                        [a, np.zeros((a.shape[0], pad) + a.shape[2:],
                                     a.dtype)], axis=1)
                return jnp.asarray(a)
            def _rows(s):    # (L, npages, H) -> padded (L, n, H)
                s = np.asarray(s, np.float32)
                if pad:
                    s = np.concatenate(
                        [s, np.zeros((s.shape[0], pad, s.shape[2]),
                                     np.float32)], axis=1)
                return jnp.asarray(s)
            (self.k, self.v,
             self.k_scale, self.v_scale) = self._adopt_q_jit(
                self.k, self.v, self.k_scale, self.v_scale, idx,
                _pages(qk), _rows(snap["k_scale"]),
                _pages(qv), _rows(snap["v_scale"]))
            return
        k, v = np.asarray(snap["k"]), np.asarray(snap["v"])
        n_tokens = k.shape[1]
        npages = self.pages_for(n_tokens)
        pages = list(np.asarray(page_table)[:npages])
        n = _bucket_pow2(npages)
        pad_pages = n - npages
        pad_tok = n * ps - n_tokens
        if pad_tok:
            k = np.concatenate(
                [k, np.zeros((k.shape[0], pad_tok) + k.shape[2:],
                             k.dtype)], axis=1)
            v = np.concatenate(
                [v, np.zeros((v.shape[0], pad_tok) + v.shape[2:],
                             v.dtype)], axis=1)
        idx = jnp.asarray(np.asarray(pages + [scratch_page] * pad_pages,
                                     np.int32))
        shp = (k.shape[0], n, ps) + k.shape[2:]
        self.k, self.v = self._adopt_jit(
            self.k, self.v, idx, jnp.asarray(k.reshape(shp)),
            jnp.asarray(v.reshape(shp)))
