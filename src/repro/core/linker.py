"""The MPIC **Linker** — blends library KV caches into a per-request cache.

Analogous to a linker for position-independent code: stored segment caches
are "compiled" at canonical position 0; at link time each is *relocated* to
its offset in the prompt (exact RoPE delta rotation) and placed into the
request's KV cache.  Selected (to-be-recomputed) slots get the **dummy
cache** (zeros) — their real K/V are scattered in during the single-step
selective-attention prefill.

Two targets: :func:`link_prompt` builds a dense per-request blended cache
(the baselines' path, and the fallback when no page pool exists);
:func:`link_paged` relocates the same segments straight into a
:class:`~repro.cache.paged.PagedKVPool`'s reserved pages with one donated
scatter per segment, each fed the library entry's own K/V at the
segment's exact length (no concatenation, no padding) — the serving
engine's zero-copy prefill path.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core.segments import Prompt
from repro.core.select import selection_indices
from repro.models.layers import INVALID_POS, rope_relink
from repro.models.model import Model
from repro.tracing import span


@dataclasses.dataclass
class LinkResult:
    cache: dict                 # blended KV cache (batch=1)
    sel_idx: np.ndarray         # (S_sel,) positions of recomputed tokens
    sel_tokens: np.ndarray      # (S_sel,) token ids (media slots: pad 0)
    sel_media_embeds: np.ndarray  # (S_sel, D)
    sel_media_mask: np.ndarray    # (S_sel,)
    n_reused: int
    n_recomputed: int
    misses: list                # media ids absent from the library


@dataclasses.dataclass
class PagedLinkResult:
    """Link result when placed segments go straight into the page pool.

    No dense blended cache exists: reused KV already sits in the request's
    reserved pages (scattered by ``link_paged``), and the selected tokens'
    K/V are written into their pages during the paged selective prefill.
    ``forced`` records tokens whose segment missed the library — a later
    re-selection (cacheblend's deviation pass) must keep them selected.
    """
    sel_idx: np.ndarray
    sel_tokens: np.ndarray
    sel_media_embeds: np.ndarray
    sel_media_mask: np.ndarray
    n_reused: int
    n_recomputed: int
    misses: list
    total: int
    forced: np.ndarray          # (total,) bool — recompute is mandatory


def selection_arrays(prompt: Prompt, d_model: int, sel_idx: np.ndarray):
    """Gather the per-selected-token inputs (ids, media embeds, media mask)."""
    with span("mpic.prefill.pack"):
        flat_tokens = prompt.flat_tokens()
        media_mask = prompt.media_mask()
        media_embeds = prompt.flat_media_embeds(d_model)
        return (flat_tokens[sel_idx], media_embeds[sel_idx],
                media_mask[sel_idx])


def precompute_media_kv(model: Model, params, embeds: jnp.ndarray):
    """KV of a media segment standalone (canonical position 0).

    embeds (length, D) -> (k, v) each (L, length, Hkv, Dh).  This is what
    the library stores when a user uploads a file (workflow step ①).
    """
    length = embeds.shape[0]
    cache = model.make_cache(1, length)
    tokens = jnp.zeros((1, length), jnp.int32)
    mask = jnp.ones((1, length), bool)
    _, cache = model.prefill(params, tokens, cache,
                             media_embeds=embeds[None], media_mask=mask)
    return np.asarray(cache["k"][:, 0]), np.asarray(cache["v"][:, 0])


def scale_row_ids(n: int, qkv) -> np.ndarray:
    """Token → scale-row map for one quantized segment: whole-sequence
    scales collapse to row 0, ``block_tokens`` granular scales step every
    ``bt`` tokens.  Shared by the linker's and the engine's spool→pool
    zero-copy links (``PagedKVPool.link_write_q8``)."""
    if qkv.block_tokens is None:
        return np.zeros(n, np.int32)
    return (np.arange(n) // qkv.block_tokens).astype(np.int32)


def _gather_placements(prompt: Prompt, library, selection: np.ndarray,
                       entries=None):
    """Resolve each media segment to a library entry (or a forced recompute).

    Returns (sel, placed, misses): the selection mask grown by missing
    segments, the placed list [(offset, entry, length)], and the miss ids.
    Placed entries are NOT dequantized here — the caller picks the fp or
    int8 residency per link target (``link_paged`` rescales int8 blocks
    straight onto an int8 pool's page grid).
    """
    sel = selection.copy()
    misses = []
    placed = []
    for off, seg in prompt.media_segments():
        if entries is not None:
            entry = entries.get(seg.media_id)
        else:
            entry = library.get(prompt.user_id, seg.media_id) if library \
                else None
        if entry is None:
            # expired/missing: recompute the whole segment (paper Fig. 6, m misses)
            sel[off:off + seg.length] = True
            misses.append(seg.media_id)
        else:
            placed.append((off, entry, seg.length))
    return sel, placed, misses


def link_prompt(model: Model, prompt: Prompt, library, selection: np.ndarray,
                *, kv_len: Optional[int] = None, entries=None) -> LinkResult:
    """Build the blended cache for one request (workflow step ⑤).

    ``entries`` is an optional per-media gather source (anything with a
    ``.get(media_id) -> Entry | None`` method, e.g. a
    :class:`repro.cache.transfer.PrefetchHandle`).  When given, each entry is
    gathered *here*, at link time — blocking only on fetches the pipelined
    scheduler has not finished yet — instead of through a synchronous
    ``library.get`` per segment.
    """
    cfg = model.cfg
    total = prompt.total_len
    kv_len = kv_len or total + 1          # +1 scratch slot for pad scatter
    assert kv_len >= total + 1

    sel, placed, misses = _gather_placements(prompt, library, selection,
                                             entries)
    L, Hkv, Dh = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    dt = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    pos = np.full((kv_len,), INVALID_POS, np.int64)
    k_buf = jnp.zeros((L, kv_len, Hkv, Dh), dt)
    v_buf = jnp.zeros((L, kv_len, Hkv, Dh), dt)
    sel_idx = selection_indices(sel)

    if placed:
        # one host→device transfer of all placed segments and ONE batched
        # rope_relink over the concatenation — the per-segment relink used
        # to round-trip through host numpy once per segment
        k_cat = jnp.asarray(np.concatenate([np.asarray(e.k)
                                            for _, e, _ in placed], axis=1))
        v_cat = jnp.asarray(np.concatenate([np.asarray(e.v)
                                            for _, e, _ in placed], axis=1))
        idx = np.concatenate([np.arange(off, off + n)
                              for off, _, n in placed])
        if cfg.rope_theta and not cfg.learned_pos_emb:
            # exact position relocation: K(p+Δ) = R(Δ)·K(p), per token
            delta = np.concatenate([np.full(n, off, np.int32)
                                    for off, _, n in placed])
            k_cat = rope_relink(k_cat, jnp.asarray(delta), cfg.rope_theta)
        k_buf = k_buf.at[:, idx].set(k_cat.astype(dt))
        v_buf = v_buf.at[:, idx].set(v_cat.astype(dt))
        for off, _, n in placed:
            pos[off:off + n] = np.arange(off, off + n)
        # dummy cache: selected slots stay zero and INVALID until the
        # selective prefill scatters the recomputed K/V into them
        # (single-step property) — selection may overlap placed segments
        # (MPIC recomputes each segment's first-k tokens), so zero AFTER
        # placing
        if len(sel_idx):
            k_buf = k_buf.at[:, sel_idx].set(0.0)
            v_buf = v_buf.at[:, sel_idx].set(0.0)
    pos[sel_idx] = INVALID_POS

    cache = {
        "k": k_buf[:, None],
        "v": v_buf[:, None],
        "pos": jnp.asarray(pos[None], jnp.int32),
    }

    sel_tokens, sel_media_embeds, sel_media_mask = selection_arrays(
        prompt, cfg.d_model, sel_idx)
    return LinkResult(
        cache=cache,
        sel_idx=sel_idx,
        sel_tokens=sel_tokens,
        sel_media_embeds=sel_media_embeds,
        sel_media_mask=sel_media_mask,
        n_reused=int(total - sel.sum()),
        n_recomputed=int(sel.sum()),
        misses=misses,
    )


def bucket(n: int, lo: int = 8) -> int:
    """Next power of two ≥ max(n, lo) — bounds distinct jit shapes to
    O(log max_seq_len) like the engine's page-table bucketing.  Shared by
    the int8 direct link scatter and the prefill step
    (``core/paged_prefill``) so their compile-cache behavior cannot drift
    apart."""
    b = max(lo, 1)
    while b < n:
        b *= 2
    return b


def link_paged(model: Model, prompt: Prompt, library,
               selection: np.ndarray, pool, page_row: np.ndarray, *,
               scratch_page: int, entries=None) -> PagedLinkResult:
    """Link a prompt's reused segments DIRECTLY into reserved pool pages.

    The paged twin of :func:`link_prompt`: each placed segment is
    relinked and scattered into the request's pages by its own call of
    the pool's donated link write (``PagedKVPool.link_write``), whose K/V
    operands are the library entry's own arrays — no dense
    ``(L, kv_len, H, D)`` blended cache and no host concatenation of the
    segments is ever materialized, and nothing needs splicing after the
    prefill.  Selected slots are NOT zeroed (the dense path's dummy cache):
    the paged selective prefill scatters fresh K/V into them before each
    layer's attention reads the pool, so stale bytes there are never
    observed.

    A scatter carries exactly one segment's tokens, unpadded, so the link
    compiles once per distinct media length: prompts that differ in image
    count or order reuse a warm compile cache.  On an int8 pool each write
    quantizes through the running page scales, so a page two segments
    share is requantized when the later one raises its scale, as after
    any later write to a page.  Only the int8 direct link
    (every entry int8-resident on an int8 pool) still packs all segments
    into one scatter padded to a power-of-two bucket, its pad rows landing
    on ``scratch_page``.
    """
    cfg = model.cfg
    total = prompt.total_len
    with span("mpic.link.gather"):
        sel, placed, misses = _gather_placements(prompt, library, selection,
                                                 entries)
    forced = sel & ~selection                   # miss-driven recomputes
    sel_idx = selection_indices(sel)

    if placed:
        with span("mpic.link.pack"):
            direct, host = _pack_placed(pool, placed, page_row,
                                        scratch_page)
        with span("mpic.link.upload"):
            calls = [[jnp.asarray(a) for a in ops] for ops in host]
        relink = bool(cfg.rope_theta) and not cfg.learned_pos_emb
        write = pool.link_write_q8 if direct else pool.link_write
        with span("mpic.link.write"):
            for ops in calls:
                write(*ops, theta=cfg.rope_theta, relink=relink)
        if direct and library is not None:
            library.note_direct_link(len(placed))

    sel_tokens, sel_media_embeds, sel_media_mask = selection_arrays(
        prompt, cfg.d_model, sel_idx)
    return PagedLinkResult(
        sel_idx=sel_idx,
        sel_tokens=sel_tokens,
        sel_media_embeds=sel_media_embeds,
        sel_media_mask=sel_media_mask,
        n_reused=int(total - sel.sum()),
        n_recomputed=int(sel.sum()),
        misses=misses,
        total=total,
        forced=forced,
    )


def _pack_placed(pool, placed, page_row, scratch_page):
    """Host operands of the pool writes that link ``placed`` segments:
    ``(direct, calls)``, where ``direct`` picks ``pool.link_write_q8``
    over ``pool.link_write`` and each of ``calls`` is one write's
    positional operands (pages, offsets, K/V or their int8 rows and
    scales, delta).

    The fp link writes once per segment, and its K/V operands are the
    entry's own arrays: only the (n,) index operands are built here, so
    no host array larger than one entry's K or V is allocated."""
    ps = pool.cfg.page_size
    page_row = np.asarray(page_row)
    direct = (getattr(pool, "quantized", False)
              and all(getattr(e, "payload", None) is not None
                      and e.payload.qk is not None
                      and e.payload.qk.block_tokens
                      == e.payload.qv.block_tokens
                      for _, e, _ in placed))
    if not direct:
        calls = []
        for off, e, n in placed:
            t = np.arange(off, off + n)
            calls.append((page_row[t // ps], (t % ps).astype(np.int32),
                          e.k, e.v, np.full(n, off, np.int32)))
        return False, calls
    # spool→pool zero copy: every placed entry is int8-resident, so its
    # bytes rescale straight onto the pool's page grid inside one donated
    # jit — no dequantize→requantize fp round trip and no fp copy of any
    # block.  The placed-token axis is padded to a power-of-two bucket
    # (pad rows land on ``scratch_page``).  Scale rows from all segments
    # stack into one (L, rows, H, Dh) operand; ``seg_ids`` maps each
    # placed token to its row (whole-seq or block_tokens granular).
    idx = np.concatenate([np.arange(off, off + n) for off, _, n in placed])
    delta = np.concatenate([np.full(n, off, np.int32)
                            for off, _, n in placed])
    n_placed = len(idx)
    b = min(bucket(n_placed), max(ps, 8) * max(len(page_row), 1))
    pad = b - n_placed
    if pad > 0:
        delta = np.concatenate([delta, np.zeros(pad, np.int32)])
    pages = np.full((b,), scratch_page, np.int32)
    offs = np.zeros((b,), np.int32)
    pages[:n_placed] = page_row[idx // ps]
    offs[:n_placed] = idx % ps
    qks, qvs, ksr, vsr, sids = [], [], [], [], []
    base = 0
    for off, e, n in placed:
        qk, qv = e.payload.qk, e.payload.qv
        qks.append(qk.q[:, :n])
        qvs.append(qv.q[:, :n])
        ksr.append(qk.scale)
        vsr.append(qv.scale)
        sids.append(base + scale_row_ids(n, qk))
        base += qk.scale.shape[1]
    qk_cat = np.concatenate(qks, axis=1)
    qv_cat = np.concatenate(qvs, axis=1)
    sid = np.concatenate(sids)
    if pad > 0:
        z = np.zeros(qk_cat.shape[:1] + (pad,) + qk_cat.shape[2:], np.int8)
        qk_cat = np.concatenate([qk_cat, z], axis=1)
        qv_cat = np.concatenate([qv_cat, z], axis=1)
        sid = np.concatenate([sid, np.zeros(pad, np.int32)])
    ks_cat = np.concatenate(ksr, axis=1)
    vs_cat = np.concatenate(vsr, axis=1)
    # bucket the scale-row axis too (pad rows are never referenced)
    rpad = bucket(base, 1) - base
    if rpad > 0:
        zr = np.ones(ks_cat.shape[:1] + (rpad,) + ks_cat.shape[2:],
                     np.float32)
        ks_cat = np.concatenate([ks_cat, zr], axis=1)
        vs_cat = np.concatenate([vs_cat, zr], axis=1)
    return True, [(pages, offs, qk_cat, ks_cat, qv_cat, vs_cat, sid, delta)]


def reselect_paged(model: Model, prompt: Prompt, link: PagedLinkResult,
                   selection: np.ndarray) -> PagedLinkResult:
    """New selection over an already-linked paged prompt (no re-scatter).

    Placement is selection-independent in the paged path (selected slots
    are overwritten during the prefill, not zeroed at link time), so
    cacheblend's deviation-driven re-selection only needs fresh selection
    arrays.  Miss-forced tokens stay selected.
    """
    sel = selection | link.forced
    sel_idx = selection_indices(sel)
    sel_tokens, sel_media_embeds, sel_media_mask = selection_arrays(
        prompt, model.cfg.d_model, sel_idx)
    return dataclasses.replace(
        link, sel_idx=sel_idx, sel_tokens=sel_tokens,
        sel_media_embeds=sel_media_embeds, sel_media_mask=sel_media_mask,
        n_reused=int(link.total - sel.sum()),
        n_recomputed=int(sel.sum()))


def session_suffix_link(tokens, n_ctx: int, d_model: int) -> PagedLinkResult:
    """Link result for a thawed session's new-turn suffix.

    A frozen session's KV is already position-baked (it was written at the
    live decode positions, not canonical position 0), so thaw adopts the
    snapshot pages verbatim — no ``rope_relink``, no scatter, and the whole
    prefix counts as reused.  What remains is the new turn's suffix: plain
    text tokens at positions ``n_ctx .. n_ctx+S-1``, all selected, all
    forced (there is nothing in the library to reuse for them).  This
    builds the :class:`PagedLinkResult` that hands that suffix to the
    normal paged selective prefill (``core/paged_prefill``).
    """
    toks = np.asarray(tokens, np.int32).reshape(-1)
    s = int(toks.shape[0])
    total = n_ctx + s
    forced = np.zeros(total, bool)
    forced[n_ctx:] = True
    return PagedLinkResult(
        sel_idx=np.arange(n_ctx, total, dtype=np.int64),
        sel_tokens=toks,
        sel_media_embeds=np.zeros((s, d_model), np.float32),
        sel_media_mask=np.zeros(s, bool),
        n_reused=n_ctx,
        n_recomputed=s,
        misses=[],
        total=total,
        forced=forced,
    )
