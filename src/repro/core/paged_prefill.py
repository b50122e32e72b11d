"""Bucketed, jitted, donated paged selective prefill — the MPIC hot path.

The seed prefill was the last eager, shape-polymorphic stage in the system:
every request built a throwaway dense blended cache, ran an unjitted
``selective_prefill`` whose shapes differed per prompt, and the engine then
scattered the result into the page pool and discarded the dense copy.

:class:`PagedPrefiller` replaces all of that with ONE device call per
request:

  * the linker scatters reused segments straight into the request's
    reserved pages (:func:`repro.core.linker.link_paged` — no dense
    intermediate);
  * the selected tokens are padded to a power-of-two **shape bucket**
    (token ids / positions / media embeds; pad rows write their K/V to the
    scratch page, and their logits rows are never read);
  * the page table is sliced to the bucketed live page count;
  * the whole step — embed, layer scan with per-layer K/V write-back into
    pages, paged selective attention, logits — runs under one ``jax.jit``
    that **donates** the pool buffers.

Steady-state traffic with varying prompt lengths therefore hits a warm
compile cache (one trace per (selection bucket, page bucket) pair, i.e.
O(log²( max_seq_len )) traces total) and performs zero host round-trips
between link and first token.  ``traces`` counts actual retraces — the
increment executes only while JAX is tracing — so tests can assert that
same-bucket prompt lengths do not recompile.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import numpy as np

from repro.core.linker import (
    PagedLinkResult,
    bucket,
    link_paged,
    reselect_paged,
)
from repro.core.segments import Prompt
from repro.models.model import Model


class PagedPrefiller:
    """Owns the jitted paged-prefill step for one engine's pool."""

    def __init__(self, model: Model, pool, scratch_page: int, *,
                 backend: str = "ref", interpret=None,
                 bucket_min: int = 16, sharding=None, param_shardings=None):
        """``sharding``: optional
        :class:`repro.serving.sharding.ServingSharding` — the prefill jit
        then pins the pool in/out to its head-sharded layout,
        ``param_shardings`` on the params, and replicates the per-request
        host operands (prefill batch is 1; there is nothing to split on
        ``data``).  The step is traced under ``sharding.activate()`` so the
        model's logical ``shard()`` annotations apply."""
        self.model = model
        self.pool = pool
        self.scratch_page = int(scratch_page)
        self.backend = backend
        self.interpret = interpret
        self.bucket_min = int(bucket_min)
        self.sharding = sharding
        self.traces = 0          # incremented at TRACE time only
        self.quantized = bool(getattr(pool, "quantized", False))
        jit_kw = {}
        if sharding is not None:
            pool_sh, rep = sharding.pool(), sharding.replicated
            if self.quantized:
                ssh = sharding.pool_scale()
                jit_kw = dict(
                    in_shardings=(param_shardings, pool_sh, pool_sh,
                                  ssh, ssh) + (rep,) * 9,
                    out_shardings=(rep, pool_sh, pool_sh, ssh, ssh))
            else:
                jit_kw = dict(
                    in_shardings=(param_shardings, pool_sh, pool_sh)
                    + (rep,) * 9,
                    out_shardings=(rep, pool_sh, pool_sh))
        donate = (1, 2, 3, 4) if self.quantized else (1, 2)
        self._jit = jax.jit(
            self._step_fn_q if self.quantized else self._step_fn,
            donate_argnums=donate, **jit_kw)

    # -- the traced step ---------------------------------------------------
    def _step_fn(self, params, pool_k, pool_v, tokens, positions,
                 media_embeds, media_mask, page_table, lengths,
                 write_pages, write_offs, last_idx):
        # trace-time side effect: runs once per distinct shape bucket, so
        # ``traces`` is a direct compile-count probe for the tests
        self.traces += 1
        logits, pool_k, pool_v = self.model.selective_prefill_paged(
            params, tokens, positions, pool_k, pool_v, page_table, lengths,
            write_pages, write_offs, media_embeds=media_embeds,
            media_mask=media_mask, backend=self.backend,
            interpret=self.interpret)
        return logits[0, last_idx], pool_k, pool_v

    def _step_fn_q(self, params, pool_k, pool_v, k_scales, v_scales, tokens,
                   positions, media_embeds, media_mask, page_table, lengths,
                   write_pages, write_offs, last_idx):
        """Int8-pool prefill step — scale buffers donate and update in
        place beside the pages (quantize-on-write inside the layer scan)."""
        self.traces += 1
        logits, pool_k, pool_v, ks, vs = self.model.selective_prefill_paged(
            params, tokens, positions, pool_k, pool_v, page_table, lengths,
            write_pages, write_offs, k_scales, v_scales,
            media_embeds=media_embeds, media_mask=media_mask,
            backend=self.backend, interpret=self.interpret)
        return logits[0, last_idx], pool_k, pool_v, ks, vs

    # -- host-side bucketing + dispatch ------------------------------------
    def prefill(self, params, link: PagedLinkResult,
                page_row: np.ndarray) -> np.ndarray:
        """Run the selective prefill for one linked request.

        Pads the selection to its shape bucket, slices the page table to
        the bucketed live page count, and invokes the donated jit.  Returns
        the last real selected token's logits row as float32 numpy (the
        first-output-token logits, matching the dense ``_selective_step``).
        """
        pool = self.pool
        ps = pool.cfg.page_size
        page_row = np.asarray(page_row)
        n = len(link.sel_idx)
        sb = bucket(n, self.bucket_min)

        positions = np.zeros((sb,), np.int32)
        positions[:n] = link.sel_idx
        tokens = np.zeros((sb,), np.int32)
        tokens[:n] = link.sel_tokens
        emb = np.zeros((sb, self.model.cfg.d_model), np.float32)
        emb[:n] = link.sel_media_embeds
        mask = np.zeros((sb,), bool)
        mask[:n] = link.sel_media_mask
        # pad rows park their K/V on the scratch page (never read: the
        # attention mask covers only slots < total)
        wp = np.full((sb,), self.scratch_page, np.int32)
        wo = np.full((sb,), ps - 1, np.int32)
        wp[:n] = page_row[link.sel_idx // ps]
        wo[:n] = link.sel_idx % ps

        mp = min(bucket(pool.pages_for(link.total)), len(page_row))
        ctx = (self.sharding.activate() if self.sharding is not None
               else contextlib.nullcontext())
        host = (np.asarray(tokens[None]), np.asarray(positions[None]),
                np.asarray(emb[None]), np.asarray(mask[None]),
                np.asarray(page_row[None, :mp]),
                np.asarray([link.total], np.int32),
                np.asarray(wp[None]), np.asarray(wo[None]),
                np.int32(max(n - 1, 0)))
        with ctx:   # logical shard() annotations apply at trace time
            if self.quantized:
                out, pool.k, pool.v, pool.k_scale, pool.v_scale = self._jit(
                    params, pool.k, pool.v, pool.k_scale, pool.v_scale,
                    *host)
            else:
                out, pool.k, pool.v = self._jit(params, pool.k, pool.v,
                                                *host)
        return np.asarray(out, np.float32)

    def bind(self, page_row: np.ndarray) -> "BoundPagedPrefill":
        return BoundPagedPrefill(self, np.asarray(page_row))


@dataclasses.dataclass
class BoundPagedPrefill:
    """Per-request view handed to the policies: the prefiller plus the
    slot's (scratch-padded) page-table row."""
    prefiller: PagedPrefiller
    page_row: np.ndarray

    @property
    def pool(self):
        return self.prefiller.pool

    def link(self, model: Model, prompt: Prompt, library,
             selection: np.ndarray, *, entries=None) -> PagedLinkResult:
        return link_paged(model, prompt, library, selection,
                          self.prefiller.pool, self.page_row,
                          scratch_page=self.prefiller.scratch_page,
                          entries=entries)

    def reselect(self, model: Model, prompt: Prompt, link: PagedLinkResult,
                 selection: np.ndarray) -> PagedLinkResult:
        return reselect_paged(model, prompt, link, selection)

    def gather_k0(self, n_tokens: int) -> np.ndarray:
        """Layer-0 cached K over the first ``n_tokens`` slots (cacheblend's
        deviation probe reads the pool instead of a dense blended cache).
        Gathers ONLY layer 0 of K — not all L layers of K and V."""
        ps = self.pool.cfg.page_size
        slots = np.arange(n_tokens)
        pages = np.asarray(self.page_row)[slots // ps]
        k0 = np.asarray(self.pool.k[0][pages, slots % ps])
        if getattr(self.pool, "quantized", False):
            # int8 pool: the probe compares fp deviations, so hand it the
            # dequantized view (layer-0 K scale rows per gathered page)
            s0 = np.asarray(self.pool.k_scale[0])[pages]      # (n, Hkv)
            k0 = k0.astype(np.float32) * s0[..., None]
        # writable copy: the probe blanks the selected rows
        return np.array(k0)

    def prefill(self, params, link: PagedLinkResult) -> np.ndarray:
        return self.prefiller.prefill(params, link, self.page_row)
