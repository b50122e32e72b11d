"""Public model facade: config + pure functions for every execution mode.

``Model`` is a thin, stateless wrapper; params live outside (pytree), so
everything composes with pjit/shard_map and the training loop.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as tf
from repro.models.layers import INVALID_POS


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # -- init ---------------------------------------------------------------
    def init(self, key) -> dict:
        return tf.init_params(key, self.cfg)

    # -- embedding (handles the modality-frontend carve-out) ----------------
    def embed(self, params, tokens, media_embeds=None, media_mask=None,
              positions=None):
        x = tf.embed_tokens(params, self.cfg, tokens, media_embeds, media_mask)
        if self.cfg.learned_pos_emb:
            if positions is None:
                s = tokens.shape[1]
                positions = jnp.broadcast_to(
                    jnp.arange(s, dtype=jnp.int32), tokens.shape)
            x = x + params["pos_embed"][positions]
        return x

    # -- training -----------------------------------------------------------
    def loss(self, params, batch) -> jnp.ndarray:
        logits, aux = tf.forward_train(
            params, self.cfg, batch["tokens"],
            media_embeds=batch.get("media_embeds"),
            media_mask=batch.get("media_mask"),
            audio_embeds=batch.get("audio_embeds"),
        )
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        if self.cfg.arch_type == "moe":
            loss = loss + self.cfg.router_aux_loss_coef * aux / max(
                self.cfg.num_layers, 1)
        return loss

    def forward(self, params, tokens, **kw):
        # pad to the SSD chunk multiple; outputs at pad positions are
        # discarded and (causality) never influence real positions
        s = tokens.shape[1]
        needs_chunk = self.cfg.arch_type == "ssm" or self.cfg.hybrid
        pad = (-s) % self.cfg.ssm_chunk if needs_chunk else 0
        if pad:
            tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
            if kw.get("media_embeds") is not None:
                kw["media_embeds"] = jnp.pad(
                    kw["media_embeds"], ((0, 0), (0, pad), (0, 0)))
                kw["media_mask"] = jnp.pad(
                    kw["media_mask"], ((0, 0), (0, pad)))
        logits, _ = tf.forward_train(params, self.cfg, tokens, **kw)
        return logits[:, :s]

    # -- serving ------------------------------------------------------------
    def make_cache(self, batch: int, kv_len: int, dtype=None) -> dict:
        return tf.make_cache(self.cfg, batch, kv_len, dtype)

    def prefill(self, params, tokens, cache, *, media_embeds=None,
                media_mask=None, positions=None, write_idx=None,
                audio_embeds=None):
        """Plain (contiguous) prefill into ``cache``; returns (logits, cache)."""
        b, s = tokens.shape
        contiguous = positions is None and write_idx is None
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        if write_idx is None:
            write_idx = positions
        if self.cfg.is_encoder_decoder and audio_embeds is not None:
            enc_out = tf.encode(params, self.cfg, audio_embeds)
            ck, cv = tf.compute_cross_kv(params, self.cfg, enc_out)
            cache = dict(cache, cross_k=ck, cross_v=cv)

        # SSM/hybrid need seq % ssm_chunk == 0: right-pad with dt-masked
        # no-op steps (state neither decays nor absorbs on pads) and park
        # the pad KV writes in the scratch slot.
        ssm_mask = ssm_tail = None
        needs_chunk = self.cfg.arch_type == "ssm" or self.cfg.hybrid
        pad = (-s) % self.cfg.ssm_chunk if needs_chunk else 0
        if pad:
            kv_len = (cache["pos"].shape[1] if "pos" in cache
                      else None)
            scratch = (kv_len - 1) if kv_len else 0
            tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
            # pads carry INVALID_POS: their KV (parked in the scratch slot)
            # can never be attended to
            positions = jnp.pad(positions, ((0, 0), (0, pad)),
                                constant_values=INVALID_POS)
            write_idx = jnp.pad(write_idx, ((0, 0), (0, pad)),
                                constant_values=scratch)
            if media_embeds is not None:
                media_embeds = jnp.pad(media_embeds,
                                       ((0, 0), (0, pad), (0, 0)))
                media_mask = jnp.pad(media_mask, ((0, 0), (0, pad)))
        if needs_chunk:
            total = s + pad
            ssm_mask = (jnp.arange(total)[None, :] < s).astype(jnp.float32)
            ssm_mask = jnp.broadcast_to(ssm_mask, (b, total))
            ssm_tail = jnp.full((b,), s - (self.cfg.ssm_conv_width - 1),
                                jnp.int32)

        x = self.embed(params, tokens, media_embeds, media_mask, positions)
        logits, cache, _ = tf.forward_with_cache(
            params, self.cfg, x, positions, cache, write_idx,
            ssm_mask=ssm_mask, ssm_tail_start=ssm_tail,
            contiguous=contiguous)
        if pad:
            logits = logits[:, :s]
        return logits, cache

    def selective_prefill(self, params, sel_tokens, sel_positions, cache,
                          write_idx, *, media_embeds=None, media_mask=None):
        """MPIC selective-attention prefill (single step).

        ``cache`` is the *linked* cache: reused segment KV already placed
        (with relinked RoPE) and dummy (zero) KV in the selected slots;
        ``cache["pos"]`` marks reused slots with their linked positions.
        ``sel_tokens``/``sel_positions`` are the recomputed tokens (all text
        + first-k of each image segment); their K/V overwrite the dummy
        slots *inside this one forward pass* — the paper's single-step
        property.
        """
        assert self.cfg.arch_type not in ("ssm",), \
            "selective prefill needs attention KV (see DESIGN.md)"
        x = self.embed(params, sel_tokens, media_embeds, media_mask,
                       sel_positions)
        logits, cache, _ = tf.forward_with_cache(
            params, self.cfg, x, sel_positions, cache, write_idx)
        return logits, cache

    def supports_paged_prefill(self) -> bool:
        """Selective prefill straight into the page pool — same coverage as
        paged decode (pure-attention KV; no SSM state, no cross KV)."""
        return self.supports_paged_decode()

    def selective_prefill_paged(self, params, sel_tokens, sel_positions,
                                pool_k, pool_v, page_table, lengths,
                                write_pages, write_offs, k_scales=None,
                                v_scales=None, *,
                                media_embeds=None, media_mask=None,
                                backend: str = "ref",
                                interpret=None):
        """MPIC selective prefill against the shared paged KV pool.

        See :func:`repro.models.transformer.selective_prefill_paged` for
        shapes.  Returns (logits (B, Sq, V), pool_k, pool_v) — callers
        donate the pool buffers so the K/V writes are in place.  On an int8
        pool pass ``k_scales``/``v_scales`` (L, P, Hkv); the updated scale
        buffers ride along in the return tuple.
        """
        assert self.cfg.arch_type not in ("ssm",), \
            "selective prefill needs attention KV (see DESIGN.md)"
        x = self.embed(params, sel_tokens, media_embeds, media_mask,
                       sel_positions)
        return tf.selective_prefill_paged(
            params, self.cfg, x, sel_positions, pool_k, pool_v, page_table,
            lengths, write_pages, write_offs, k_scales, v_scales,
            backend=backend, interpret=interpret)

    def decode_step(self, params, token, position, cache, write_idx):
        """One decode step. token (B,1), position (B,1), write_idx (B,1)."""
        x = self.embed(params, token, positions=position)
        logits, cache, _ = tf.forward_with_cache(
            params, self.cfg, x, position, cache, write_idx)
        return logits[:, -1, :], cache

    def supports_paged_decode(self) -> bool:
        """Paged decode covers pure-attention KV caches — including sliding
        windows, which the paged kernel masks like the dense decode path.
        Archs with SSM state or cross KV keep the dense decode path."""
        cfg = self.cfg
        return (not cfg.attn_free and not cfg.hybrid
                and cfg.arch_type not in ("ssm", "hybrid")
                and not cfg.is_encoder_decoder)

    def decode_step_paged(self, params, token, position, pool_k, pool_v,
                          page_table, lengths, write_pages, write_offs,
                          k_scales=None, v_scales=None, *,
                          backend: str = "ref", interpret=None):
        """One decode step against the shared paged KV pool (all slots).

        See :func:`repro.models.transformer.decode_paged` for shapes.
        Returns (logits (B, V), pool_k, pool_v) — callers donate the pool
        buffers so the write is in place.  On an int8 pool pass
        ``k_scales``/``v_scales`` (L, P, Hkv); the updated scale buffers
        ride along in the return tuple.
        """
        x = self.embed(params, token, positions=position)
        return tf.decode_paged(
            params, self.cfg, x, position, pool_k, pool_v, page_table,
            lengths, write_pages, write_offs, k_scales, v_scales,
            backend=backend, interpret=interpret)

    # -- whisper helpers ------------------------------------------------------
    def encode_audio(self, params, audio_embeds):
        return tf.encode(params, self.cfg, audio_embeds)

    def cross_kv(self, params, enc_out):
        return tf.compute_cross_kv(params, self.cfg, enc_out)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
