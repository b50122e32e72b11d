"""Sharding plan for the mesh-aware serving engine.

One object owns every :class:`~jax.sharding.NamedSharding` the engine's
donated jits need, all derived from the same logical-axis rules the model
code annotates with (``repro.launch.pspec``):

  * the paged KV pool — kv heads on the ``model`` axis,
    ``P(None, None, None, 'model', None)`` over ``(L, P, ps, Hkv, Dh)``;
  * params — MaxText-style tensor-parallel specs from
    ``repro.launch.specs.param_pspecs`` (no fsdp: serving wants weights
    resident, not gathered per step);
  * the dense fallback cache pytree (``specs.cache_pspecs``);
  * small host-built operands (tokens, page tables, lengths) — batch-of-
    slots on ``data`` when divisible, replicated otherwise.

Every mapping is divisibility-guarded exactly like ``pspec.shard`` (4 kv
heads never shard on a 16-way axis), so the same engine code runs on one
device, a forced-host 4-device test mesh, and the 16×16 v5e pod.
``activate()`` returns the ``use_policy`` context the engine traces its
jits under, which turns the model's logical ``shard()`` annotations on.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch import specs as S
from repro.launch.mesh import serving_rules
from repro.launch.pspec import axis_divides, use_policy


class ServingSharding:
    """Mesh + rules + model config -> the engine's sharding plan."""

    def __init__(self, mesh, model_cfg, rules: dict = None):
        self.mesh = mesh
        self.cfg = model_cfg
        self.rules = dict(rules or serving_rules())

    # -- primitives ---------------------------------------------------------
    def axis(self, logical: str, dim: int):
        """Mesh axis for a logical name, or None if it does not divide."""
        ax = self.rules.get(logical)
        if ax is None or not axis_divides(self.mesh, ax, dim):
            return None
        return ax

    def named(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def replicated(self) -> NamedSharding:
        return self.named()

    def activate(self):
        """Context manager enabling the model's logical ``shard()`` calls
        (and the kernels' shard_map dispatch) under this plan."""
        return use_policy(self.mesh, self.rules)

    # -- serving buffers ----------------------------------------------------
    def pool(self) -> NamedSharding:
        """(L, P, page_size, Hkv, Dh) — kv heads on the model axis."""
        return self.named(None, None, None,
                          self.axis("kv_heads", self.cfg.num_kv_heads), None)

    def pool_scale(self) -> NamedSharding:
        """(L, P, Hkv) int8-pool scale buffers — the scale rows shard with
        their pages' kv heads so the dequant-in-kernel shard_map path stays
        collective-free (same guard as :meth:`pool`)."""
        return self.named(None, None,
                          self.axis("kv_heads", self.cfg.num_kv_heads))

    def batch_axis(self, batch: int):
        return self.axis("batch", batch)

    def batched(self, batch: int, ndim: int) -> NamedSharding:
        """(B, ...) host operand: slots on ``data`` when divisible."""
        return self.named(self.batch_axis(batch), *([None] * (ndim - 1)))

    def params(self, params) -> dict:
        """Tensor-parallel NamedShardings for the whole param pytree."""
        ms = self.mesh.devices.shape[-1]
        rep_ssm = ((self.cfg.arch_type == "ssm" or self.cfg.hybrid)
                   and self.cfg.ssm_num_heads % ms != 0)
        pspecs = S.param_pspecs(params, self.mesh, fsdp=False,
                                replicate_ssm=rep_ssm)
        return S.to_shardings(pspecs, self.mesh)

    def dense_cache(self, batch: int, cache: dict) -> dict:
        """NamedShardings for the dense fallback batch-cache pytree.

        ``cache`` is the concrete pytree (``model.make_cache``) — every
        spec is divisibility-guarded against the actual leaf shapes, so
        e.g. the kv-seq-on-'model' fallback cache_pspecs picks when kv
        heads cannot shard drops to replicated when ``max_seq_len`` does
        not divide either (never a shape error)."""
        bspec = self.batch_axis(batch)
        pspecs = S.cache_pspecs(self.cfg, self.mesh, bspec, None)
        return {k: NamedSharding(
            self.mesh, S._guard(pspecs[k], v.shape, self.mesh))
            for k, v in cache.items()}


def init_params(model, key, mesh=None):
    """Initialise ``model``'s parameters straight onto where they serve
    from: the tensor-parallel shardings of ``mesh``, or the default device.
    One jitted init with the target ``out_shardings``, so no parameter is
    ever built whole on one device first — at published widths the whole
    model may not fit one chip."""
    if mesh is None:
        return jax.jit(model.init)(key)
    shapes = jax.eval_shape(model.init, key)
    shardings = ServingSharding(mesh, model.cfg).params(shapes)
    return jax.jit(model.init, out_shardings=shardings)(key)
