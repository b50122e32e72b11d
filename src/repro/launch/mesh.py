"""Production mesh + logical-axis rules.

TPU v5e target: 256 chips/pod (16×16), optionally 2 pods = 512 chips.
Functions, not module constants — importing this module never touches jax
device state (smoke tests must keep seeing 1 CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the model's logical
    ``shard()`` constraints and GSPMD propagation assume it (``Explicit``
    axes, the default of ``make_mesh`` in this JAX, reject both)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def batch_axes(multi_pod: bool):
    return ("pod", "data") if multi_pod else ("data",)


def make_serving_mesh(*, data: int = 1, model: int = 0):
    """Mesh for the mesh-sharded serving engine.

    Defaults to all visible devices on the ``model`` (tensor-parallel) axis
    — decode batches are small, so TP is the serving-side win (head-sharded
    KV pool + attention).  ``data`` carves out a replica axis for the
    decode-slot batch.
    """
    n = len(jax.devices())
    model = model or max(n // max(data, 1), 1)
    return _auto_mesh((data, model), ("data", "model"))


def serving_rules() -> dict:
    """Logical-axis rules for the serving path: heads / kv-heads / mlp /
    vocab tensor-parallel on ``model``, batch-of-slots on ``data``, no
    sequence sharding (decode reads one token per slot)."""
    return activation_rules()


def activation_rules(*, multi_pod: bool = False, shard_kv_seq: bool = False,
                     seq_parallel: bool = False) -> dict:
    """Logical-name -> mesh-axis rules for `repro.launch.pspec.shard`.

    shard_kv_seq: long-context decode (B=1) — KV sequence dim on 'data'
    (flash-decoding-style partial softmax; XLA inserts the reductions).
    seq_parallel: shard the *activation* seq dim on 'data' as well.
    """
    ba = batch_axes(multi_pod)
    return {
        "batch": ba,
        "seq": "data" if seq_parallel else None,
        "kv_seq": "data" if shard_kv_seq else None,
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "experts": "model",
        "vocab": "model",
    }
