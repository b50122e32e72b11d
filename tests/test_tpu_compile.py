"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU v5e.

Interpret mode (the rest of the suite) cannot see what Mosaic refuses:
block shapes whose last two dims are not tile-aligned, loads it cannot
prove aligned, scratch that does not fit VMEM.  The TPU compiler is
installed even where no chip is attached, so these tests compile each
kernel at ``llava-1.6-7b`` widths (32 kv heads x 128, page_size 16) for a
*described* v5e chip and check that the kernel really is in the program.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and under pytest-xdist every
worker imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attn.ops import paged_attention_call
from repro.kernels.selective_attn.ops import (
    selective_attention,
    selective_attention_paged_call,
)

CFG = get_config("llava-1.6-7b")
PAGE = 16
POOL_PAGES = 4 * 2048 // PAGE + 1          # 4 slots x 2048 tokens + scratch
WINDOW = CFG.sliding_window


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    can never be read back without one — keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _pool_args(sh, pool_dtype):
    hkv, dh = CFG.num_kv_heads, CFG.head_dim
    dt = jnp.int8 if pool_dtype == "int8" else jnp.bfloat16
    pool = jax.ShapeDtypeStruct((POOL_PAGES, PAGE, hkv, dh), dt, sharding=sh)
    scales = ()
    if pool_dtype == "int8":
        s = jax.ShapeDtypeStruct((POOL_PAGES, hkv), jnp.float32, sharding=sh)
        scales = (s, s)
    return pool, scales


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("pool_dtype", ["bfloat16", "int8"])
def test_paged_decode_compiles_for_v5e(one_chip, pool_dtype):
    b, max_pages = 4, 128
    q = jax.ShapeDtypeStruct((b, CFG.num_heads, CFG.head_dim), jnp.bfloat16,
                             sharding=one_chip)
    pool, scales = _pool_args(one_chip, pool_dtype)
    table = jax.ShapeDtypeStruct((b, max_pages), jnp.int32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)

    def step(q, k, v, table, lengths, *scales):
        ks, vs = scales or (None, None)
        return paged_attention_call(q, k, v, table, lengths, k_scale=ks,
                                    v_scale=vs, window=WINDOW,
                                    backend="pallas", interpret=False)

    _assert_kernel(jax.jit(step).lower(q, pool, pool, table, lengths,
                                       *scales).compile())


@pytest.mark.parametrize("pool_dtype", ["bfloat16", "int8"])
def test_paged_prefill_compiles_for_v5e(one_chip, pool_dtype):
    sq, n_pages = 256, 128              # a 2-image MMDU selection bucket
    q = jax.ShapeDtypeStruct((1, sq, CFG.num_heads, CFG.head_dim),
                             jnp.bfloat16, sharding=one_chip)
    pool, scales = _pool_args(one_chip, pool_dtype)
    table = jax.ShapeDtypeStruct((1, n_pages), jnp.int32, sharding=one_chip)
    q_pos = jax.ShapeDtypeStruct((1, sq), jnp.int32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)

    def step(q, k, v, table, q_pos, lengths, *scales):
        ks, vs = scales or (None, None)
        return selective_attention_paged_call(
            q, k, v, table, q_pos, lengths, k_scale=ks, v_scale=vs,
            window=WINDOW, backend="pallas", interpret=False)

    _assert_kernel(jax.jit(step).lower(q, pool, pool, table, q_pos, lengths,
                                       *scales).compile())


def test_dense_selective_attention_compiles_for_v5e(one_chip):
    b, sq, skv = 2, 256, 1280           # b > 1: a (1, BQ) position block fails
    q = jax.ShapeDtypeStruct((b, sq, CFG.num_heads, CFG.head_dim),
                             jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, skv, CFG.num_kv_heads, CFG.head_dim),
                              jnp.bfloat16, sharding=one_chip)
    q_pos = jax.ShapeDtypeStruct((b, sq), jnp.int32, sharding=one_chip)
    kv_pos = jax.ShapeDtypeStruct((b, skv), jnp.int32, sharding=one_chip)
    fn = functools.partial(selective_attention, window=WINDOW,
                           interpret=False)
    _assert_kernel(jax.jit(fn).lower(q, kv, kv, q_pos, kv_pos).compile())
