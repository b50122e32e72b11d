"""Int8 KV quantization: round-trip error bounds + MPIC quality impact."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.cache import KVLibrary
from repro.cache.quant import dequantize_kv, quantize_kv
from repro.configs import get_smoke_config
from repro.core import (POLICIES, Prompt, media_segment,
                        precompute_media_kv, text_segment)
from repro.models import build_model


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 16), scale=st.floats(0.01, 100.0))
def test_quant_roundtrip_bound(seed, scale):
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((2, 16, 4, 8)) * scale).astype(np.float32)
    deq = dequantize_kv(quantize_kv(x))
    # per-channel symmetric int8: |err| <= amax/254 per (L,H,Dh) channel
    amax = np.max(np.abs(x), axis=1, keepdims=True)
    assert np.all(np.abs(deq - x) <= amax / 254.0 + 1e-7)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 16), scale=st.floats(0.01, 100.0),
       bt=st.sampled_from([1, 3, 4, 8, 16, 23]))
def test_quant_block_roundtrip_bound(seed, scale, bt):
    """Page-granular scales: |err| <= per-BLOCK amax/254 — strictly finer
    than the whole-sequence bound when magnitudes vary along tokens."""
    rng = np.random.default_rng(seed)
    L, S, H, Dh = 2, 16, 4, 8
    x = (rng.standard_normal((L, S, H, Dh)) * scale).astype(np.float32)
    q = quantize_kv(x, block_tokens=bt)
    assert q.block_tokens == bt
    nb = -(-S // bt)
    assert q.scale.shape == (L, nb, H, Dh)
    deq = dequantize_kv(q)
    assert deq.shape == x.shape
    pad = nb * bt - S
    xp_ = np.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    amax = np.max(np.abs(xp_.reshape(L, nb, bt, H, Dh)), axis=2)
    bound = np.repeat(amax, bt, axis=1)[:, :S] / 254.0 + 1e-7
    assert np.all(np.abs(deq - x) <= bound)


def test_quant_block_scales_tighter_than_whole_seq():
    """A sequence whose magnitude grows 10x along tokens: whole-seq amax
    drags every token's scale up; per-block scales keep early tokens on a
    fine grid.  (This is exactly the int8 pool's page-granularity claim.)"""
    rng = np.random.default_rng(0)
    L, S, H, Dh, bt = 2, 64, 4, 8, 16
    ramp = np.linspace(1.0, 10.0, S)[None, :, None, None]
    x = (rng.standard_normal((L, S, H, Dh)) * ramp).astype(np.float32)
    err_whole = np.abs(dequantize_kv(quantize_kv(x)) - x)
    err_block = np.abs(dequantize_kv(quantize_kv(x, block_tokens=bt)) - x)
    # early (small-magnitude) tokens: block scales are ~3x finer (the
    # first block's amax tops out near ramp(bt) ~ 3 vs the whole-seq 10)
    assert err_block[:, :bt].max() < err_whole[:, :bt].max() / 2
    # ...and the global error never gets worse
    assert err_block.max() <= err_whole.max() + 1e-7


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16),
       bt=st.sampled_from([4, 8, 16]), whole_v=st.booleans())
def test_quant_block_spool_wire_roundtrip(seed, bt, whole_v):
    """The npz wire format must carry block granularity explicitly
    (ceil-division makes it non-inferable from shapes) — a spooled
    block-granular entry must unspool bit-identical with block_tokens
    intact, independently per K and V."""
    import io
    import types

    from repro.cache.quant import spool_payload, unspool_payload

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((2, 19, 4, 8)).astype(np.float32)
    qk = quantize_kv(x, block_tokens=bt)
    qv = quantize_kv(x * 2, block_tokens=None if whole_v else bt)
    buf = io.BytesIO()
    spool_payload(buf, types.SimpleNamespace(k=None, v=None, qk=qk, qv=qv))
    buf.seek(0)
    back = unspool_payload(buf)
    for got, want in ((back["qk"], qk), (back["qv"], qv)):
        assert got.block_tokens == want.block_tokens
        np.testing.assert_array_equal(got.q, want.q)
        np.testing.assert_array_equal(got.scale, want.scale)
    np.testing.assert_allclose(dequantize_kv(back["qk"]),
                               dequantize_kv(qk), rtol=0, atol=0)


def test_quant_halves_storage():
    x = np.random.default_rng(0).standard_normal((4, 64, 8, 32)) \
        .astype(np.float32)
    q = quantize_kv(x)
    assert q.nbytes < x.nbytes / 3.5          # ~4x smaller than fp32


def test_quantized_library_roundtrip(tmp_path):
    lib = KVLibrary(spool_dir=str(tmp_path), quantize=True,
                    hbm_capacity=1 << 10, host_capacity=1 << 10)  # force disk
    x = np.random.default_rng(0).standard_normal((2, 32, 2, 16)) \
        .astype(np.float32)
    lib.put("u", "m", x, x * 2)
    e = lib.get("u", "m")
    amax = np.max(np.abs(x))
    np.testing.assert_allclose(e.k, x, atol=amax / 100)
    np.testing.assert_allclose(e.v, x * 2, atol=2 * amax / 100)


def test_mpic_quality_with_quantized_library(tmp_path):
    """int8 media KV + selective recompute: quality stays near the fp
    library (the compression error is absorbed like the reuse error)."""
    cfg = get_smoke_config("llava-1.6-7b")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    emb = (rng.standard_normal((24, cfg.d_model)) * 0.02).astype(np.float32)
    k, v = precompute_media_kv(m, params, jnp.asarray(emb))

    prompt = Prompt([
        text_segment(rng.integers(8, 200, 6)),
        media_segment("IMG", emb),
        text_segment(rng.integers(8, 200, 5)),
    ], user_id="u")

    def run(quantize):
        lib = KVLibrary(spool_dir=str(tmp_path / str(quantize)),
                        quantize=quantize)
        lib.put("u", "IMG", k, v)
        return POLICIES["mpic"](m, params, prompt, lib, k=4)

    oracle = POLICIES["full_recompute"](m, params, prompt)

    def kl(r):
        p = jax.nn.softmax(jnp.asarray(oracle.first_logits))
        q = jax.nn.log_softmax(jnp.asarray(r.first_logits))
        return float(jnp.sum(p * (jnp.log(p + 1e-20) - q)))

    r_fp, r_q = run(False), run(True)
    kl_fp, kl_q = kl(r_fp), kl(r_q)
    # int8 adds at most a small increment over the fp-library reuse error
    assert kl_q < kl_fp + 5e-3
    # ...and does not change the greedy token relative to the fp library
    # (vs the recompute oracle the *reuse* error already dominates, so the
    # right invariant is fp-mpic ≡ int8-mpic, not mpic ≡ oracle)
    assert int(np.argmax(r_q.first_logits)) == \
        int(np.argmax(r_fp.first_logits))


def test_quantized_spool_halves_disk_bytes(tmp_path):
    """The opt-in int8 disk format (``KVLibrary(quantize=True)``) must
    write at least ~2x fewer spool bytes per entry than the bf16-equivalent
    fp path (4x vs fp32 minus the fp32 scale rows), and survive a
    disk→host→link round trip through ``materialize``."""
    import os

    x = np.random.default_rng(0).standard_normal((4, 64, 8, 32)) \
        .astype(np.float32)

    def spool_bytes(quantize):
        d = tmp_path / ("q" if quantize else "fp")
        lib = KVLibrary(spool_dir=str(d), quantize=quantize,
                        hbm_capacity=1, host_capacity=1)   # force disk
        lib.put("u", "m", x, x)
        files = [os.path.join(d, f) for f in os.listdir(d)]
        assert len(files) == 1
        size = os.path.getsize(files[0])
        e = lib.get("u", "m")                 # disk → host → dequantize
        amax = np.max(np.abs(x))
        np.testing.assert_allclose(e.k, x, atol=amax / 100)
        return size

    fp, q = spool_bytes(False), spool_bytes(True)
    assert q < fp / 2, f"int8 spool {q}B should halve the fp {fp}B"
