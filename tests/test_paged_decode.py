"""Paged decode path parity: the pooled paged-attention decode must match
the dense ``attend`` decode within tolerance — at the transformer level
(Pallas kernel, interpret mode, GQA sweep) and end to end on the engine."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import PagedConfig, PagedKVPool
from repro.configs import get_smoke_config
from repro.configs.base import ModelConfig
from repro.core import Prompt, media_segment, text_segment
from repro.data import image_embeds
from repro.models import build_model
from repro.serving import EngineConfig, MPICEngine, Request

PAGE = 8


def _tiny_cfg(hq, hkv, window=0):
    return ModelConfig(name=f"tiny-{hq}-{hkv}", arch_type="dense",
                       num_layers=2, d_model=64, num_heads=hq,
                       num_kv_heads=hkv, head_dim=16, d_ff=128,
                       vocab_size=128, sliding_window=window,
                       param_dtype="float32", compute_dtype="float32")


# fp32 pool is exact vs the dense path; the int8 pool dequantizes
# in-kernel from per-page scales, so logits carry the KV quantization
# error: each K/V element is off by at most half a step of amax/127 of its
# page, and on these tiny models (logits of magnitude ~1) that moves a
# logit by at most ~0.04 — under 0.05, which a coarser pool (e.g. 4-bit
# steps of amax/7) would exceed.  Greedy argmax can still flip where the
# top two logits are nearer than that (see
# test_engine_paged_int8_matches_fp32_pool)
POOL_TOL = {"float32": dict(atol=1e-4, rtol=1e-4),
            "int8": dict(atol=5e-2, rtol=0)}


@pytest.mark.parametrize("pool_dtype", ["float32", "int8"])
@pytest.mark.parametrize("hq,hkv,window", [
    (4, 4, 0),      # MHA, full causal
    (4, 2, 0),      # GQA 2:1
    (8, 1, 0),      # MQA
    (4, 2, 6),      # GQA + sliding window that BINDS during decode
])
def test_paged_decode_matches_dense_gqa(hq, hkv, window, pool_dtype):
    """N decode steps: dense forward_with_cache vs decode_step_paged with
    the Pallas kernel (interpret=True on CPU), logits allclose each step —
    exactly for the fp32 pool, within POOL_TOL for the int8 pool (whose
    kernel gathers int8 pages + per-page scales and dequantizes
    in-register)."""
    cfg = _tiny_cfg(hq, hkv, window)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    t0, steps, kv_len = 11, 5, 32

    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (1, t0)), jnp.int32)
    cache = model.make_cache(1, kv_len)
    logits, cache = model.prefill(params, toks, cache)

    pool = PagedKVPool(PagedConfig(num_pages=8, page_size=PAGE,
                                   num_layers=cfg.num_layers,
                                   num_kv_heads=hkv, head_dim=cfg.head_dim,
                                   dtype=pool_dtype))
    pt = pool.alloc("r", t0 + steps)
    pool.write_tokens(pt, 0, cache["k"][:, 0, :t0], cache["v"][:, 0, :t0])
    page_table = jnp.asarray(pt[None])

    tok = int(jnp.argmax(logits[0, -1]))
    for i in range(steps):
        cur = t0 + i
        t = jnp.full((1, 1), tok, jnp.int32)
        p = jnp.full((1, 1), cur, jnp.int32)
        dense_logits, cache = model.decode_step(params, t, p, cache, p)
        step_args = (params, t, p, pool.k, pool.v, page_table,
                     jnp.asarray([cur + 1], jnp.int32),
                     jnp.asarray([pt[cur // PAGE]], jnp.int32),
                     jnp.asarray([cur % PAGE], jnp.int32))
        if pool.quantized:
            (paged_logits, pool.k, pool.v, pool.k_scale,
             pool.v_scale) = model.decode_step_paged(
                *step_args, pool.k_scale, pool.v_scale,
                backend="pallas", interpret=True)
        else:
            paged_logits, pool.k, pool.v = model.decode_step_paged(
                *step_args, backend="pallas", interpret=True)
        np.testing.assert_allclose(np.asarray(paged_logits[0], np.float32),
                                   np.asarray(dense_logits[0], np.float32),
                                   **POOL_TOL[pool_dtype])
        tok = int(jnp.argmax(dense_logits[0]))

    # written pool slots equal the dense cache region (same KV material;
    # the int8 gather returns the dequantized view — a single quantize is
    # within amax/254, but the running-amax write protocol REQUANTIZES a
    # page's earlier rows whenever a later token raises its scale, so each
    # incremental decode write can add another half-step of rounding;
    # a few steps of slack covers the compounding)
    k_pool, _ = pool.gather(pt, t0 + steps)
    k_want = np.asarray(cache["k"][:, 0, :t0 + steps])
    if pool.quantized:
        page_of = np.asarray(pt)[np.arange(t0 + steps) // PAGE]
        step = np.asarray(pool.k_scale)[:, page_of][..., None]
        err = np.abs(np.asarray(k_pool) - k_want)
        worst = float((err / np.maximum(step, 1e-9)).max())
        assert worst <= 5.0, f"gather off by {worst:.2f} quant steps"
    else:
        np.testing.assert_allclose(np.asarray(k_pool), k_want,
                                   atol=1e-5, rtol=1e-5)


def _engine_outputs(cfg, model, params, *, paged, n_req=3, pool_dtype="",
                    static_library=None):
    eng = MPICEngine(model, params,
                     EngineConfig(max_seq_len=128, decode_slots=2,
                                  paged=paged, page_size=PAGE,
                                  pool_dtype=pool_dtype),
                     static_library=static_library)
    for mid in ("A", "B"):
        eng.upload("u1", mid, image_embeds(mid, 16, cfg.d_model))
    eng.upload("*", "RAG1", image_embeds("RAG1", 12, cfg.d_model),
               dynamic=True)
    reqs = []
    for i in range(n_req):
        r = np.random.default_rng(i)
        prompt = Prompt([
            text_segment(r.integers(8, 200, 5)),
            media_segment("A", image_embeds("A", 16, cfg.d_model)),
            text_segment(r.integers(8, 200, 4)),
            media_segment("B", image_embeds("B", 16, cfg.d_model)),
        ], user_id="u1")
        req = Request(prompt=prompt, max_new_tokens=6, policy="mpic",
                      policy_kwargs={"k": 4})
        if i == 0:      # exercise the paged MRAG link path too
            req.retrieval_query = image_embeds("RAG1", 12,
                                               cfg.d_model).mean(0)
        reqs.append(eng.submit(req))
    eng.run()
    return eng, reqs


@pytest.fixture(scope="module")
def fp32_llava():
    cfg = dataclasses.replace(get_smoke_config("llava-1.6-7b"),
                              param_dtype="float32",
                              compute_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_engine_paged_matches_dense(fp32_llava):
    """Same requests through the paged and dense engines produce the same
    greedy continuations (fp32; includes an MRAG-linked request)."""
    cfg, model, params = fp32_llava
    eng_p, reqs_p = _engine_outputs(cfg, model, params, paged=True)
    eng_d, reqs_d = _engine_outputs(cfg, model, params, paged=False)
    assert eng_p._use_paged and not eng_d._use_paged
    for rp, rd in zip(reqs_p, reqs_d):
        assert rp.output_tokens == rd.output_tokens
        assert rp.linked_media == rd.linked_media
    assert "RAG1" in reqs_p[0].linked_media


def test_engine_paged_int8_matches_fp32_pool(fp32_llava, monkeypatch):
    """End to end with ``pool_dtype='int8'``: the same requests through the
    int8-resident pool give the fp32 pool's logits within
    ``POOL_TOL['int8']`` — at the first token (prefill through the int8
    pages) and at every decode step fed the same tokens — and the pool
    reports quantized buffers.  Sampled tokens are not compared past a
    divergence: with random weights the top two logits can sit closer than
    the KV quantization error, so one rounding flips an argmax."""
    cfg, model, params = fp32_llava
    rows = {}
    select = MPICEngine._select_token

    def spy(self, req, logits_row):
        key = (self.pool.quantized, req.req_id)
        rows.setdefault(key, []).append(np.array(logits_row))
        return select(self, req, logits_row)

    monkeypatch.setattr(MPICEngine, "_select_token", spy)
    eng_q, reqs_q = _engine_outputs(cfg, model, params, paged=True,
                                    pool_dtype="int8")
    eng_f, reqs_f = _engine_outputs(cfg, model, params, paged=True)
    assert eng_q._use_paged and eng_q.pool.quantized
    assert not eng_f.pool.quantized
    for rq, rf in zip(reqs_q, reqs_f):
        assert rq.done and rf.done
        assert rq.linked_media == rf.linked_media
        lq, lf = rows[(True, rq.req_id)], rows[(False, rf.req_id)]
        assert len(lq) == len(lf) == len(rq.output_tokens)
        for step, (a, b) in enumerate(zip(lq, lf)):
            np.testing.assert_allclose(a, b, **POOL_TOL["int8"])
            if rq.output_tokens[step] != rf.output_tokens[step]:
                break       # later steps were fed different tokens
    # pages recycle identically (scale buffers free with their pages)
    assert eng_q.pool.free_pages == eng_q.pool.cfg.num_pages - 1


def test_engine_int8_pool_zero_copy_links(fp32_llava):
    """Satellite: an int8 library feeding an int8 pool links by pure
    rescaling — no dequantize→requantize fp round trip.  The library's
    stats must show every static link took the direct path and that lazy
    dequantization never fired."""
    from repro.cache import KVLibrary

    cfg, model, params = fp32_llava
    lib = KVLibrary(quantize=True)
    eng, reqs = _engine_outputs(cfg, model, params, paged=True,
                                pool_dtype="int8", static_library=lib)
    assert all(r.done for r in reqs)
    st = lib.stats()
    assert st["direct_links"] > 0, "int8→int8 zero-copy path never taken"
    assert st["dequants"] == 0, "fp materialization defeated the fast path"

    # the fp32 pool cannot take the quantized fast path: it dequantizes at
    # link time instead (counted), and takes zero direct links
    lib_fp = KVLibrary(quantize=True)
    _, reqs_fp = _engine_outputs(cfg, model, params, paged=True,
                                 static_library=lib_fp)
    assert all(r.done for r in reqs_fp)
    st_fp = lib_fp.stats()
    assert st_fp["direct_links"] == 0 and st_fp["dequants"] > 0


def test_dense_engine_rejects_int8_pool():
    """Satellite: the dense fallback cache carries no per-page scales, so
    ``pool_dtype='int8'`` without the paged pool must fail loudly at
    construction — both when dense is requested and when an unsupported
    arch silently falls back to dense."""
    cfg = _tiny_cfg(4, 2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="paged KV pool"):
        MPICEngine(model, params,
                   EngineConfig(max_seq_len=64, decode_slots=1, paged=False,
                                pool_dtype="int8"))
    # ssm arch has no paged decode path -> paged=True still lands on the
    # dense fallback, which must reject int8 the same way
    mcfg = get_smoke_config("mamba2-130m")
    mmodel = build_model(mcfg)
    mparams = mmodel.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="paged KV pool"):
        MPICEngine(mmodel, mparams,
                   EngineConfig(max_seq_len=64, decode_slots=1, paged=True,
                                pool_dtype="int8"))


def test_engine_paged_pool_recycled(fp32_llava):
    """All pages return to the pool when requests complete (scratch stays)."""
    cfg, model, params = fp32_llava
    eng, _ = _engine_outputs(cfg, model, params, paged=True)
    assert eng.running == [None, None]
    total = eng.pool.cfg.num_pages
    assert eng.pool.free_pages == total - 1         # only scratch retained


def test_unsupported_arch_falls_back_to_dense():
    cfg = get_smoke_config("mamba2-130m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = MPICEngine(model, params,
                     EngineConfig(max_seq_len=96, decode_slots=1, paged=True))
    assert not eng._use_paged and eng.pool is None
    r = np.random.default_rng(0)
    req = Request(prompt=Prompt([text_segment(r.integers(8, 200, 12))],
                                user_id="u"), max_new_tokens=2)
    eng.submit(req)
    eng.run()
    assert len(req.output_tokens) == 2


def test_chunked_prefill_reserves_pages_up_front():
    """A pool with room for ONE prompt + chunked prefill: the second request
    must be held in the queue until the first frees its pages (regression:
    the gate used to double-count pages not yet allocated by in-flight
    chunked prefills, crashing the later finalize)."""
    cfg = _tiny_cfg(4, 2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = MPICEngine(model, params,
                     EngineConfig(max_seq_len=64, decode_slots=2, paged=True,
                                  page_size=PAGE, num_pages=4,  # scratch + 3
                                  prefill_chunk_tokens=8))
    r = np.random.default_rng(0)
    reqs = [eng.submit(Request(prompt=Prompt(
                [text_segment(r.integers(1, 100, 20))], user_id="u"),
            max_new_tokens=2, policy="full_recompute")) for _ in range(2)]
    eng.run()
    assert all(q.done for q in reqs)
    assert all(len(q.output_tokens) == 2 for q in reqs)
    assert eng.pool.free_pages == 3


def test_overlong_prompt_for_pool_rejected_at_submit():
    cfg = _tiny_cfg(4, 2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = MPICEngine(model, params,
                     EngineConfig(max_seq_len=64, decode_slots=1, paged=True,
                                  page_size=PAGE, num_pages=3))  # 16 usable
    r = np.random.default_rng(0)
    big = Prompt([text_segment(r.integers(1, 100, 20))], user_id="u")
    with pytest.raises(AssertionError):
        eng.submit(Request(prompt=big, max_new_tokens=1))


def test_paged_pool_exhaustion_truncates_decode():
    """An undersized pool finishes the request early instead of wedging."""
    cfg = _tiny_cfg(4, 2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = MPICEngine(model, params,
                     EngineConfig(max_seq_len=64, decode_slots=1, paged=True,
                                  page_size=PAGE, num_pages=3))  # 1 scratch
    r = np.random.default_rng(0)
    req = Request(prompt=Prompt([text_segment(r.integers(1, 100, 12))],
                                user_id="u"),
                  max_new_tokens=32, policy="full_recompute")
    eng.submit(req)
    eng.run()
    assert req.done
    assert req.prefill_stats.get("truncated") is True
    assert 0 < len(req.output_tokens) < 32
    assert eng.pool.free_pages == eng.pool.cfg.num_pages - 1
