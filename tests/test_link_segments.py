"""The fp link writes each placed segment from its library entry's own K/V.

``link_paged`` scatters every placed media segment into the pool with its
own donated link write, at the segment's exact length: no host
concatenation of the segments and no zero padding to a bucket.  These tests
hold it to the single padded scatter it replaced (same pool bytes, same
link result), to the dense ``link_prompt`` path (same greedy tokens), and
to one link program per media length (a prompt with the same image lengths
in another order or count compiles nothing new).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import KVLibrary, PagedConfig, PagedKVPool
from repro.configs import get_smoke_config
from repro.core import Prompt, media_segment, precompute_media_kv, \
    text_segment
from repro.core.linker import bucket, link_paged
from repro.core.select import mpic_selection
from repro.data import image_embeds
from repro.models import build_model
from repro.serving import EngineConfig, MPICEngine, Request

PAGE = 8
# two media lengths, neither a multiple of the page, so adjacent images
# share a page
LENS = {"A": 12, "B": 20, "C": 12, "D": 20}
MISS = "M"                  # in prompts, never in the library
# (system tokens, media ids in prompt order, question tokens)
PROMPTS = [
    (6, ("A", "B", "C"), 5),
    (6, ("B", MISS, "A"), 7),
    (3, ("C", "D"), 4),
]


@pytest.fixture(scope="module")
def smoke():
    cfg = get_smoke_config("llava-1.6-7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompt(cfg, n_sys, media, n_q, seed):
    r = np.random.default_rng(seed)
    segs = [text_segment(r.integers(8, 200, n_sys), kind="system")]
    for mid in media:
        n = LENS.get(mid, 12)
        segs.append(media_segment(mid, image_embeds(mid, n, cfg.d_model)))
    segs.append(text_segment(r.integers(8, 200, n_q)))
    return Prompt(segs, user_id="u")


def _old_single_scatter(pool, placed, page_row, scratch_page):
    """The operands of the link as one scatter: every placed segment's K/V
    concatenated, then padded with zeros to the power-of-two bucket, pad
    rows pointed at the scratch page."""
    ps = pool.cfg.page_size
    idx = np.concatenate([np.arange(off, off + n) for off, _, n in placed])
    delta = np.concatenate([np.full(n, off, np.int32)
                            for off, _, n in placed])
    n_placed = len(idx)
    b = min(bucket(n_placed), max(ps, 8) * max(len(page_row), 1))
    pad = b - n_placed
    pages = np.full((b,), scratch_page, np.int32)
    offs = np.zeros((b,), np.int32)
    pages[:n_placed] = np.asarray(page_row)[idx // ps]
    offs[:n_placed] = idx % ps
    k = np.concatenate([np.asarray(e.k) for _, e, _ in placed], axis=1)
    v = np.concatenate([np.asarray(e.v) for _, e, _ in placed], axis=1)
    z = np.zeros(k.shape[:1] + (pad,) + k.shape[2:], k.dtype)
    k, v = np.concatenate([k, z], axis=1), np.concatenate([v, z], axis=1)
    delta = np.concatenate([delta, np.zeros(pad, np.int32)])
    return [jnp.asarray(a) for a in (pages, offs, k, v, delta)]


def _polluted_pools(cfg, n_pages, dtype):
    """Two pools holding the same stale bytes (a previous tenant's)."""
    pcfg = PagedConfig(num_pages=n_pages, page_size=PAGE,
                       num_layers=cfg.num_layers,
                       num_kv_heads=cfg.num_kv_heads,
                       head_dim=cfg.head_dim, dtype=dtype)
    pools = [PagedKVPool(pcfg), PagedKVPool(pcfg)]
    rng = np.random.default_rng(3)
    shape = pools[0].k.shape
    if dtype == "int8":
        k = rng.integers(-127, 128, shape).astype(np.int8)
        v = rng.integers(-127, 128, shape).astype(np.int8)
        sshape = pools[0].k_scale.shape
        ks = rng.uniform(0.0, 0.02, sshape).astype(np.float32)
        vs = rng.uniform(0.0, 0.02, sshape).astype(np.float32)
    else:
        k = rng.standard_normal(shape).astype(np.float32)
        v = rng.standard_normal(shape).astype(np.float32)
    for p in pools:
        p.k, p.v = jnp.asarray(k, p.k.dtype), jnp.asarray(v, p.v.dtype)
        if dtype == "int8":
            p.k_scale, p.v_scale = jnp.asarray(ks), jnp.asarray(vs)
    return pools


def _bits(x):
    a = np.asarray(x)
    return a.view(f"u{a.dtype.itemsize}")


@pytest.mark.parametrize("pool_dtype", ["float32", "bfloat16", "int8"])
def test_link_matches_single_scatter_reference(smoke, pool_dtype):
    """Per-segment writes leave the pool as the concatenate-pad-scatter
    link did, and the link result is unchanged.

    Prompts mix two media lengths in varied order, with one library miss;
    the pools start from the same stale bytes.  fp pools: every page is
    bit-identical except the scratch page, which only the padded scatter
    wrote and the per-segment link leaves alone.  int8 pool fed fp entries
    (quantize-on-write through running page scales): scales bit-identical
    everywhere, rows bit-identical on every page one segment touches; on a
    page two adjacent segments share, the later segment's write raises the
    page scale after the earlier one's rows were quantized, so those rows
    are requantized once more — the same as any later write to a page —
    and may move by one int8 step."""
    cfg, model, params = smoke
    lib = KVLibrary()
    for mid, n in LENS.items():
        k, v = precompute_media_kv(model, params, jnp.asarray(
            image_embeds(mid, n, cfg.d_model)))
        lib.put("u", mid, k, v)
    prompts = [_prompt(cfg, *p, seed=i) for i, p in enumerate(PROMPTS)]
    n_pages = 1 + sum(-(-p.total_len // PAGE) + 1 for p in prompts)
    pool, ref = _polluted_pools(cfg, n_pages, pool_dtype)
    scratch = int(pool.alloc("__scratch__", 1)[0])
    assert int(ref.alloc("__scratch__", 1)[0]) == scratch
    relink = bool(cfg.rope_theta) and not cfg.learned_pos_emb
    touched = {}            # page -> segments written into it, all prompts
    for i, prompt in enumerate(prompts):
        row = pool.alloc(f"r{i}", prompt.total_len + 1)
        assert np.array_equal(ref.alloc(f"r{i}", prompt.total_len + 1), row)
        selection = mpic_selection(prompt, 4)
        scratch_before = [_bits(a[:, scratch]) for a in (pool.k, pool.v)]
        got = link_paged(model, prompt, lib, selection, pool, row,
                         scratch_page=scratch)

        placed = []
        want_sel = selection.copy()
        for off, seg in prompt.media_segments():
            if seg.media_id == MISS:
                want_sel[off:off + seg.length] = True
                continue
            placed.append((off, lib.get("u", seg.media_id), seg.length))
            for p in set(row[np.arange(off, off + seg.length) // PAGE]):
                touched[int(p)] = touched.get(int(p), 0) + 1
        ref.link_write(*_old_single_scatter(ref, placed, row, scratch),
                       theta=cfg.rope_theta, relink=relink)

        assert got.misses == ([MISS] if MISS in PROMPTS[i][1] else [])
        np.testing.assert_array_equal(got.sel_idx, np.nonzero(want_sel)[0])
        np.testing.assert_array_equal(got.forced, want_sel & ~selection)
        assert got.n_recomputed == int(want_sel.sum())
        assert got.n_reused == prompt.total_len - int(want_sel.sum())
        assert got.total == prompt.total_len
        # the per-segment link never writes the scratch page
        for before, a in zip(scratch_before, (pool.k, pool.v)):
            np.testing.assert_array_equal(_bits(a[:, scratch]), before)
        if pool.quantized:
            for a, b in ((pool.k_scale, ref.k_scale),
                         (pool.v_scale, ref.v_scale)):
                keep = np.ones(a.shape[1], bool)
                keep[scratch] = False
                np.testing.assert_array_equal(_bits(a[:, keep]),
                                              _bits(b[:, keep]))
        once = [p for p in range(n_pages)
                if p != scratch and touched.get(p, 0) <= 1]
        twice = [p for p, c in touched.items() if c > 1]
        for a, b in ((pool.k, ref.k), (pool.v, ref.v)):
            np.testing.assert_array_equal(_bits(a[:, once]),
                                          _bits(b[:, once]))
            if twice:
                a2 = np.asarray(a[:, twice])
                b2 = np.asarray(b[:, twice])
                if pool.quantized:
                    diff = np.abs(a2.astype(np.int32) - b2.astype(np.int32))
                    assert diff.max() <= 1
                else:
                    np.testing.assert_array_equal(_bits(a2), _bits(b2))
    assert twice, "the prompts must put two segments on one page"


def test_each_link_write_carries_one_segment_and_compiles_once(smoke):
    """The pool's link jit sees one segment's tokens per call (K, V, pages,
    offsets and delta all at the segment's length), and a prompt with the
    same image lengths in another order and count compiles no new link."""
    cfg, model, params = smoke
    eng = MPICEngine(model, params,
                     EngineConfig(max_seq_len=128, decode_slots=2,
                                  page_size=PAGE))
    for mid, n in LENS.items():
        eng.upload("u", mid, image_embeds(mid, n, cfg.d_model))
    pool = eng.pool
    link_jit = pool._link_jit
    calls = []

    def recording(pool_k, pool_v, pages, offs, k, v, delta, **kw):
        calls.append((k.shape[1], v.shape[1], pages.shape[0],
                      offs.shape[0], delta.shape[0]))
        return link_jit(pool_k, pool_v, pages, offs, k, v, delta, **kw)

    pool._link_jit = recording

    def serve(media, seed):
        calls.clear()
        req = eng.submit(Request(prompt=_prompt(cfg, 6, media, 5, seed),
                                 max_new_tokens=2, policy="mpic",
                                 policy_kwargs={"k": 4}))
        eng.run()
        assert req.state.value == "done"
        for c in calls:
            assert len(set(c)) == 1, f"operands of unequal length {c}"
        return [c[0] for c in calls]

    assert serve(("A", "B", "C"), 0) == [12, 20, 12]
    n_programs = link_jit._cache_size()
    assert serve(("D", "A"), 1) == [20, 12]
    assert serve(("B", "C", "D", "A"), 2) == [20, 12, 20, 12]
    assert link_jit._cache_size() == n_programs, \
        "a known media length must not compile another link"


def test_engine_tokens_match_dense_link_with_mixed_lengths(smoke):
    """Greedy tokens through the per-segment paged link equal the dense
    ``link_prompt`` path's (fp32 smoke llava), over prompts with two media
    lengths in varied order and one library miss."""
    cfg, _, _ = smoke
    cfg = dataclasses.replace(cfg, param_dtype="float32",
                              compute_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def drive(paged_prefill):
        eng = MPICEngine(model, params,
                         EngineConfig(max_seq_len=128, decode_slots=2,
                                      page_size=PAGE,
                                      paged_prefill=paged_prefill))
        for mid, n in LENS.items():
            eng.upload("u", mid, image_embeds(mid, n, cfg.d_model))
        reqs = [eng.submit(Request(prompt=_prompt(cfg, *p, seed=i),
                                   max_new_tokens=5, policy="mpic",
                                   policy_kwargs={"k": 4}))
                for i, p in enumerate(PROMPTS)]
        eng.run()
        assert all(r.state.value == "done" for r in reqs)
        return eng, reqs

    eng_new, new = drive(True)
    eng_old, old = drive(False)
    assert eng_new._prefiller is not None and eng_old._prefiller is None
    assert new[1].prefill_stats["misses"] == [MISS]
    for a, b in zip(new, old):
        assert a.output_tokens == b.output_tokens
