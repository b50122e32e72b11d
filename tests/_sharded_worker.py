"""Sharded-serving parity worker — run in a SUBPROCESS only.

Forces a 4-device host platform (the env vars below must be set before
jax initializes, which is why this cannot run inside the pytest process —
conftest must keep seeing 1 CPU device) and checks that the mesh-sharded
serving path numerically matches the single-device path:

  kernel      shard_map'd pallas paged attention == unsharded ref
  decode      paged decode: model-level logits + engine greedy tokens
  prefill     mpic paged prefill (link + selective attention into the pool)
  mrag        dynamic-library retrieval linking mid-decode
  cacheblend  deviation-driven re-selection policy
  dense       paged=False fallback (sharded dense splice/link/decode)

Each case prints ``PARITY-OK <case>`` on success; the parent test asserts
on it.  Usage: ``python tests/_sharded_worker.py <case>``.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import jax                                                 # noqa: E402
import jax.numpy as jnp                                    # noqa: E402
import numpy as np                                         # noqa: E402

from repro.cache import KVLibrary                          # noqa: E402
from repro.cache.paged import PagedConfig, PagedKVPool     # noqa: E402
from repro.configs.base import ModelConfig                 # noqa: E402
from repro.core import Prompt, media_segment, text_segment  # noqa: E402
from repro.data import image_embeds                        # noqa: E402
from repro.launch.mesh import make_serving_mesh, serving_rules  # noqa: E402
from repro.launch.pspec import use_policy                  # noqa: E402
from repro.models import build_model                       # noqa: E402
from repro.serving import EngineConfig, MPICEngine, Request  # noqa: E402
from repro.serving.sharding import ServingSharding         # noqa: E402

PAGE = 16


def _cfg(hq=4, hkv=4, window=0):
    return ModelConfig(name=f"shard-vlm-{hq}-{hkv}", arch_type="vlm",
                       num_layers=2, d_model=64, num_heads=hq,
                       num_kv_heads=hkv, head_dim=16, d_ff=128,
                       vocab_size=256, is_multimodal=True,
                       media_token_len=16, sliding_window=window,
                       param_dtype="float32", compute_dtype="float32")


def _prompt(cfg, seed):
    r = np.random.default_rng(seed)
    return Prompt([
        text_segment(r.integers(8, 200, 5)),
        media_segment("A", image_embeds("A", 16, cfg.d_model)),
        text_segment(r.integers(8, 200, 4)),
        media_segment("B", image_embeds("B", 16, cfg.d_model)),
    ], user_id="u1")


def _engine_pair(cfg, engine_cfg, *, dynamic_media=()):
    """Baseline (unsharded) and sharded engines over SHARED libraries, so
    both consume byte-identical precomputed entries."""
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    static, dynamic = KVLibrary(), KVLibrary(shared=True)
    mesh = make_serving_mesh()          # (1, 4): 4-way tensor parallel
    assert mesh.devices.size == 4, "worker needs the forced 4-device host"
    base = MPICEngine(model, params, engine_cfg,
                      static_library=static, dynamic_library=dynamic)
    shrd = MPICEngine(model, params, engine_cfg,
                      static_library=static, dynamic_library=dynamic,
                      mesh=mesh)
    for eng in (base, shrd):            # second upload overwrites the shared
        for mid in ("A", "B"):          # entry; both engines then read the
            eng.upload("u1", mid,       # same final bytes
                       image_embeds(mid, 16, cfg.d_model))
        for mid in dynamic_media:
            eng.upload("*", mid, image_embeds(mid, 12, cfg.d_model),
                       dynamic=True)
    return base, shrd


def _run_pair(base, shrd, reqs_fn, *, check_reused=True):
    outs = []
    for eng in (base, shrd):
        reqs = [eng.submit(r) for r in reqs_fn()]
        eng.run()
        for r in reqs:
            assert r.state.value == "done", f"{r.req_id}: {r.state}"
        outs.append(reqs)
    for rb, rs in zip(*outs):
        assert rb.output_tokens == rs.output_tokens, (
            f"token divergence: {rb.output_tokens} vs {rs.output_tokens}")
        if check_reused:
            assert rb.prefill_stats.get("n_reused") == \
                rs.prefill_stats.get("n_reused")
    return outs


def case_kernel():
    """shard_map'd pallas paged attention == unsharded ref, 4-way mesh."""
    from repro.kernels.paged_attn.ops import paged_attention_call
    rng = np.random.default_rng(0)
    b, hq, hkv, dh, pages = 2, 8, 4, 16, 6
    q = jnp.asarray(rng.standard_normal((b, hq, dh)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((pages, PAGE, hkv, dh)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((pages, PAGE, hkv, dh)),
                     jnp.float32)
    table = jnp.asarray(
        rng.permutation(pages)[:4][None, :].repeat(b, 0), jnp.int32)
    lengths = jnp.asarray([37, 12], jnp.int32)
    want = paged_attention_call(q, kp, vp, table, lengths, backend="ref")

    mesh = make_serving_mesh()
    sh = ServingSharding(mesh, _cfg(hq, hkv))
    # commit the layer's pool slices head-sharded like the engine does
    hs = sh.named(None, None, "model", None)
    kp_s, vp_s = jax.device_put(kp, hs), jax.device_put(vp, hs)
    with use_policy(mesh, serving_rules()):
        got = jax.jit(lambda *a: paged_attention_call(
            *a, backend="pallas", interpret=True))(
            q, kp_s, vp_s, table, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
    # windowed variant through the ref backend under GSPMD
    with use_policy(mesh, serving_rules()):
        got_w = jax.jit(lambda *a: paged_attention_call(
            *a, window=8, backend="ref"))(q, kp_s, vp_s, table, lengths)
    want_w = paged_attention_call(q, kp, vp, table, lengths, window=8,
                                  backend="ref")
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w),
                               atol=2e-4, rtol=2e-4)


def case_decode():
    """Model-level sharded paged decode logits + engine greedy parity."""
    cfg = _cfg(hq=8, hkv=4)             # GQA: 8 q / 4 kv heads on 4 devices
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    t0, steps = 11, 5
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (1, t0)), jnp.int32)
    cache = model.make_cache(1, 64)
    logits, cache = model.prefill(params, toks, cache)

    mesh = make_serving_mesh()
    sh = ServingSharding(mesh, cfg)
    pool = PagedKVPool(PagedConfig(num_pages=8, page_size=PAGE,
                                   num_layers=cfg.num_layers,
                                   num_kv_heads=4, head_dim=cfg.head_dim,
                                   dtype="float32"), sharding=sh.pool())
    pt = pool.alloc("r", t0 + steps)
    pool.write_tokens(pt, 0, cache["k"][:, 0, :t0], cache["v"][:, 0, :t0])
    page_table = jnp.asarray(pt[None])
    params_s = jax.device_put(params, sh.params(params))

    tok = int(jnp.argmax(logits[0, -1]))
    for i in range(steps):
        cur = t0 + i
        t = jnp.full((1, 1), tok, jnp.int32)
        p = jnp.full((1, 1), cur, jnp.int32)
        dense_logits, cache = model.decode_step(params, t, p, cache, p)
        with use_policy(mesh, serving_rules()):
            paged_logits, pool.k, pool.v = model.decode_step_paged(
                params_s, t, p, pool.k, pool.v, page_table,
                jnp.asarray([cur + 1], jnp.int32),
                jnp.asarray([pt[cur // PAGE]], jnp.int32),
                jnp.asarray([cur % PAGE], jnp.int32), backend="ref")
        np.testing.assert_allclose(np.asarray(paged_logits[0], np.float32),
                                   np.asarray(dense_logits[0], np.float32),
                                   atol=2e-4, rtol=2e-4)
        tok = int(jnp.argmax(dense_logits[0]))

    # end to end: engine greedy rollout parity (paged decode jit with
    # explicit in/out shardings, donated pool)
    cfg = _cfg()
    base, shrd = _engine_pair(cfg, EngineConfig(max_seq_len=128,
                                                decode_slots=2,
                                                page_size=PAGE))
    assert shrd._use_paged and shrd.pool.sharding is not None
    # mpic exercises the paged prefiller; full_recompute exercises the
    # dense-policy-result -> sharded-pool splice (_splice_paged)
    _run_pair(base, shrd, lambda: [
        Request(prompt=_prompt(cfg, i), max_new_tokens=6, policy="mpic",
                policy_kwargs={"k": 4}) for i in range(3)] + [
        Request(prompt=_prompt(cfg, 50), max_new_tokens=6,
                policy="full_recompute")])


def case_prefill():
    """mpic paged prefill (pool link + selective attention) parity."""
    cfg = _cfg()
    base, shrd = _engine_pair(cfg, EngineConfig(max_seq_len=128,
                                                decode_slots=2,
                                                page_size=PAGE))
    assert shrd._prefiller is not None and \
        shrd._prefiller.sharding is not None
    _run_pair(base, shrd, lambda: [
        Request(prompt=_prompt(cfg, 10 + i), max_new_tokens=4,
                policy="mpic", policy_kwargs={"k": 4}) for i in range(2)])
    # same-bucket traffic must not retrace the sharded prefill jit either
    assert shrd._prefiller.traces == base._prefiller.traces


def case_mrag():
    cfg = _cfg()
    base, shrd = _engine_pair(cfg, EngineConfig(max_seq_len=128,
                                                decode_slots=2,
                                                page_size=PAGE),
                              dynamic_media=("RAG1",))

    def reqs():
        r = Request(prompt=_prompt(cfg, 99), max_new_tokens=4,
                    policy="mpic", policy_kwargs={"k": 4})
        r.retrieval_query = image_embeds("RAG1", 12, cfg.d_model).mean(0)
        return [r]

    outs = _run_pair(base, shrd, reqs)
    for reqs_ in outs:
        assert "RAG1" in reqs_[0].linked_media


def case_cacheblend():
    """Deviation-probe policy under 4-way TP.  The probe's layer-0 K
    deviations of linked media are float noise (sharded and unsharded sums
    round differently), so ranking them picks different tokens on the two
    engines; pin the selection — as test_paged_prefill's cacheblend parity
    does — and compare the sharded machinery exactly.  The deviations the
    sharded probe measures must still match the unsharded probe's."""
    from repro.core import policies as pol_mod

    devs = []                                      # one per engine, in order

    def fixed_selection(prompt_, dev, r):
        sel = np.zeros((prompt_.total_len,), bool)
        sel[~prompt_.media_mask()] = True          # all text
        media_idx = np.nonzero(prompt_.media_mask())[0]
        sel[media_idx[::4]] = True                 # every 4th media token
        assert dev.shape == (prompt_.total_len,)
        devs.append(np.asarray(dev, np.float64))
        return sel

    pol_mod.sel_mod.cacheblend_selection = fixed_selection
    cfg = _cfg()
    base, shrd = _engine_pair(cfg, EngineConfig(max_seq_len=128,
                                                decode_slots=2,
                                                page_size=PAGE))
    _run_pair(base, shrd, lambda: [
        Request(prompt=_prompt(cfg, 7), max_new_tokens=4,
                policy="cacheblend", policy_kwargs={"r": 0.25})])
    # f32: text deviations are O(10), media ones O(1e-5); the two probes
    # agree to ~1e-5 absolute
    dev_base, dev_shrd = devs
    np.testing.assert_allclose(dev_shrd, dev_base, rtol=1e-5, atol=1e-4)


def case_dense():
    """paged=False fallback: sharded dense cache + splice/link jits."""
    cfg = _cfg()
    base, shrd = _engine_pair(cfg, EngineConfig(max_seq_len=128,
                                                decode_slots=2,
                                                paged=False),
                              dynamic_media=("RAG1",))
    assert not shrd._use_paged and shrd._batch_cache is not None

    def reqs():
        a = Request(prompt=_prompt(cfg, 3), max_new_tokens=5, policy="mpic",
                    policy_kwargs={"k": 4})
        a.retrieval_query = image_embeds("RAG1", 12, cfg.d_model).mean(0)
        b = Request(prompt=_prompt(cfg, 4), max_new_tokens=5,
                    policy="full_recompute")
        return [a, b]

    _run_pair(base, shrd, reqs, check_reused=False)


def case_int8():
    """Int8-resident pool under 4-way tensor parallelism: the per-page
    scale buffers must carry the same kv-head sharding as the pages, and
    the quantized decode/prefill jits (donating pages AND scales) must
    produce the same greedy tokens as the unsharded int8 engine."""
    cfg = _cfg()
    base, shrd = _engine_pair(cfg, EngineConfig(max_seq_len=128,
                                                decode_slots=2,
                                                page_size=PAGE,
                                                pool_dtype="int8"),
                              dynamic_media=("RAG1",))
    assert base.pool.quantized and shrd.pool.quantized
    assert shrd.pool.scale_sharding is not None
    # scales are (L, P, Hkv): kv heads live on 'model', like the pages
    assert shrd.pool.scale_sharding.spec[2] == "model"
    assert shrd.pool.k_scale.sharding.spec[2] == "model"

    def reqs():
        out = [Request(prompt=_prompt(cfg, 40 + i), max_new_tokens=6,
                       policy="mpic", policy_kwargs={"k": 4})
               for i in range(2)]
        out[0].retrieval_query = image_embeds("RAG1", 12,
                                              cfg.d_model).mean(0)
        return out

    outs = _run_pair(base, shrd, reqs)
    for reqs_ in outs:
        assert "RAG1" in reqs_[0].linked_media
    # pages + scales recycle cleanly on both engines
    for eng in (base, shrd):
        assert eng.pool.free_pages == eng.pool.cfg.num_pages - 1


def case_nondiv():
    """Head counts that do NOT divide the 4-way model axis: every guard
    (ServingSharding.axis, head_shard_axis, pspec.shard) must fall back to
    replicated — same tokens, no shape error (README's guarantee)."""
    cfg = _cfg(hq=6, hkv=6)
    base, shrd = _engine_pair(cfg, EngineConfig(max_seq_len=128,
                                                decode_slots=2,
                                                page_size=PAGE))
    assert shrd.pool.sharding is not None
    assert shrd.pool.sharding.spec[3] is None    # 6 % 4 != 0 -> replicated
    assert shrd.sharding.axis("kv_heads", cfg.num_kv_heads) is None
    _run_pair(base, shrd, lambda: [
        Request(prompt=_prompt(cfg, 20 + i), max_new_tokens=5,
                policy="mpic", policy_kwargs={"k": 4}) for i in range(2)])

    # dense fallback with the SAME non-dividing heads AND a kv length that
    # does not divide either: cache_pspecs's kv-seq-on-'model' fallback
    # must drop to replicated (guarded against the concrete cache shapes),
    # not crash engine construction
    base_d, shrd_d = _engine_pair(cfg, EngineConfig(max_seq_len=130,
                                                    decode_slots=2,
                                                    paged=False))
    assert not shrd_d._use_paged
    _run_pair(base_d, shrd_d, lambda: [
        Request(prompt=_prompt(cfg, 30), max_new_tokens=4, policy="mpic",
                policy_kwargs={"k": 4})])


CASES = {"kernel": case_kernel, "decode": case_decode,
         "prefill": case_prefill, "mrag": case_mrag,
         "cacheblend": case_cacheblend, "dense": case_dense,
         "nondiv": case_nondiv, "int8": case_int8}


def main():
    case = sys.argv[1]
    assert len(jax.devices()) == 4, jax.devices()
    CASES[case]()
    print(f"PARITY-OK {case}")


if __name__ == "__main__":
    main()
