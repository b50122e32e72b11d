#!/usr/bin/env python3
"""Smoke run of MPIC serving on a TPU — a start-up proof, not a benchmark.

Serves ``llava-1.6-7b`` at its published widths (d_model 4096, 32 MHA
heads x 128, d_ff 11008, vocab 32000, 576 media tokens per image), with
random weights from ``PRNGKey(0)``, through ``repro.launch.serve``'s own
code: upload -> precompute media KV -> MPIC link + selective prefill into
the paged pool -> paged decode, for 2-image MMDU-style requests under the
``mpic`` and ``full_recompute`` policies.  Only depth is cut, to what one
16 GB v5e chip holds (16 of 32 layers: ~7.0 GB of bf16 weights, a 4 x 2048
token pool of 2.1 GB, and the library's images).

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --mesh 1x4   # four chips, tensor-parallel only

One chip: every request must finish with tokens and none may fail; the
engine must have resolved the compiled Pallas kernels (``interpret`` off,
``tpu_custom_call`` in the decode step's HLO); one decode step's logits
from the Pallas kernels must match the ``ref`` backend's on the chip, and
one paged-attention call on the served pages must match float64 numpy.

``--mesh 1x4`` runs only the sharded path and what it is compared with:
the full 32-layer model (which one chip cannot hold) serving a few
requests over four chips, with each chip's peak memory; then the same
requests at the one-chip depth, unsharded on one chip and sharded 1x4,
whose logits must be close and greedy tokens identical (a token may differ
only on a near-tie of the reference's top two logits; each is printed).

Without a TPU the script exits non-zero and prints no result.  The last
line of a passing run is the JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
# the TPU runtime otherwise logs to a directory outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ARCH = "llava-1.6-7b"
ONE_CHIP_LAYERS = 16        # of 32: what one 16 GB v5e chip holds (above)
REQUESTS = 4
MAX_NEW_TOKENS = 8
SLOTS = 4
MAX_SEQ_LEN = 2048
# Logits of two bf16 computations of the same step (Pallas vs the jnp
# reference, or sharded vs unsharded) differ by bf16 rounding (relative
# 2**-8 ~ 0.4% per op) carried along the residual stream: over the ~2 ops
# per layer of 16 layers that is ~0.4% x sqrt(32) ~ 2.2% in RMS.  Allowed:
# an RMS difference of 5% of the reference's RMS.  An fp8 computation
# (2**-4 ~ 6% per op) would exceed it at the first layer.
LOGIT_RMS_RTOL = 0.05
# One paged-attention call on the pool's bf16 pages, against float64 numpy
# on the same inputs: the kernel accumulates in f32 and rounds its output
# to bf16 (at most 2**-8 ~ 0.4% relative, ~0.16% in RMS).  Allowed: 0.5%
# in RMS.
ATTN_RMS_RTOL = 0.005


def _serve_args(*extra):
    from repro.launch import serve
    return serve.build_parser().parse_args(
        ["--arch", ARCH, "--requests", str(REQUESTS),
         "--policy", "mpic,full_recompute",
         "--max-new-tokens", str(MAX_NEW_TOKENS), "--slots", str(SLOTS),
         "--max-seq-len", str(MAX_SEQ_LEN), *extra])


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _close(label: str, got, want, limit: float = LOGIT_RMS_RTOL,
           what: str = "logits") -> float:
    """RMS of ``got - want`` over the RMS of ``want``; raises past
    ``limit`` (the largest difference is printed beside it)."""
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise AssertionError(f"{label}: non-finite {what}")
    rms = float(np.sqrt(np.mean((got - want) ** 2)
                        / max(np.mean(want ** 2), 1e-12)))
    worst = float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))
    _log(f"{label}: rms diff / rms = {rms:.5f} (limit {limit}); "
         f"max diff / max = {worst:.5f}")
    if rms > limit:
        raise AssertionError(f"{label}: {what} differ by {rms:.5f} in RMS "
                             f"(> {limit})")
    return rms


def _memory_line(devices) -> str:
    rows = []
    for d in devices:
        st = d.memory_stats() or {}
        rows.append(f"dev{d.id}: peak_bytes_in_use="
                    f"{st.get('peak_bytes_in_use', 0) / 1e9:.2f} GB, "
                    f"bytes_in_use={st.get('bytes_in_use', 0) / 1e9:.2f} GB"
                    f" of {st.get('bytes_limit', 0) / 1e9:.2f} GB")
    return "; ".join(rows)


def _record_logits(engine) -> dict:
    """Keep every logits row each request's tokens are selected from."""
    rows = {}
    select = engine._select_token

    def spy(req, logits_row):
        rows.setdefault(req.req_id, []).append(logits_row.copy())
        return select(req, logits_row)

    engine._select_token = spy
    return rows


def _compare_greedy(label: str, want: list, got: list) -> None:
    """``want``/``got``: per request ``(tokens, logits rows)`` of the same
    greedy requests from two computations.  Up to and including the first
    step where the tokens differ, both saw the same context, so those
    steps' logits must be close.  The tokens may differ only where the
    reference's top-2 margin is a near-tie: within twice the allowed RMS
    noise (bf16 rounding then picks either)."""
    import numpy as np
    same = 0
    for i, ((tok_w, rows_w), (tok_g, rows_g)) in enumerate(zip(want, got)):
        t = next((s for s, (a, b) in enumerate(zip(tok_w, tok_g)) if a != b),
                 None)
        for s in range(len(tok_w) if t is None else t + 1):
            _close(f"{label}, request {i}, step {s}", rows_g[s], rows_w[s])
        if t is None:
            same += 1
            continue
        top2 = np.sort(rows_w[t])[-2:]
        bound = 2 * LOGIT_RMS_RTOL * float(np.sqrt(np.mean(rows_w[t] ** 2)))
        _log(f"{label}, request {i}: tokens part at step {t} "
             f"({tok_w[t]} vs {tok_g[t]}), reference top-2 margin "
             f"{top2[1] - top2[0]:.4f} (near-tie bound {bound:.4f})")
        if top2[1] - top2[0] > bound:
            raise AssertionError(f"{label}: request {i} greedy tokens differ "
                                 f"at step {t} without a near-tie")
    _log(f"{label}: greedy tokens identical for {same} of {len(want)} "
         "requests")


def _check_served(eng, reqs, label: str) -> None:
    """Every request finished with all its tokens; none failed or expired.
    Logs each one."""
    bad = [(r.req_id, r.state, r.error) for r in eng.failed + eng.expired]
    if bad:
        raise AssertionError(f"{label}: requests failed: {bad}")
    for r in reqs:
        if not (r.done and len(r.output_tokens) == MAX_NEW_TOKENS):
            raise AssertionError(f"{label}: {r.req_id} ended {r.state} with "
                                 f"{len(r.output_tokens)} tokens")
        _log(f"{label}:   {r.req_id} {r.policy:15s} "
             f"prompt={r.prompt.total_len} "
             f"reused={r.prefill_stats.get('n_reused', 0)} "
             f"ttft={r.ttft * 1e3:.0f} ms tokens={r.output_tokens}")


def _run(server, args, *, label):
    """Submit the dialogues and serve them to completion."""
    from repro.launch import serve
    t0 = time.perf_counter()
    reqs = serve.submit_dialogues(server, args)
    t1 = time.perf_counter()
    server.engine.run()
    _log(f"{label}: uploads {t1 - t0:.1f} s, serving "
         f"{time.perf_counter() - t1:.1f} s (compilation included; smoke "
         "run, not a benchmark)")
    _check_served(server.engine, reqs, label)
    return reqs


def _check_decode_step(server) -> None:
    """With every request admitted and decoding: the decode step's HLO
    holds the Pallas kernel, and its logits match the ref backend's."""
    import jax
    import numpy as np

    eng, model = server.engine, server.engine.model
    live = [r for r in eng.running if r is not None
            and r.state.value == "running"]
    if not live:
        raise AssertionError("no decoding request to check")
    live, ops = eng.decode_operands(live)
    pool = eng.pool
    lowered = eng._decode_jit.lower(eng.params, pool.k, pool.v, *ops)
    # interpreted kernels (the CPU rehearsal) lower to plain HLO; on the
    # chip _check_engine_kernels has already required compiled ones
    has_kernel = "tpu_custom_call" in lowered.as_text()
    if not (has_kernel or eng._interpret):
        raise AssertionError("decode step HLO holds no tpu_custom_call")
    mem = lowered.compile().memory_analysis()
    _log(f"decode step: tpu_custom_call {'present' if has_kernel else 'absent'}"
         f"; memory_analysis: args={mem.argument_size_in_bytes / 1e9:.2f} GB"
         f" temp={mem.temp_size_in_bytes / 1e9:.2f} GB "
         f"out={mem.output_size_in_bytes / 1e9:.2f} GB")

    def logits(backend):
        fn = jax.jit(lambda p, k, v, *o: model.decode_step_paged(
            p, o[0], o[1], k, v, *o[2:], backend=backend,
            interpret=eng._interpret)[0])
        return np.asarray(fn(eng.params, pool.k, pool.v, *ops), np.float32)

    slots = [r.slot for r in live]
    got, want = logits("pallas"), logits("ref")
    _close(f"decode step, pallas vs ref, {len(slots)} slots",
           got[slots], want[slots])
    _check_attention(eng, slots, ops[2], ops[3])


def _check_attention(eng, slots, page_table, lengths) -> None:
    """One paged-attention call over the engine's layer-0 pages and the
    live slots' page tables, each backend against float64 numpy."""
    import jax
    import numpy as np

    from repro.kernels.paged_attn.ops import paged_attention_call
    cfg = eng.model.cfg
    k, v = eng.pool.k[0], eng.pool.v[0]              # (P, ps, Hkv, Dh)
    table = np.asarray(page_table)[slots]
    lens = np.asarray(lengths)[slots]
    q = jax.random.normal(jax.random.PRNGKey(1),
                          (len(slots), cfg.num_heads, cfg.head_dim), k.dtype)

    # float64 oracle on the same (rounded) inputs
    qn = np.asarray(q, np.float64)
    kn = np.asarray(k, np.float64)[table].reshape(len(slots), -1,
                                                  *k.shape[2:])
    vn = np.asarray(v, np.float64)[table].reshape(kn.shape)
    rep = cfg.num_heads // k.shape[2]
    kn, vn = np.repeat(kn, rep, axis=2), np.repeat(vn, rep, axis=2)
    s = np.einsum("bhd,bkhd->bhk", qn, kn) / np.sqrt(cfg.head_dim)
    s = np.where(np.arange(s.shape[-1]) < lens[:, None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhk,bkhd->bhd", p / p.sum(-1, keepdims=True), vn)

    for backend in ("pallas", "ref"):
        got = jax.jit(lambda *a: paged_attention_call(
            *a, backend=backend, interpret=eng._interpret))(
            q, k, v, table, lens)
        _close(f"layer-0 paged attention, {backend} vs float64, "
               f"{len(slots)} slots", got, want, ATTN_RMS_RTOL, "outputs")


def _check_engine_kernels(eng) -> None:
    if eng._paged_backend != "pallas" or eng._interpret:
        raise AssertionError(f"engine resolved backend={eng._paged_backend} "
                             f"interpret={eng._interpret}")
    _log("engine: paged backend pallas, interpret=False")


def one_chip(*, layers: int = ONE_CHIP_LAYERS, smoke: bool = False) -> None:
    """The main path on one chip (``smoke``: the CPU preset, for rehearsal
    off the chip through a caller that skips the platform check)."""
    import jax

    from repro.launch import serve
    args = _serve_args("--layers", str(layers), *(["--smoke"] if smoke
                                                   else []))
    t0 = time.perf_counter()
    server = serve.build_server(args)
    jax.block_until_ready(server.engine.params)
    cfg, eng = server.cfg, server.engine
    _log(f"{cfg.name}: {cfg.num_layers} of 32 layers, d_model={cfg.d_model}, "
         f"heads={cfg.num_heads}x{cfg.head_dim}, d_ff={cfg.d_ff}, "
         f"vocab={cfg.vocab_size}, media={cfg.media_token_len} tokens/image; "
         f"params {cfg.n_params() / 1e9:.2f} B; pool "
         f"{eng.pool.cfg.num_pages} pages x {eng.pool.cfg.page_nbytes / 1e6:.2f}"
         f" MB; built in {time.perf_counter() - t0:.1f} s")
    _check_engine_kernels(eng)

    # serve until every request is decoding, check one decode step, then
    # serve the rest to completion
    submitted = serve.submit_dialogues(server, args)
    t1 = time.perf_counter()
    steps = 0
    while sum(r.state.value == "running" for r in submitted) < SLOTS:
        if not eng.has_work:
            raise AssertionError("requests stopped before all decoded")
        eng.step()
        steps += 1
    _log(f"all {SLOTS} requests admitted after {steps} engine steps, "
         f"{time.perf_counter() - t1:.1f} s (compilation included)")
    _check_decode_step(server)
    eng.run()
    _log(f"served in {time.perf_counter() - t1:.1f} s (smoke run, "
         "not a benchmark); " + _memory_line(jax.devices()[:1]))
    _check_served(eng, submitted, f"{cfg.num_layers} layers")


def four_chips(mesh: str, *, smoke: bool = False) -> None:
    """Tensor-parallel serving over ``mesh`` and what it is compared with."""
    import jax

    from repro.launch import serve
    extra = ["--smoke"] if smoke else []
    depth = ["--layers", "2"] if smoke else []

    # the whole model, which one chip cannot hold, over the mesh
    args = _serve_args("--mesh", mesh, *extra, *depth)
    server = serve.build_server(args)
    _log(f"{server.cfg.name}: {server.cfg.num_layers} layers on mesh {mesh}")
    _run(server, args, label=f"{server.cfg.num_layers} layers, {mesh}")
    _log("per-device memory: " + _memory_line(jax.devices()))
    del server
    gc.collect()

    # parity at the one-chip depth: unsharded on one chip vs sharded
    layers = "2" if smoke else str(ONE_CHIP_LAYERS)
    outs = {}
    for m in ("none", mesh):
        args = _serve_args("--mesh", m, "--layers", layers, *extra)
        server = serve.build_server(args)
        rows = _record_logits(server.engine)
        reqs = _run(server, args, label=f"{layers} layers, mesh {m}")
        outs[m] = [(r.output_tokens, rows[r.req_id]) for r in reqs]
        del server
        gc.collect()
    _compare_greedy(f"{mesh} vs unsharded", outs["none"], outs[mesh])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", default="",
                    help="e.g. 1x4: run only the tensor-parallel path over "
                         "that mesh and its comparison")
    args = ap.parse_args(argv)
    try:
        from repro.launch.device import (attached_tpu_chips,
                                         enable_compile_cache,
                                         require_platform)
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    try:
        info = require_platform("tpu")
    except RuntimeError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    enable_compile_cache()
    _log(f"device: {info['kind']} x{info['count']} "
         f"({attached_tpu_chips()} TPU device nodes attached)")
    if args.mesh:
        n = 1
        for x in args.mesh.lower().split("x"):
            n *= int(x)
        if info["count"] < n:
            print(f"chip_smoke: mesh {args.mesh} needs {n} chips, "
                  f"{info['count']} attached", file=sys.stderr)
            return 1
        four_chips(args.mesh)
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": info["platform"], "kind": info["kind"],
        "count": info["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
