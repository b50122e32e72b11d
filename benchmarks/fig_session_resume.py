"""Session freeze/thaw resume: warm thaw TTFT vs cold full-recompute,
across idle-eviction tiers, plus the fork (tree-search) page-sharing leg.

A multi-turn session is frozen mid-decode (its live KV pages snapshot
into the tiered library under the session's ``cache_salt``) and later
resumed with the next user turn's suffix.  Three matched resume legs
answer "what does a returning user pay?":

  * **thaw_memory** — the snapshot never left the memory tier; thaw
    adopts the pages and prefills ONLY the new turn's suffix.
  * **thaw_disk** — the idle sweep spooled the snapshot to disk
    (``KVLibrary.spool_now``); thaw additionally pays the disk read
    (+ requant-free int8 adopt when the pool is quantized).
  * **cold** — no snapshot: the full token history (prompt + every
    generated token) is re-prefilled from scratch, the paper's
    recompute-on-return baseline.

TTFT is the wall clock of the resume call itself (adopt + suffix
prefill + first sampled token), jit-warm: a full warmup cycle runs
first in a DISJOINT user scope with identical shapes, so the timed
probes pay no compile.  Gates (skipped under ``--smoke``):

  * warm (memory) thaw TTFT ≥ 5x faster than cold full-recompute.
  * token parity, both ways: ``frozen[:-1] + thawed`` equals the
    never-frozen session, and suffix-thaw tokens equal the cold leg's.

The fork leg freezes one session and forks ``FORK_N`` copy-on-write
children: the pool must report ZERO page copies at fork time (children
share every parent page) and exactly the divergence cost — one write
page per child beyond the last owner — after one decode step.  Emits
``BENCH_sessions.json`` (``.smoke.json`` under ``--smoke``).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import build_bench_model, emit, scaled, smoke
from repro.core import Prompt, text_segment
from repro.serving import EngineConfig, MPICEngine, Request

PROMPT_LEN = scaled(384, 32)
FREEZE_AFTER = scaled(16, 3)
SUFFIX_LEN = scaled(16, 4)
MAX_NEW = scaled(24, 6)
MAX_SEQ_LEN = scaled(1024, 128)
N_PROBE = scaled(3, 1)
FORK_N = 4

OUT_PATH = os.environ.get(
    "MPIC_BENCH_OUT",
    "BENCH_sessions.smoke.json" if smoke() else "BENCH_sessions.json")


def _toks(seed, n):
    return np.random.default_rng(seed).integers(8, 200, n)


def _req(toks, user_id, *, max_new=MAX_NEW, freeze_after=None, seed=9):
    return Request(prompt=Prompt([text_segment(toks)], user_id=user_id),
                   max_new_tokens=max_new, policy="full_recompute",
                   seed=seed, freeze_after=freeze_after)


def _engine(model, params, lib=None, *, slots=2):
    return MPICEngine(model, params,
                      EngineConfig(max_seq_len=MAX_SEQ_LEN,
                                   decode_slots=slots),
                      static_library=lib)


def _freeze_session(eng, toks, user_id, *, seed=9):
    """Run a session to its freeze point; returns (request, handle)."""
    r = _req(toks, user_id, freeze_after=FREEZE_AFTER, seed=seed)
    eng.submit(r)
    eng.run()
    assert r.state.value == "frozen", r.state
    return r, eng.sessions.handles[r.session_id]


def _timed_thaw(eng, handle, suffix):
    """Wall clock of the resume itself: snapshot fetch + page adopt +
    suffix prefill + first token.  The engine then runs the request to
    completion (freeing its pages) outside the timed region."""
    t0 = time.perf_counter()
    req = eng.thaw(handle, suffix, max_new_tokens=2)
    dt = time.perf_counter() - t0
    eng.run()
    return dt, req


def _timed_cold(eng, full_history, user_id, suffix):
    """The no-snapshot baseline: re-prefill the ENTIRE history plus the
    new turn, timed to the first token (host-side TTFT — submit triggers
    the prefill, so the clock wraps it)."""
    toks = np.concatenate([np.asarray(full_history, np.int32),
                           np.asarray(suffix, np.int32)])
    r = _req(toks, user_id, max_new=2)
    steps = 0
    t0 = time.perf_counter()
    eng.submit(r)
    while not r.t_first_token and steps < 10_000:
        eng.step()
        steps += 1
    dt = time.perf_counter() - t0
    eng.run()
    return dt, r


def _resume_cycle(model, params, engines, user_id, seed):
    """One full freeze → (memory thaw, disk thaw, cold) cycle in its own
    user scope.  The engines are SHARED across cycles (per-engine jits
    compile once): the first cycle runs as warmup in a disjoint scope,
    so the timed probes pay no compile."""
    e_fz, e_base, e_pi, e_thaw, e_cold = engines
    toks = _toks(seed, PROMPT_LEN)
    suffix = [int(t) for t in _toks(seed + 1, SUFFIX_LEN)]

    frozen, handle = _freeze_session(e_fz, toks, user_id, seed=seed)
    lib = e_fz.static_lib

    # parity leg: thaw with NO suffix continues the original decode —
    # the frozen prefix plus the thawed tail must equal a session that
    # was never interrupted
    base = _req(toks, user_id, seed=seed)
    e_base.submit(base)
    e_base.run()
    cont = e_pi.thaw(handle)
    e_pi.run()
    got = frozen.output_tokens[:-1] + cont.output_tokens
    assert got == base.output_tokens, \
        f"resume parity broken: {got} != {base.output_tokens}"

    warm_t, warm_req = _timed_thaw(e_thaw, handle, suffix)

    # idle eviction: demote the snapshot to the disk tier, as the
    # engine's freeze_idle_s sweep would, then thaw again (re-spooled
    # before each probe — a get may promote it back to memory)
    assert lib.spool_now(handle.user_id, handle.media_id)
    disk_t, disk_req = _timed_thaw(e_thaw, handle, suffix)
    assert disk_req.output_tokens == warm_req.output_tokens

    history = (list(toks) + frozen.output_tokens[:-1]
               + [handle.next_token])
    cold_t, cold_req = _timed_cold(e_cold, history, user_id, suffix)
    assert cold_req.output_tokens == warm_req.output_tokens, \
        (f"suffix-thaw parity broken: {warm_req.output_tokens} != "
         f"{cold_req.output_tokens}")
    return warm_t, disk_t, cold_t, lib


def fork_leg(model, params):
    """Tree search over one frozen session: FORK_N children must share
    every parent page at fork time (zero copies) and pay exactly the
    divergence cost — FORK_N−1 copies of the shared write page — on
    their first decode step."""
    toks = _toks(31, PROMPT_LEN)
    e_fz = _engine(model, params)
    _, handle = _freeze_session(e_fz, toks, "ufork", seed=31)

    e = _engine(model, params, e_fz.static_lib, slots=FORK_N + 1)
    free0 = e.pool.free_pages
    kids = e.fork(handle, FORK_N, max_new_tokens=2)
    parent_pages = e.pool.pages_for(handle.n_ctx + 1)
    shared = e.pool.pages_shared
    assert e.pool.cow_copies == 0, \
        f"fork copied {e.pool.cow_copies} pages before any write"
    assert e.pool.free_pages == free0 - parent_pages, \
        "fork allocated beyond the one shared parent footprint"
    assert shared == parent_pages * FORK_N
    e.run()
    copies = e.pool.cow_copies
    assert copies == FORK_N - 1, \
        f"divergence cost {copies} != {FORK_N - 1} (one write page per " \
        "child beyond the last owner)"
    for k in kids:
        assert k.output_tokens[0] == handle.next_token
    return {"label": "fork", "children": FORK_N,
            "parent_pages": int(parent_pages),
            "pages_shared_at_fork": int(shared),
            "cow_copies_at_fork": 0,
            "cow_copies_after_decode": int(copies)}


def main():
    cfg, model, params = build_bench_model()

    e_fz = _engine(model, params)
    lib0 = e_fz.static_lib
    engines = (e_fz, _engine(model, params),
               _engine(model, params, lib0),
               _engine(model, params, lib0), _engine(model, params))

    # jit warmup: a full cycle in a disjoint user scope — every timed
    # shape (full prefill, adopt, suffix prefill, decode) compiles here,
    # on the SAME engine instances the timed probes use
    _resume_cycle(model, params, engines, "uwarm", seed=101)

    warm, disk, cold = [], [], []
    lib = None
    for j in range(N_PROBE):
        w, d, c, lib = _resume_cycle(model, params, engines, f"u{j}",
                                     seed=7 + j)
        warm.append(w)
        disk.append(d)
        cold.append(c)
    warm_t, disk_t, cold_t = (float(np.mean(x)) for x in (warm, disk, cold))
    speedup = cold_t / warm_t
    print(f"  resume TTFT: memory {1e3 * warm_t:.1f}ms / disk "
          f"{1e3 * disk_t:.1f}ms / cold {1e3 * cold_t:.1f}ms "
          f"({speedup:.1f}x warm vs cold)", flush=True)
    if not smoke():
        # acceptance: adopting n_ctx cached tokens + prefilling only the
        # suffix beats re-prefilling the whole history by a wide margin
        assert speedup >= 5.0, (
            f"warm thaw {warm_t:.3f}s only {speedup:.2f}x faster than "
            f"cold recompute {cold_t:.3f}s (need >= 5x)")

    fork = fork_leg(model, params)
    print(f"  fork: {fork['children']} children, "
          f"{fork['pages_shared_at_fork']} pages shared, "
          f"{fork['cow_copies_after_decode']} CoW copies after decode",
          flush=True)

    rows = [
        {"label": "thaw_memory", "ttft_ms": 1e3 * warm_t,
         "n_ctx": PROMPT_LEN + FREEZE_AFTER, "suffix": SUFFIX_LEN},
        {"label": "thaw_disk", "ttft_ms": 1e3 * disk_t,
         "n_ctx": PROMPT_LEN + FREEZE_AFTER, "suffix": SUFFIX_LEN},
        {"label": "cold_recompute", "ttft_ms": 1e3 * cold_t,
         "n_ctx": 0, "suffix": SUFFIX_LEN},
        fork,
    ]
    emit(rows, "sessions")
    out = {"bench": "session_resume", "rows": rows,
           "warm_vs_cold_speedup": round(speedup, 3),
           "token_parity": True,
           "sessions": lib.stats().get("sessions", {})}
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
    print(f"[sessions] warm thaw {speedup:.1f}x faster than cold "
          f"recompute; fork shared {fork['pages_shared_at_fork']} pages "
          f"with {fork['cow_copies_at_fork']} copies; wrote {OUT_PATH}")
    return rows


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache
    enable_compile_cache()
    main()
