"""Session KV store: freeze / thaw / fork of live decode state
(serving/sessions.py + the copy-on-write page pool underneath).

The contract under test is the tentpole invariant set:

  * **Resume parity** — ``frozen.output_tokens[:-1] + thawed.output_tokens``
    equals an uninterrupted session, bit-exactly, on the fp AND int8
    pools (the int8 snapshot rides raw page bytes + scale rows, no
    requant round trip).
  * **Fork is free until divergence** — N children share every parent
    page (zero copies, zero new pages beyond the parent footprint) and
    the first divergent write costs exactly N−1 page copies.
  * **Refcount soundness** — random freeze/thaw/fork/free/write
    sequences never leak a page, double-free one, or write into a page
    while it is shared (property test over the pool).
  * **Lifecycle plumbing** — FROZEN state, idle-sweep spooling, the
    ``sessions`` counter block in ``KVLibrary.stats()`` and the cluster
    ``report()``, and resume-anywhere via the cluster's thaw routing.
"""
from collections import Counter

import jax
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.cache import TIER_DISK
from repro.cache.paged import PagedConfig, PagedKVPool
from repro.configs import get_smoke_config
from repro.core import Prompt, text_segment
from repro.serving import (
    ClusterConfig,
    EngineConfig,
    MPICCluster,
    MPICEngine,
    Request,
)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_smoke_config("llava-1.6-7b")
    from repro.models import build_model
    m = build_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _toks(seed, n=12):
    return np.random.default_rng(seed).integers(8, 200, n)


def _req(toks, *, max_new=8, freeze_after=None, user="u", seed=0):
    return Request(prompt=Prompt([text_segment(toks)], user_id=user),
                   max_new_tokens=max_new, policy="full_recompute",
                   seed=seed, freeze_after=freeze_after)


def _eng(m, params, lib=None, *, slots=2, dtype="", idle=0.0):
    return MPICEngine(m, params,
                      EngineConfig(max_seq_len=128, decode_slots=slots,
                                   pool_dtype=dtype, freeze_idle_s=idle),
                      static_library=lib)


# ---------------------------------------------------------------------------
# resume parity (the acceptance criterion, both pool dtypes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pool_dtype", ["", "int8"])
def test_thaw_resumes_token_identical(model_and_params, pool_dtype):
    """Freeze mid-decode, thaw on a DIFFERENT engine sharing only the
    library: the composed output equals a never-frozen run bit-exactly
    (int8: the snapshot restores raw page bytes + per-page scales, so
    even the lossy pool resumes on its own exact state)."""
    cfg, m, params = model_and_params
    toks = _toks(3)

    e1 = _eng(m, params, dtype=pool_dtype)
    base = _req(toks)
    e1.submit(base)
    e1.run()

    e2 = _eng(m, params, dtype=pool_dtype)
    fz = _req(toks, freeze_after=4)
    e2.submit(fz)
    e2.run()
    assert fz.state.value == "frozen"
    assert fz in e2.frozen and fz.slot == -1
    assert e2.pool.owned_pages(fz.req_id) == 0     # frozen = zero pages
    handle = e2.sessions.handles[fz.session_id]
    assert handle.n_ctx == len(toks) + 3           # prompt + outputs[:-1]
    assert handle.next_token == fz.output_tokens[-1]

    e3 = _eng(m, params, e2.static_lib, dtype=pool_dtype)
    th = e3.thaw(handle)
    e3.run()
    assert fz.output_tokens[:-1] + th.output_tokens == base.output_tokens


def test_suffix_thaw_matches_cold_recompute(model_and_params):
    """Thawing with the next turn's suffix (adopt pages + prefill ONLY
    the suffix) produces the same greedy tokens as re-prefilling the
    whole history from scratch."""
    cfg, m, params = model_and_params
    toks = _toks(5)
    suffix = [int(t) for t in _toks(6, 5)]

    e1 = _eng(m, params)
    fz = _req(toks, freeze_after=4)
    e1.submit(fz)
    e1.run()
    h = e1.sessions.handles[fz.session_id]

    e2 = _eng(m, params, e1.static_lib)
    th = e2.thaw(h, suffix, max_new_tokens=4)
    assert th.prefill_stats["thawed"]
    assert th.prefill_stats["n_reused"] == h.n_ctx
    assert th.prefill_stats["n_recomputed"] == len(suffix) + 1
    e2.run()

    e3 = _eng(m, params)
    full = list(toks) + fz.output_tokens[:-1] + [h.next_token] + suffix
    cold = _req(np.asarray(full, np.int32), max_new=4)
    e3.submit(cold)
    e3.run()
    assert th.output_tokens == cold.output_tokens


# ---------------------------------------------------------------------------
# fork: copy-on-write sharing
# ---------------------------------------------------------------------------


def test_fork_allocates_nothing_until_divergence(model_and_params):
    """N forked children allocate ZERO new pages at fork time (every
    parent page is shared) and the first divergent write costs exactly
    N−1 page copies — the last owner writes in place."""
    cfg, m, params = model_and_params
    e1 = _eng(m, params)
    fz = _req(_toks(7), freeze_after=4)
    e1.submit(fz)
    e1.run()
    h = e1.sessions.handles[fz.session_id]

    e = _eng(m, params, e1.static_lib, slots=4)
    free0 = e.pool.free_pages
    kids = e.fork(h, 3, max_new_tokens=3)
    parent_pages = e.pool.pages_for(h.n_ctx + 1)
    assert e.pool.cow_copies == 0
    assert e.pool.free_pages == free0 - parent_pages
    assert e.pool.pages_shared == parent_pages * 3
    for k in kids:
        assert k.output_tokens == [h.next_token]

    e.run()
    assert e.pool.cow_copies == 2                  # n−1 divergence cost
    # identical seeds + greedy tail → children decode identical tokens,
    # each on its own (partially CoW-copied) page table
    assert kids[0].output_tokens == kids[1].output_tokens \
        == kids[2].output_tokens
    sess = e.static_lib.stats()["sessions"]
    assert sess["forks"] == 3 and sess["cow_copies"] == 2
    assert sess["pages_shared"] == parent_pages * 3


# ---------------------------------------------------------------------------
# lifecycle plumbing: errors, idle sweep, counters, cluster routing
# ---------------------------------------------------------------------------


def test_freeze_thaw_error_paths(model_and_params):
    cfg, m, params = model_and_params
    e = _eng(m, params)
    with pytest.raises(KeyError):
        e.freeze("no-such-req")
    fz = _req(_toks(9), freeze_after=3)
    e.submit(fz)
    e.run()
    h = e.sessions.handles[fz.session_id]
    # pool-geometry mismatch is refused up front, not corrupted into
    e8 = _eng(m, params, e.static_lib, dtype="int8")
    with pytest.raises(ValueError, match="pool"):
        e8.thaw(h)
    with pytest.raises(ValueError):
        e.fork(h, 0)
    # thawing an evicted/unknown snapshot is a LookupError
    e.static_lib.delete(h.user_id, h.media_id)
    e2 = _eng(m, params, e.static_lib)
    with pytest.raises(LookupError):
        e2.thaw(h)


def test_idle_sweep_spools_frozen_sessions(model_and_params):
    """With ``freeze_idle_s`` set, a frozen handle idle past the
    threshold is demoted to the disk tier by the engine's step sweep."""
    cfg, m, params = model_and_params
    e = _eng(m, params, idle=30.0)
    fz = _req(_toks(11))
    e.submit(fz)
    while len(fz.output_tokens) < 3:
        e.step()
    # manual freeze keeps the snapshot memory-resident (spool=False);
    # freeze_after-triggered freezes spool immediately instead
    h = e.freeze(fz.req_id)
    assert e.static_lib.peek_tier(h.user_id, h.media_id,
                                  salt=h.cache_salt) != TIER_DISK
    assert e.sessions.sweep_idle(30.0) == 0        # not idle long enough
    h.frozen_at -= 60.0
    e.step()                                       # sweep runs in step()
    assert e.static_lib.peek_tier(h.user_id, h.media_id,
                                  salt=h.cache_salt) == TIER_DISK
    assert e.sessions.stats()["spooled_handles"] == 1
    # spooled is still thawable (disk → pages)
    e2 = _eng(m, params, e.static_lib)
    th = e2.thaw(h)
    e2.run()
    assert th.output_tokens[0] == h.next_token


def test_session_counters_and_cluster_resume(model_and_params):
    """freeze/thaw/fork counters aggregate into ``stats()['sessions']``
    and the cluster ``report()``; a session frozen on one replica thaws
    on whichever replica has slot headroom (shared library)."""
    cfg, m, params = model_and_params
    cluster = MPICCluster(m, params,
                          EngineConfig(max_seq_len=128, decode_slots=2),
                          ClusterConfig(replicas=2))
    fz = _req(_toks(13), freeze_after=3)
    cluster.submit(fz)
    cluster.run()
    assert fz.state.value == "frozen"
    handles = cluster.session_handles()
    assert fz.session_id in handles
    h = handles[fz.session_id]

    th = cluster.thaw(h)
    cluster.run()
    assert th.replica in (0, 1)
    assert th.output_tokens[0] == h.next_token

    kids = cluster.fork(h, 2)
    cluster.run()
    assert len({k.replica for k in kids}) == 1     # one pool, one replica
    rep = cluster.report()
    assert rep["sessions"]["freezes"] == 1
    assert rep["sessions"]["thaws"] == 1
    assert rep["sessions"]["forks"] == 2
    assert rep["sessions"]["pages_shared"] > 0


# ---------------------------------------------------------------------------
# property test: pool refcount invariants under random op sequences
# ---------------------------------------------------------------------------


@st.composite
def _op_seqs(draw):
    n = draw(st.integers(min_value=5, max_value=30))
    return [(draw(st.sampled_from(
                ["alloc", "extend", "free", "fork", "write"])),
             draw(st.integers(min_value=0, max_value=7)),
             draw(st.integers(min_value=1, max_value=9)))
            for _ in range(n)]


@given(ops=_op_seqs())
@settings(max_examples=25, deadline=None)
def test_pool_refcount_invariants(ops):
    """Whatever interleaving of alloc/extend/free/fork/write happens, the
    pool never leaks a page, never double-frees one, and never lets a
    write land in a page that is still shared."""
    NP, PS = 12, 4
    pool = PagedKVPool(PagedConfig(num_pages=NP, page_size=PS,
                                   num_layers=1, num_kv_heads=1,
                                   head_dim=4))
    tokens = {}
    next_id = 0
    for op, a, b in ops:
        names = sorted(tokens)
        if op == "alloc":
            rid = f"r{next_id}"
            next_id += 1
            if pool.alloc(rid, b) is not None:
                tokens[rid] = b
        elif op == "extend" and names:
            rid = names[a % len(names)]
            if pool.extend(rid, b, tokens[rid]) is not None:
                tokens[rid] += b
        elif op == "free" and names:
            rid = names[a % len(names)]
            pool.free(rid)
            del tokens[rid]
            pool.free(rid)                         # double-free: no-op
        elif op == "fork" and names:
            rid = names[a % len(names)]
            kids = [f"r{next_id + i}" for i in range(1 + a % 2)]
            next_id += len(kids)
            pool.fork(rid, kids)
            for kid in kids:
                tokens[kid] = tokens[rid]
        elif op == "write" and names:
            rid = names[a % len(names)]
            pos = (b - 1) % max(tokens[rid], 1)
            pages = pool.make_exclusive(rid, pos)
            if pages is not None:                  # None = CoW budget miss
                # the write target must now be exclusively owned
                assert pool.page_ref(int(pages[pos // PS])) == 1

        # invariants, after every op -----------------------------------
        owned = [int(p) for r in tokens for p in pool._owned[r]]
        distinct = set(owned)
        free = set(pool._free)
        assert len(pool._free) == len(free)        # free stack: no dups
        assert not (distinct & free)               # never free AND owned
        assert len(distinct) + len(free) == NP     # no page leaked/lost
        for page, holders in Counter(owned).items():
            assert pool._refs.get(page) == holders  # refs == owner count
        for page in free:
            assert pool._refs.get(page, 0) == 0
