"""MPIC serving engine — Fig. 5 workflow, continuous batching.

Components wired here:
  ① ``upload``       user file → precompute KV → **static library** (+ spool)
  ② ``submit``       query with media references
  ③ library lookup   per-user scoping, expiry (the Linker pulls entries)
  ④ ``Retriever``    MRAG over the **dynamic library**
  ⑤ Linker + selective attention (policy = mpic / baselines)
  ⑥ decode loop      continuous batching over fixed slots

Continuous batching under XLA static shapes: a fixed number of decode
*slots*.  Admission runs through the **pipelined scheduler**
(``serving/scheduler.py``): the waiting queue is priority-ordered, media
fetches for the next ``prefetch_depth`` queued requests are issued while
the current request's policy recompute runs, and entries are gathered per
media id at link time.  Long prompts prefill in chunks
(``prefill_chunk_tokens``) across engine steps so decode slots never stall;
every engine step advances ALL running slots by one token with a single
jit'd decode step.

**Paged decode path** (default for attention archs): the batch cache is a
:class:`~repro.cache.paged.PagedKVPool` — slots own page lists, admission
allocates pages for the linked prompt, completion frees them.  The decode
step runs the paged-attention kernel over a page table bucketed to the
*live* maximum length (work scales with ``cur_len``, not ``max_seq_len``)
and **donates** the pool buffers (mirroring the train-step donation in
``training/train_loop.py``), so no full-cache copy happens per token.
Prefill splice-in and MRAG linking are each a single jit'd, donated scatter
into the pool.  Sliding-window archs stay paged (the kernel masks the
window like the dense decode path); archs with SSM state or cross KV keep
the dense ``(L, B, max_seq_len, …)`` cache (``paged=False`` forces it
anywhere, and is the benchmark baseline).

**Paged prefill path** (default on paged engines): mpic/cacheblend
admissions never build a dense blended cache — the linker scatters reused
segments straight into the slot's reserved pages
(``core/linker.link_paged``) and the selective prefill runs as ONE
shape-bucketed, donated jit against the pool
(``core/paged_prefill.PagedPrefiller``): selected tokens pad to a
power-of-two bucket, the page table to the live page bucket, so
varying-length traffic reuses a warm compile cache with zero host
round-trips between link and first token.  Other policies (and chunked
prefills) keep the dense per-request cache + splice fallback.

**Mesh-sharded serving** (``MPICEngine(..., mesh=...)``): the engine serves
tensor-parallel across a ``data × model`` mesh.  Params get MaxText-style
TP shardings (``launch/specs.param_pspecs``), the KV pool is head-sharded
on ``model`` (``serving/sharding.ServingSharding``), every donated jit
carries explicit in/out shardings so GSPMD keeps the pool resident and
partitioned for the engine's lifetime, and each step runs under the
``launch/pspec`` logical-axis policy so the model's ``shard()``
annotations (heads / kv_heads on ``model``, batch-of-slots on ``data``)
and the Pallas kernels' shard_map dispatch activate.  The same code path
runs unsharded when no mesh is given — every mapping is
divisibility-guarded per axis.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache.faults import FaultPlan, ReplicaCrash
from repro.cache.library import KVLibrary
from repro.cache.paged import PagedConfig, PagedKVPool
from repro.cache.transfer import ParallelLoader, PrefetchHandle
from repro.core.linker import bucket, precompute_media_kv, scale_row_ids
from repro.core.paged_prefill import PagedPrefiller
from repro.core.policies import POLICIES, PolicyResult, PrefixStore
from repro.kernels.paged_attn.ops import resolve_backend
from repro.kernels.platform import pallas_interpret
from repro.models.layers import INVALID_POS, rope_relink
from repro.models.model import Model
from repro.serving.request import Request, State
from repro.serving.retriever import Retriever
from repro.serving.scheduler import (
    CHUNKABLE_POLICIES,
    ChunkedPrefillTask,
    PipelinedScheduler,
)
from repro.serving.sessions import SessionHandle, SessionStore
from repro.serving.sharding import ServingSharding


@dataclasses.dataclass
class EngineConfig:
    max_seq_len: int = 512          # kv region per slot (incl. scratch slot)
    decode_slots: int = 4           # continuous-batching capacity
    max_prefills_per_step: int = 1  # admissions per engine step
    greedy: bool = True             # False → temperature/top-k sampling
    temperature: float = 1.0        # sampling temperature (greedy=False)
    top_k: int = 0                  # restrict sampling to top-k logits (0=all)
    prefetch_depth: int = 2         # queued requests with loads in flight
    prefill_chunk_tokens: int = 0   # >0: chunk long prefills across steps
    pipelined: bool = True          # False → sequential admission baseline
    queue_aging_s: float = 0.0      # >0: priority aging (anti-starvation)
    # -- paged decode path -------------------------------------------------
    paged: bool = True              # pool-backed decode (attention archs)
    page_size: int = 16             # tokens per KV page
    num_pages: int = 0              # 0 → slots·⌈max_seq_len/page⌉ + scratch
    donate_decode: bool = True      # donate pool buffers into the decode jit
    paged_backend: str = "auto"     # pallas | ref | auto (pallas on TPU)
    pool_dtype: str = ""            # "" → model compute dtype; "int8" →
                                    # quantized pool, dequant-in-kernel
                                    # (paged engines only)
    # -- paged prefill path ------------------------------------------------
    paged_prefill: bool = True      # mpic/cacheblend prefill straight into
                                    # pool pages (bucketed, donated jit)
    prefill_bucket_min: int = 16    # smallest selection shape bucket
    # -- session store (serving/sessions.py) -------------------------------
    freeze_idle_s: float = 0.0      # >0: frozen sessions idle this long
                                    # are demoted to the disk tier each step


# -- jit'd, donated cache-mutation helpers ----------------------------------
# Each is ONE device call that updates the (donated) cache/pool in place —
# replacing the seed's per-key host-side splice loops.  The impls are
# module-level; unsharded engines share the module-level jits below, while
# a mesh-sharded engine compiles its own instances with the batch-cache
# shardings pinned on the outputs (see MPICEngine.__init__).

def _dense_splice_impl(bc: dict, rc: dict, slot) -> dict:
    """Splice a per-request cache ``rc`` into batch cache ``bc`` at ``slot``
    (a traced scalar: one compilation covers every slot)."""
    out = dict(bc)
    for key in bc:
        if key == "pos":
            out["pos"] = bc["pos"].at[slot].set(rc["pos"][0])
        else:
            out[key] = bc[key].at[:, slot].set(
                rc[key][:, 0].astype(bc[key].dtype))
    return out


def _dense_link_impl(bc: dict, k_seg, v_seg, off, slot, *, theta: float,
                     relink: bool) -> dict:
    """Link one MRAG segment at position ``off`` into ``bc`` at ``slot``."""
    length = k_seg.shape[1]
    idx = off + jnp.arange(length, dtype=jnp.int32)
    if relink:
        k_seg = rope_relink(k_seg, jnp.full((length,), off, jnp.int32), theta)
    out = dict(bc)
    out["k"] = bc["k"].at[:, slot, idx].set(k_seg.astype(bc["k"].dtype))
    out["v"] = bc["v"].at[:, slot, idx].set(v_seg.astype(bc["v"].dtype))
    out["pos"] = bc["pos"].at[slot, idx].set(idx)
    return out


_dense_splice = functools.partial(jax.jit, donate_argnums=(0,))(
    _dense_splice_impl)
_dense_link = functools.partial(jax.jit, donate_argnums=(0,),
                                static_argnames=("theta", "relink"))(
    _dense_link_impl)


class MPICEngine:
    def __init__(self, model: Model, params, engine_cfg: EngineConfig = None,
                 *, static_library: Optional[KVLibrary] = None,
                 dynamic_library: Optional[KVLibrary] = None,
                 mesh=None, shard_rules: Optional[dict] = None,
                 replica_id: Optional[int] = None,
                 loader: Optional[ParallelLoader] = None,
                 retriever: Optional[Retriever] = None,
                 faults: Optional[FaultPlan] = None):
        """``mesh``: optional :class:`jax.sharding.Mesh` (axes ``data`` ×
        ``model``, e.g. ``repro.launch.mesh.make_serving_mesh``) — the
        engine then serves tensor-parallel: params are committed to
        MaxText-style TP shardings, the KV pool is head-sharded on the
        ``model`` axis, and every donated jit (decode, paged prefill,
        splice, link) carries explicit in/out shardings so GSPMD keeps the
        pool resident and partitioned.  ``shard_rules`` overrides the
        logical-axis rules (default ``repro.launch.mesh.serving_rules``).

        **Shared-library (cluster) mode** — ``serving/cluster.py`` runs N
        engines as data-parallel replicas: pass a shared ``static_library``
        / ``dynamic_library`` / ``loader`` / ``retriever`` plus a distinct
        ``replica_id`` per engine.  Library fetches are then tagged with
        the replica id (per-replica HBM warmth for the affinity router,
        cross-replica fetch dedup on the shared loader).  With
        ``replica_id=None`` (default) every library interaction keeps the
        legacy single-engine semantics."""
        self.model = model
        self.cfg = engine_cfg or EngineConfig()
        self.replica_id = replica_id
        self.faults = faults        # FaultPlan: engine.step crash injection
        self.sharding = None
        self._param_sh = None
        if mesh is not None:
            self.sharding = ServingSharding(mesh, model.cfg,
                                            rules=shard_rules)
            self._param_sh = self.sharding.params(params)
            params = jax.device_put(params, self._param_sh)
        self.params = params
        self.static_lib = static_library or KVLibrary(faults=faults)
        self.dynamic_lib = dynamic_library or KVLibrary(shared=True)
        self.retriever = retriever if retriever is not None else Retriever()
        self.prefix_store = PrefixStore()
        self.loader = loader if loader is not None else ParallelLoader(
            self.static_lib, replica=replica_id)
        self.scheduler = PipelinedScheduler(
            self.loader, prefetch_depth=self.cfg.prefetch_depth,
            pipelined=self.cfg.pipelined,
            prefetch_filter=self._policy_consumes_entries,
            replica=replica_id, aging_s=self.cfg.queue_aging_s)

        self.running: List[Optional[Request]] = [None] * self.cfg.decode_slots
        self.finished: List[Request] = []
        self.failed: List[Request] = []     # prefill raised (see _abort_prefill)
        self.expired: List[Request] = []    # deadline_s elapsed (DEADLINE)
        self.frozen: List[Request] = []     # FROZEN via sessions.freeze()
        self._prefill_tasks: Dict[int, ChunkedPrefillTask] = {}
        self._rngs: Dict[str, np.random.Generator] = {}

        self._use_paged = self.cfg.paged and model.supports_paged_decode()
        if self.cfg.pool_dtype == "int8" and not self._use_paged:
            # satellite invariant: the dense fallback cache has no scale
            # buffers and no dequant-in-kernel read path — an int8 request
            # there would silently serve garbage, so fail loudly at build
            raise ValueError(
                "pool_dtype='int8' requires the paged KV pool: set "
                "EngineConfig.paged=True and use an attention arch that "
                "supports paged decode (the dense fallback cache carries "
                "no per-page scales)")
        if self._use_paged:
            mcfg = model.cfg
            ps = self.cfg.page_size
            self._pages_per_slot = -(-self.cfg.max_seq_len // ps)
            num_pages = self.cfg.num_pages or (
                self.cfg.decode_slots * self._pages_per_slot + 1)
            pool_dtype = self.cfg.pool_dtype or mcfg.compute_dtype
            pool_sh = self.sharding.pool() if self.sharding else None
            scale_sh = (self.sharding.pool_scale()
                        if self.sharding and pool_dtype == "int8" else None)
            self.pool = PagedKVPool(PagedConfig(
                num_pages=num_pages, page_size=ps,
                num_layers=mcfg.num_layers, num_kv_heads=mcfg.num_kv_heads,
                head_dim=mcfg.head_dim, dtype=pool_dtype),
                sharding=pool_sh, scale_sharding=scale_sh)
            # scratch page: absorbs padding writes (splice tails, idle
            # slots) so real pages are never aliased
            self._scratch_page = int(self.pool.alloc("__scratch__", 1)[0])
            self._page_tables = np.full(
                (self.cfg.decode_slots, self._pages_per_slot),
                self._scratch_page, np.int32)
            self._paged_backend = resolve_backend(self.cfg.paged_backend)
            # compiled Mosaic on a TPU, the Pallas interpreter elsewhere
            self._interpret = pallas_interpret()
            self._batch_cache = None
            q8 = self.pool.quantized
            if self.cfg.donate_decode:
                donate = (1, 2, 3, 4) if q8 else (1, 2)
            else:
                donate = ()
            jit_kw = {}
            if self.sharding:
                # explicit in/out shardings: the pool enters AND leaves the
                # step head-sharded (donation keeps it in place; an int8
                # pool's scale buffers ride along with the same treatment),
                # host-built operands go batch-on-data or replicated, logits
                # come back replicated over vocab for the host-side sampler
                B = self.cfg.decode_slots
                tok = self.sharding.batched(B, 2)
                vec = self.sharding.batched(B, 1)
                ins = [self._param_sh, pool_sh, pool_sh]
                outs = [tok, pool_sh, pool_sh]
                if q8:
                    ins += [scale_sh, scale_sh]
                    outs += [scale_sh, scale_sh]
                ins += [tok, tok, tok, vec, vec, vec]
                jit_kw = dict(in_shardings=tuple(ins),
                              out_shardings=tuple(outs))
            self._decode_jit = jax.jit(
                self._paged_decode_q8_fn if q8 else self._paged_decode_fn,
                donate_argnums=donate, **jit_kw)
            # paged prefill: mpic/cacheblend link + selective-prefill
            # straight into pool pages through one bucketed, donated jit
            self._prefiller = None
            if self.cfg.paged_prefill and model.supports_paged_prefill():
                self._prefiller = PagedPrefiller(
                    model, self.pool, self._scratch_page,
                    backend=self._paged_backend,
                    interpret=self._interpret,
                    bucket_min=self.cfg.prefill_bucket_min,
                    sharding=self.sharding, param_shardings=self._param_sh)
            self._splice_jit = self._link_jit = None
        else:
            self.pool = None
            self._prefiller = None
            self._batch_cache = model.make_cache(self.cfg.decode_slots,
                                                 self.cfg.max_seq_len)
            if self.sharding:
                cache_sh = self.sharding.dense_cache(self.cfg.decode_slots,
                                                     self._batch_cache)
                self._batch_cache = jax.device_put(self._batch_cache,
                                                   cache_sh)
                tok = self.sharding.batched(self.cfg.decode_slots, 2)
                self._decode_jit = jax.jit(
                    self._decode_step_fn,
                    in_shardings=(self._param_sh, cache_sh, tok, tok),
                    out_shardings=(tok, cache_sh))
                # per-engine dense splice/link with the cache sharding
                # pinned on the outputs (the module-level jits stay
                # unsharded — compile caches must not mix constraints)
                self._splice_jit = jax.jit(
                    _dense_splice_impl, donate_argnums=(0,),
                    out_shardings=cache_sh)
                self._link_jit = jax.jit(
                    _dense_link_impl, donate_argnums=(0,),
                    static_argnames=("theta", "relink"),
                    out_shardings=cache_sh)
            else:
                self._decode_jit = jax.jit(self._decode_step_fn)
                self._splice_jit = _dense_splice
                self._link_jit = _dense_link

        # session store: freeze/thaw/fork live decode state (paged only —
        # the thaw/adopt path is page-shaped).  The pool's live CoW gauges
        # register with the shared library so cluster report()/fleet
        # heartbeats surface them beside the freeze/thaw/fork census.
        self.sessions = SessionStore(self)
        if self._use_paged:
            pool = self.pool
            self.static_lib.add_session_source(
                lambda: {"cow_copies": pool.cow_copies,
                         "pages_shared": pool.pages_shared})

    @property
    def waiting(self):
        """The scheduler's priority queue (len/bool/iter like the old deque)."""
        return self.scheduler.queue

    @property
    def prefill_trace_count(self) -> int:
        """Retraces of the paged-prefill jit (compile-count guard probe)."""
        return self._prefiller.traces if self._prefiller is not None else 0

    # ------------------------------------------------------------------
    # workflow ①: upload → precompute KV → store
    # ------------------------------------------------------------------
    def upload(self, user_id: str, media_id: str, embeds: np.ndarray, *,
               ttl: float = float("inf"), dynamic: bool = False) -> None:
        k, v = precompute_media_kv(self.model, self.params,
                                   jnp.asarray(embeds))
        lib = self.dynamic_lib if dynamic else self.static_lib
        lib.put(user_id, media_id, k, v, ttl=ttl)
        if dynamic:
            self.retriever.add(media_id, embeds)

    # ------------------------------------------------------------------
    # workflow ②: submit a query
    # ------------------------------------------------------------------
    def submit(self, request: Request) -> Request:
        assert request.prompt.total_len + 1 < self.cfg.max_seq_len, \
            "prompt exceeds slot kv region"
        if self._use_paged:
            # a prompt that can never fit the pool would livelock admission
            usable = self.pool.cfg.num_pages - 1          # minus scratch
            assert self.pool.pages_for(request.prompt.total_len + 1) \
                <= usable, "prompt exceeds paged pool capacity"
        self.scheduler.enqueue(request)
        return request

    # ------------------------------------------------------------------
    # engine step: advance chunked prefills, admit, decode running slots
    # ------------------------------------------------------------------
    def _shard_ctx(self):
        """Logical-axis policy for the mesh-sharded engine: every jit traced
        inside a step (decode, paged prefill, policies' dense fallbacks)
        sees the mesh rules, so the model's ``shard()`` annotations and the
        kernels' shard_map dispatch activate.  Identity without a mesh."""
        return (self.sharding.activate() if self.sharding
                else contextlib.nullcontext())

    def step(self) -> None:
        # crash injection runs BEFORE any per-request work: an injected
        # replica failure must leave the engine state exactly as the last
        # completed step did, so the cluster's failover drain sees a clean
        # snapshot and no individual request gets blamed
        if self.faults is not None:
            rule = self.faults.check("engine.step",
                                     f"replica{self.replica_id}")
            if rule is not None and rule.kind == "crash":
                raise ReplicaCrash(
                    f"injected crash on replica {self.replica_id} "
                    f"({rule.describe()})")
        with self._shard_ctx():
            self._reap_deadlines()
            self._advance_prefills()
            self._admit()
            self._decode()
        if self.cfg.freeze_idle_s > 0:
            self.sessions.sweep_idle(self.cfg.freeze_idle_s)

    # -- session store delegates (serving/sessions.py) ---------------------
    def freeze(self, req_id: str, *, spool: bool = False) -> SessionHandle:
        """Freeze a RUNNING request's live KV into the library and free
        its slot — see :meth:`repro.serving.sessions.SessionStore.freeze`."""
        with self._shard_ctx():
            return self.sessions.freeze(req_id, spool=spool)

    def thaw(self, handle: SessionHandle, suffix_tokens=None, *,
             max_new_tokens: Optional[int] = None) -> Request:
        """Resume a frozen session into a free slot (optionally with the
        next turn's suffix) — see :meth:`SessionStore.thaw`."""
        with self._shard_ctx():
            return self.sessions.thaw(handle, suffix_tokens,
                                      max_new_tokens=max_new_tokens)

    def fork(self, handle: SessionHandle, n: int, *,
             max_new_tokens: Optional[int] = None) -> List[Request]:
        """Thaw one snapshot into ``n`` copy-on-write children — see
        :meth:`SessionStore.fork`."""
        with self._shard_ctx():
            return self.sessions.fork(handle, n,
                                      max_new_tokens=max_new_tokens)

    def run(self, max_steps: int = 10_000) -> List[Request]:
        steps = 0
        while (self.scheduler.queue or any(self.running)) \
                and steps < max_steps:
            self.step()
            steps += 1
        return self.finished

    # ------------------------------------------------------------------
    def _free_slot(self) -> int:
        for i, r in enumerate(self.running):
            if r is None:
                return i
        return -1

    def _admit(self) -> None:
        admitted = 0
        while (self.scheduler.queue
               and admitted < self.cfg.max_prefills_per_step):
            slot = self._free_slot()
            if slot < 0:
                return
            if self._use_paged:
                # paged admission control: hold the request until the pool
                # can page its prompt (running requests free pages as they
                # complete)
                nxt = self.scheduler.queue.peek(1)[0]
                need = self.pool.pages_for(nxt.prompt.total_len + 1)
                if need > self.pool.free_pages:
                    return
            req, handle = self.scheduler.pop()
            if req.past_deadline():
                # reap at admission: a request that waited out its budget
                # must not occupy a slot just to be reaped next step
                if handle is not None:
                    handle.release()
                self._expire(req)
                continue
            self._begin_prefill(req, slot, handle)
            admitted += 1

    # -- admission ------------------------------------------------------
    def _resolve_policy(self, req: Request) -> str:
        policy_name = req.policy
        # PIC needs attention KV — prefix-only semantics for SSM/hybrid
        # (DESIGN.md §Arch-applicability)
        if self.model.cfg.arch_type in ("ssm", "hybrid") and policy_name in (
                "mpic", "cacheblend", "full_reuse"):
            policy_name = "full_recompute"
        return policy_name

    def _policy_consumes_entries(self, req: Request) -> bool:
        """Does this request's *resolved* policy gather library entries?
        (prefix_caching / full_recompute — incl. the SSM/hybrid rewrite —
        never link media KV, so prefetching for them is wasted loader time)"""
        return self._resolve_policy(req) in ("mpic", "cacheblend",
                                             "full_reuse")

    def _chunkable(self, req: Request, policy_name: str) -> bool:
        cfg = self.model.cfg
        chunk = self.cfg.prefill_chunk_tokens
        return (chunk > 0
                and policy_name in CHUNKABLE_POLICIES
                and cfg.arch_type not in ("ssm", "hybrid")
                and not cfg.is_encoder_decoder
                and req.prompt.total_len > chunk)

    def _begin_prefill(self, req: Request,
                       slot: int, handle: Optional[PrefetchHandle]) -> None:
        policy_name = self._resolve_policy(req)
        if policy_name not in POLICIES:
            # a bad policy name in one request (e.g. a typo in a request
            # trace) must fail THAT request with a clear error and keep the
            # engine serving — not hard-exit the whole run
            req.state = State.FAILED
            req.error = (f"unknown policy {req.policy!r} "
                         f"(known: {sorted(POLICIES)})")
            self.failed.append(req)
            if handle is not None:
                handle.release()
            return
        req.slot = slot
        req.state = State.PREFILLING
        self.running[slot] = req
        if self._use_paged:
            # reserve the prompt's pages NOW: a chunked prefill holds its
            # slot for several steps, and only an up-front allocation keeps
            # the admission gate's free_pages check truthful for the
            # requests admitted in between
            pages = self.pool.alloc(req.req_id, req.prompt.total_len + 1)
            assert pages is not None, "admission gate checked free_pages"
            self._set_page_row(slot, pages)

        try:
            if self._chunkable(req, policy_name):
                task = ChunkedPrefillTask(
                    self.model, self.params, req, self.static_lib,
                    kv_len=self.cfg.max_seq_len,
                    chunk_tokens=self.cfg.prefill_chunk_tokens,
                    policy_name=policy_name, scheduler=self.scheduler,
                    entries=handle)
                self._prefill_tasks[slot] = task
                if task.advance():          # first chunk runs this step
                    del self._prefill_tasks[slot]
                    self._finalize_prefill(req, task.result, handle)
                return

            # monolithic path: one policy call inside a measured compute
            # window; the linker gathers this request's prefetched entries
            # at link time.  mpic/cacheblend on a paged engine get the
            # slot-bound prefiller: link → selective prefill → first token
            # happens inside the pool with no dense blended cache
            paged_ctx = None
            if (self._prefiller is not None
                    and policy_name in ("mpic", "cacheblend")):
                paged_ctx = self._prefiller.bind(self._page_tables[slot])
            with self.scheduler.compute_window():
                result = POLICIES[policy_name](
                    self.model, self.params, req.prompt, self.static_lib,
                    kv_len=self.cfg.max_seq_len,
                    prefix_store=self.prefix_store,
                    entries=handle, paged=paged_ctx, **req.policy_kwargs)
            self._finalize_prefill(req, result, handle)
        except BaseException as exc:
            self._abort_prefill(slot, handle=handle, error=repr(exc))
            raise

    def _advance_prefills(self) -> None:
        for slot, task in list(self._prefill_tasks.items()):
            try:
                done = task.advance()
                if done:
                    del self._prefill_tasks[slot]
                    self._finalize_prefill(task.req, task.result, task.handle)
            except BaseException as exc:
                self._abort_prefill(slot, handle=task.handle,
                                    error=repr(exc))
                raise

    def _abort_prefill(self, slot: int,
                       handle: Optional[PrefetchHandle] = None,
                       error: Optional[str] = None, *,
                       state: State = State.FAILED,
                       sink: Optional[List[Request]] = None) -> None:
        """Free a slot whose prefill raised (or was reaped), so capacity is
        not leaked: handle pins released, the slot's pages freed, the
        sampling generator dropped.

        By default the request goes terminal (FAILED, in ``self.failed``)
        rather than back into the queue: a deterministic error (bad policy
        kwargs, …) must not retry forever, and a caller that catches the
        exception from ``step()``/``run()`` can inspect/resubmit it
        explicitly.  Deadline reaping and cluster failover reuse the same
        resource path with a different terminal ``state``/``sink``.
        """
        if handle is not None:
            handle.release()
        self._prefill_tasks.pop(slot, None)
        req = self.running[slot]
        if req is not None:
            req.slot = -1
            req.state = state
            req.error = error
            req.t_done = time.perf_counter()
            (self.failed if sink is None else sink).append(req)
            # drop the sampling generator too: a resubmit must reproduce
            # from Request.seed, not resume an advanced stream
            self._rngs.pop(req.req_id, None)
            if self._use_paged:
                self.pool.free(req.req_id)
                self._page_tables[slot] = self._scratch_page
        self.running[slot] = None

    # -- deadlines + failover ---------------------------------------------
    def _expire(self, req: Request) -> None:
        """Terminal DEADLINE transition (resources already released)."""
        req.state = State.DEADLINE
        req.error = f"deadline exceeded ({req.deadline_s:.3f}s)"
        req.t_done = time.perf_counter()
        self.expired.append(req)

    def _release_slot(self, r: Request) -> None:
        """Free a RUNNING slot's resources without finishing the request."""
        self.running[r.slot] = None
        self._rngs.pop(r.req_id, None)
        if self._use_paged:
            self.pool.free(r.req_id)
            self._page_tables[r.slot] = self._scratch_page
        else:
            self._clear_slot(r.slot)
        r.slot = -1

    def _reap_deadlines(self) -> None:
        """Expire requests whose wall-clock budget elapsed: waiting queue
        (with any pre-issued prefetch handle released), mid-chunked-prefill
        slots (through the ``_abort_prefill`` resource path), and decoding
        slots (pages freed, pins none, partial output kept on the request).
        Runs at the top of every engine step; cheap when nothing carries a
        ``deadline_s``."""
        now = time.perf_counter()
        stale = [r for r in self.scheduler.queue if r.past_deadline(now)]
        for req in stale:
            self.scheduler.discard(req)
            self._expire(req)
        for slot, r in enumerate(self.running):
            if r is None or not r.past_deadline(now):
                continue
            if r.state is State.PREFILLING:
                task = self._prefill_tasks.get(slot)
                self._abort_prefill(
                    slot, handle=task.handle if task is not None else None,
                    error=f"deadline exceeded ({r.deadline_s:.3f}s)",
                    state=State.DEADLINE, sink=self.expired)
            else:
                self._release_slot(r)
                self._expire(r)

    def _reset_for_resubmit(self, req: Request) -> None:
        """Return a drained request to a fresh WAITING state for re-routing.
        Resubmission is idempotent — decode sampling replays from
        ``Request.seed`` (the advanced generator was dropped with the slot)
        so the retried request produces identical tokens.  ``t_arrival`` is
        preserved: a deadline clock keeps running across a failover."""
        req.state = State.WAITING
        req.error = None
        req.slot = -1
        req.replica = -1
        req.output_tokens = []
        req.cur_len = 0
        req.t_admitted = req.t_first_token = req.t_done = 0.0
        req.prefill_stats = {}
        req.linked_media = []
        req.load_s = req.load_blocked_s = 0.0
        req.compute_s = req.overlap_s = 0.0

    def drain_for_failover(self) -> List[Request]:
        """Strip every non-terminal request off this replica so the cluster
        can re-route it after a crash: in-flight chunked prefills and
        running decodes abort through the standard ``_abort_prefill``
        resource path (pages freed, pins released), queued requests leave
        via ``scheduler.discard`` (prefetch handles released).  Every
        drained request comes back reset to WAITING (see
        :meth:`_reset_for_resubmit`)."""
        reclaim: List[Request] = []
        for slot in list(self._prefill_tasks):
            task = self._prefill_tasks[slot]
            self._abort_prefill(slot, handle=task.handle,
                                error="replica failover", sink=reclaim)
        for slot, r in enumerate(self.running):
            if r is not None:
                self._abort_prefill(slot, error="replica failover",
                                    sink=reclaim)
        queued = list(self.scheduler.queue)
        for req in queued:
            self.scheduler.discard(req)
        out = reclaim + queued
        for req in out:
            self._reset_for_resubmit(req)
        return out

    def _finalize_prefill(self, req: Request, result: PolicyResult,
                          handle: Optional[PrefetchHandle]) -> None:
        req.prefill_stats = result.stats
        req.linked_media = [seg.media_id
                            for _, seg in req.prompt.media_segments()]

        first_tok = self._select_token(
            req, np.asarray(result.first_logits, np.float32))
        req.output_tokens.append(first_tok)
        req.t_first_token = time.perf_counter()
        req.cur_len = req.prompt.total_len
        req.state = State.RUNNING
        self.scheduler.account(req, handle, result.stats.get("wall_s", 0.0))
        if handle is not None:
            # entries are consumed (linked/spliced): release the pins so the
            # shared library may demote them again under pressure
            handle.release()

        # splice the request cache into the batch cache / page pool at
        # `slot` (paged: pages were reserved at _begin_prefill).  A paged
        # prefill (result.cache is None) already wrote every K/V into the
        # slot's pages — nothing to splice, no dense copy ever existed.
        if result.cache is None:
            pass
        elif self._use_paged:
            self._splice_paged(req.slot, result.cache, req.cur_len + 1)
        else:
            self._batch_cache = self._splice_jit(
                self._batch_cache, result.cache,
                jnp.asarray(req.slot, jnp.int32))

        # workflow ④: MRAG — link retrieved KV position-independently,
        # with NO recompute of the existing cache (PIC's payoff)
        if req.retrieval_query is not None:
            self._mrag_link(req)

    # -- paged page-table / splice helpers -------------------------------
    def _set_page_row(self, slot: int, pages: np.ndarray) -> None:
        row = np.full((self._pages_per_slot,), self._scratch_page, np.int32)
        row[:len(pages)] = pages
        self._page_tables[slot] = row

    def _splice_paged(self, slot: int, rc: dict, n_tokens: int) -> None:
        """ONE donated scatter of the per-request cache into the pool.

        The token count is bucketed to the next power of two (compiles are
        O(log max_seq_len), like the decode step's page-table bucketing) so
        splice work scales with the prompt, not ``max_seq_len``.  Bucket
        rows beyond the slot's owned pages land on the scratch page (the
        page-table row is scratch-padded); owned slots beyond ``n_tokens``
        may keep a previous tenant's stale KV — every read is
        length-masked, so it is never observed.
        """
        b = min(bucket(n_tokens, 1), rc["k"].shape[2])
        self.pool.write_tokens(self._page_tables[slot], 0,
                               rc["k"][:, 0, :b], rc["v"][:, 0, :b])

    def _mrag_link(self, req: Request) -> None:
        hits = self.retriever.query(req.retrieval_query, req.retrieval_top_k)
        cfg = self.model.cfg
        relink = bool(cfg.rope_theta) and not cfg.learned_pos_emb
        for media_id, score in hits:
            # pinned for the duration of the link: a concurrent replica's
            # rebalance must not spool the arrays while we scatter them
            entry = self.dynamic_lib.get(req.prompt.user_id, media_id,
                                         replica=self.replica_id, pin=True)
            if entry is None:
                continue
            try:
                payload = entry.payload
                length = (payload.qk.q.shape[1] if payload.qk is not None
                          else payload.k.shape[1])
                off = req.cur_len
                if off + length + 1 >= self.cfg.max_seq_len:
                    break
                if self._use_paged:
                    pages = self.pool.extend(req.req_id, length, off)
                    if pages is None:           # pool full: stop linking
                        break
                    # CoW guard: an MRAG link scatters into [off, off+len)
                    # — duplicate any page still shared with a forked
                    # sibling before the donated write lands
                    pages = self.pool.make_exclusive(req.req_id, off,
                                                     length)
                    if pages is None:
                        break
                    self._set_page_row(req.slot, pages)
                    ps = self.cfg.page_size
                    t = off + np.arange(length)
                    pages_t = jnp.asarray(self._page_tables[req.slot][t // ps])
                    offs_t = jnp.asarray((t % ps).astype(np.int32))
                    delta = jnp.full((length,), off, jnp.int32)
                    qk, qv = payload.qk, payload.qv
                    if (self.pool.quantized and qk is not None
                            and qk.block_tokens == qv.block_tokens):
                        # spool→pool fast path: the library's int8 bytes
                        # link by pure rescaling onto the page grid — no
                        # dequantize→requantize fp round trip (the skipped
                        # conversion is counted in the library stats)
                        self.pool.link_write_q8(
                            pages_t, offs_t,
                            jnp.asarray(qk.q), jnp.asarray(qk.scale),
                            jnp.asarray(qv.q), jnp.asarray(qv.scale),
                            jnp.asarray(scale_row_ids(length, qk)), delta,
                            theta=cfg.rope_theta, relink=relink)
                        self.dynamic_lib.note_direct_link(1)
                    else:
                        self.pool.link_write(
                            pages_t, offs_t,
                            jnp.asarray(entry.k), jnp.asarray(entry.v),
                            delta, theta=cfg.rope_theta, relink=relink)
                else:
                    self._batch_cache = self._link_jit(
                        self._batch_cache, jnp.asarray(entry.k),
                        jnp.asarray(entry.v), jnp.asarray(off, jnp.int32),
                        jnp.asarray(req.slot, jnp.int32),
                        theta=cfg.rope_theta, relink=relink)
            finally:
                self.dynamic_lib.unpin(entry)
            req.cur_len += length
            req.linked_media.append(media_id)

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def _decode_step_fn(self, params, cache, tokens, positions):
        logits, cache = self.model.decode_step(
            params, tokens, positions, cache, positions)
        return logits, cache

    def _paged_decode_fn(self, params, pool_k, pool_v, tokens, positions,
                         page_table, lengths, write_pages, write_offs):
        return self.model.decode_step_paged(
            params, tokens, positions, pool_k, pool_v, page_table, lengths,
            write_pages, write_offs, backend=self._paged_backend,
            interpret=self._interpret)

    def _paged_decode_q8_fn(self, params, pool_k, pool_v, k_scales, v_scales,
                            tokens, positions, page_table, lengths,
                            write_pages, write_offs):
        """Int8-pool decode step: the scale buffers enter (and leave,
        updated by the in-step quantized write) alongside the pages."""
        return self.model.decode_step_paged(
            params, tokens, positions, pool_k, pool_v, page_table, lengths,
            write_pages, write_offs, k_scales, v_scales,
            backend=self._paged_backend,
            interpret=self._interpret)

    def _select_token(self, req: Request, logits_row: np.ndarray) -> int:
        """Greedy argmax, or seeded temperature/top-k sampling per request."""
        if self.cfg.greedy:
            return int(np.argmax(logits_row))
        rng = self._rngs.setdefault(req.req_id,
                                    np.random.default_rng(req.seed))
        z = logits_row.astype(np.float64)
        if 0 < self.cfg.top_k < z.size:
            kth = np.partition(z, -self.cfg.top_k)[-self.cfg.top_k]
            z = np.where(z < kth, -np.inf, z)
        z = z / max(self.cfg.temperature, 1e-6)
        z = z - z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(rng.choice(z.size, p=p))

    def _decode(self) -> None:
        live = [r for r in self.running
                if r is not None and r.state is State.RUNNING]
        if not live:
            return
        if self._use_paged:
            live, logits = self._decode_paged_step(live)
        else:
            logits = self._decode_dense_step(live)
        for r in live:
            nxt = self._select_token(r, logits[r.slot])
            r.output_tokens.append(nxt)
            r.cur_len += 1
            if (r.freeze_after is not None
                    and len(r.output_tokens) >= r.freeze_after
                    and self._use_paged):
                # deterministic mid-decode freeze point (fleet resume
                # smoke): snapshot NOW, before this token is fed — the
                # thawed session re-emits it, so resume parity composes as
                # frozen[:-1] + thawed.  Spooled immediately: the point of
                # an automated freeze is surviving whatever comes next.
                self.sessions.freeze(r.req_id, spool=True)
                continue
            if len(r.output_tokens) >= r.max_new_tokens or \
                    r.cur_len + 1 >= self.cfg.max_seq_len:
                self._finish(r)

    def _decode_dense_step(self, live: List[Request]) -> np.ndarray:
        B = self.cfg.decode_slots
        tokens = np.zeros((B, 1), np.int32)
        positions = np.full((B, 1), self.cfg.max_seq_len - 1, np.int32)
        for r in live:
            tokens[r.slot, 0] = r.output_tokens[-1]
            positions[r.slot, 0] = r.cur_len
        with self.scheduler.compute_window():
            logits, self._batch_cache = self._decode_jit(
                self.params, self._batch_cache, jnp.asarray(tokens),
                jnp.asarray(positions))
            logits = np.asarray(logits, np.float32)
        return logits

    def _decode_paged_step(self, live: List[Request]):
        """One donated decode step over the page pool for all live slots
        (operands from :meth:`decode_operands`)."""
        live, ops = self.decode_operands(live)
        if not live:
            return live, None
        with self.scheduler.compute_window():
            pool = self.pool
            if pool.quantized:
                (logits, pool.k, pool.v,
                 pool.k_scale, pool.v_scale) = self._decode_jit(
                    self.params, pool.k, pool.v, pool.k_scale, pool.v_scale,
                    *ops)
            else:
                logits, pool.k, pool.v = self._decode_jit(
                    self.params, pool.k, pool.v, *ops)
            logits = np.asarray(logits, np.float32)
        return live, logits

    def decode_operands(self, live: List[Request]):
        """Host-built operands of the next paged decode step for ``live``:
        ``(tokens, positions, page_table, lengths, write_pages,
        write_offs)``, after growing each slot's pages (and resolving
        copy-on-write) for the token it writes.  Slots the pool cannot
        grow finish truncated and drop out of the returned ``live``.

        The page table is sliced to the live maximum page count, bucketed to
        the next power of two (bounds retraces to O(log max_seq_len)) — the
        attention work each step scales with the longest *live* cache, not
        with ``max_seq_len``.
        """
        live = list(live)
        B, ps = self.cfg.decode_slots, self.cfg.page_size
        tokens = np.zeros((B, 1), np.int32)
        positions = np.zeros((B, 1), np.int32)
        lengths = np.zeros((B,), np.int32)
        wp = np.full((B,), self._scratch_page, np.int32)
        wo = np.zeros((B,), np.int32)
        for r in list(live):
            if self.pool.capacity(r.req_id) < r.cur_len + 1:
                pages = self.pool.extend(r.req_id, 1, r.cur_len)
                if pages is None:
                    # pool exhausted mid-decode: finish truncated rather
                    # than stall the whole batch
                    r.prefill_stats["truncated"] = True
                    self._finish(r)
                    live.remove(r)
                    continue
                self._set_page_row(r.slot, pages)
            row = self._page_tables[r.slot]
            if self.pool.page_ref(int(row[r.cur_len // ps])) > 1:
                # copy-on-write: this step writes into a page shared with
                # a forked sibling — duplicate it first (one donated copy)
                pages = self.pool.make_exclusive(r.req_id, r.cur_len)
                if pages is None:
                    r.prefill_stats["truncated"] = True
                    self._finish(r)
                    live.remove(r)
                    continue
                self._set_page_row(r.slot, pages)
                row = self._page_tables[r.slot]
            tokens[r.slot, 0] = r.output_tokens[-1]
            positions[r.slot, 0] = r.cur_len
            lengths[r.slot] = r.cur_len + 1
            wp[r.slot] = row[r.cur_len // ps]
            wo[r.slot] = r.cur_len % ps
        if not live:
            return live, None
        mp_need = max(self.pool.pages_for(r.cur_len + 1) for r in live)
        mp = min(bucket(mp_need, 1), self._pages_per_slot)
        ops = (jnp.asarray(tokens), jnp.asarray(positions),
               jnp.asarray(self._page_tables[:, :mp]), jnp.asarray(lengths),
               jnp.asarray(wp), jnp.asarray(wo))
        return live, ops

    def _finish(self, r: Request) -> None:
        r.state = State.DONE
        r.t_done = time.perf_counter()
        self.finished.append(r)
        self.running[r.slot] = None
        self._rngs.pop(r.req_id, None)
        if self._use_paged:
            self.pool.free(r.req_id)
            self._page_tables[r.slot] = self._scratch_page
        else:
            self._clear_slot(r.slot)

    def _clear_slot(self, slot: int) -> None:
        bc = self._batch_cache
        if "pos" in bc:
            bc["pos"] = bc["pos"].at[slot].set(INVALID_POS)

    # ------------------------------------------------------------------
    # cluster hooks: external drivers (serving/cluster.py) poll these to
    # route and to apply admission backpressure across replicas
    # ------------------------------------------------------------------
    @property
    def has_work(self) -> bool:
        """Anything queued, prefilling, or decoding on this replica?"""
        return bool(self.scheduler.queue
                    or any(r is not None for r in self.running))

    def load_info(self) -> dict:
        """Instantaneous load snapshot for routing/backpressure decisions."""
        if self._use_paged:
            free_pages = self.pool.free_pages
            total_pages = self.pool.cfg.num_pages
        else:
            free_pages = total_pages = 0
        return {
            "replica": self.replica_id,
            "free_slots": sum(1 for r in self.running if r is None),
            "queue_depth": len(self.scheduler.queue),
            "prefills_inflight": len(self._prefill_tasks),
            "free_pages": free_pages,
            "total_pages": total_pages,
        }

    # ------------------------------------------------------------------
    def report(self) -> dict:
        done = self.finished
        if not done:
            return {}
        ttfts = [r.ttft for r in done]
        return {
            "replica": self.replica_id,
            "requests": len(done),
            "failed": len(self.failed),
            "expired": len(self.expired),
            "mean_ttft_s": float(np.mean(ttfts)),
            "p90_ttft_s": float(np.percentile(ttfts, 90)),
            "total_tokens": sum(len(r.output_tokens) for r in done),
            "paged": self._use_paged,
            "scheduler": self.scheduler.stats(done),
            # cluster mode shares ONE library across replicas — its stats
            # belong to the cluster report, not N identical copies here
            **({} if self.replica_id is not None
               else {"library": self.static_lib.stats()}),
        }
