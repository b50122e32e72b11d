"""Fault tolerance: serving throughput and completeness under injected
failures — healthy vs dead-peer vs slow-peer vs replica-crash.

Four matched 2-replica cluster legs serve the same request wave (mixed
locally-uploaded media — the MPIC reuse path — and *phantom* media that no
host owns, so every leg takes the same reuse/recompute decisions and must
decode **token-identical** greedy outputs):

  * **healthy** — a live (empty) peer block server answers every phantom
    probe with a fast 404.
  * **dead-peer** — ``peer.request:blackhole``: every probe hangs for the
    transport timeout.  The circuit breaker (``cache/net.py``) must open
    after ``threshold`` consecutive transport failures so steady-state
    misses stop paying the timeout: the acceptance gate is throughput
    ≥ 0.8× the healthy leg (without the breaker this leg pays
    ``timeout × retries`` per phantom miss, forever).
  * **slow-peer** — ``peer.request:latency``: probes answer after a delay.
    Any HTTP response is breaker-health, so the breaker stays closed and
    every miss pays the (bounded) latency — reported for contrast.
  * **replica-crash** — ``engine.step:crash`` kills replica 0 mid-wave.
    The cluster quarantines it and fails its queue over to replica 1
    (``drain_for_failover``): the gate is **100 % completion** with tokens
    identical to the healthy leg (idempotent seeded resubmit).

All faults come from seeded :class:`~repro.cache.faults.FaultPlan` rules —
nothing is hand-mocked — and the plan is armed *after* the per-leg jit
warmup so rule event-windows are deterministic over the timed wave.
Emits ``BENCH_faults.json``.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import build_bench_model, emit, scaled, smoke
from repro.cache import (
    DictBlockStore,
    FaultPlan,
    KVLibrary,
    KVPeerServer,
    PeerTransport,
)
from repro.core import Prompt, media_segment, text_segment
from repro.data import image_embeds
from repro.serving import ClusterConfig, MPICCluster, Request
from repro.serving.engine import EngineConfig

MEDIA_LEN = scaled(16, 12)
N_REQ = scaled(12, 6)
MAX_NEW = scaled(3, 2)
PEER_TIMEOUT_S = 0.2
BREAKER_COOLDOWN_S = 2.0
CRASH_AT_STEP = scaled(4, 2)     # replica 0's Nth step of the timed wave

OUT_PATH = os.environ.get(
    "MPIC_BENCH_OUT",
    "BENCH_faults.smoke.json" if smoke() else "BENCH_faults.json")


def _prompt(cfg, seed, media_ids, user_id="fu"):
    r = np.random.default_rng(seed)
    segs = [text_segment(r.integers(8, 200, 5))]
    for mid in media_ids:
        segs.append(media_segment(mid,
                                  image_embeds(mid, MEDIA_LEN, cfg.d_model)))
        segs.append(text_segment(r.integers(8, 200, 4)))
    return Prompt(segs, user_id=user_id)


def make_trace(cfg):
    """Each request: one uploaded media (reuse) + two phantoms (recompute,
    probed on the peers).  Identical decisions on every leg."""
    prompts, uploaded = [], []
    for i in range(N_REQ):
        uploaded.append(f"fm{i}")
        prompts.append(_prompt(
            cfg, 300 + i, [f"fm{i}", f"ghost{i}a", f"ghost{i}b"]))
    return prompts, uploaded


def _engine_cfg():
    return EngineConfig(max_seq_len=128, decode_slots=2, prefetch_depth=3)


def _requests(prompts):
    return [Request(prompt=p, max_new_tokens=MAX_NEW, policy="mpic",
                    policy_kwargs={"k": 4}) for p in prompts]


def _arm(cluster, plan):
    """Install the fault plan after warmup: engines, library, disk, and
    peer transports all read their ``faults`` attribute per event, so rule
    windows start counting at the timed wave, not at jit-warm time."""
    cluster.faults = plan
    for e in cluster.engines:
        e.faults = plan
    lib = cluster.static_lib
    lib.faults = plan
    lib.disk.faults = plan
    if lib.network is not None:
        for t in lib.network.transports:
            t.faults = plan


def run_leg(model, params, cfg, prompts, uploaded, *, label, plan=None,
            peer_addr=None):
    lib = KVLibrary(spool_dir=f"/tmp/mpic_spool_faults_{label}")
    if peer_addr is not None:
        lib.connect_peers(
            [PeerTransport(peer_addr, timeout_s=PEER_TIMEOUT_S, retries=0)],
            breaker_cooldown_s=BREAKER_COOLDOWN_S)
    cluster = MPICCluster(
        model, params, _engine_cfg(),
        # 1 loader worker per replica: phantom probes serialize, so the
        # breaker's consecutive-failure count reflects probe order and
        # later misses deterministically hit the open breaker
        ClusterConfig(replicas=2, router="least_loaded", router_seed=0,
                      max_queue_per_replica=8,
                      loader_workers_per_replica=1),
        static_library=lib)
    for mid in uploaded:
        cluster.upload("fu", mid, image_embeds(mid, MEDIA_LEN, cfg.d_model))

    # jit warmup outside the timed window, on media the wave never touches
    cluster.upload("w", "fwarm-a", image_embeds("fwarm-a", MEDIA_LEN,
                                                cfg.d_model))
    cluster.upload("w", "fwarm-b", image_embeds("fwarm-b", MEDIA_LEN,
                                                cfg.d_model))
    warm = Request(prompt=_prompt(cfg, 7, ["fwarm-a", "fwarm-b"], "w"),
                   max_new_tokens=MAX_NEW, policy="mpic",
                   policy_kwargs={"k": 4})
    cluster.submit(warm)
    cluster.run()
    for e in cluster.engines:
        e.finished.clear()

    if plan is not None:
        _arm(cluster, plan)

    reqs = _requests(prompts)
    t0 = time.perf_counter()
    for r in reqs:
        cluster.submit(r)
    cluster.run()
    wall = time.perf_counter() - t0
    rep = cluster.report()
    cluster.close()

    net = rep["cache_tiers"].get("network", {})
    row = {
        "label": label,
        "requests": len(reqs),
        "completed": sum(1 for r in reqs if r.done),
        "wall_s": round(wall, 3),
        "throughput_rps": round(len(reqs) / wall, 3),
        "ttft_ms": round(1e3 * float(np.mean(
            [r.ttft for r in reqs if r.done])), 1),
        "quarantined": rep["quarantined"],
        "requeued": rep["requeued"],
        "peer_timeouts": net.get("timeouts", 0),
        "breaker_skips": net.get("breaker_skips", 0),
        "breakers": net.get("breakers", {}),
        "fault_plan": plan.stats() if plan is not None else [],
        "tokens": [r.output_tokens for r in reqs],
    }
    assert row["completed"] == len(reqs), \
        f"{label}: {row['completed']}/{len(reqs)} requests completed"
    return row


def main():
    cfg, model, params = build_bench_model()
    prompts, uploaded = make_trace(cfg)

    # one live (empty) block server answers every phantom probe with a
    # fast 404; the dead/slow behaviors are injected client-side, so the
    # same server backs all peer legs
    server = KVPeerServer(DictBlockStore())

    legs = [
        ("healthy", None, server.address),
        ("dead_peer",
         FaultPlan.parse("peer.request:blackhole", seed=0), server.address),
        ("slow_peer",
         FaultPlan.parse("peer.request:latency:delay=0.05", seed=0),
         server.address),
        ("replica_crash",
         FaultPlan.parse(
             f"engine.step:crash:target=replica0,"
             f"start={CRASH_AT_STEP},stop={CRASH_AT_STEP + 1}", seed=0),
         None),
    ]
    rows = []
    for label, plan, addr in legs:
        row = run_leg(model, params, cfg, prompts, uploaded,
                      label=label, plan=plan, peer_addr=addr)
        print(f"  {label}: {row['throughput_rps']} req/s  "
              f"completed={row['completed']}/{row['requests']}  "
              f"breaker_skips={row['breaker_skips']}  "
              f"quarantined={list(row['quarantined'])}", flush=True)
        rows.append(row)
    server.close()

    by = {r["label"]: r for r in rows}
    ref = by["healthy"].pop("tokens")
    by["healthy"]["token_parity"] = True
    for label in ("dead_peer", "slow_peer", "replica_crash"):
        assert by[label].pop("tokens") == ref, \
            f"{label}: token parity broken vs healthy leg"
        by[label]["token_parity"] = True

    # the breaker must have opened on the dead peer (skips prove the
    # steady state stopped paying per-miss timeouts)...
    assert by["dead_peer"]["breaker_skips"] > 0, \
        "dead-peer leg never tripped the circuit breaker"
    # ...and the crash leg must have actually failed over
    assert list(by["replica_crash"]["quarantined"]) == [0], \
        f"crash leg quarantined {by['replica_crash']['quarantined']}"
    assert by["replica_crash"]["requeued"] > 0, \
        "crash leg completed without re-routing any request"

    dead_ratio = round(by["dead_peer"]["throughput_rps"]
                       / by["healthy"]["throughput_rps"], 3)
    slow_ratio = round(by["slow_peer"]["throughput_rps"]
                       / by["healthy"]["throughput_rps"], 3)
    crash_ratio = round(by["replica_crash"]["throughput_rps"]
                        / by["healthy"]["throughput_rps"], 3)
    if not smoke():
        # acceptance: a dead peer costs its timeout once per cooldown
        # window, not per miss — throughput within 20% of healthy
        assert dead_ratio >= 0.8, \
            f"dead-peer throughput {dead_ratio} < 0.8x healthy"

    emit(rows, "faults")
    out = {"bench": "fault_tolerance", "rows": rows,
           "dead_peer_vs_healthy": dead_ratio,
           "slow_peer_vs_healthy": slow_ratio,
           "replica_crash_vs_healthy": crash_ratio,
           "crash_leg_completion": by["replica_crash"]["completed"],
           "token_parity_all_legs": True}
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
    print(f"[faults] dead-peer {dead_ratio}x, slow-peer {slow_ratio}x, "
          f"crash {crash_ratio}x of healthy; crash leg completed "
          f"{by['replica_crash']['completed']}/{N_REQ}; wrote {OUT_PATH}")
    return rows


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache
    enable_compile_cache()
    main()
