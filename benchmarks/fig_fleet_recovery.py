"""Fleet crash recovery: kill -9 an engine host mid-wave, complete the
wave token-identically; measure warm (disk-rehydrated) vs cold rejoin.

Two matched 2-host **multi-process** fleet legs serve the same request
wave (every request ships its own media embeds, so any host can serve any
request, reusing the library when warm and recomputing when not):

  * **baseline** — both hosts stay up for the whole wave.
  * **crash** — host 0 is ``kill -9``-ed with the full wave in flight.
    The supervisor's heartbeat declares it dead, fails its in-flight
    requests over to host 1 (byte-identical resubmission + seeded replay
    → same tokens), and respawns it with the same identity.  Gates:
    **100 % completion** with tokens identical to the baseline leg, at
    least one death and one failover resubmission.

Then, on the crash leg's fleet, the warm-vs-cold rejoin probe: host 0's
auto-restart rehydrated its spool dir (self-verifying content-hash
blocks → disk-tier index, no payload reads), so a probe request pinned
to it reuses media KV straight from disk.  Restarting it again with the
spool wiped forces a full recompute of the same prompt.  Both probes run
after a two-round jit warmup in a disjoint user scope (round 1 warms the
full-prefill path, round 2 the reuse path), so the timed delta is
KV-load-vs-recompute, not compile time.  Gate: warm TTFT < cold TTFT.

Tight library budgets (``hbm_bytes=1, host_bytes=1``) force every block
to the disk tier immediately — the rehydration path is load-bearing, not
decorative.  Emits ``BENCH_fleet.json``.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import build_bench_model, emit, scaled, smoke
from repro.core import Prompt, media_segment, text_segment
from repro.data import image_embeds
from repro.launch.fleet import FleetSupervisor
from repro.serving import Request

MEDIA_LEN = scaled(24, 12)
PROBE_LEN = scaled(256, 64)     # long media: reuse must beat recompute
N_REQ = scaled(10, 4)
MAX_NEW = scaled(16, 4)
N_PROBE = scaled(3, 2)
MAX_SEQ_LEN = 1024

OUT_PATH = os.environ.get(
    "MPIC_BENCH_OUT",
    "BENCH_fleet.smoke.json" if smoke() else "BENCH_fleet.json")


def _prompt(cfg, seed, media, user_id="u1"):
    """media: list of (media_id, embeds) — embeds are generated ONCE in
    this process and shipped with every request, so both legs (and every
    host process, whatever its PYTHONHASHSEED) see identical bytes."""
    r = np.random.default_rng(seed)
    segs = [text_segment(r.integers(8, 200, 6))]
    for mid, emb in media:
        segs.append(media_segment(mid, emb))
        segs.append(text_segment(r.integers(8, 200, 5)))
    return Prompt(segs, user_id=user_id)


def make_trace(cfg):
    media = {f"flm{i}": image_embeds(f"flm{i}", MEDIA_LEN, cfg.d_model)
             for i in range(N_REQ)}
    prompts = []
    for i in range(N_REQ):
        ids = [f"flm{i}", f"flm{(i + 1) % N_REQ}"]
        prompts.append(_prompt(cfg, 500 + i, [(m, media[m]) for m in ids]))
    return prompts, media


def _requests(prompts):
    reqs = []
    for i, p in enumerate(prompts):
        r = Request(prompt=p, max_new_tokens=MAX_NEW, policy="mpic",
                    policy_kwargs={"k": 8}, seed=900 + i)
        r.req_id = f"wave{i}"       # stable across legs (parity is by id)
        reqs.append(r)
    return reqs


def _fleet():
    return FleetSupervisor(2, smoke=True, hbm_bytes=1, host_bytes=1,
                           max_seq_len=MAX_SEQ_LEN, heartbeat_s=0.2,
                           miss_threshold=3, linger_s=60.0)


def run_leg(cfg, prompts, media, probe_media, *, label, crash):
    """Serve the wave; on the crash leg, kill -9 host 0 with everything
    in flight.  Returns (fleet, row) — the crash leg's fleet is reused
    for the rejoin probes."""
    fleet = _fleet()
    fleet.start()
    # wave media are replicated to EVERY host: reuse decisions (and
    # therefore greedy tokens — MPIC's relink+recompute path is not
    # bit-identical to a fresh prefill) stay the same whether a request
    # runs where it was routed or where failover lands it
    for hid in range(len(fleet.hosts)):
        for mid, emb in media.items():
            fleet.upload("u1", mid, emb, host=hid)
    for mid, emb in probe_media.items():
        # probe media live on host 0 only: its spool is what rehydrates
        fleet.upload("u1", mid, emb, host=0)
    time.sleep(0.5)         # let the rebalancer spool everything
    reqs = _requests(prompts)
    t0 = time.perf_counter()
    for r in reqs:
        fleet.submit(r)
    if crash:
        fleet.kill_host(0)
    fleet.run_until_done(timeout_s=600)
    wall = time.perf_counter() - t0
    rep = fleet.report()
    rows = fleet.results
    row = {
        "label": label,
        "requests": len(reqs),
        "completed": rep["completed"] - rep["failed"],
        "wall_s": round(wall, 3),
        "deaths": rep["deaths"],
        "restarts": rep["restarts"],
        "requeued": rep["requeued"],
        "tokens": {rid: r["tokens"] for rid, r in rows.items()},
    }
    assert row["completed"] == len(reqs), (
        f"{label}: {row['completed']}/{len(reqs)} completed "
        f"({[r['error'] for r in rows.values() if r['error']]})")
    return fleet, row


def _probe_ttft(fleet, cfg, probe_media, *, tag, expect_reuse):
    """Mean host-side TTFT of probe requests pinned to host 0, after a
    jit warmup in a disjoint scope that compiles BOTH prefill paths —
    round 1 serves never-uploaded media (the full recompute path the
    cold probe takes), round 2 uploaded media (the reuse/link path the
    warm probe takes) — so neither leg's timed probe pays compile.
    Warmup media match the probes' ``PROBE_LEN``, so prompt shapes (and
    therefore compiled kernels) are identical to the timed probes'."""
    recomp = [(f"fwa{tag}{j}",
               image_embeds(f"fwa{tag}{j}", PROBE_LEN, cfg.d_model))
              for j in range(2)]
    w = Request(prompt=_prompt(cfg, 41, recomp, "w"),
                max_new_tokens=2, policy="mpic",
                policy_kwargs={"k": 8}, seed=77)
    w.req_id = f"warm-{tag}-recompute"
    fleet.submit(w, host=0)
    fleet.run_until_done(timeout_s=300)

    reuse = [(f"fwb{tag}{j}",
              image_embeds(f"fwb{tag}{j}", PROBE_LEN, cfg.d_model))
             for j in range(2)]
    for mid, emb in reuse:
        fleet.upload("w", mid, emb, host=0)
    w = Request(prompt=_prompt(cfg, 43, reuse, "w"),
                max_new_tokens=2, policy="mpic",
                policy_kwargs={"k": 8}, seed=78)
    w.req_id = f"warm-{tag}-reuse"
    fleet.submit(w, host=0)
    fleet.run_until_done(timeout_s=300)

    ttfts = []
    probes = sorted(probe_media.items())
    for j in range(N_PROBE):
        p = Request(prompt=_prompt(cfg, 600 + j, probes), policy="mpic",
                    max_new_tokens=2, policy_kwargs={"k": 8}, seed=80 + j)
        p.req_id = f"probe-{tag}-{j}"
        fleet.submit(p, host=0)
        fleet.run_until_done(timeout_s=300)
        row = fleet.results[p.req_id]
        assert row["state"] == "done", f"probe {p.req_id}: {row['error']}"
        if expect_reuse:
            assert row["n_reused"] > 0, \
                f"warm probe {p.req_id} reused nothing (not warm at all)"
        else:
            assert row["n_reused"] == 0, \
                f"cold probe {p.req_id} reused {row['n_reused']} (not cold)"
        ttfts.append(row["ttft"])
    return float(np.mean(ttfts))


def main():
    cfg, _, _ = build_bench_model()
    prompts, media = make_trace(cfg)
    probe_media = {f"flp{j}": image_embeds(f"flp{j}", PROBE_LEN,
                                           cfg.d_model)
                   for j in range(2)}

    base_fleet, base = run_leg(cfg, prompts, media, probe_media,
                               label="baseline", crash=False)
    base_fleet.stop()
    print(f"  baseline: {base['completed']}/{base['requests']} in "
          f"{base['wall_s']}s", flush=True)

    crash_fleet, crash = run_leg(cfg, prompts, media, probe_media,
                                 label="crash", crash=True)
    print(f"  crash: {crash['completed']}/{crash['requests']} in "
          f"{crash['wall_s']}s deaths={crash['deaths']} "
          f"requeued={crash['requeued']}", flush=True)

    # gates: the murdered leg finishes everything, token-identically
    assert crash["deaths"] >= 1, "crash leg: host 0 was never declared dead"
    assert crash["requeued"] >= 1, \
        "crash leg: no in-flight request was failed over"
    ref = base.pop("tokens")
    tok = crash.pop("tokens")
    assert tok == ref, "crash leg: token parity broken vs baseline"
    base["token_parity"] = crash["token_parity"] = True

    try:
        # warm rejoin: auto-restarted host 0 rehydrated its spool
        fleet = crash_fleet
        fleet.wait_healthy([0], timeout_s=300)
        rehydrated = (fleet._host(0).health or {}).get("rehydrate", {})
        assert rehydrated.get("rehydrated", 0) > 0, \
            f"restarted host 0 rehydrated nothing: {rehydrated}"
        warm_ttft = _probe_ttft(fleet, cfg, probe_media, tag="warm",
                                expect_reuse=True)

        # cold rejoin: same host, spool wiped before respawn
        fleet.restart_host(0, wipe_spool=True, timeout_s=300)
        cold_ttft = _probe_ttft(fleet, cfg, probe_media, tag="cold",
                                expect_reuse=False)
    finally:
        crash_fleet.stop()

    speedup = cold_ttft / warm_ttft
    print(f"  rejoin: warm {1e3 * warm_ttft:.1f}ms vs cold "
          f"{1e3 * cold_ttft:.1f}ms TTFT ({speedup:.2f}x), "
          f"rehydrated={rehydrated.get('rehydrated')}", flush=True)
    if not smoke():
        # acceptance: disk-rehydrated rejoin beats recompute-everything
        assert warm_ttft < cold_ttft, (
            f"warm rejoin TTFT {warm_ttft:.3f}s not better than cold "
            f"{cold_ttft:.3f}s")

    rows = [base, crash]
    emit(rows, "fleet")
    out = {"bench": "fleet_recovery", "rows": rows,
           "rehydrated_blocks": rehydrated,
           "warm_rejoin_ttft_ms": round(1e3 * warm_ttft, 2),
           "cold_rejoin_ttft_ms": round(1e3 * cold_ttft, 2),
           "warm_vs_cold_speedup": round(speedup, 3),
           "token_parity": True}
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
    print(f"[fleet] crash leg {crash['completed']}/{crash['requests']} "
          f"complete, {crash['requeued']} failed over; warm rejoin "
          f"{speedup:.2f}x faster than cold; wrote {OUT_PATH}")
    return rows


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache
    enable_compile_cache()
    main()
