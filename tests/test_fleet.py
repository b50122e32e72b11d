"""Multi-process fleet: wire blobs, heartbeat routing, and the live
supervise→kill→failover→drain loop (launch/fleet.py).

The unit half runs in-process (encode/decode round-trips, heartbeat-fed
affinity views).  The smoke half spawns a REAL 2-host fleet — separate
engine processes with their own spool dirs and peer block servers —
serves a wave, ``kill -9``s one host mid-wave, and requires the
supervisor to finish everything and drain cleanly.  It is the CI fleet
job; pytest-timeout (marker below + the global cap) guards against a
wedged fleet hanging the suite.
"""
import os
import time

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import Prompt, media_segment, text_segment
from repro.data import image_embeds
from repro.launch.fleet import (
    FleetSupervisor,
    decode_request,
    encode_request,
    encode_upload,
    unpack_blob,
)
from repro.serving.request import Request


def _prompt(cfg, seed, media, user_id="u1"):
    r = np.random.default_rng(seed)
    segs = [text_segment(r.integers(8, 200, 6))]
    for mid, emb in media:
        segs.append(media_segment(mid, emb))
        segs.append(text_segment(r.integers(8, 200, 5)))
    return Prompt(segs, user_id=user_id)


# ---------------------------------------------------------------------------
# wire blobs
# ---------------------------------------------------------------------------


def test_request_blob_roundtrip():
    cfg = get_smoke_config("llava-1.6-7b")
    media = [("m0", image_embeds("m0", 16, cfg.d_model))]
    req = Request(prompt=_prompt(cfg, 0, media), policy="mpic",
                  policy_kwargs={"k": 4}, max_new_tokens=5, seed=99,
                  deadline_s=12.5, priority=2)
    got = decode_request(encode_request(req))
    assert got.req_id == req.req_id          # identity survives the wire
    assert got.policy == "mpic" and got.policy_kwargs == {"k": 4}
    assert got.max_new_tokens == 5 and got.seed == 99
    assert got.deadline_s == 12.5 and got.priority == 2
    assert got.prompt.user_id == "u1"
    assert len(got.prompt.segments) == len(req.prompt.segments)
    for a, b in zip(got.prompt.segments, req.prompt.segments):
        assert a.kind == b.kind and a.length == b.length
        np.testing.assert_array_equal(np.asarray(a.tokens if a.kind == "text"
                                                 else a.embeds),
                                      np.asarray(b.tokens if b.kind == "text"
                                                 else b.embeds))


def test_upload_blob_roundtrip():
    emb = image_embeds("mx", 8, 32)
    header, arrays = unpack_blob(
        encode_upload("u9", "mx", emb, ttl=60.0, dynamic=True))
    assert header["user_id"] == "u9" and header["media_id"] == "mx"
    assert header["ttl"] == 60.0 and header["dynamic"] is True
    np.testing.assert_array_equal(arrays["embeds"], np.asarray(emb))


# ---------------------------------------------------------------------------
# heartbeat-fed affinity routing (no processes)
# ---------------------------------------------------------------------------


def test_heartbeat_view_routes_to_warm_host():
    from repro.cache.backends import scope_digest
    from repro.serving.router import AffinityRouter, heartbeat_view

    cfg = get_smoke_config("llava-1.6-7b")
    media = [("warmmed", image_embeds("warmmed", 8, cfg.d_model))]
    req = Request(prompt=_prompt(cfg, 1, media), policy="mpic")
    ident = scope_digest(("u1", "warmmed"))
    load = {"free_slots": 2, "queue_depth": 0,
            "free_pages": 8, "total_pages": 8}
    cold = {"load": load, "media": {}}
    warm = {"load": load, "media": {ident: "disk"}}

    views = [heartbeat_view(0, "127.0.0.1:1000", cold, req),
             heartbeat_view(1, "127.0.0.1:1001", warm, req)]
    assert views[1].warmth == {"disk": 1}
    decision = AffinityRouter().route(req, views)
    assert decision.replica == 1             # disk-warm beats cold
    assert decision.address == "127.0.0.1:1001"   # route-by-address


# ---------------------------------------------------------------------------
# the real thing: 2 engine processes + router, kill one, drain
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.timeout(560)
def test_fleet_smoke_kill_one_host_drain_completes(tmp_path):
    cfg = get_smoke_config("llava-1.6-7b")
    fleet = FleetSupervisor(2, base_dir=str(tmp_path), smoke=True,
                            hbm_bytes=1, host_bytes=1, max_seq_len=1024,
                            heartbeat_s=0.2, miss_threshold=3,
                            linger_s=30.0)
    try:
        fleet.start()
        media = {f"fsm{i}": image_embeds(f"fsm{i}", 16, cfg.d_model)
                 for i in range(6)}
        for mid, emb in media.items():
            fleet.upload("u1", mid, emb)
        pairs = sorted(media.items())
        for i in range(6):
            req = Request(
                prompt=_prompt(cfg, 10 + i,
                               [pairs[i % 6], pairs[(i + 1) % 6]]),
                policy="mpic", policy_kwargs={"k": 4},
                max_new_tokens=6, seed=50 + i)
            fleet.submit(req)
        fleet.kill_host(0)        # kill -9 with the whole wave in flight
        fleet.run_until_done(timeout_s=420)

        rep = fleet.report()
        assert rep["completed"] == 6 and rep["failed"] == 0, rep
        assert rep["deaths"] >= 1, "the murder was never detected"

        # the restarted host rejoined warm: its spool rehydrated
        fleet.wait_healthy([0], timeout_s=240)
        stats = (fleet._host(0).health or {}).get("rehydrate", {})
        assert stats.get("rehydrated", 0) > 0, stats

        # graceful drain: every host process exits on its own
        fleet.drain(timeout_s=120)
        for h in fleet.hosts:
            assert h.proc is None or h.proc.poll() is not None, \
                f"host {h.spec.host_id} still running after drain"
    finally:
        fleet.stop()


@pytest.mark.slow
@pytest.mark.timeout(560)
def test_fleet_freeze_on_a_kill_a_thaw_on_b(tmp_path):
    """Session resume-anywhere across a host death: a session frozen
    (and spooled) on host A survives ``kill -9`` of A — the restarted A
    rehydrates the snapshot from its spool dir, and a thaw on host B
    pulls it over the peer block protocol.  The resumed output must be
    token-identical to a session that was never interrupted."""
    import time

    r = np.random.default_rng(7)
    toks = r.integers(8, 200, 12)

    def mk(**kw):
        return Request(prompt=Prompt([text_segment(toks)], user_id="u1"),
                       max_new_tokens=8, policy="full_recompute", seed=5,
                       **kw)

    fleet = FleetSupervisor(2, base_dir=str(tmp_path), smoke=True,
                            slots=2,
                            heartbeat_s=0.2, miss_threshold=3,
                            linger_s=30.0)
    try:
        fleet.start()
        # unkilled baseline on host B
        base = mk()
        fleet.submit(base, host=1)
        fleet.run_until_done(timeout_s=240)
        base_toks = fleet.results[base.req_id]["tokens"]

        # freeze_after on host A: the host freezes + spools mid-decode
        # and reports a terminal "frozen" row carrying the handle
        fz = mk(freeze_after=4)
        fleet.submit(fz, host=0)
        fleet.run_until_done(timeout_s=240)
        row = fleet.results[fz.req_id]
        assert row["state"] == "frozen", row
        handle = row["session"]
        assert handle and handle["session_id"].startswith("sess-")
        assert handle["cache_salt"] and handle["n_ctx"] == 15
        # the freeze counter aggregates while A is still alive (its
        # in-process counters die with it below; the snapshot does not)
        fleet.heartbeat()
        assert fleet.report().get("sessions", {}).get("freezes", 0) >= 1

        # kill -9 host A; the supervisor detects the death and respawns
        # it with the same spool dir — the snapshot rehydrates from disk
        fleet.kill_host(0)
        deadline = time.monotonic() + 240
        while fleet.deaths == 0 and time.monotonic() < deadline:
            fleet.pump()
            time.sleep(0.05)
        assert fleet.deaths == 1, "the murder was never detected"
        fleet.wait_healthy([0], timeout_s=240)
        stats = (fleet._host(0).health or {}).get("rehydrate", {})
        assert stats.get("rehydrated", 0) > 0, stats

        # resume on host B: it never held the snapshot — the thaw's
        # library get falls through to the network tier and pulls the
        # block from the restarted A
        rid = fleet.thaw(1, handle)
        fleet.run_until_done(timeout_s=240)
        th = fleet.results[rid]
        assert th["state"] == "done" and th["host"] == 1, th
        assert row["tokens"][:-1] + th["tokens"] == base_toks

        # fleet-wide session visibility + aggregated counters
        assert handle["session_id"] in fleet.session_handles()
        fleet.heartbeat()
        rep = fleet.report()
        assert rep["frozen"] == 1
        assert rep.get("sessions", {}).get("thaws", 0) >= 1
        fleet.drain(timeout_s=120)
    finally:
        fleet.stop()


@pytest.mark.timeout(300)
def test_fleet_single_host_serves_and_drains(tmp_path):
    """1-host fleet: the degenerate topology must still serve + drain
    (covers the supervisor without the failover machinery)."""
    cfg = get_smoke_config("llava-1.6-7b")
    fleet = FleetSupervisor(1, base_dir=str(tmp_path), smoke=True,
                            max_seq_len=1024,
                            heartbeat_s=0.25, linger_s=30.0)
    try:
        fleet.start()
        emb = image_embeds("solo", 16, cfg.d_model)
        fleet.upload("u1", "solo", emb)
        req = Request(prompt=_prompt(cfg, 3, [("solo", emb)]),
                      policy="mpic", policy_kwargs={"k": 4},
                      max_new_tokens=4, seed=7)
        fleet.submit(req)
        fleet.run_until_done(timeout_s=240)
        row = fleet.results[req.req_id]
        assert row["state"] == "done" and len(row["tokens"]) == 4
        assert row["n_reused"] > 0       # the uploaded block was reused
        fleet.drain(timeout_s=120)
    finally:
        fleet.stop()


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------

def test_fleet_refuses_more_hosts_than_chips(monkeypatch, tmp_path):
    """On a TPU machine every engine host needs a chip of its own: a fleet
    larger than the attached chips is refused before anything spawns."""
    import repro.launch.device as device
    monkeypatch.setattr(device, "attached_tpu_chips", lambda: 1)
    with pytest.raises(ValueError, match="one process per chip"):
        FleetSupervisor(2, base_dir=str(tmp_path), smoke=True,
                        env={"JAX_PLATFORMS": "tpu"})
    # as many hosts as chips is fine (nothing is spawned until start())
    FleetSupervisor(1, base_dir=str(tmp_path), smoke=True,
                    env={"JAX_PLATFORMS": "tpu"})


@pytest.mark.timeout(180)
def test_fleet_host_without_its_chip_fails_fast(monkeypatch, tmp_path):
    """A host that cannot get the platform it expects (here: told to find
    a TPU on a CPU machine, as a second host finds the chip held by the
    first) exits at start-up, and ``start`` reports it at once instead of
    waiting out the start grace."""
    import repro.launch.device as device
    monkeypatch.setattr(device, "attached_tpu_chips", lambda: 1)
    env = {**os.environ, "JAX_PLATFORMS": "tpu",
           # off a TPU VM: no metadata-server lookup
           "TPU_SKIP_MDS_QUERY": "1"}
    fleet = FleetSupervisor(1, base_dir=str(tmp_path), smoke=True,
                            start_grace_s=600.0, env=env)
    t0 = time.monotonic()
    try:
        with pytest.raises(RuntimeError, match="exited with"):
            fleet.start(timeout_s=600.0)
    finally:
        fleet.stop()
    assert time.monotonic() - t0 < 120
