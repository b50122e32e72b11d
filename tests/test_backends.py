"""Storage-backend contract + tier fault injection (cache/backends.py).

One parametrized contract suite runs against all three backends — memory,
disk, and network (the latter over a real loopback HTTP server) — so a new
backend only has to join the fixture to inherit the whole conformance
surface.  The fault-injection half checks the property serving relies on:
corrupt disk bytes and peer timeouts degrade to *recompute fallback*, never
to wedged pins, leaked dedup slots, or garbage KV.
"""
import threading
import time

import numpy as np
import pytest

from repro.cache import (
    TIER_DISK,
    TIER_HOST,
    TIER_NETWORK,
    BlockMetadata,
    DictBlockStore,
    DiskBackend,
    KVLibrary,
    KVPayload,
    KVPeerServer,
    MemoryBackend,
    NetworkBackend,
    ParallelLoader,
    PeerTransport,
    content_key,
)
from repro.cache.backends import payload_from_bytes, payload_to_bytes
from repro.cache.quant import quantize_kv


def _payload(seed=0, nbytes=1 << 12):
    rng = np.random.default_rng(seed)
    n = nbytes // 8 // 16
    return KVPayload(k=rng.standard_normal((1, n, 2, 8)).astype(np.float32),
                     v=rng.standard_normal((1, n, 2, 8)).astype(np.float32))


@pytest.fixture(params=["memory", "disk", "network"])
def backend(request, tmp_path):
    """Each param yields (backend, cleanup) with an empty store."""
    if request.param == "memory":
        yield MemoryBackend()
    elif request.param == "disk":
        yield DiskBackend(str(tmp_path))
    else:
        server = KVPeerServer(DictBlockStore())
        be = NetworkBackend([server.address])
        yield be
        server.close()


class TestBackendContract:
    """The five-method contract every tier must satisfy identically."""

    def test_roundtrip(self, backend):
        p = _payload(1)
        key = content_key(p, ("u", "m"))
        assert backend.get(key) is None            # empty store: miss
        assert not backend.contains(key)
        backend.put(key, p, BlockMetadata(media_id="m"))
        assert backend.contains(key)
        got = backend.get(key)
        assert got is not None
        np.testing.assert_array_equal(got.k, p.k)
        np.testing.assert_array_equal(got.v, p.v)

    def test_quantized_roundtrip(self, backend):
        raw = _payload(2)
        p = KVPayload(qk=quantize_kv(raw.k), qv=quantize_kv(raw.v))
        key = content_key(p, ("u", "q"))
        backend.put(key, p)
        got = backend.get(key)
        assert got is not None and got.qk is not None
        np.testing.assert_array_equal(got.qk.q, p.qk.q)
        np.testing.assert_array_equal(got.qk.scale, p.qk.scale)

    def test_overwrite_is_idempotent(self, backend):
        p = _payload(3)
        key = content_key(p, ("u", "m"))
        backend.put(key, p)
        backend.put(key, p)
        assert backend.contains(key)
        np.testing.assert_array_equal(backend.get(key).k, p.k)

    def test_delete(self, backend):
        p = _payload(4)
        key = content_key(p, ("u", "m"))
        backend.put(key, p)
        backend.delete(key)
        assert not backend.contains(key)
        assert backend.get(key) is None
        backend.delete(key)                        # idempotent

    def test_stats_counters(self, backend):
        p = _payload(5)
        key = content_key(p, ("u", "m"))
        backend.put(key, p)
        backend.get(key)
        backend.get("no-such-key")
        s = backend.stats()
        assert s["puts"] >= 1 and s["hits"] >= 1 and s["misses"] >= 1
        assert s["bytes_written"] > 0 and s["bytes_read"] > 0

    def test_scoped_keys_do_not_collide(self, backend):
        """Identical content under two scopes → two independent blocks
        (the user-isolation property of the salted content key)."""
        p = _payload(6)
        ka = content_key(p, ("alice", "m"))
        kb = content_key(p, ("bob", "m"))
        assert ka != kb
        backend.put(ka, p)
        assert backend.get(kb) is None
        backend.delete(kb)
        assert backend.contains(ka)


def test_session_salt_isolates_identical_kv(tmp_path):
    """Two sessions freezing byte-identical KV must land under DISTINCT
    keys (the per-session ``cache_salt`` is part of both the content key
    and the wire ident), and neither the local get path nor a peer's
    block protocol can cross the salt boundary."""
    from repro.cache.backends import scope_digest

    p = _payload(12)
    ka = content_key(p, ("u", "sess"), salt="salt-a")
    kb = content_key(p, ("u", "sess"), salt="salt-b")
    assert len({ka, kb, content_key(p, ("u", "sess"))}) == 3
    assert scope_digest(("u", "sess"), "salt-a") \
        != scope_digest(("u", "sess"), "salt-b")
    # no salt → the legacy digest, bit-identical (media keys unchanged)
    assert scope_digest(("u", "sess"), None) == scope_digest(("u", "sess"))

    src = KVLibrary(spool_dir=str(tmp_path / "src"))
    k = np.random.default_rng(5).standard_normal((1, 8, 2, 8)) \
        .astype(np.float32)
    ea = src.put("u", "sess-a", k, k + 1, salt="salt-a")
    eb = src.put("u", "sess-b", k, k + 1, salt="salt-b")
    assert ea.meta.key != eb.meta.key          # same bytes, distinct keys
    assert src.get("u", "sess-a", salt="wrong") is None   # local miss
    assert src.get("u", "sess-a") is None                 # unsalted miss
    assert src.get("u", "sess-a", salt="salt-a") is not None

    server = KVPeerServer(src)
    try:
        dst = KVLibrary(spool_dir=str(tmp_path / "dst"),
                        peers=[server.address])
        # wrong scope over the wire: the salted ident IS the address, so
        # a peer probing with the wrong salt misses outright
        assert dst.get("u", "sess-a", salt="salt-b") is None
        assert dst.get("u", "sess-a") is None
        got = dst.get("u", "sess-a", salt="salt-a")
        assert got is not None
        np.testing.assert_array_equal(got.k, k)
    finally:
        server.close()


def test_session_salt_survives_spool_rehydration(tmp_path):
    """The salt rides the spool sidecar: a restarted library rehydrates
    a salted entry and still enforces the salt boundary."""
    lib = KVLibrary(spool_dir=str(tmp_path))
    k = np.full((1, 8, 2, 8), 3.0, np.float32)
    lib.put("u", "sess", k, k, salt="s1")
    assert lib.spool_now("u", "sess")
    lib2 = KVLibrary(spool_dir=str(tmp_path), rehydrate=True)
    assert lib2.rehydrate_stats["rehydrated"] == 1
    assert lib2.get("u", "sess") is None           # unsalted: still a miss
    got = lib2.get("u", "sess", salt="s1")
    assert got is not None
    np.testing.assert_array_equal(got.k, k)


def test_wire_format_roundtrip():
    p = _payload(7)
    got = payload_from_bytes(payload_to_bytes(p))
    np.testing.assert_array_equal(got.k, p.k)
    with pytest.raises(Exception):
        payload_from_bytes(b"this is not an npz")


# ---------------------------------------------------------------------------
# fault injection: every tier failure must degrade to recompute fallback
# ---------------------------------------------------------------------------

def _mini_lib(tmp_path, **kw):
    lib = KVLibrary(hbm_capacity=1, host_capacity=1,   # force spool
                    spool_dir=str(tmp_path), **kw)
    k = np.random.default_rng(0).standard_normal((1, 8, 2, 8)) \
        .astype(np.float32)
    e = lib.put("u", "m", k, k + 1)
    assert e.tier == TIER_DISK
    return lib, k


def test_corrupt_disk_read_falls_back_to_miss(tmp_path):
    lib, _ = _mini_lib(tmp_path)
    e = lib._entries[lib._key("u", "m")]
    with open(e.path, "wb") as f:
        f.write(b"\x00garbage" * 16)               # corrupt the spool file
    assert lib.get("u", "m") is None               # miss, not garbage KV
    assert lib.disk.counters["corrupt"] == 1
    assert lib._key("u", "m") not in lib._entries  # zombie healed
    # the library still works: a re-put (the recompute path) serves again
    k2 = np.ones((1, 8, 2, 8), np.float32)
    lib.put("u", "m", k2, k2)
    got = lib.get("u", "m")
    assert got is not None and got._pins == 0
    np.testing.assert_array_equal(got.k, k2)


def test_truncated_disk_read_falls_back_to_miss(tmp_path):
    lib, _ = _mini_lib(tmp_path)
    e = lib._entries[lib._key("u", "m")]
    data = open(e.path, "rb").read()
    with open(e.path, "wb") as f:
        f.write(data[:len(data) // 2])             # truncate mid-archive
    assert lib.get("u", "m") is None
    assert lib.disk.counters["corrupt"] == 1


def test_content_hash_mismatch_detected(tmp_path):
    """A spool file whose bytes parse fine but hold DIFFERENT arrays than
    the key's content hash (bitrot, crossed files) must read as a miss."""
    disk = DiskBackend(str(tmp_path))
    p, imposter = _payload(8), _payload(9)
    key = content_key(p, ("u", "m"))
    disk.put(key, imposter)                        # valid npz, wrong content
    assert disk.get(key) is None
    assert disk.counters["corrupt"] == 1


def test_corrupt_disk_does_not_wedge_loader(tmp_path):
    """A prefetch whose disk read hits corruption must complete its future
    with None (recompute fallback), retire its dedup slot, and leave no
    pins behind."""
    lib, _ = _mini_lib(tmp_path)
    e = lib._entries[lib._key("u", "m")]
    with open(e.path, "wb") as f:
        f.write(b"junk")
    loader = ParallelLoader(lib)
    h = loader.prefetch_handle("u", ["m"])
    assert h.get("m", timeout=10) is None          # miss, not a hang
    h.release()
    time.sleep(0.1)                                # done-callbacks drain
    assert not loader._inflight                    # dedup slot retired
    assert e._pins == 0
    loader.close()


def test_network_timeout_falls_back_to_recompute(tmp_path):
    """A peer slower than the client timeout costs at most
    timeout × (1 + single retry) and then reads as a miss."""
    src = KVLibrary(spool_dir=str(tmp_path / "src"), hbm_capacity=1,
                    host_capacity=1)
    k = np.ones((1, 8, 2, 8), np.float32)
    src.put("u", "m", k, k)
    server = KVPeerServer(src, delay_s=1.0)        # 5× the client timeout
    try:
        lib = KVLibrary(spool_dir=str(tmp_path / "dst"))
        lib.network = NetworkBackend(
            [PeerTransport(server.address, timeout_s=0.2)])
        t0 = time.perf_counter()
        assert lib.get("u", "m") is None           # timeout → miss
        wall = time.perf_counter() - t0
        assert wall < 3.0                          # bounded: 2 × 0.2s + slack
        s = lib.stats()["tiers"][TIER_NETWORK]
        assert s["timeouts"] >= 1 and s["retries"] == 1
        assert s["fetch_misses"] == 1
    finally:
        server.close()


def test_network_timeout_does_not_leak_dedup_slot(tmp_path):
    src = KVLibrary(spool_dir=str(tmp_path / "src"))
    k = np.ones((1, 8, 2, 8), np.float32)
    src.put("u", "m", k, k)
    server = KVPeerServer(src, delay_s=1.0)
    try:
        lib = KVLibrary(spool_dir=str(tmp_path / "dst"))
        lib.network = NetworkBackend(
            [PeerTransport(server.address, timeout_s=0.1)])
        loader = ParallelLoader(lib)
        h = loader.prefetch_handle("u", ["m"])
        assert h.get("m", timeout=10) is None
        h.release()
        time.sleep(0.1)
        assert not loader._inflight
        # peer recovers → the SAME identity is fetchable again (no poisoned
        # negative cache)
        server.delay_s = 0.0
        got = lib.get("u", "m")
        assert got is not None
        np.testing.assert_array_equal(got.k, k)
        loader.close()
    finally:
        server.close()


def test_network_pull_and_tier_accounting(tmp_path):
    """Happy path end-to-end: a library that misses locally admits the
    peer's block (bit-exact through spool → HTTP → admit) and accounts it
    on the network tier."""
    src = KVLibrary(spool_dir=str(tmp_path / "src"), hbm_capacity=1,
                    host_capacity=1)                # block lives on disk
    rng = np.random.default_rng(3)
    k = rng.standard_normal((2, 16, 2, 8)).astype(np.float32)
    src.put("u", "m", k, k * 2, ttl=60.0)
    server = KVPeerServer(src)
    try:
        lib = KVLibrary(spool_dir=str(tmp_path / "dst"),
                        peers=[server.address])
        got = lib.get("u", "m")
        assert got is not None
        np.testing.assert_array_equal(got.k, k)    # bit-exact over the wire
        np.testing.assert_array_equal(got.v, k * 2)
        assert got.expires - time.time() < 61      # peer TTL honoured
        tiers = lib.stats()["tiers"]
        assert tiers[TIER_NETWORK]["promotes"] == 1
        assert tiers[TIER_NETWORK]["fetches"] == 1
        assert tiers[TIER_NETWORK]["fetch_s"] > 0
        # admitted block is now local: the second get never hits the wire
        assert lib.get("u", "m") is not None
        assert lib.network.counters["hits"] == 1   # still just one fetch
        # the handler thread counts a block after its body is sent, which
        # the client may already have read: give it a moment to finish
        deadline = time.monotonic() + 5.0
        while (server.stats()["served_blocks"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert server.stats()["served_blocks"] == 1
        # scope isolation across the wire: bob cannot pull alice's block
        assert lib.get("bob", "m") is None
    finally:
        server.close()


def test_register_remote_prefetches_over_network(tmp_path):
    """register_remote plants a network-tier placeholder that the normal
    prefetch path pulls — the cross-host analogue of a disk prefetch."""
    src = KVLibrary(spool_dir=str(tmp_path / "src"))
    k = np.full((1, 8, 2, 8), 2.0, np.float32)
    src.put("u", "m", k, k)
    server = KVPeerServer(src)
    try:
        lib = KVLibrary(spool_dir=str(tmp_path / "dst"),
                        peers=[server.address])
        e = lib.register_remote("u", "m", nbytes=k.nbytes * 2)
        assert e is not None and e.tier == TIER_NETWORK
        assert lib.peek_tier("u", "m") == TIER_NETWORK
        assert lib.warmth("u", ["m"], replica=0)[TIER_NETWORK] == 1
        loader = ParallelLoader(lib)
        h = loader.prefetch_handle("u", ["m"])
        got = h.get("m", timeout=10)
        assert got is not None and got.tier != TIER_NETWORK
        np.testing.assert_array_equal(got.k, k)
        h.release()
        loader.close()
    finally:
        server.close()


def test_pushed_block_is_served_back(tmp_path):
    """PUT push-replication: a block pushed to a peer server is immediately
    fetchable by other peers through the same server."""
    store = KVLibrary(spool_dir=str(tmp_path))
    server = KVPeerServer(store)
    try:
        p = _payload(11)
        key = content_key(p, ("u", "m"))
        be = NetworkBackend([server.address])
        meta = BlockMetadata(media_id="m", expires=time.time() + 60)
        be.put(key, p, meta)
        assert be.contains(key)
        got = be.get(key)
        assert got is not None
        np.testing.assert_array_equal(got.k, p.k)
        be.delete(key)
        assert not be.contains(key)
    finally:
        server.close()


def test_concurrent_backend_access(tmp_path):
    """Backends must tolerate concurrent put/get/delete (the loader pool
    does exactly this against disk)."""
    disk = DiskBackend(str(tmp_path))
    payloads = {f"m{i}": _payload(i) for i in range(8)}
    keys = {m: content_key(p, ("u", m)) for m, p in payloads.items()}
    errors = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        for _ in range(30):
            m = f"m{int(rng.integers(8))}"
            op = rng.integers(3)
            try:
                if op == 0:
                    disk.put(keys[m], payloads[m])
                elif op == 1:
                    got = disk.get(keys[m])
                    if got is not None:
                        np.testing.assert_array_equal(got.k, payloads[m].k)
                else:
                    disk.delete(keys[m])
            except Exception as exc:      # noqa: BLE001
                errors.append(repr(exc))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors, errors[:3]
