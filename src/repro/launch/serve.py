"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Boots an MPIC engine — or, with ``--replicas N``, a data-parallel
:class:`~repro.serving.cluster.MPICCluster` — for the chosen architecture
at its published widths (``--layers N`` cuts only its depth, as one chip
forces; ``--smoke`` takes the reduced CPU preset instead), feeds it a
synthetic 2-image MMDU-style request stream with the config's own media
length, and prints the TTFT / throughput report.  The run exits non-zero
if any request failed or expired.  On a TPU machine the process checks
that JAX really got the chip (it falls back to the CPU in silence when
another process holds it).

Every engine knob is drivable from the CLI: ``--no-paged`` /
``--no-pipelined`` select the dense / sequential baselines,
``--prefill-chunk`` chunks long prompts across steps, ``--mesh DxM``
(e.g. ``--mesh 1x4``, or ``--mesh auto`` for all visible devices on the
tensor-parallel axis) runs the mesh-sharded serving path — pair it with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to try it on a
CPU-only box — and ``--replicas N --router {random,least_loaded,affinity}``
serves the request stream through the routed replica fleet.  The network
KV tier spans *processes*: ``--serve-blocks PORT`` exports this server's
static library to peers and ``--peers host:port[,...]`` pulls locally
missing entries from theirs before falling back to recompute (see
docs/ARCHITECTURE.md, "network tier").

``--policy`` takes a comma-separated trace cycled over the request stream
(e.g. ``--policy mpic,full_recompute``).  An unknown policy name in the
trace fails *that request* with a per-request error and the server keeps
serving the others; the exit code then reports the failure.

:func:`build_server` and :func:`submit_dialogues` are the two halves of a
run that ``chip_smoke.py`` drives directly.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import List, Optional

import jax

from repro.configs import serving_config
from repro.data import image_embeds, make_dialogues
from repro.launch.device import (
    enable_compile_cache,
    expected_platform,
    require_platform,
)
from repro.models import build_model
from repro.serving import (
    ClusterConfig,
    EngineConfig,
    MPICCluster,
    MPICEngine,
    Request,
)
from repro.serving.sharding import init_params


def parse_mesh(spec: str):
    """'none' -> None; 'auto' -> all devices on model; 'DxM' -> that mesh."""
    from repro.launch.mesh import make_serving_mesh
    if spec in ("none", ""):
        return None
    if spec == "auto":
        return make_serving_mesh()
    data, model = (int(x) for x in spec.lower().split("x"))
    return make_serving_mesh(data=data, model=model)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llava-1.6-7b")
    ap.add_argument("--smoke", action="store_true",
                    help="the reduced CPU preset of --arch (<=2 layers, "
                         "d_model<=256) instead of its published widths")
    ap.add_argument("--layers", type=int, default=0,
                    help=">0: cut the model to this many decoder layers "
                         "(depth only; widths stay as published)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--policy", default="mpic",
                    help="policy per request, comma-separated trace cycled "
                         "over the stream (mpic, prefix_caching, full_reuse,"
                         " cacheblend, full_recompute); unknown names fail "
                         "per-request, not the server")
    ap.add_argument("--mpic-k", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq-len", type=int, default=0,
                    help="kv tokens per slot; 0 = the longest prompt plus "
                         "--max-new-tokens, rounded up to 256")
    ap.add_argument("--paged", dest="paged", action="store_true",
                    default=True, help="pool-backed decode path (default)")
    ap.add_argument("--no-paged", dest="paged", action="store_false",
                    help="dense batch-cache baseline")
    ap.add_argument("--pipelined", dest="pipelined", action="store_true",
                    default=True, help="pipelined admission (default)")
    ap.add_argument("--no-pipelined", dest="pipelined",
                    action="store_false",
                    help="sequential admission baseline")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help=">0: chunk long prefills across engine steps")
    ap.add_argument("--freeze-idle-s", type=float, default=0.0,
                    help=">0: frozen session snapshots idle this many "
                         "seconds are spooled to the disk tier (freeze/"
                         "thaw session store — see serving/sessions.py)")
    ap.add_argument("--mesh", default="none",
                    help="'none' (default), 'auto', or 'DxM' data×model "
                         "mesh for tensor-parallel serving (e.g. 1x4)")
    ap.add_argument("--replicas", type=int, default=1,
                    help=">1: serve through an MPICCluster of N "
                         "data-parallel engine replicas")
    ap.add_argument("--fleet", type=int, default=0,
                    help=">0: serve through a supervised MULTI-PROCESS "
                         "fleet of N engine hosts (one process + spool "
                         "dir + peer block server each, one chip each on "
                         "a TPU machine) behind the heartbeat router — "
                         "see launch/fleet.py")
    ap.add_argument("--router", default="affinity",
                    choices=["random", "least_loaded", "affinity"],
                    help="cluster routing policy (with --replicas > 1)")
    ap.add_argument("--peers", default="",
                    help="comma-separated host:port peer block servers — "
                         "enables the network KV tier (a local cache miss "
                         "pulls the peer's spooled entry instead of "
                         "recomputing)")
    ap.add_argument("--serve-blocks", type=int, default=None,
                    metavar="PORT",
                    help="export this server's static KV library to peers "
                         "on PORT (0 = pick a free port)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock budget from arrival; "
                         "requests not finished in time are reaped "
                         "(terminal DEADLINE state, resources freed)")
    ap.add_argument("--fault-plan", default="",
                    help="chaos testing: ';'-separated fault rules "
                         "site:kind[:k=v,...] (see cache/faults.py), e.g. "
                         "'peer.request:blackhole;engine.step:crash:"
                         "target=replica0,start=5,stop=6'")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for fault-plan probability draws")
    return ap


@dataclasses.dataclass
class Server:
    """What :func:`build_server` assembled for one run."""
    cfg: object                     # the ModelConfig served
    engine: object                  # MPICEngine or MPICCluster
    mesh: object = None
    dialogues: list = None
    peer_server: object = None
    faults: object = None


def build_server(args) -> Server:
    """Model, parameters and engine (or replica cluster) exactly as
    ``serve`` runs them.  Parameters are initialised straight onto the
    mesh's shardings (or the default device), from ``PRNGKey(0)``."""
    peers = [p.strip() for p in args.peers.split(",") if p.strip()]
    faults = None
    if args.fault_plan:
        from repro.cache.faults import FaultPlan
        faults = FaultPlan.parse(args.fault_plan, seed=args.fault_seed)

    cfg = serving_config(args.arch, smoke=args.smoke, layers=args.layers)
    dialogues = make_dialogues(n=args.requests, n_images=2,
                               d_model=cfg.d_model,
                               media_len=cfg.media_token_len,
                               style="mmdu", user_id="u1")
    max_seq_len = args.max_seq_len
    if max_seq_len <= 0:
        need = max(d.prompt.total_len for d in dialogues) \
            + args.max_new_tokens + 1
        max_seq_len = -(-need // 256) * 256
    model = build_model(cfg)
    mesh = parse_mesh(args.mesh)
    params = init_params(model, jax.random.PRNGKey(0), mesh)
    engine_cfg = EngineConfig(
        max_seq_len=max_seq_len, decode_slots=args.slots,
        paged=args.paged, pipelined=args.pipelined,
        prefill_chunk_tokens=args.prefill_chunk,
        freeze_idle_s=args.freeze_idle_s)
    peer_server = None
    if args.replicas > 1:
        eng = MPICCluster(model, params, engine_cfg,
                          ClusterConfig(replicas=args.replicas,
                                        router=args.router,
                                        peers=peers or None,
                                        serve_port=args.serve_blocks,
                                        deadline_s=args.deadline_s,
                                        faults=faults,
                                        on_stuck="report"),
                          mesh=mesh)
        peer_server = eng.peer_server
    else:
        from repro.cache.library import KVLibrary
        static_lib = (KVLibrary(peers=peers, faults=faults)
                      if peers or faults else None)
        eng = MPICEngine(model, params, engine_cfg, mesh=mesh,
                         static_library=static_lib, faults=faults)
        if args.serve_blocks is not None:
            from repro.cache.net import KVPeerServer
            peer_server = KVPeerServer(eng.static_lib,
                                       port=args.serve_blocks)
    return Server(cfg, eng, mesh, dialogues, peer_server, faults)


def submit_dialogues(server: Server, args) -> List[Request]:
    """Upload every image of the run's dialogues (precompute its KV into
    the library), then submit one request per dialogue, cycling the
    ``--policy`` trace.  Returns the submitted requests."""
    cfg, eng = server.cfg, server.engine
    seen = set()
    for d in server.dialogues:
        for mid in d.media_ids:
            if mid not in seen:
                eng.upload("u1", mid, image_embeds(mid, cfg.media_token_len,
                                                   cfg.d_model))
                seen.add(mid)
    policies = [p.strip() for p in args.policy.split(",") if p.strip()]
    reqs = []
    for i, d in enumerate(server.dialogues):
        policy = policies[i % len(policies)]
        kw = {"k": args.mpic_k} if policy == "mpic" else {}
        reqs.append(eng.submit(Request(prompt=d.prompt,
                                       max_new_tokens=args.max_new_tokens,
                                       policy=policy, policy_kwargs=kw,
                                       deadline_s=args.deadline_s)))
    return reqs


def main(argv: Optional[List[str]] = None) -> int:
    ap = build_parser()
    args = ap.parse_args(argv)
    if not [p for p in args.policy.split(",") if p.strip()]:
        ap.error("--policy needs at least one policy name")
    enable_compile_cache()
    if args.fleet > 0:
        # multi-process path: the supervisor owns the request wave and
        # never touches JAX itself (each host process holds its own chip)
        from repro.launch.fleet import run_fleet
        rep = run_fleet(hosts=args.fleet, requests=args.requests,
                        arch=args.arch, policy=args.policy,
                        max_new_tokens=args.max_new_tokens,
                        mpic_k=args.mpic_k, router=args.router,
                        deadline_s=args.deadline_s,
                        freeze_idle_s=args.freeze_idle_s,
                        smoke=args.smoke, layers=args.layers)
        return 1 if rep["failed"] else 0
    info = require_platform(expected_platform())
    server = build_server(args)
    eng, cfg, mesh = server.engine, server.cfg, server.mesh
    if server.peer_server is not None:
        print(f"serving KV blocks to peers at {server.peer_server.address}")
    submit_dialogues(server, args)
    done = eng.run()
    mesh_desc = "x".join(str(s) for s in mesh.devices.shape) if mesh \
        else "unsharded"
    print(f"\narch={cfg.name} layers={cfg.num_layers} "
          f"device={info['kind']}x{info['count']} policy={args.policy} "
          f"paged={args.paged} pipelined={args.pipelined} mesh={mesh_desc} "
          f"replicas={args.replicas}"
          + (f" router={args.router}" if args.replicas > 1 else ""))
    for r in done:
        rep = f" replica={r.replica}" if args.replicas > 1 else ""
        print(f"  {r.req_id}: ttft={r.ttft * 1e3:7.0f} ms  "
              f"reused={r.prefill_stats.get('n_reused', 0):4d}  "
              f"tokens={len(r.output_tokens)}{rep}")
    for r in eng.failed:
        print(f"  {r.req_id}: FAILED — {r.error}")
    for r in eng.expired:
        print(f"  {r.req_id}: DEADLINE — {r.error}")
    if server.faults is not None:
        print(f"  fault_plan: {server.faults.stats()}")
    for k, v in eng.report().items():
        print(f"  {k}: {v}")
    return 1 if (eng.failed or eng.expired
                 or len(done) < len(server.dialogues)) else 0


if __name__ == "__main__":
    sys.exit(main())
