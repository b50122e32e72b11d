"""Mesh-sharded serving benchmark: 1 vs 4 (simulated) devices.

Measures steady-state decode step latency and TTFT of the SAME engine
config twice — unsharded on 1 device, and tensor-parallel on a forced-host
4-device ``1x4`` mesh (params TP-sharded, KV pool head-sharded, donated
jits with explicit shardings).  Each leg runs in a SUBPROCESS because the
jax device count locks at backend init.

Parity is asserted INSIDE the 4-device leg: a sharded and an unsharded
engine in the same process, over shared library entries, must produce
token-identical greedy rollouts (the same invariant as
``tests/_sharded_worker.py``).  Tokens are NOT compared across processes:
forcing a different host device count changes XLA-CPU's intra-op thread
partitioning, which alone perturbs low bits and flips near-tie argmaxes on
a random-init model — that is measurement noise, not a sharding defect.

On a CPU container the 4 "devices" are threads of one chip, so the
partitioned step is NOT expected to be faster — the artifact is the parity
proof plus the measured partitioning overhead; on real hardware the same
code splits the pool bytes/step by the mesh size.  Emits
``BENCH_sharded.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

SMOKE = os.environ.get("MPIC_BENCH_SMOKE", "") == "1"
N_REQ = 2 if SMOKE else 6
NEW_TOK = 4 if SMOKE else 8
STEADY_STEPS = 6 if SMOKE else 24


def _worker(devices: int, sharded: bool) -> None:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import numpy as np

    from repro.cache import KVLibrary
    from repro.configs.base import ModelConfig
    from repro.core import Prompt, media_segment, text_segment
    from repro.data import image_embeds
    from repro.launch.mesh import make_serving_mesh
    from repro.models import build_model
    from repro.serving import EngineConfig, MPICEngine, Request

    assert len(jax.devices()) == devices
    cfg = ModelConfig(name="bench-sharded-vlm", arch_type="vlm",
                      num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=4, head_dim=16, d_ff=128,
                      vocab_size=256, is_multimodal=True,
                      media_token_len=16, param_dtype="float32",
                      compute_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_seq_len=256, decode_slots=4, page_size=16)
    static = KVLibrary()

    def make_engine(mesh):
        return MPICEngine(model, params, ecfg, static_library=static,
                          mesh=mesh)

    def prompt(seed):
        r = np.random.default_rng(seed)
        return Prompt([text_segment(r.integers(8, 200, 6)),
                       media_segment("A", image_embeds("A", 16,
                                                       cfg.d_model)),
                       text_segment(r.integers(8, 200, 5)),
                       media_segment("B", image_embeds("B", 16,
                                                       cfg.d_model))],
                      user_id="u1")

    def run_batch(eng, seed0):
        reqs = [eng.submit(Request(prompt=prompt(seed0 + i),
                                   max_new_tokens=NEW_TOK, policy="mpic",
                                   policy_kwargs={"k": 4}))
                for i in range(N_REQ)]
        eng.run()
        return reqs

    mesh = make_serving_mesh() if sharded else None
    eng = make_engine(mesh)
    for mid in ("A", "B"):
        eng.upload("u1", mid, image_embeds(mid, 16, cfg.d_model))

    parity = "n/a"
    if sharded:
        # in-process parity: an unsharded engine over the SAME library
        # entries must reproduce the sharded greedy rollout exactly
        base = make_engine(None)
        got = run_batch(eng, 0)
        want = run_batch(base, 0)
        for a, b in zip(got, want):
            assert a.output_tokens == b.output_tokens, (
                f"sharded rollout diverged: {a.output_tokens} vs "
                f"{b.output_tokens}")
        parity = "token-identical"

    # TTFT over the request stream (jit-warm: measure the second batch)
    run_batch(eng, 100)
    reqs = run_batch(eng, 200)
    ttfts = [r.ttft for r in reqs]

    # steady-state decode: fill every slot, then time pure decode steps
    long_reqs = [eng.submit(Request(prompt=prompt(500 + i),
                                    max_new_tokens=STEADY_STEPS + 8,
                                    policy="mpic", policy_kwargs={"k": 4}))
                 for i in range(ecfg.decode_slots)]
    while any(s is None for s in eng.running):
        eng.step()
    eng.step()                                    # warm the decode bucket
    t0 = time.perf_counter()
    for _ in range(STEADY_STEPS):
        eng.step()
    dt = (time.perf_counter() - t0) / STEADY_STEPS
    eng.run()
    assert all(r.done for r in long_reqs)

    print("RESULT " + json.dumps({
        "devices": devices, "sharded": sharded, "parity": parity,
        "mean_ttft_ms": 1e3 * sum(ttfts) / len(ttfts),
        "decode_step_us": 1e6 * dt,
    }), flush=True)


def main() -> None:
    legs = []
    for devices, sharded in ((1, False), (4, True)):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        cmd = [sys.executable, "-m", "benchmarks.fig_sharded_serving",
               "--worker", "--devices", str(devices)]
        if sharded:
            cmd.append("--sharded")
        p = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           timeout=900,
                           cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))))
        assert p.returncode == 0, (
            f"worker devices={devices} failed\n{p.stdout[-2000:]}\n"
            f"{p.stderr[-2000:]}")
        line = [ln for ln in p.stdout.splitlines()
                if ln.startswith("RESULT ")][-1]
        legs.append(json.loads(line[len("RESULT "):]))

    base, shrd = legs
    assert shrd["parity"] == "token-identical"
    ratio = base["decode_step_us"] / max(shrd["decode_step_us"], 1e-9)
    out = {
        "config": {"requests": N_REQ, "new_tokens": NEW_TOK,
                   "steady_steps": STEADY_STEPS, "smoke": SMOKE},
        "unsharded_1dev": base, "sharded_1x4": shrd,
        "decode_step_ratio_1dev_over_4dev": ratio,
    }
    for leg, name in ((base, "sharded_serving_1dev"),
                      (shrd, "sharded_serving_4dev")):
        print(f"{name},{leg['decode_step_us']:.0f},"
              f"ttft_ms={leg['mean_ttft_ms']:.1f}")
    print(f"decode step 1dev/4dev ratio: {ratio:.2f} "
          f"(CPU emulation — parity is the claim, not speedup)")
    with open("BENCH_sharded.json", "w") as f:
        json.dump(out, f, indent=1)
    print("wrote BENCH_sharded.json")


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--sharded", action="store_true")
    a = ap.parse_args()
    if a.worker:
        _worker(a.devices, a.sharded)
    else:
        main()
