"""Find the knee of a cell's traffic: the highest offered rate that builds
no growing backlog.  One process: set-up once, then a short open-loop
window at each rate, draining the engine between rates.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 15 \
        --rates 2,4,6,8

Prints one JSON object per rate (stderr and ``--out``).  Used once, by
hand, to fix the rate written into a traffic file.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    from bench import generator, harness, readers, spec
    from bench.stats import pct
    import jax
    cell = spec.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        harness.log("refused: no TPU")
        return 3
    counter = harness.CompileCounter()
    harness.enable_compile_cache()
    srv = harness.Server(cell, args.seed, args.seconds)
    srv.warm_up()
    harness.log(f"set-up {time.perf_counter() - t0:.1f} s")
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        c = copy.deepcopy(cell)
        c.traffic["arrivals"]["rate_per_s"] = rate
        c.traffic["after_window"] = "stop"
        srv.cell = c
        srv.specs = generator.schedule(c.traffic, args.seed, args.seconds,
                                       srv.cfg.vocab_size)
        run = harness.Run(c, args.seed, args.seconds, False)
        harness.serve_window(srv, run, counter)
        eng = srv.eng
        queued = len(eng.waiting)
        w = run.window
        half = [r for r in w if r.spec.due_s >= args.seconds / 2]
        row = {
            "rate": rate, "due": len(w),
            "first_tokens": sum(1 for r in w if r.stamps),
            "queued_at_end": queued,
            "ttft_p50_ms": pct(readers.ttfts_s(run), 50),
            "ttft_p95_ms": pct(readers.ttfts_s(run), 95),
            "ttft_p50_ms_2nd_half": pct([r.ttft for r in half
                                         if r.ttft is not None], 50),
            "itl_p95_ms": pct(readers.itl_gaps_s(run), 95),
            "output_tok_per_s": readers.tokens_in_window(run) / args.seconds,
            "compiles_in_window": run.compiles_in_window,
        }
        for k in ("ttft_p50_ms", "ttft_p95_ms", "ttft_p50_ms_2nd_half",
                  "itl_p95_ms"):
            if row[k] is not None:
                row[k] *= 1e3
        rows.append(row)
        harness.log(json.dumps(row))
        # drain before the next rate
        for r in list(eng.waiting):
            eng.scheduler.discard(r)
        while eng.has_work:
            eng.step()
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(json.dumps(r) for r in rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
