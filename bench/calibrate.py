"""Readings that a cell's correctness limit is set from.

For each seed: set the cell up, serve a short window at the cell's own
load, then read the widest logit gap of the program's served tokens
against the float32 reference (the lower reading).  For the control
seeds, also put the reference computed in float8 in the program's place:
at each position of the same prompts and served tokens, take the token the
float8 reference puts first and read its gap in the float32 reference (the
upper reading).  Both readings pass through ``check.compare``, so each
row also says whether the cell's limit holds for it (the control's must
not).  All seeds run in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 10 --out <file.jsonl>
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def readings(cell, seed: int, seconds: float, control: bool,
             counter=None) -> dict:
    """Serve one short window from ``seed``; return the program's widest
    gap and, with ``control``, the float8 reference's, each with the
    verdict ``check.compare`` gives it."""
    import jax
    from bench import check, harness

    counter = counter or harness.CompileCounter()
    srv = harness.Server(cell, seed, seconds)
    srv.warm_up()
    run = harness.Run(cell, seed, seconds, False)
    harness.serve_window(srv, run, counter)
    finished = [r for r in run.recs if r.req.state.value == "done"]
    params = srv.params
    srv.eng.loader.close()
    del srv
    gc.collect()
    res = check.compare(cell, params, finished, seed, control=control)
    del params
    gc.collect()
    jax.clear_caches()
    num = res["numbers"]
    out = {"seed": seed, "requests": res["requests"],
           "tokens": num["tokens_compared"]["value"],
           "program_max_gap": num["max_logit_gap"]["value"],
           "program_tokens_off_argmax": res["off_argmax"],
           "program_correct": res["correct"]}
    if control:
        c = res["control"]
        out["control_max_gap"] = c["numbers"]["max_logit_gap"]["value"]
        out["control_tokens_off_argmax"] = c["off_argmax"]
        out["control_correct"] = c["correct"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import jax
    from bench import harness, spec
    cell = spec.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        harness.log("refused: no TPU")
        return 3
    harness.enable_compile_cache()
    counter = harness.CompileCounter()
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for s in [int(x) for x in args.seeds.split(",")]:
        row = readings(cell, s, args.seconds, s in ctrl, counter)
        rows.append(row)
        harness.log(json.dumps(row))
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            Path(args.out).write_text("\n".join(json.dumps(r) for r in rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
