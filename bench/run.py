"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, metrics and limits come from
``BENCHMARK.json`` and the files it names.  A run needs the chips its cell
asks for: on a machine without them it exits non-zero and prints no
result.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics from a profiled part of the window.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path


def _process_start() -> float:
    """perf_counter value at which this process started (Linux), so that
    set-up counts interpreter start and imports too."""
    now = time.perf_counter()
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        start = int(Path("/proc/self/stat").read_text()
                    .rsplit(")", 1)[1].split()[19]) / ticks
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return now - max(0.0, uptime - start)
    except (OSError, ValueError, IndexError):
        return now


T_PROC0 = _process_start()
ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import spec
    cell = spec.load_cell(args.workload)
    import jax
    from bench import harness
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        harness.log(f"refused: cell {cell.name} needs {cell.chips} TPU "
                    f"chip(s); JAX found {len(devs)} {devs[0].platform} "
                    f"device(s) ({devs[0].device_kind})")
        return 3
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_PROC0)
    print(json.dumps(out), flush=True)
    for name, c in out["checks"].items():       # the compared numbers last
        harness.log(f"check {name} {c['value']} limit {c['limit']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
