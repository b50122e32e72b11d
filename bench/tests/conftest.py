"""Benchmark tests run on the CPU at a tiny size (Pallas interpreted)."""
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
TINY_LIMIT = {"max_logit_gap": {"limit": 0.1}, "min_tokens_compared": 10}


def make_root(tmp: Path, traffic: str = "tiny-mrag", cell: str = "tiny.mrag",
              limit: dict = None) -> Path:
    """A checkout-shaped directory holding one tiny cell: BENCHMARK.json,
    its configuration, traffic and limits files (found by name)."""
    for d in ("configs", "traffic", "limits"):
        (tmp / "bench" / d).mkdir(parents=True, exist_ok=True)
    shutil.copy(DATA / "tiny.json", tmp / "bench/configs/tiny.json")
    src = DATA / f"{traffic}.json"
    if src.exists():
        shutil.copy(src, tmp / f"bench/traffic/{traffic}.json")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "tiny",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "CPU rehearsal"}]
    spec["workloads"] = [{"name": cell, "config": "tiny", "traffic": traffic,
                          "chips": 1, "why": "CPU rehearsal"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp / f"bench/limits/{cell}.json").write_text(
        json.dumps(limit or TINY_LIMIT))
    return tmp


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
