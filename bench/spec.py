"""What a cell is: its entry in ``BENCHMARK.json`` and the files it names.

Everything here is data found by name: a configuration file holds the
model's published sizes under their published keys, a traffic file the
parameters of the one generator, a limits file the correctness limit of a
cell.  Adding a cell adds files and entries; no code changes.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
WORK = BENCH / ".work"          # run-time output inside the checkout


@dataclasses.dataclass
class Cell:
    name: str
    config: dict                 # the configuration file, as written
    traffic: dict                # the traffic file, as written
    chips: int
    end_to_end: list             # metric entries this cell reports
    per_layer: list
    limits: dict                 # bench/limits/<cell>.json


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Optional[Path] = None) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` and its files (root:
    the checkout)."""
    root = Path(root) if root else ROOT
    bench = root / "bench"
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    return Cell(
        name=name,
        config=load_json(root / cfg_entry["file"]),
        traffic=load_json(bench / "traffic" / f"{w['traffic']}.json"),
        chips=int(w["chips"]),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        limits=load_json(bench / "limits" / f"{name}.json"),
    )


def model_config(conf: dict):
    """A ``ModelConfig`` from a configuration file's published keys."""
    from repro.configs.base import ModelConfig
    heads = conf["num_attention_heads"]
    window = conf.get("sliding_window") or 0
    if not conf.get("use_sliding_window", window > 0):
        window = 0
    return ModelConfig(
        name=conf["name"],
        arch_type="vlm",
        source=conf["source"],
        num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        num_heads=heads,
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or conf["hidden_size"] // heads,
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        qkv_bias=bool(conf.get("qkv_bias", False)),
        rope_theta=float(conf["rope_theta"]),
        rms_norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        sliding_window=int(window),
        max_position_embeddings=conf["max_position_embeddings"],
        is_multimodal=True,
        media_token_len=conf["assumed"]["image_tokens"],
        param_dtype=conf["torch_dtype"],
        compute_dtype=conf["torch_dtype"],
    )
