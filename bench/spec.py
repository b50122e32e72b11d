"""What a cell is: its entry in ``BENCHMARK.json`` and the files it names.

Everything here is data found by name: a configuration file holds the
model's published sizes under their published keys, a traffic file the
parameters of the one generator, a limits file the correctness limit of a
cell.  Adding a cell adds files and entries; no code changes.

A configuration brings its own architecture as one optional key of its
file, ``arch``: the path of a ``.py`` file under ``bench/`` that exports
the block's plain reference, its work counts and the program fields and
weight scales it needs (``bench/arch/dense_gqa.py`` describes the
interface and is the default).
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import re
import sys
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
    root: Path = ROOT            # the checkout the files were read from


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
        root=root,
    )


@functools.lru_cache(maxsize=None)
def load_module(path: Path):
    """The module in the file ``path``, executed once per process."""
    name = "bench_file_" + re.sub(r"\W", "_", str(path))
    sp = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(sp)
    sys.modules[name] = mod         # dataclasses look their module up
    sp.loader.exec_module(mod)
    return mod


# the architecture file of a configuration that names none
DEFAULT_ARCH = "bench/arch/dense_gqa.py"


def arch(cell: Cell):
    """The architecture module the cell's configuration names under
    ``arch``: a ``.py`` file under ``bench/`` of the cell's checkout, or the
    dense GQA block."""
    rel = cell.config.get("arch")
    if rel is None:
        return load_module(ROOT / DEFAULT_ARCH)
    path = (cell.root / rel).resolve()
    if path.suffix != ".py" or (cell.root / "bench").resolve() \
            not in path.parents:
        raise ValueError(f"configuration {cell.config['name']}: arch "
                         f"{rel!r} is not a .py file under bench/")
    return load_module(path)


def model_config(conf: dict, program: Optional[dict] = None):
    """A ``ModelConfig`` from a configuration file's published keys, plus
    the ``program`` fields of its architecture file (its ``PROGRAM``),
    which may set ``arch_type`` and fields this leaves at their
    defaults, and no other."""
    from repro.configs.base import ModelConfig
    heads = conf["num_attention_heads"]
    window = conf.get("sliding_window") or 0
    if not conf.get("use_sliding_window", window > 0):
        window = 0
    derived = dict(
        name=conf["name"],
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
    # the kind of block is the architecture file's to choose
    program = {"arch_type": "vlm", **(program or {})}
    unknown = sorted(set(program) - {f.name for f in
                                     dataclasses.fields(ModelConfig)})
    if unknown:
        raise ValueError(f"configuration {conf['name']}: PROGRAM names no "
                         f"ModelConfig field: {', '.join(unknown)}")
    taken = sorted(set(program) & set(derived))
    if taken:
        raise ValueError(f"configuration {conf['name']}: PROGRAM may not "
                         f"set {', '.join(taken)}, which the configuration "
                         f"file sets")
    return ModelConfig(**derived, **program)
