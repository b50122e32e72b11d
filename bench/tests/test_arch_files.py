"""A configuration brings its own architecture as one file, named by its
``arch`` key: the plain reference, the work counts, ``PROGRAM`` fields
over the derived ``ModelConfig`` and ``STD`` weight scales.  The default
file keeps every count and every weight of the parent benchmark, to the
bit."""
import hashlib
import json
import time

import jax
import numpy as np
import pytest

from conftest import DATA, ROOT, make_root

# Counts of bench/flops.py as it was before it read the configuration file
# (computed from the program's ModelConfig), for both served configurations.
PINNED = {
    "llava-1.6-mistral-7b": {
        "head_params": 131072000,
        "token_flops": [6979584000, 7241728000, 6996099072, 7258243072,
                        7130578944, 7392722944, 7520387072, 7782531072],
        "paged_attn_call": [(16384, 20480), (45563904, 11440128),
                            (4194304, 1310720)],
        "selective_attn_call": [(16384, 20480), (378273792, 9764864),
                                (2151677952, 10485760)],
    },
    "llava-ov-qwen2-7b": {
        "head_params": 544997376,
        "token_flops": [6525618176, 7615612928, 6538262528, 7628257280,
                        6641223680, 7731218432, 6939670528, 8029665280],
        "paged_attn_call": [(14336, 16384), (39868416, 5738496),
                            (3670016, 753664)],
        "selective_attn_call": [(14336, 16384), (330989568, 5472256),
                                (1882718208, 8388608)],
    },
}
CTX = [(c, h) for c in (1, 64, 577, 2064) for h in (False, True)]
LENGTHS = [[1], [700, 33, 2048], [16] * 16]
SELECTIONS = [([0], 1), (list(range(64)) + list(range(640, 672)), 2000),
              (list(range(512)), 512)]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_work_counts_equal_the_parents(name):
    from bench import spec
    flops = spec.load_module(ROOT / spec.DEFAULT_ARCH)
    conf = spec.load_json(ROOT / "bench" / "configs" / f"{name}.json")
    want = PINNED[name]
    assert flops.head_params(conf) == want["head_params"]
    assert [flops.token_flops(conf, c, h) for c, h in CTX] == \
        want["token_flops"]
    assert [flops.paged_attn_call(conf, n) for n in LENGTHS] == \
        want["paged_attn_call"]
    assert [flops.selective_attn_call(conf, p, t) for p, t in SELECTIONS] \
        == want["selective_attn_call"]


COUNTER = '''

import collections as _collections

CALLS = _collections.Counter()


def _counted(name, f):
    def counted(*a, **kw):
        CALLS[name] += 1
        return f(*a, **kw)
    return counted
'''
WORK_FNS = ("head_params", "token_flops", "paged_attn_call",
            "selective_attn_call")


def _refuse(*_a, **_kw):
    raise AssertionError("the default architecture file was used")


def _arch_text() -> str:
    """Today's dense reference and work counts as one new file."""
    work = (ROOT / "bench/flops.py").read_text()
    return ((ROOT / "bench/reference.py").read_text()
            + work.replace("from __future__ import annotations\n", ""))


def test_architecture_from_files_alone(tmp_path, monkeypatch):
    """A tiny root whose configuration names its own architecture file, a
    new file with ``PROGRAM`` fields, ``STD`` scales, a reference and work
    counts, is served, checked and read through that file and through no
    other."""
    from bench import flops, harness, reference, spec
    root = make_root(tmp_path)
    arch = root / "bench" / "arch"
    arch.mkdir()
    (arch / "tiny.py").write_text(
        _arch_text() + COUNTER
        + "".join(f"{n} = _counted({n!r}, {n})\n" for n in WORK_FNS)
        + "Reference.logits = _counted('logits', Reference.logits)\n"
        + "PROGRAM = {'scan_layers': False}\n"
        + "STD = {'layers/mlp/w_gate': 0.02}\n")
    conf_path = root / "bench/configs/tiny.json"
    conf = json.loads(conf_path.read_text())
    conf["arch"] = "bench/arch/tiny.py"
    conf_path.write_text(json.dumps(conf))
    # the default cannot be reached: neither as a file nor as imports
    monkeypatch.setattr(spec, "DEFAULT_ARCH", "bench/none.py")
    for n in WORK_FNS:
        monkeypatch.setattr(flops, n, _refuse)
    monkeypatch.setattr(reference, "Reference", _refuse)
    # peaks for the work readers, as on the chip
    monkeypatch.setattr(harness, "device_info", lambda: dict(
        platform="tpu", kind="TPU v5 lite", count=1))
    built, fill = {}, harness.make_params

    def make_params(model, seed, std=None):
        built.update(cfg=model.cfg, std=std)
        return fill(model, seed, std)
    monkeypatch.setattr(harness, "make_params", make_params)

    cell = spec.load_cell("tiny.mrag", root)
    out = harness.run_cell(cell, 2 ** 33 + 9, 3.0, True, time.perf_counter())
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["mfu"]["value"] > 0
    mod = spec.arch(cell)
    assert mod.__file__ == str(arch / "tiny.py")
    assert built["cfg"].scan_layers is False
    assert built["std"] == {"layers/mlp/w_gate": 0.02}
    for n in WORK_FNS:              # mfu, mfu.prefill and both rooflines
        assert mod.CALLS[n] > 0, n
    assert mod.CALLS["logits"] > 0


@pytest.mark.parametrize("program, named", [
    ({"num_expert": 8}, "num_expert"),           # no such field
    ({"num_layers": 3}, "num_layers"),           # widths and depth
    ({"d_ff": 1024}, "d_ff"),
    ({"vocab_size": 4096}, "vocab_size"),
    ({"num_kv_heads": 2}, "num_kv_heads"),
    ({"param_dtype": "float8_e4m3fn"}, "param_dtype"),   # precision
    ({"media_token_len": 8}, "media_token_len"),
])
def test_configuration_files_refused(tmp_path, program, named):
    """A ``PROGRAM`` field that names no ``ModelConfig`` field, or one that
    the configuration file sets, is refused by name; so is an architecture
    file outside bench/."""
    from bench import spec
    conf = spec.load_json(DATA / "tiny.json")
    with pytest.raises(ValueError, match=named):
        spec.model_config(conf, program)
    assert spec.model_config(conf, {"scan_layers": False}).arch_type == "vlm"
    assert spec.model_config(conf, {"arch_type": "moe"}).arch_type == "moe"
    root = make_root(tmp_path)
    conf_path = root / "bench/configs/tiny.json"
    conf["arch"] = "src/repro/configs/base.py"
    conf_path.write_text(json.dumps(conf))
    with pytest.raises(ValueError, match="arch 'src/repro/configs/base.py'"):
        spec.arch(spec.load_cell("tiny.mrag", root))


# sha256 over (leaf name, bytes) of the tiny configuration's weights from
# seed 2**33 + 5, as the parent benchmark filled them
PARENT_WEIGHTS = \
    "e6ca026a9d14924ef8085a0e5f7c529e02fbdd563f9bac0b39a792f2f0d2bcf6"


def _leaves(params):
    from bench.weights import _leaf_name
    return {_leaf_name(p): np.asarray(v) for p, v in
            jax.tree_util.tree_flatten_with_path(params)[0]}


def test_weights_keep_the_parents_and_take_a_named_std():
    from bench import spec
    from bench.weights import make_params
    from repro.models import build_model
    conf = spec.load_json(DATA / "tiny.json")
    model = build_model(spec.model_config(conf))
    seed = 2 ** 33 + 5
    base = _leaves(make_params(model, seed))
    h = hashlib.sha256()
    for name, v in base.items():
        h.update(name.encode())
        h.update(v.tobytes())
    assert h.hexdigest() == PARENT_WEIGHTS
    named = "layers/mlp/w_gate"
    got = _leaves(make_params(model, seed, {named: 0.125}))
    for name, v in base.items():
        if name != named:
            assert np.array_equal(got[name], v), name
    std = float(np.std(got[named].astype(np.float32)))
    assert abs(std - 0.125) < 0.01, std
    with pytest.raises(ValueError, match="no parameter: layers/mlp/w_gat;"):
        make_params(model, seed, {"layers/mlp/w_gat": 1.0})
