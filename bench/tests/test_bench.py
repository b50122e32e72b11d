"""CPU rehearsal of the benchmark: the harness loop at a tiny size, the
refusal of a CPU by a cell run, cells found by name, and the correctness
check failing its control and a planted fault."""
import json
import os
import subprocess
import sys

import numpy as np

from conftest import DATA, ROOT, make_root


def test_cell_run_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mistral7b.mrag-zipf",
         "--seed", str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "refused" in p.stderr
    assert p.stdout.strip() == ""


def test_every_cell_resolves_by_name():
    from bench import harness, spec
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert spec.model_config(cell.config).num_layers > 0
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e and m["moves"] in names, m["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert harness.reader_path(m["name"]).exists(), m["name"]


def test_traffic_file_is_found_by_name(tmp_path):
    from bench import generator, spec
    root = make_root(tmp_path, traffic="new-mix", cell="tiny.new")
    mix = json.loads((DATA / "tiny-mrag.json").read_text())
    mix["arrivals"]["rate_per_s"] = 3.0
    (root / "bench/traffic/new-mix.json").write_text(json.dumps(mix))
    cell = spec.load_cell("tiny.new", root)
    assert cell.traffic["arrivals"]["rate_per_s"] == 3.0
    a = generator.schedule(cell.traffic, 5, 10.0, 512)
    b = generator.schedule(cell.traffic, 6, 10.0, 512)
    # every seed serves the same sizes, in another order
    assert sorted(s.max_new for s in a) == sorted(s.max_new for s in b)
    assert sorted(s.text_len for s in a) == sorted(s.text_len for s in b)
    assert [s.max_new for s in a] != [s.max_new for s in b]


def _run(root, traced=False, seed=2 ** 33 + 7, seconds=3.0):
    from bench import harness, spec
    import time
    cell = spec.load_cell("tiny.mrag", root)
    return harness.run_cell(cell, seed, seconds, traced, time.perf_counter())


def test_harness_loop_tiny(tiny_root):
    out = _run(tiny_root)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    m = out["metrics"]
    for name in ("ttft_p50_ms", "itl_p95_ms", "setup_s",
                 "output_tok_per_s"):
        assert m[name]["value"] > 0, name
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"
    traced = _run(tiny_root, traced=True, seed=11)
    assert traced["correct"] is True
    pl = traced["metrics"]
    assert pl["compiles_in_window"]["value"] == 0
    assert 0 < pl["recomputed_token_share"]["value"] < 100
    assert "window_s" in traced["device"]


def test_control_fails_and_program_passes(tiny_root):
    """The float8 reference in the program's place fails the tiny cell's
    limit on each seed; the program passes it."""
    from bench import calibrate, spec
    cell = spec.load_cell("tiny.mrag", tiny_root)
    lim = cell.limits["max_logit_gap"]["limit"]
    for seed in (1, 2, 3):
        r = calibrate.readings(cell, seed, 3.0, control=True)
        assert r["program_max_gap"] <= lim, r
        assert r["control_max_gap"] > lim, r
        assert r["program_correct"] is True, r
        assert r["control_correct"] is False, r


def test_altered_token_fails(tiny_root, monkeypatch):
    """A token altered where it is produced makes ``correct`` false."""
    from repro.serving.engine import MPICEngine
    real = MPICEngine._select_token

    def altered(self, req, row):
        tok = real(self, req, row)
        if len(req.output_tokens) == 2:
            return int(np.argmin(row))
        return tok

    monkeypatch.setattr(MPICEngine, "_select_token", altered)
    out = _run(tiny_root)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > \
        out["checks"]["max_logit_gap"]["limit"]


def test_mix_classes_and_gamma_arrivals_are_data():
    """Bursty arrivals and a mix of request classes need only a traffic
    file: the gamma gaps keep the mean rate, and each class keeps its own
    lengths."""
    from bench import generator
    mix = json.loads((DATA / "tiny-mrag.json").read_text())
    mix["arrivals"] = {"process": "gamma", "cv": 3.0, "rate_per_s": 40.0}
    mix["classes"] = [
        {"weight": 3},
        {"weight": 1, "images_per_request": 0, "system_tokens": 0,
         "question_tokens": {"dist": "uniform", "lo": 300, "hi": 400}}]
    specs = generator.schedule(mix, 9, 200.0, 512)
    long = [s for s in specs if not s.images]
    assert 0.15 < len(long) / len(specs) < 0.35
    assert all(300 <= s.text_len <= 400 and s.system_len == 0 for s in long)
    assert all(len(s.images) == mix["images_per_request"]
               for s in specs if s.images)
    gaps = np.diff([s.due_s for s in specs])
    assert abs(len(specs) / specs[-1].due_s - 40.0) < 8.0
    assert gaps.std() / gaps.mean() > 2.0
    assert generator.max_lengths(mix, 32)[0] == 400


def test_split_metric_reads_its_base_reader():
    from bench import harness
    assert harness.reader_path("prefill_ms_p50.sat").name == \
        "prefill_ms_p50.py"
    assert harness.reader_path("mfu.prefill").name == "mfu.prefill.py"


def test_text_only_cell_tiny(tmp_path):
    """The chat-text shape: unshared text prompts, no library image, so
    the link is bypassed and every prompt token is recomputed."""
    from bench import harness, spec
    import time
    root = make_root(tmp_path, traffic="tiny-text", cell="tiny.text")
    cell = spec.load_cell("tiny.text", root)
    out = harness.run_cell(cell, 2 ** 33 + 3, 3.0, False, time.perf_counter())
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    for name in ("ttft_p50_ms", "itl_p95_ms", "setup_s"):
        assert out["metrics"][name]["value"] > 0, name
