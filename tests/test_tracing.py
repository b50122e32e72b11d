"""Program spans: ``MPICEngine.step()`` phases in the JAX profiler's trace.

MRAG-shaped requests (a system prompt, three library images in varying
order, a question) are served through a smoke-size paged ``mpic`` engine,
once without the profiler and once under ``jax.profiler.trace``; the
``.xplane.pb`` it writes is read back with ``ProfileData``.
"""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.core import Prompt, media_segment, text_segment
from repro.data import image_embeds
from repro.models import build_model
from repro.serving import EngineConfig, MPICEngine, Request
from repro.tracing import PREFIX

IMAGES = ("I0", "I1", "I2", "I3")
IMG_LEN = 16
ADMIT = ["mpic.link.gather", "mpic.link.pack", "mpic.link.upload",
         "mpic.link.write", "mpic.prefill.pack", "mpic.prefill.dispatch",
         "mpic.prefill.fetch", "mpic.sample"]
DECODE = ["mpic.decode.operands", "mpic.decode.dispatch",
          "mpic.decode.fetch", "mpic.decode.sample"]
NAMES = {"mpic.step", "mpic.admit", "mpic.decode"} | set(ADMIT) | set(DECODE)
OUTER = "test.serve"        # the test's own span around the serving loop


def _prompts(cfg):
    out = []
    for i, order in enumerate([(0, 1, 2), (2, 0, 3), (3, 1, 0)]):
        r = np.random.default_rng(i)
        segs = [text_segment(r.integers(8, 200, 6), kind="system")]
        for j in order:
            segs.append(media_segment(
                IMAGES[j], image_embeds(IMAGES[j], IMG_LEN, cfg.d_model)))
        segs.append(text_segment(r.integers(8, 200, 5 + i)))
        out.append(Prompt(segs, user_id="u"))
    return out


def _serve(eng, prompts):
    reqs = [eng.submit(Request(prompt=p, max_new_tokens=3, policy="mpic",
                               policy_kwargs={"k": 4})) for p in prompts]
    with jax.profiler.TraceAnnotation(OUTER):
        while eng.has_work:
            eng.step()
    assert all(r.state.value == "done" for r in reqs)
    return reqs


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Tokens of an unprofiled and a profiled pass over the same requests
    (the first pass also compiles every shape), the profiled pass's
    requests, and its host events: (line index, name, start, end, stats)."""
    cfg = get_smoke_config("llava-1.6-7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = MPICEngine(model, params,
                     EngineConfig(max_seq_len=128, decode_slots=4,
                                  page_size=8))
    for mid in IMAGES:
        eng.upload("u", mid, image_embeds(mid, IMG_LEN, cfg.d_model))
    prompts = _prompts(cfg)
    plain = [r.output_tokens for r in _serve(eng, prompts)]
    trace_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(trace_dir)):
        traced = _serve(eng, prompts)
    path, = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith((PREFIX, OUTER)):
                    events.append((li, e.name, e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   dict(e.stats)))
    return plain, traced, events


def _inside(events, outer):
    _, _, s, t, _ = outer
    return sorted((e for e in events if s <= e[2] and e[3] <= t
                   and e is not outer), key=lambda e: e[2])


def _named(events, name):
    return [e for e in events if e[1] == name]


def test_profiler_leaves_tokens_unchanged(served):
    plain, traced, _ = served
    assert [r.output_tokens for r in traced] == plain
    assert all(len(t) == 3 for t in plain)


def test_span_names_are_the_documented_ones(served):
    _, _, events = served
    names = {e[1] for e in events if e[1] != OUTER}
    assert names == NAMES


def test_spans_sit_on_the_calling_thread(served):
    _, _, events = served
    outer, = _named(events, OUTER)
    assert {e[0] for e in events} == {outer[0]}
    assert all(outer[2] <= e[2] and e[3] <= outer[3] for e in events)


def test_admit_holds_link_prefill_and_sample_in_order(served):
    _, traced, events = served
    admits = _named(events, "mpic.admit")
    assert sorted(a[4]["req"] for a in admits) == sorted(
        r.req_id for r in traced)
    for a in admits:
        kids = [e[1] for e in _inside(events, a)]
        assert set(kids) == set(ADMIT), kids
        rank = [ADMIT.index(k) for k in kids]
        # link, then prefill, then the first token's sampling
        assert rank == sorted(rank), kids


def test_decode_holds_its_four_children(served):
    _, _, events = served
    decodes = _named(events, "mpic.decode")
    # three requests of three tokens: the first comes from the prefill
    assert len(decodes) >= 2
    for d in decodes:
        assert [e[1] for e in _inside(events, d)] == DECODE


def test_step_marker_numbers_every_step_and_holds_its_phases(served):
    _, _, events = served
    steps = _named(events, "mpic.step")
    nums = [s[4]["step_num"] for s in sorted(steps, key=lambda e: e[2])]
    assert nums == list(range(nums[0], nums[0] + len(nums)))
    for e in _named(events, "mpic.admit") + _named(events, "mpic.decode"):
        assert sum(s[2] <= e[2] and e[3] <= s[3] for s in steps) == 1
