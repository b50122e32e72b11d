"""The trace reduction, checked on a recorded excerpt of a chip trace.

``data/trace_excerpt.json`` holds 60 ms of a traced ``mistral7b.mrag-zipf``
window on a TPU v5e: the device's ``XLA Ops`` and ``XLA Modules`` events
(two executions of the paged decode program) and the harness's host spans.
Operation names are cut to their HLO name and, for Pallas calls, the
``tpu_custom_call`` target; that is all the reduction reads of them.
"""
import json

import numpy as np
import pytest

from conftest import DATA
from bench import trace as tr


@pytest.fixture(scope="module")
def events():
    rows = json.loads((DATA / "trace_excerpt.json").read_text())
    return [tr.Event(**r) for r in rows]


def _grid_busy(ops, t0, t1, step=100.0):
    """Busy time by brute force on a 100 ns grid."""
    n = int((t1 - t0) / step) + 1
    on = np.zeros(n, bool)
    for e in ops:
        a = int((e.start_ns - t0) / step)
        b = int(np.ceil((e.end_ns - t0) / step))
        on[max(a, 0):min(b, n)] = True
    return on.sum() * step * 1e-9


def test_busy_matches_brute_force(events):
    ops = tr.device_ops(events)
    assert len(ops) > 500
    t0 = min(e.start_ns for e in ops)
    t1 = max(e.end_ns for e in ops)
    assert tr.busy_s(ops) == pytest.approx(_grid_busy(ops, t0, t1),
                                           rel=2e-3)
    assert 0 < tr.busy_s(ops) <= (t1 - t0) * 1e-9


def test_kernel_calls_attributed_to_their_program(events):
    ops, mods = tr.device_ops(events), tr.modules(events)
    decode = [m for m in mods if "paged_decode" in m.name]
    assert len(decode) == 2
    secs, n = tr.kernel_time_s(ops, mods, "paged_decode")
    # by hand: custom calls that start inside a decode execution
    want = [e for e in ops if tr.KERNEL_MARK in e.name
            and any(m.start_ns <= e.start_ns < m.end_ns for m in decode)]
    assert n == len(want) and n % 16 == 0          # one call per layer
    assert secs == pytest.approx(sum(e.dur_ns for e in want) * 1e-9)
    assert tr.kernel_time_s(ops, mods, "jit__step_fn") == (0.0, 0)
    times = tr.module_times_s(mods, "paged_decode")
    assert times == pytest.approx([m.dur_ns * 1e-9 for m in decode])


def test_top_ops_and_idle_gaps_add_up(events):
    ops, mods = tr.device_ops(events), tr.modules(events)
    top = tr.top_ops(ops, mods, n=1000)
    assert sum(v for _, v in top) == pytest.approx(
        sum(e.dur_ns for e in ops) * 1e-9)
    assert all("/" in k for k, _ in top)
    assert top == sorted(top, key=lambda kv: -kv[1])
    t0 = min(e.start_ns for e in ops)
    t1 = max(e.end_ns for e in ops)
    gaps = tr.idle_gaps(ops, tr.host_spans(events), t0, t1, n=1000)
    idle = sum(v for _, v in gaps)
    assert idle == pytest.approx((t1 - t0) * 1e-9 - tr.busy_s(ops),
                                 rel=1e-6, abs=1e-9)
