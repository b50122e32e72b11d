"""One run of one cell: set-up, an open-loop window, metrics, correctness.

The window drives the program as ``repro.launch.serve`` does: requests go
in through ``MPICEngine.submit`` when they fall due, and the harness calls
``MPICEngine.step`` in between.  The engine keeps no per-token times, so
after each step the harness stamps every request whose output grew.  Time
to first token runs from a request's due time.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import generator, spec, trace as tr
from bench.peaks import peaks
from bench.weights import image_embeds, make_params

USER = "bench"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
clock = time.perf_counter


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Rec:
    """One request of the run, from the harness's side."""
    spec: generator.Spec
    req: object                   # repro.serving.Request
    due: float                    # absolute due time (harness clock)
    submitted: float
    selected: List[int]           # positions the mpic rule recomputes
    total: int                    # prompt tokens
    stamps: List[float] = dataclasses.field(default_factory=list)

    @property
    def ttft(self) -> Optional[float]:
        return self.stamps[0] - self.due if self.stamps else None


@dataclasses.dataclass
class StepRec:
    decode_ctx: List[int]         # context length of each decode token
    prefills: List[Rec]           # requests admitted in this step
    start: float = 0.0            # harness clock around ``eng.step()``
    end: float = 0.0
    after_idle: bool = False      # the loop slept since the last step


class GcPauses:
    """Collector pauses of the process (seconds, by start time)."""

    def __init__(self):
        self.pauses: List[tuple] = []
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = (clock(), info["generation"])
        elif self._t0 is not None:
            t, gen = self._t0
            self.pauses.append((t, clock() - t, gen))
            self._t0 = None

    def close(self):
        gc.callbacks.remove(self._on)


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name in COMPILE_EVENTS:
            self.n += 1


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout, or where
    ``JAX_COMPILATION_CACHE_DIR`` says."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        spec.WORK / "jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def selected_positions(s: generator.Spec, image_tokens: int, k: int):
    """Positions the ``mpic`` rule recomputes: all text and the first k
    tokens of each image (``core/select.py`` states the rule)."""
    pos, off = list(range(s.system_len)), s.system_len
    for _ in s.images:
        pos += range(off, off + min(k, image_tokens))
        off += image_tokens
    pos += range(off, off + s.text_len)
    return pos


def reader_path(name: str, bench=None):
    """The reader file of metric ``name``: ``metrics/<name>.py``, or, for a
    quantity split by the end-to-end metric it moves
    (``prefill_ms_p50.sat``), the file of the name before the last dot."""
    d = (bench or spec.BENCH) / "metrics"
    path = d / f"{name}.py"
    if not path.exists() and "." in name:
        path = d / f"{name.rsplit('.', 1)[0]}.py"
    return path


def load_reader(name: str):
    return spec.load_module(reader_path(name)).read


class Run:
    """Everything a metric reader may read about one run."""

    def __init__(self, cell: spec.Cell, seed: int, seconds: float,
                 traced: bool):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.traced = traced
        self.conf = cell.config       # the configuration file, as written
        self.arch = spec.arch(cell)   # its architecture file's module
        self.recs: List[Rec] = []
        self.steps: List[StepRec] = []
        self.traced_steps: List[StepRec] = []
        self.trace = None             # TraceView of the traced window
        self.lib_delta: Dict[str, int] = {}
        self.compiles_in_window = 0
        self.failures: List[str] = []

    # -- views the readers use ------------------------------------------
    @property
    def window(self) -> List[Rec]:
        return [r for r in self.recs if 0.0 <= r.spec.due_s < self.seconds]

    def in_window(self, t: float) -> bool:
        return self.t_w0 <= t <= self.t_w0 + self.seconds


@dataclasses.dataclass
class TraceView:
    ops: list
    mods: list
    spans: list
    busy_s: float
    window_s: float
    steps: List[StepRec]


def build(cell: spec.Cell, seed: int):
    """Model, the benchmark's weights and the engine, as served."""
    from repro.cache.library import KVLibrary
    from repro.models import build_model
    from repro.serving import EngineConfig, MPICEngine

    arch = spec.arch(cell)
    cfg = spec.model_config(cell.config, getattr(arch, "PROGRAM", None))
    model = build_model(cfg)
    params = make_params(model, seed, getattr(arch, "STD", None))
    jax.block_until_ready(params)
    serving = cell.config["serving"]
    ps = int(serving["page_size"])
    p_max, o_max = generator.max_lengths(cell.traffic, cfg.media_token_len)
    max_seq = -(-(p_max + o_max + 2) // ps) * ps
    ecfg = EngineConfig(max_seq_len=max_seq,
                        decode_slots=int(serving["decode_slots"]),
                        page_size=ps, greedy=True)
    spool = spec.WORK / "spool"
    eng = MPICEngine(
        model, params, ecfg,
        static_library=KVLibrary(spool_dir=str(spool / "static")),
        dynamic_library=KVLibrary(shared=True,
                                  spool_dir=str(spool / "dynamic")))
    return cfg, params, eng


class Server:
    """The engine plus the harness's bookkeeping around it."""

    def __init__(self, cell, seed, seconds):
        self.cell, self.seed = cell, seed
        self.cfg, self.params, self.eng = build(cell, seed)
        cfg = self.cfg
        self.k = int(cell.traffic["mpic_k"])
        self.specs = generator.schedule(cell.traffic, seed, seconds,
                                        cfg.vocab_size)
        self.host_img: Dict[int, np.ndarray] = {}
        if cell.traffic.get("library_images"):
            for idx in generator.upload_order(cell.traffic, seed):
                emb = image_embeds(seed, int(idx), cfg.media_token_len,
                                   cfg.d_model)
                self.eng.upload(USER, f"img{idx}", emb)
                self.host_img[int(idx)] = np.asarray(emb)

    def prompt(self, s: generator.Spec):
        from repro.core.segments import Prompt, media_segment, text_segment
        segs = []
        if s.system_len:
            segs.append(text_segment(s.tokens[:s.system_len], kind="system"))
        for idx in s.images:
            segs.append(media_segment(f"img{idx}", self.host_img[idx]))
        segs.append(text_segment(s.tokens[s.system_len:]))
        return Prompt(segs, user_id=USER)

    def submit(self, s: generator.Spec, due: float, max_new=None) -> Rec:
        from repro.serving import Request
        req = Request(prompt=self.prompt(s),
                      max_new_tokens=max_new or s.max_new,
                      policy=self.cell.traffic["policy"],
                      policy_kwargs={"k": self.k}, seed=s.index)
        self.eng.submit(req)
        return Rec(spec=s, req=req, due=due, submitted=clock(),
                   selected=selected_positions(s, self.cfg.media_token_len,
                                               self.k),
                   total=s.prompt_len(self.cfg.media_token_len))

    # -- warm-up: exactly the shapes this cell's traffic reaches ----------
    def warm_plan(self):
        from repro.core.linker import bucket
        eng = self.eng
        ps, pps = eng.cfg.page_size, -(-eng.cfg.max_seq_len //
                                       eng.cfg.page_size)

        def pages(n):
            return -(-n // ps)

        def pkey(s):
            n_sel = len(selected_positions(s, self.cfg.media_token_len,
                                           self.k))
            return (bucket(n_sel, eng.cfg.prefill_bucket_min),
                    min(bucket(pages(s.prompt_len(
                        self.cfg.media_token_len))), pps))

        def dkey(length):
            return min(bucket(pages(length), 1), pps)

        plan, seen_p, seen_d = [], set(), set()
        for s in self.specs:
            if pkey(s) not in seen_p:
                seen_p.add(pkey(s))
                plan.append((s, 2))
                seen_d.add(dkey(s.prompt_len(self.cfg.media_token_len) + 1))
        reach: Dict[int, tuple] = {}
        for s in self.specs:
            t = s.prompt_len(self.cfg.media_token_len)
            for m in range(2, s.max_new + 1):
                d = dkey(t + m - 1)
                if d not in reach or reach[d][1] > m:
                    reach[d] = (s, m)
        for d in sorted(set(reach) - seen_d):
            plan.append(reach[d])
        return plan

    def warm_up(self) -> None:
        for s, m in self.warm_plan():
            self.submit(s, clock(), max_new=m)
            while self.eng.has_work:
                self.eng.step()
        if self.eng.failed:
            raise RuntimeError(f"warm-up request failed: "
                               f"{self.eng.failed[0].error}")
        self.eng.finished.clear()


TERMINAL = ("done", "failed", "deadline")


def start_trace(trace_dir: str) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def serve_window(srv: Server, run: Run, counter: CompileCounter) -> None:
    """The open loop: submit each request when it falls due, step the
    engine, stamp new tokens.

    A traced run starts the profiler before the first arrival and stops it
    after the loop, so that neither call holds the loop while requests are
    due; the device metrics read the part of the window that the span
    ``bench.traced_window`` marks."""
    tf = srv.cell.traffic
    lead = float(tf["arrivals"].get("lead_s", 0.0))
    until_first = tf["after_window"] == "first_tokens"
    max_wait = float(tf["max_wait_s"])
    tcfg = tf.get("trace", {})
    eng = srv.eng
    lib = eng.static_lib
    pending = collections.deque(srv.specs)
    active: List[Rec] = []
    seen: Dict[int, int] = {}
    trace_dir = str(spec.WORK / "trace" / f"{srv.cell.name}-{run.seed}")
    if run.traced:
        start_trace(trace_dir)
    run.t_w0 = t_w0 = clock() + lead
    run.t_traffic0 = t_w0 - lead
    t_end = t_w0 + run.seconds
    lib0 = c0 = None
    tracing = traced_done = idled = False
    win_span = None
    while True:
        now = clock()
        if lib0 is None and now >= t_w0:
            lib0, c0 = lib.stats(), counter.n
        if run.traced and not tracing and not traced_done \
                and now >= t_w0 + float(tcfg.get("start_s", 5.0)):
            tracing = True
            run.trace_t0 = clock()
            win_span = jax.profiler.TraceAnnotation("bench.traced_window")
            win_span.__enter__()
        with jax.profiler.TraceAnnotation("bench.submit"):
            while pending and t_w0 + pending[0].due_s <= now:
                s = pending.popleft()
                rec = srv.submit(s, t_w0 + s.due_s)
                run.recs.append(rec)
                active.append(rec)
                seen[id(rec)] = 0
        if eng.has_work:
            t_s = clock()
            try:
                with jax.profiler.TraceAnnotation("bench.step"):
                    eng.step()
            except Exception as exc:      # the engine marks the request
                run.failures.append(repr(exc))
            t1 = clock()
            with jax.profiler.TraceAnnotation("bench.stamp"):
                step = StepRec([], [], t_s, t1, idled)
                idled = False
                still = []
                for rec in active:
                    r = rec.req
                    n = len(r.output_tokens)
                    grew = n - seen[id(rec)]
                    if grew > 0:
                        dec = grew
                        if seen[id(rec)] == 0:
                            step.prefills.append(rec)
                            dec -= 1
                        step.decode_ctx += [r.cur_len] * dec
                        rec.stamps += [t1] * grew
                        seen[id(rec)] = n
                    if r.state.value not in TERMINAL:
                        still.append(rec)
                active = still
                run.steps.append(step)
                if tracing:
                    run.traced_steps.append(step)
        else:
            nxt = t_w0 + pending[0].due_s if pending else now + 0.01
            idled = True
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(max(0.0, min(nxt - now, 0.01)))
        now = clock()
        if tracing and now >= run.trace_t0 + float(tcfg.get("seconds", 5.0)):
            win_span.__exit__(None, None, None)
            run.trace_t1 = clock()
            tracing, traced_done = False, True
        if now >= t_end:
            if run.lib_delta == {} and lib0 is not None:
                lib1 = lib.stats()
                run.lib_delta = _lib_delta(lib0, lib1)
                run.compiles_in_window = counter.n - c0
            if not until_first:
                break
            waiting = [r for r in run.window if not r.stamps
                       and r.req.state.value not in ("failed", "deadline")]
            if not waiting or now >= t_end + max_wait:
                break
    if tracing:
        win_span.__exit__(None, None, None)
        run.trace_t1 = clock()
        traced_done = True
    if run.traced:
        t0 = clock()
        jax.profiler.stop_trace()
        path = tr.find_xplane(trace_dir)
        size = os.path.getsize(path) if path else 0
        events = tr.load(path) if path else []
        shutil.rmtree(trace_dir, ignore_errors=True)   # read once, kept small
        spans = tr.host_spans(events)
        w = tr.host_window_ns(spans)
        log(f"trace: {size} bytes, {len(events)} events kept, stopped and "
            f"read in {clock() - t0:.1f} s; span {w}")
        if traced_done and w:
            ops = tr.within(tr.device_ops(events), *w, clip=True)
            run.trace = TraceView(ops=ops,
                                  mods=tr.within(tr.modules(events), *w),
                                  spans=tr.within(spans, *w, clip=True),
                                  busy_s=tr.busy_s(ops),
                                  window_s=(w[1] - w[0]) * 1e-9,
                                  steps=run.traced_steps)


def log_stalls(run: Run, pauses: GcPauses) -> None:
    """What held the loop longest in the window: the longest engine
    steps, the longest stretch between two steps while requests were
    due, and the collector's pauses (diagnostics on standard error)."""
    w0, w1 = run.t_w0, run.t_w0 + run.seconds
    steps = [s for s in run.steps if w0 <= s.start < w1]
    top = sorted(steps, key=lambda s: s.start - s.end)[:3]
    gaps = [(b.start - a.end, a.end - w0) for a, b in zip(steps, steps[1:])
            if not b.after_idle]
    gc_w = [(d, g) for t, d, g in pauses.pauses if w0 <= t < w1]
    log("stalls: longest steps " + ", ".join(
        f"{s.end - s.start:.3f} s at {s.start - w0:.1f} s "
        f"({len(s.prefills)} admitted)" for s in top)
        + f"; longest gap between steps {max(gaps, default=(0, 0))[0]:.3f} s"
        f" at {max(gaps, default=(0, 0))[1]:.1f} s; gc pauses "
        f"{len(gc_w)}, longest {max(gc_w, default=(0, 0))[0]:.3f} s "
        f"(generation {max(gc_w, default=(0, 0))[1]})")


def _lib_delta(a: dict, b: dict) -> Dict[str, int]:
    out = {}
    for tier, c in b["tiers"].items():
        out[tier] = c["hits"] - a["tiers"].get(tier, {}).get("hits", 0)
    out["misses"] = b["misses"] - a["misses"]
    return out


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak() -> int:
    peak = 0
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             t_proc0: float) -> dict:
    """One run; returns the result object printed as the last line."""
    from bench import check

    counter = CompileCounter()
    enable_compile_cache()
    info = device_info()
    run = Run(cell, seed, seconds, traced)
    srv = Server(cell, seed, seconds)
    srv.warm_up()
    log(f"set-up done: {len(srv.specs)} requests scheduled, "
        f"{counter.n} programs compiled or loaded")
    pauses = GcPauses()
    serve_window(srv, run, counter)
    pauses.close()
    run.setup_s = run.t_traffic0 - t_proc0
    log_stalls(run, pauses)
    run.peak = peaks(info["kind"]) if info["platform"] == "tpu" else None
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = load_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = dict(platform=info["platform"], kind=info["kind"],
                  count=info["count"], memory_peak_bytes=memory_peak())
    breakdown = None
    if traced and run.trace is not None:
        t = run.trace
        device["busy_s"] = t.busy_s
        device["window_s"] = t.window_s
        w = tr.host_window_ns(t.spans)
        breakdown = {"device_ops": tr.top_ops(t.ops, t.mods),
                     "idle_gaps": tr.idle_gaps(t.ops, t.spans, *w)
                     if w else []}
    window = run.window
    failed = [r for r in window
              if r.req.state.value in ("failed", "deadline")
              or (cell.traffic["after_window"] == "first_tokens"
                  and not r.stamps)]
    # free the program's state before the reference runs
    params = srv.params
    finished = [r for r in run.recs if r.req.state.value == "done"]
    srv.eng.loader.close()
    del srv
    gc.collect()
    checks = check.compare(cell, params, finished, seed)
    out = {"correct": bool(checks["correct"]) and not run.failures,
           "attempted": len(window), "failed": len(failed),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks["numbers"]
    return out
