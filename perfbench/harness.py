"""The measuring loops and what they observe. Driven by data: a cell is an
entry of ``workloads`` in BENCHMARK.json, and whatever belongs to one
configuration, one traffic mix, one cell or one metric sits in a file of its
own that is found by name:

  perfbench/configs/<config>.json        sizes, the user's choices, ``correct``
  perfbench/traffic/<mix>.json           parameters for loadgen.Traffic
  perfbench/cells/<cell>.json            the cell's own numbers (optional)
  perfbench/adapters/<adapter>.py        the system under test (named by the
                                         configuration)
  perfbench/e2e_metrics/<metric>.py      ``read(obs)`` -> number or None
  perfbench/layer_metrics/<metric>.py    ``read(obs)`` -> number or None

The traffic mix's ``loop`` picks the loop below; the adapter must offer it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.util
import json
import math
import os
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import loadgen, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read_json(*parts: str) -> Dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(path: str):
    """A Python file by path (metric names carry dots, so no import name),
    executed once."""
    full = os.path.join(ROOT, path)
    name = "perfbench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(full, ROOT))
    spec = importlib.util.spec_from_file_location(name, full)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ---------------------------------------------------------------------------
# cells
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    params: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _deep_update(base: Dict, over: Dict) -> Dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = (_deep_update(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def load_cell(name: str, rehearse: bool = False) -> Cell:
    bench = read_json("BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(known: {sorted(by_name)})")
    w = by_name[name]
    config_file = {c["name"]: c["file"] for c in bench["configs"]}[w["config"]]
    config = read_json(config_file)
    traffic = read_json("perfbench", "traffic", w["traffic"] + ".json")
    cell_file = os.path.join("perfbench", "cells", name + ".json")
    params = (read_json(cell_file)["params"]
              if os.path.exists(os.path.join(ROOT, cell_file)) else {})
    if rehearse:
        tiny = read_json("perfbench", "testdata", "rehearsal", name + ".json")
        config = _deep_update(config, tiny.get("config", {}))
        traffic = _deep_update(traffic, tiny.get("traffic", {}))
        params = _deep_update(params, tiny.get("params", {}))

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or name in m["workloads"]]

    return Cell(name, int(w["chips"]), config, traffic, params,
                mine(bench["end_to_end"]), mine(bench["per_layer"]))


# ---------------------------------------------------------------------------
# host spans and the device trace
# ---------------------------------------------------------------------------
class Spans:
    """The benchmark's own host spans, kept in memory; each is also a
    ``jax.profiler.TraceAnnotation``, so that inside a traced window it lands
    on the device trace's clock."""

    def __init__(self, clock: Callable[[], float]):
        from jax.profiler import TraceAnnotation
        self._annotate = TraceAnnotation
        self._clock = clock
        self.records: Dict[str, List] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = self._clock()
        with self._annotate(name):
            yield
        self.records.setdefault(name, []).append((t0, self._clock()))

    def durations(self, name: str, window) -> List[float]:
        return [b - a for a, b in self.records.get(name, ())
                if window[0] <= a and b <= window[1]]


class Tracer:
    """Traces ``seconds`` of the run from ``start_at`` on (a short
    sub-window: traces are large and tracing slows the host). Armed by the
    loop once it knows its clock: the open loop and training by the clock (the
    window's last ``seconds``), a batch by its progress (``serve_batch``)."""

    def __init__(self, out_dir: Optional[str], seconds: float):
        self.dir = out_dir
        self.seconds = seconds
        self.start_at = math.inf
        self.state = "idle" if out_dir else "off"
        #: the loop ended while the trace still ran (``finish`` stopped it)
        self.overran = False

    def arm(self, start_at: float) -> None:
        self.start_at = start_at

    @property
    def armed(self) -> bool:
        return self.start_at < math.inf

    def _start(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # our spans, not every frame
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def _stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def poll(self, now: float) -> None:
        """Called by the loops between two dispatches."""
        if self.state == "idle" and now >= self.start_at:
            self._start()
            self.state = "tracing"
        elif self.state == "tracing" \
                and now >= self.start_at + self.seconds:
            self._stop()
            self.state = "done"

    def finish(self) -> Optional[trace_reduce.TraceSummary]:
        """Stop if still tracing, and reduce what was written."""
        if self.state == "tracing":
            self._stop()
            self.state, self.overran = "done", True
        path = (trace_reduce.find_xplane(self.dir)
                if self.state == "done" else None)
        if path is None:
            return None
        return trace_reduce.reduce_trace(trace_reduce.load_xplane(path))


# ---------------------------------------------------------------------------
# what a run observed
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RequestRecord:
    index: int
    prompt: np.ndarray
    n_out: int                       # the request's output budget
    due: Optional[float]             # open loop: when it was due (clock)
    sent: float = 0.0
    token_times: List[float] = dataclasses.field(default_factory=list)
    handle: Any = None
    outcome: Optional[str] = None    # None: running; "ok"; else the failure

    @property
    def n_prompt(self) -> int:
        return len(self.prompt)

    @property
    def origin(self) -> float:
        """Open loop: when the request was due; closed: when it was sent."""
        return self.sent if self.due is None else self.due

    @property
    def first(self) -> float:
        return self.token_times[0] if self.token_times else math.inf

    @property
    def complete(self) -> bool:
        return self.outcome == "ok"


@dataclasses.dataclass
class StepRecord:
    done: float                      # when the host saw the step complete
    loss: float


@dataclasses.dataclass
class Observations:
    """Everything a metric reader may look at."""
    cell: Cell
    window: tuple                    # (start, end) on the host clock
    setup_s: float
    requests: List[RequestRecord]    # serving loops
    steps: List[StepRecord]          # training loop
    tokens_per_step: int
    spans: Spans
    counters: Dict[str, float]       # read from the program by the adapter
    device: Dict
    peaks: Optional[Dict]
    trace: Optional[trace_reduce.TraceSummary] = None

    @property
    def seconds(self) -> float:
        return self.window[1] - self.window[0]

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]


# ---------------------------------------------------------------------------
# serving loops
# ---------------------------------------------------------------------------
class _Serving:
    def __init__(self, server, spans: Spans, clock):
        self.server, self.spans, self.clock = server, spans, clock
        self.records: List[RequestRecord] = []
        self._live: List[RequestRecord] = []

    def submit(self, req: loadgen.Request, due: Optional[float]) -> None:
        rec = RequestRecord(req.index, req.prompt, req.max_new_tokens, due,
                            sent=self.clock())
        clock, times = self.clock, rec.token_times
        rec.handle = self.server.submit(
            req.prompt, req.max_new_tokens, lambda _tok: times.append(clock()))
        self.records.append(rec)
        self._live.append(rec)

    def drain(self, requests: List[loadgen.Request], clients: int,
              tracer: Optional[Tracer] = None, trace_after: int = 0) -> None:
        """``clients`` callers take the requests one after another, each
        sending its next when its previous one completed, until all are
        done. The tracer is armed once ``trace_after`` of them have ended."""
        todo = list(requests)
        for _ in range(min(clients, len(todo))):
            self.submit(todo.pop(0), None)
        done = 0
        while self.server.busy():
            if tracer is not None:
                now = self.clock()
                if done >= trace_after and not tracer.armed:
                    tracer.arm(now)
                tracer.poll(now)
            with self.spans.span("bench.sched_step"):
                self.server.step()
            ended = self.sweep()
            done += ended
            if ended and todo:
                with self.spans.span("bench.submit"):
                    for _ in range(min(ended, len(todo))):
                        self.submit(todo.pop(0), None)
        if todo:
            raise RuntimeError("the server stopped with requests unsent")

    def sweep(self) -> int:
        """Note which requests ended in the last step; returns how many."""
        ended = 0
        for rec in list(self._live):
            rec.outcome = self.server.outcome(rec.handle)
            if rec.outcome is not None:
                self._live.remove(rec)
                ended += 1
        return ended


def serve_open(server, traffic: loadgen.Traffic, spans: Spans,
               tracer: Tracer, clock=time.perf_counter, sleep=time.sleep):
    """Open loop: compile on a few warm-up requests, run the mix's own
    arrivals for ``warmup_s``, then measure for ``traffic.seconds``. One
    thread: submit what is due, step, look; when nothing is in flight, wait
    for the next arrival. Returns (records, window)."""
    s = _Serving(server, spans, clock)
    warm = traffic.warmup()
    s.drain(warm, len(warm))
    t0 = clock() + traffic.warmup_s
    t1 = t0 + traffic.seconds
    tracer.arm(t1 - tracer.seconds)     # stopping falls after the window
    plan = traffic.schedule()
    nxt, opened = 0, False
    while True:
        now = clock()
        if now >= t1:
            break
        if not opened and now >= t0:
            server.begin_window()
            opened = True
        tracer.poll(now)
        if nxt < len(plan) and t0 + plan[nxt].due_s <= now:
            with spans.span("bench.submit"):
                while nxt < len(plan) and t0 + plan[nxt].due_s <= now:
                    s.submit(plan[nxt], t0 + plan[nxt].due_s)
                    nxt += 1
        if server.busy():
            with spans.span("bench.sched_step"):
                server.step()
            s.sweep()
        else:
            due = t0 + plan[nxt].due_s if nxt < len(plan) else t1
            with spans.span("bench.wait_arrival"):
                sleep(max(0.0, min(due, t1) - clock()))
    return s.records, (t0, t1)


def serve_batch(server, traffic: loadgen.Traffic, spans: Spans,
                tracer: Tracer, clock=time.perf_counter):
    """A fixed batch through a closed loop: ``traffic.clients`` callers take
    the batch's requests one after another, each sending its next when its
    previous one completed; the window runs from the first submit to the last
    completion, on a server that is empty at both ends — so every token of
    the batch was worked inside the window, and none of another request's.
    A few warm-up requests, drained, come first. A traced run is traced from
    the moment a quarter of the batch's requests have completed, whatever the
    clock says: every caller is busy then (they run dry once all but
    ``clients`` requests have completed), and a batch that a faster program
    finishes sooner is still traced while it is loaded (by the clock, PR 25
    left the four-chip cell's trace 1.5 s of drain tail). Returns (records,
    window)."""
    s = _Serving(server, spans, clock)
    s.drain(traffic.warmup(), traffic.clients)
    server.begin_window()
    t0 = clock()
    batch = traffic.batch()
    s.drain(batch, traffic.clients, tracer,
            trace_after=math.ceil(len(batch) / 4))
    return s.records, (t0, clock())


# ---------------------------------------------------------------------------
# training loop
# ---------------------------------------------------------------------------
def train(trainer, traffic: Dict, seconds: float, seed: int, spans: Spans,
          tracer: Tracer, clock=time.perf_counter):
    """Warm up, fence, then step for ``seconds`` with one step in flight (the
    host makes the next batch while the device works). The window ends at the
    fence of the first step that completes after ``seconds``, so the clock
    and the token count stop at the same instant. Returns (steps, window)."""
    import jax
    rng = np.random.default_rng([seed, 7])
    loss = None
    for _ in range(int(traffic["warmup_steps"])):
        loss = trainer.step(*trainer.make_batch(rng))
    jax.block_until_ready(loss)
    t0 = clock()
    t1 = t0 + seconds
    tracer.arm(t1 - tracer.seconds)
    steps: List = []
    pending = None
    while True:
        tracer.poll(clock())
        with spans.span("bench.make_batch"):
            ids, labels = trainer.make_batch(rng)
        with spans.span("bench.train_step"):
            loss = trainer.step(ids, labels)
        if pending is not None:
            with spans.span("bench.wait_step"):
                jax.block_until_ready(pending)
            steps.append((clock(), pending))
            if steps[-1][0] >= t1:
                with spans.span("bench.wait_step"):
                    jax.block_until_ready(loss)
                steps.append((clock(), loss))
                break
        pending = loss
    records = [StepRecord(t, float(x)) for t, x in steps]
    return records, (t0, records[-1].done)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def read_metrics(obs: Observations, metrics: List[Dict], folder: str) -> Dict:
    """``{name: {"value", "unit"}}`` of every listed metric whose reader
    found something to read; a reader that returns None is left out. A
    reading that moves different end-to-end metrics in different cells is
    listed once for each as ``<reading>-<suffix>``; all share the reader
    ``<reading>.py``."""
    out = {}
    for m in metrics:
        reader = load_module(os.path.join(
            "perfbench", folder, m["name"].split("-")[0] + ".py"))
        value = reader.read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
