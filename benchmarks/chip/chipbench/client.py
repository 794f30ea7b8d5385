"""The benchmark's client: drives ``ServeEngine.step()`` through a
measured window, then drains what was due in it.

Requests carry their due times (seconds from the window's start); the
engine admits each once its clock passes that time, so the loop is
open: a slow step delays later requests, and their latencies count the
wait.  Each ``step()`` is timed on the host clock and inside a
``bench.step`` span; the program's own phase seconds (dispatch to synced
result of its jitted calls) are read beside it.  After each step the
client notes, for every request in the engine, how many tokens it has,
when the latest came and the gap since the one before (the first token
is stamped by the engine, the others by the client after the step that
made them).  After the window the engine keeps stepping
until every request due in it has finished, or ``drain_seconds`` have
passed.  Compilations are counted through JAX's monitoring events from
the window's start to the end of the drain.
"""
from __future__ import annotations

import math
import os
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the traced part of the window, as a span on the trace's own clock
WINDOW_SPAN = "bench.window"


@dataclass
class StepRec:
    t: float            # start, seconds from the window's start
    wall: float         # seconds around engine.step()
    program: float      # the program's phase seconds in this step
    prefill: float
    decode: float
    traced: bool


@dataclass
class Obs:
    """One request due in the window, as the client saw it (engine clock,
    seconds from the window's start)."""
    rid: int
    arrival: float
    admitted: Optional[float] = None
    first: Optional[float] = None     # first token (stamped by the engine)
    last: Optional[float] = None      # latest token
    n: int = 0                        # tokens served
    finished: bool = False
    tokens: List[int] = field(default_factory=list)
    gaps: List[float] = field(default_factory=list)   # between tokens

    def served(self, n: int, first: float, t: float) -> None:
        """It has ``n`` tokens, the first at ``first`` and the latest at
        ``t``: the gaps of the new ones after the first (a step that made
        several splits its time evenly)."""
        if self.first is None:
            self.first, self.last, self.n = first, first, 1
        k = n - self.n
        if k > 0:
            self.gaps.extend([(t - self.last) / k] * k)
            self.n, self.last = n, t

    @property
    def ttft(self) -> float:
        """Due time to first token; a request that never got one is
        missing (infinitely late)."""
        return math.inf if self.first is None else self.first - self.arrival

    @property
    def tpot(self) -> Optional[float]:
        """Mean gap between its tokens (None below two tokens)."""
        if self.first is None or self.n < 2:
            return None
        return (self.last - self.first) / (self.n - 1)


@dataclass
class Window:
    seconds: float
    steps: List[StepRec] = field(default_factory=list)
    requests: Dict[int, Obs] = field(default_factory=dict)
    tokens_at_close: int = 0
    compiles: int = 0
    drain_s: float = 0.0
    trace_dir: Optional[str] = None
    report: Dict[str, Any] = field(default_factory=dict)
    n_finished_seen: int = 0          # engine records already read

    @property
    def attempted(self) -> int:
        return len(self.requests)

    @property
    def failed(self) -> int:
        """Requests due in the window that got no first token by the end
        of the drain: never served.  (One still decoding then is late,
        not failed.)"""
        return sum(o.first is None for o in self.requests.values())

    @property
    def unfinished(self) -> int:
        return sum(not o.finished for o in self.requests.values())


class _CompileCounter:
    def __init__(self):
        self.n = 0

    def __call__(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1


def _trace_options():
    # device ops and host spans; Python function events would slow the
    # host loop the trace is meant to show
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def warm_profiler() -> None:
    """Start and stop the profiler once around a small device op, so
    that its start-up in this process falls in set-up, not in the
    window."""
    d = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(d, profiler_options=_trace_options())
        with jax.profiler.TraceAnnotation("bench.warm"):
            jax.block_until_ready(jax.numpy.arange(8) + 1)
        jax.profiler.stop_trace()
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _observe(engine, win: Window, outputs: Dict[int, List[int]]) -> None:
    now = engine.clock.now()
    live = [st for st in engine.state_by_slot if st is not None]
    for st in live + list(engine.front.resume):
        o = win.requests.get(st.req.rid)
        if o is None:
            continue
        o.admitted = st.admitted_time
        if st.n_generated:
            o.served(st.n_generated, st.first_token_time, now)
            o.tokens = st.output
    done = engine.metrics.requests
    for r in done[win.n_finished_seen:]:
        o = win.requests.get(r.rid)
        if o is not None:
            o.admitted = r.admitted_time
            o.served(r.n_generated, r.first_token_time, r.finish_time)
            o.finished = True
            o.tokens = outputs.get(r.rid, [])
    win.n_finished_seen = len(done)


def run(engine, reqs, seconds: float, drain_seconds: float, *,
        outputs: Dict[int, List[int]],
        trace_at: Optional[float] = None, trace_seconds: float = 0.0,
        on_trace: Callable[[bool], None] = lambda on: None) -> Window:
    """Serve ``reqs`` (due times inside [0, seconds)) for a window of
    ``seconds``, then drain for at most ``drain_seconds`` more.
    ``outputs`` is where the engine's finish hook leaves each finished
    request's tokens.  With ``trace_at`` the profiler records
    ``trace_seconds`` of the window starting then, into a temporary
    directory (``Window.trace_dir``); ``on_trace`` hears when the traced
    part begins and ends."""
    win = Window(seconds=seconds)
    win.requests = {r.rid: Obs(r.rid, r.arrival_time) for r in reqs}
    engine.reset_metrics()                 # zeroes the engine's clock
    clock = engine.clock
    for r in reqs:
        engine.submit(r)
    compiles = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    tracing = False
    t_stop = None
    span = None
    try:
        while clock.now() < seconds:
            now = clock.now()
            if trace_at is not None and not tracing and t_stop is None \
                    and now >= trace_at:
                win.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
                jax.profiler.start_trace(win.trace_dir,
                                         profiler_options=_trace_options())
                # made while the profiler runs, or it records nothing
                span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
                span.__enter__()
                tracing = True
                on_trace(True)
                t_stop = now + trace_seconds
            if tracing and now >= t_stop:
                on_trace(False)
                span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = False
            if not engine.has_work():       # everything due has finished
                time.sleep(min(0.01, seconds - now))
                continue
            _one_step(engine, win, tracing)
            _observe(engine, win, outputs)
        if tracing:
            on_trace(False)
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
        win.tokens_at_close = sum(o.n for o in win.requests.values())
        t_close = clock.now()
        while (win.unfinished and engine.has_work()
               and clock.now() - t_close < drain_seconds):
            engine.step()
            _observe(engine, win, outputs)
        win.drain_s = clock.now() - t_close
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
        if tracing:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
    win.compiles = compiles.n
    win.report = engine.report()
    return win


def _one_step(engine, win: Window, traced: bool) -> None:
    ph0 = dict(engine.metrics.phase_seconds)
    t0 = time.perf_counter()
    t_rel = engine.clock.now()
    with jax.profiler.TraceAnnotation("bench.step"):
        engine.step()
    wall = time.perf_counter() - t0
    ph1 = engine.metrics.phase_seconds
    d = {k: ph1.get(k, 0.0) - ph0.get(k, 0.0) for k in ph1}
    win.steps.append(StepRec(
        t=t_rel, wall=wall, program=sum(d.values()),
        prefill=d.get("prefill", 0.0) + d.get("prefix_tail", 0.0),
        decode=d.get("decode", 0.0) + d.get("verify", 0.0), traced=traced))


def trace_file(trace_dir: str) -> str:
    for root, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(root, f)
    raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")


def remove_trace(win: Window) -> None:
    if win.trace_dir:
        shutil.rmtree(win.trace_dir, ignore_errors=True)
