"""Serving metrics: per-request latency accounting, per-step MoE
schedule diagnostics, and the engine's spans.

Per request (the paper's §5 serving metrics):
  * TTFT — first_token_time - arrival_time, split three ways:
    ``queue_delay`` (arrival to admission), ``prefill_wait`` (admission
    to the start of the request's first prefill chunk) and the prefill
    itself (first chunk to first token)
  * TPOT — mean inter-token time over the decode phase
  * e2e  — finish_time - arrival_time

Per step, the engine feeds in the HarMoEny schedule diagnostics emitted by
the MoE block (moved_units, send/dest drops, max load before/after) and the
number of occupied decode slots, so batch-occupancy and load-balance
trajectories can be plotted against arrival rate and skew.

The paged engine additionally records per-step KV-block occupancy
(``record_kv``) and preemption counts, reported as ``kv_blocks_in_use`` /
``kv_utilization`` / ``preemptions``.  Speculative decoding records
drafted/accepted/committed token counts per verify step, reported as a
``speculative`` sub-dict (acceptance_rate, tokens per slot-step, steps
per committed token).  ``record_phase`` accumulates per-phase tokens and
seconds (prefill / prefix_tail / decode / verify; the seconds are those
of the engine span around the jitted call and its sync), reported as
``phases``.  With tiered expert residency the engine attaches a
``residency`` sub-dict (hit_rate, stall_units, swaps, prefetches,
bytes_staged) from the residency manager's window counters.
``report()`` is JSON-safe on an empty measurement window: percentile
reductions over zero requests come back as ``None``, never NaN.

**Spans.**  ``ServeMetrics.span(name, **args)`` is the engine's one
timing mechanism: a context manager that opens a
``jax.profiler.TraceAnnotation`` (so the span lands in a profiler trace
on the device ops' clock, with ``args`` as its arguments; a
``StepTraceAnnotation`` when ``step_num`` is among them) and adds the
span's count, seconds and self seconds (seconds minus those of the spans
opened inside it) to window totals.  It is always on: with the profiler
off a span costs one ``perf_counter`` pair, a dict update and an
inactive trace annotation.  The totals are the ``spans`` section,
``{name: {"count", "seconds", "self_seconds"}}``, reset with the
metrics object.  The engine's span names start ``serve.`` (listed in
serve/README.md, "Report schema").

**Section convention.**  Every optional subsystem block in a report —
``speculative``, ``phases``, ``load_balance``, ``residency``, ``spans``
here; ``state_pool`` from the engine; ``fleet`` from the router —
attaches through one mechanism instead of ad-hoc conditional appends: a
*section function* returns the section dict, or a falsy value to omit
the section this window.  ``ServeMetrics.register_section(name, fn)``
registers one on a metrics object (the built-ins register themselves the
same way at construction, so subsystem sections and core sections are
indistinguishable in ``report()``); the module-level ``section(rep,
name, fn)`` applies the identical rule to dicts assembled outside a
``ServeMetrics`` (the engine's and the fleet router's reports).  The
full schema is documented in serve/README.md ("Report schema").
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.serve.request import RequestState

# a report section provider: () -> the section dict, or falsy to omit
SectionFn = Callable[[], Optional[Dict[str, Any]]]


def section(rep: Dict[str, Any], name: str, fn: SectionFn) -> None:
    """Attach ``fn()`` to ``rep`` under ``name`` iff non-empty — the one
    convention every subsystem report section goes through (see module
    docstring)."""
    sec = fn()
    if sec:
        rep[name] = sec


def percentiles(xs, ps=(50, 90, 99)) -> Dict[str, float]:
    xs = np.asarray(list(xs), np.float64)
    if xs.size == 0:
        return {f"p{p}": float("nan") for p in ps} | {"mean": float("nan")}
    out = {f"p{p}": float(np.percentile(xs, p)) for p in ps}
    out["mean"] = float(xs.mean())
    return out


def _json_safe(x):
    """Recursively replace non-finite floats with None so an empty window's
    report serializes under ``json.dumps(..., allow_nan=False)``."""
    if isinstance(x, dict):
        return {k: _json_safe(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_json_safe(v) for v in x]
    if isinstance(x, float) and not np.isfinite(x):
        return None
    return x


class Span:
    """One open span of ``ServeMetrics.span``; ``seconds`` holds its
    duration once it has closed."""
    __slots__ = ("name", "seconds", "_inner", "_t0", "_annotation",
                 "_owner")

    def __init__(self, owner: "ServeMetrics", name: str, annotation):
        self.name = name
        self.seconds = 0.0
        self._inner = 0.0               # seconds of the spans inside it
        self._t0 = 0.0
        self._annotation = annotation
        self._owner = owner

    def __enter__(self) -> "Span":
        self._annotation.__enter__()
        self._owner._open.append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        open_ = self._owner._open
        open_.pop()
        if open_:
            open_[-1]._inner += dt
        self.seconds = dt
        tot = self._owner.span_totals.get(self.name)
        if tot is None:
            tot = self._owner.span_totals[self.name] = [0, 0.0, 0.0]
        tot[0] += 1
        tot[1] += dt
        tot[2] += dt - self._inner
        return False


@dataclass
class RequestRecord:
    """Immutable latency record for one finished request."""
    rid: int
    prompt_len: int
    n_generated: int
    arrival_time: float
    admitted_time: float
    first_token_time: float
    finish_time: float
    cached_prefix_tokens: int = 0   # prompt tokens served from the prefix
    #                                 cache at first admission
    prefill_start_time: Optional[float] = None   # first prefill chunk

    @property
    def ttft(self) -> float:
        return self.first_token_time - self.arrival_time

    @property
    def tpot(self) -> float:
        if self.n_generated <= 1:
            return 0.0
        return (self.finish_time - self.first_token_time) \
            / (self.n_generated - 1)

    @property
    def e2e(self) -> float:
        return self.finish_time - self.arrival_time

    def asdict(self) -> Dict[str, float]:
        return {
            "rid": self.rid, "prompt_len": self.prompt_len,
            "n_generated": self.n_generated,
            "arrival_time": self.arrival_time,
            "queue_delay": self.admitted_time - self.arrival_time,
            # admission to the first prefill chunk: the wait in the
            # prefill line (None for a request that never prefilled here)
            "prefill_wait": (None if self.prefill_start_time is None
                             else self.prefill_start_time
                             - self.admitted_time),
            "ttft": self.ttft, "tpot": self.tpot, "e2e": self.e2e,
            "cached_prefix_tokens": self.cached_prefix_tokens,
        }


class ServeMetrics:
    """Accumulates request records and per-step diagnostics."""

    def __init__(self):
        self.requests: List[RequestRecord] = []
        self.decode_steps: int = 0
        self.prefill_chunks: int = 0
        self.occupancy: List[int] = []          # active slots per decode step
        self.moe_diags: Dict[str, List[float]] = {}
        # vector-valued MoE diagnostics (per-rank rank_load [G], per-expert
        # expert_load [Ep]) — kept per step for the load_balance report
        self.load_vectors: Dict[str, List[np.ndarray]] = {}
        self.kv_blocks_in_use: List[int] = []   # per decode step (paged)
        self.kv_blocks_total: int = 0
        self.preemptions: int = 0
        # --- prefix sharing (paged) ---
        self.cow_copies: int = 0                # copy-on-write block copies
        self.evictions: int = 0                 # cached prefixes evicted
        self.resume_cached_tokens: int = 0      # prefill skipped on resume
        # --- speculative decoding ---
        self.spec_steps: int = 0                # verify steps run
        self.spec_slot_steps: int = 0           # active-slot verify passes
        self.spec_drafted: int = 0              # draft tokens proposed
        self.spec_accepted: int = 0             # draft tokens accepted
        self.spec_committed: int = 0            # tokens committed by verify
        # --- per-phase accounting (prefill / prefix_tail / decode /
        # verify): committed-or-consumed tokens and the seconds of the
        # engine span from the jitted call's dispatch to its synced result
        self.phase_tokens: Dict[str, int] = {}
        self.phase_seconds: Dict[str, float] = {}
        self.phase_steps: Dict[str, int] = {}
        # --- spans (module docstring "Spans"): name -> [count, seconds,
        # self seconds]; _open is the stack of spans now open
        self.span_totals: Dict[str, List[float]] = {}
        self._open: List[Span] = []
        # --- tiered expert residency (serve/residency.py) ---
        # window counter dict (hits, misses, lookups, swaps, prefetches,
        # stall_units, bytes_staged, hit_rate) set by the engine's
        # report() from the residency manager; None = residency off
        self.residency: Optional[Dict[str, Any]] = None
        self._t_first_arrival: Optional[float] = None
        self._t_last_finish: float = 0.0
        # --- report sections (module docstring "Section convention") ---
        # name -> provider; report() attaches each non-empty result.  The
        # built-in subsystem sections register through the same mechanism
        # engine-side sections (state_pool) do.
        self._sections: Dict[str, SectionFn] = {}
        self.register_section("speculative", self._speculative_section)
        self.register_section("phases", self._phases_section)
        self.register_section("residency",
                              lambda: self.residency
                              and dict(self.residency))
        self.register_section("load_balance", self._load_balance)
        self.register_section("spans", self._spans_section)

    def register_section(self, name: str, fn: SectionFn) -> None:
        """Register a report section provider (last registration per name
        wins).  ``fn()`` runs at ``report()`` time; a falsy return omits
        the section for this window."""
        self._sections[name] = fn

    def span(self, name: str, **args) -> Span:
        """A context manager timing one span (module docstring "Spans"):
        its ``seconds`` are set when it closes.  ``args`` become the
        trace annotation's arguments; with ``step_num`` among them the
        annotation is a ``StepTraceAnnotation``."""
        ann = (jax.profiler.StepTraceAnnotation if "step_num" in args
               else jax.profiler.TraceAnnotation)(name, **args)
        return Span(self, name, ann)

    @property
    def empty(self) -> bool:
        """No timestamps recorded yet — the measurement window is fresh."""
        return not (self.requests or self.decode_steps or self.prefill_chunks)

    # ------------------------------------------------------------------
    def record_step(self, diags: Dict[str, Any], n_active: int,
                    phase: str = "decode") -> None:
        if phase == "decode":
            self.decode_steps += 1
            self.occupancy.append(n_active)
        else:
            self.prefill_chunks += 1
        for k, v in (diags or {}).items():
            arr = np.asarray(v)
            if arr.ndim:
                self.load_vectors.setdefault(f"{phase}/{k}", []).append(
                    arr.reshape(-1).astype(np.float64))
            else:
                self.moe_diags.setdefault(f"{phase}/{k}", []).append(
                    float(arr))

    def record_phase(self, phase: str, tokens: int, seconds: float) -> None:
        """One engine step's contribution to a phase (prefill /
        prefix_tail / decode / verify): tokens processed and the seconds
        of the span around the synced jitted call."""
        self.phase_tokens[phase] = self.phase_tokens.get(phase, 0) \
            + int(tokens)
        self.phase_seconds[phase] = self.phase_seconds.get(phase, 0.0) \
            + float(seconds)
        self.phase_steps[phase] = self.phase_steps.get(phase, 0) + 1

    def record_kv(self, blocks_in_use: int, blocks_total: int) -> None:
        """Per-decode-step KV-block occupancy of the paged pool."""
        self.kv_blocks_in_use.append(int(blocks_in_use))
        self.kv_blocks_total = int(blocks_total)

    def complete(self, st: RequestState) -> RequestRecord:
        rec = RequestRecord(
            rid=st.req.rid, prompt_len=st.req.prompt_len,
            n_generated=st.n_generated,
            arrival_time=st.req.arrival_time,
            admitted_time=st.admitted_time,
            first_token_time=st.first_token_time,
            finish_time=st.finish_time,
            cached_prefix_tokens=st.cached_prefix_tokens or 0,
            prefill_start_time=st.prefill_start_time)
        self.requests.append(rec)
        if self._t_first_arrival is None \
                or rec.arrival_time < self._t_first_arrival:
            self._t_first_arrival = rec.arrival_time
        self._t_last_finish = max(self._t_last_finish, rec.finish_time)
        return rec

    # ------------------------------------------------------------------
    def report(self) -> Dict[str, Any]:
        recs = self.requests
        total_new = sum(r.n_generated for r in recs)
        total_prompt = sum(r.prompt_len for r in recs)
        span = (self._t_last_finish - self._t_first_arrival) \
            if recs and self._t_first_arrival is not None else 0.0
        rep: Dict[str, Any] = {
            "n_requests": len(recs),
            "total_new_tokens": total_new,
            "ttft": percentiles(r.ttft for r in recs),
            "tpot": percentiles(r.tpot for r in recs if r.n_generated > 1),
            "e2e": percentiles(r.e2e for r in recs),
            "queue_delay": percentiles(
                r.admitted_time - r.arrival_time for r in recs),
            "throughput_tok_s": total_new / span if span > 0 else float("nan"),
            "decode_steps": self.decode_steps,
            "prefill_chunks": self.prefill_chunks,
            "mean_occupancy": (float(np.mean(self.occupancy))
                               if self.occupancy else 0.0),
            "max_occupancy": (int(max(self.occupancy))
                              if self.occupancy else 0),
            "preemptions": self.preemptions,
            "cow_copies": self.cow_copies,
            "evictions": self.evictions,
            "resume_cached_tokens": self.resume_cached_tokens,
            # token-level prefix cache hit rate: prompt tokens whose K/V was
            # mapped from the cache at first admission / all prompt tokens
            "prefix_hit_rate": (
                sum(r.cached_prefix_tokens for r in recs) / total_prompt
                if total_prompt else None),
            "requests": [r.asdict() for r in recs],
        }
        if self.kv_blocks_in_use:
            used = np.asarray(self.kv_blocks_in_use, np.float64)
            rep["kv_blocks_in_use"] = {"mean": float(used.mean()),
                                       "max": int(used.max())}
            rep["kv_utilization"] = (float(used.mean())
                                     / max(self.kv_blocks_total, 1))
        if self.moe_diags:
            rep["moe"] = {k: float(np.mean(v))
                          for k, v in self.moe_diags.items()}
        for name, fn in self._sections.items():
            section(rep, name, fn)
        return _json_safe(rep)

    def _speculative_section(self) -> Optional[Dict[str, Any]]:
        if not self.spec_steps:
            return None
        # the per-SLOT accounting is what isolates speculation from
        # batching: plain decode spends exactly one slot-step per
        # committed token, so tokens_per_step == 1.0 marks "no win"
        # regardless of how many slots each wall-clock step batches
        return {
                "steps": self.spec_steps,
                "slot_steps": self.spec_slot_steps,
                "drafted": self.spec_drafted,
                "accepted": self.spec_accepted,
                "committed_tokens": self.spec_committed,
                # share of proposed drafts the verify step kept
                "acceptance_rate": (self.spec_accepted / self.spec_drafted
                                    if self.spec_drafted else None),
                # committed tokens per active-slot verify pass
                # (> 1.0 is the speculative win)
                "tokens_per_step": (self.spec_committed
                                    / self.spec_slot_steps
                                    if self.spec_slot_steps else None),
                # < 1.0 is the speculative win, mirrored for the paper's
                # steps-per-token framing
                "steps_per_committed_token": (
                    self.spec_slot_steps / self.spec_committed
                    if self.spec_committed else None),
        }

    def _phases_section(self) -> Optional[Dict[str, Any]]:
        if not self.phase_steps:
            return None
        return {
                ph: {
                    "steps": self.phase_steps[ph],
                    "tokens": self.phase_tokens.get(ph, 0),
                    "seconds": self.phase_seconds.get(ph, 0.0),
                    "tokens_per_s": (
                        self.phase_tokens.get(ph, 0)
                        / self.phase_seconds[ph]
                        if self.phase_seconds.get(ph, 0.0) > 0
                        else None),
                }
                for ph in sorted(self.phase_steps)
        }

    def _spans_section(self) -> Dict[str, Dict[str, float]]:
        return {name: {"count": int(c), "seconds": sec, "self_seconds": own}
                for name, (c, sec, own) in sorted(self.span_totals.items())}

    def _load_balance(self) -> Dict[str, Any]:
        """Paper §5 load metrics per phase, from the per-step vector
        diagnostics: mean per-rank/per-expert load profiles, the max/mean
        rank-load ratio (1.0 = perfect balance), the straggler-wait proxy
        (mean of max - mean scheduled units per step — the token units the
        average rank sits idle while the most-loaded rank finishes, the
        static-shape analogue of the paper's GPU idle time), and total
        scheduler drop counts."""
        out: Dict[str, Any] = {}
        for phase in ("decode", "prefill"):
            rl = self.load_vectors.get(f"{phase}/rank_load")
            el = self.load_vectors.get(f"{phase}/expert_load")
            if rl is None and el is None:
                continue
            sec: Dict[str, Any] = {}
            if rl:
                m = np.stack(rl)                      # [steps, G]
                mx, mn = m.max(axis=1), m.mean(axis=1)
                sec["rank_load_mean"] = m.mean(axis=0).tolist()
                sec["max_load_mean"] = float(mx.mean())
                sec["mean_load_mean"] = float(mn.mean())
                sec["max_mean_ratio"] = float(np.mean(
                    np.where(mn > 0, mx / np.maximum(mn, 1e-9), 1.0)))
                sec["straggler_wait_units"] = float(np.mean(mx - mn))
            if el:
                e = np.stack(el)                      # [steps, Ep]
                sec["expert_load_mean"] = e.mean(axis=0).tolist()
            for drop in ("send_drops", "dest_drops"):
                vals = self.moe_diags.get(f"{phase}/{drop}")
                if vals is not None:
                    sec[f"{drop}_total"] = float(np.sum(vals))
            out[phase] = sec
        return out


# ----------------------------------------------------------------------
def aggregate_fleet(reports: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Pool several engine ``report()`` dicts into fleet-level latency
    aggregates.  Works on the JSON-safe per-request rows each report
    carries, so it composes across replicas regardless of role: in a
    disaggregated fleet the decode engines own the completion records
    (handoffs carry the true arrival/TTFT timestamps across), so summing
    per-replica rows double-counts nothing.  All replicas must share one
    clock — the timestamps are only comparable on a common timebase."""
    rows = [r for rep in reports for r in rep.get("requests", ())]
    total_new = sum(r["n_generated"] for r in rows)
    finishes = [r["arrival_time"] + r["e2e"] for r in rows]
    span = (max(finishes) - min(r["arrival_time"] for r in rows)
            if rows else 0.0)
    agg: Dict[str, Any] = {
        "n_requests": len(rows),
        "total_new_tokens": total_new,
        "ttft": percentiles(r["ttft"] for r in rows),
        "tpot": percentiles(r["tpot"] for r in rows
                            if r["n_generated"] > 1),
        "e2e": percentiles(r["e2e"] for r in rows),
        "queue_delay": percentiles(r["queue_delay"] for r in rows),
        "throughput_tok_s": total_new / span if span > 0
        else float("nan"),
        # goodput: finished requests per second of fleet wall time — the
        # serving papers' service-level throughput
        "goodput_req_s": len(rows) / span if span > 0 else float("nan"),
        "preemptions": sum(rep.get("preemptions", 0) for rep in reports),
        "prefix_hit_rate": (
            sum(r["cached_prefix_tokens"] for r in rows)
            / sum(r["prompt_len"] for r in rows)
            if sum(r["prompt_len"] for r in rows) else None),
    }
    return _json_safe(agg)
