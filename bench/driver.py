"""Open-loop driver: feeds the program's region schedulers on a schedule.

``CrossDCDeployment.submit_batch`` serves a closed batch (it ticks until the
batch drains), so the driver feeds the schedulers itself.  Between ticks it

* routes and submits every request now due, as ``submit_batch``'s first
  loop does (``dep._route(r)``, then ``dep.schedulers[r.home].submit``);
* ticks every region that has work;
* stamps what happened on ``time.perf_counter()``.

A request is timed from its due time, not from when it was submitted.  The
stamps come from hooks the driver sets on the program's instances (nothing
in the program is edited):

* ``sched.on_unit_done``  -> first token: the prefill unit is done, the
  first token is on the host, the KV is trimmed and (offloaded) has been
  through the int8 wire;
* ``dec.admit_many``      -> admission into a decode slot;
* ``dec.step_block``      -> decode blocks (and the tokens that appear in
  ``dec.outputs`` after each);
* ``engine.prefill`` / ``engine.start_chunked`` -> prefill units and chunks.

Each phase runs inside a ``jax.profiler.TraceAnnotation`` named ``bench.*``
so that a trace can name the device's idle gaps by what the host was doing;
backend compiles are recorded from ``jax.monitoring``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass
class Rec:
    rid: int
    due: float                     # perf_counter seconds
    prompt_len: int
    n_out: int
    segment: str
    home: str = ""
    route: str = ""
    submit: Optional[float] = None
    unit_start: Optional[float] = None
    first: Optional[float] = None
    admit: Optional[float] = None
    finish: Optional[float] = None
    decoded_seen: int = 0
    tokens: Optional[list] = None
    truncated: bool = False
    kv_bytes: int = 0


@dataclass
class Records:
    """Everything the driver saw, on the perf_counter clock."""
    reqs: Dict[int, Rec] = field(default_factory=dict)
    units: List[dict] = field(default_factory=list)    # bucketed prefill
    chunks: List[dict] = field(default_factory=list)   # chunked prefill steps
    blocks: List[dict] = field(default_factory=list)   # decode blocks
    token_events: List[tuple] = field(default_factory=list)  # (t, n)
    compiles: List[tuple] = field(default_factory=list)      # (t_end, s)
    ticks: int = 0
    window: tuple = (0.0, 0.0)
    trace_window: Optional[tuple] = None
    # the part of the window that host-clock readings cover: the whole
    # window, or in a traced run the part before the profiler started
    host_span: tuple = (0.0, 0.0)


class CompileLog:
    """Backend compiles, from ``jax.monitoring`` (cache hits are not
    compiles).  One listener per process; ``log`` is appended to."""

    def __init__(self):
        import jax.monitoring
        self.log: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.log.append((time.perf_counter(), float(duration)))


def _annotate(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


class OpenLoop:
    def __init__(self, dep, arrivals, prompts, compile_log: CompileLog):
        from repro.core.router import PRFAAS

        self.dep = dep
        self.prfaas_name = PRFAAS
        self.arrivals = arrivals
        self.prompts = prompts
        self.compile_log = compile_log
        self.rec = Records()
        self.t0 = 0.0
        self._queued = set()
        self._install_hooks()

    def reset(self, arrivals, prompts):
        """A new timeline on the same deployment (hooks stay in place)."""
        self.arrivals, self.prompts = arrivals, prompts
        self.rec = Records()

    # ------------------------------------------------------------- hooks
    def _install_hooks(self):
        dep = self.dep
        for sched in dep.schedulers.values():
            inner = sched.on_unit_done

            def unit_done(engine, rs, lengths, first, caches, wall,
                          _inner=inner):
                out = _inner(engine, rs, lengths, first, caches, wall)
                t = time.perf_counter()
                for r in rs:
                    q = self.rec.reqs.get(r.rid)
                    if q is not None:
                        q.first = t
                        q.kv_bytes = int(r.kv_bytes)
                        self.rec.token_events.append((t, 1))
                return out

            sched.on_unit_done = unit_done
        for dec in dep.decoders.values():
            self._hook_decoder(dec)
        for eng in {id(dep.prfaas): dep.prfaas,
                    id(dep.pd_prefill): dep.pd_prefill}.values():
            self._hook_engine(eng)

    def _hook_decoder(self, dec):
        admit_inner, block_inner = dec.admit_many, dec.step_block

        def admit_many(entries):
            with _annotate("bench.admit"):
                n = admit_inner(entries)
            t = time.perf_counter()
            for req, *_ in list(entries)[:n]:
                q = self.rec.reqs.get(req.rid)
                if q is not None:
                    q.admit = t
            return n

        def step_block():
            active = dec.active.copy()
            lengths = dec.lengths.copy()
            t = time.perf_counter()
            with _annotate("bench.decode_block"):
                n = block_inner()
            self.rec.blocks.append({"t0": t, "t1": time.perf_counter(),
                                    "active": active, "lengths": lengths,
                                    "block": dec.block_size,
                                    "slots": dec.num_slots})
            return n

        dec.admit_many = admit_many
        dec.step_block = step_block

    def _hook_engine(self, eng):
        prefill_inner, chunked_inner = eng.prefill, eng.start_chunked

        def prefill(tokens, lengths=None):
            self._unit_started()
            t = time.perf_counter()
            with _annotate("bench.prefill_unit"):
                out = prefill_inner(tokens, lengths)
            lens = np.asarray(lengths if lengths is not None
                              else [np.asarray(tokens).shape[1]] *
                              np.asarray(tokens).shape[0])
            self.rec.units.append({"t0": t, "t1": time.perf_counter(),
                                   "lengths": lens,
                                   "bucket": eng.bucket_for(int(lens.max())),
                                   "wall": out[2]})
            return out

        def start_chunked(tokens, lengths=None):
            self._unit_started()
            cp = chunked_inner(tokens, lengths)
            step_inner = cp.step

            def step():
                i = cp.i
                t = time.perf_counter()
                with _annotate("bench.prefill_unit"):
                    done = step_inner()
                self.rec.chunks.append({"t0": t, "t1": time.perf_counter(),
                                        "index": i, "chunk": cp.C,
                                        "batch": cp.toks.shape[0],
                                        "lengths": np.asarray(cp.lens)})
                return done

            cp.step = step
            return cp

        eng.prefill = prefill
        eng.start_chunked = start_chunked

    def _unit_started(self):
        """Requests that left a region's queue since the tick began start
        their prefill unit now."""
        t = time.perf_counter()
        still = {r.rid for s in self.dep.schedulers.values()
                 for r, _ in s.queue}
        for rid in self._queued - still:
            q = self.rec.reqs.get(rid)
            if q is not None and q.unit_start is None:
                q.unit_start = t

    # -------------------------------------------------------------- loop
    def _submit(self, a):
        from repro.serving import Request

        dep = self.dep
        r = Request(rid=a.rid, tokens=self.prompts[a.rid],
                    max_new_tokens=a.n_out - 1)
        q = Rec(a.rid, self.t0 + a.due_s, a.prompt_len, a.n_out, a.segment)
        self.rec.reqs[a.rid] = q
        with _annotate("bench.route"):
            dep._route(r)
            engine = (dep.prfaas if r.route == self.prfaas_name
                      else dep.pd_prefill)
            dep.schedulers[r.home].submit(r, engine)
        q.submit = time.perf_counter()
        q.home, q.route = r.home, r.route

    def _observe(self, inflight: List[Rec]):
        t = time.perf_counter()
        left = []
        for q in inflight:
            resp = self.dep.decoders[q.home].outputs.get(q.rid)
            if resp is not None:
                n = len(resp.output_tokens) - 1
                if n > q.decoded_seen:
                    self.rec.token_events.append((t, n - q.decoded_seen))
                    q.decoded_seen = n
                if resp.finished:
                    q.finish = t
                    q.tokens = list(resp.output_tokens)
                    q.truncated = bool(resp.truncated)
                    continue
            left.append(q)
        return left

    def run(self, warm_s: float, window_s: float, post_s: float,
            trace_dir: Optional[str] = None, trace_s: float = 0.0):
        """Serve the timeline; returns ``Records``.  The window is
        [t0 + warm_s, t0 + warm_s + window_s); serving goes on until every
        window request has finished or ``post_s`` past the window's end.

        With ``trace_dir`` the profiler runs from the due time of the last
        window request due at least ``trace_s`` before the window's end, to
        the window's end: so the traced part always holds that request's
        prefill and decode.  Starting the profiler stalls the host, and
        stopping it stalls it for seconds while the trace is written: the
        stop falls after the window, and host-clock readings are taken only
        before the start (``Records.host_span``)."""
        import jax

        scheds = list(self.dep.schedulers.values())
        self.t0 = time.perf_counter()
        w0, w1 = self.t0 + warm_s, self.t0 + warm_s + window_s
        stop = w1 + post_s
        self.rec.window = self.rec.host_span = (w0, w1)
        trace_at = None
        if trace_dir:
            due = [self.t0 + a.due_s for a in self.arrivals
                   if a.segment == "window"
                   and self.t0 + a.due_s <= w1 - trace_s]
            trace_at = max(due, default=w1 - trace_s)
        nxt, inflight = 0, []
        tracing = False
        while True:
            now = time.perf_counter()
            if trace_at is not None and not tracing \
                    and self.rec.trace_window is None and now >= trace_at:
                self.rec.host_span = (w0, now)
                jax.profiler.start_trace(trace_dir)
                now = time.perf_counter()
                with _annotate("bench.mark"):      # places host stamps
                    pass
                tracing, self.rec.trace_window = True, (now, None)
            if tracing and now >= w1:
                jax.profiler.stop_trace()
                tracing = False
                self.rec.trace_window = (self.rec.trace_window[0], now)
            while nxt < len(self.arrivals) and \
                    self.t0 + self.arrivals[nxt].due_s <= now:
                self._submit(self.arrivals[nxt])
                inflight.append(self.rec.reqs[self.arrivals[nxt].rid])
                nxt += 1
            if now >= w1 and not any(q.segment == "window"
                                     for q in inflight):
                break                      # every window request finished
            if now >= stop:
                break
            busy = [s for s in scheds if s.has_work]
            if busy:
                self._queued = {r.rid for s in scheds for r, _ in s.queue}
                for s in busy:
                    with _annotate("bench.tick"):
                        s.tick()
                    self.rec.ticks += 1
                inflight = self._observe(inflight)
            elif nxt < len(self.arrivals):
                wait = self.t0 + self.arrivals[nxt].due_s - time.perf_counter()
                if wait > 0:
                    with _annotate("bench.wait_arrival"):
                        time.sleep(min(wait, 0.05))
            else:
                break
        if tracing:
            jax.profiler.stop_trace()
            self.rec.trace_window = (self.rec.trace_window[0],
                                     time.perf_counter())
        self.rec.compiles = list(self.compile_log.log)
        return self.rec


def warm_pass(dep, arrivals, seed: int, vocab: int, rid0: int = 10 ** 9):
    """Serve one request per distinct prompt length of the timeline as a
    closed batch (one output token each), so that every length-dependent
    program of the served path (trim, wire quantize/dequantize, admission
    padding) is compiled before the window."""
    from repro.serving import Request

    rng = np.random.default_rng([seed, 3])
    lengths = sorted({a.prompt_len for a in arrivals})
    reqs = [Request(rid=rid0 + i,
                    tokens=rng.integers(0, vocab, n).astype(np.int32),
                    max_new_tokens=1)
            for i, n in enumerate(lengths)]
    dep.submit_batch(reqs)
    return len(reqs)
