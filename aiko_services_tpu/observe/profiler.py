# Fleet health plane, part 2: the decode-round phase profiler
# (ISSUE 11; a record of every round and the device trace's clock,
# ISSUE 24).
#
# ContinuousDecoder.pump() tells a PhaseProfiler where every phase of
# a serving round begins —
#
#   plan           host-side round planning (active mask, budgets,
#                  cache fit)
#   scan_dispatch  dispatching the compiled decode scan (async)
#   spec_verify    same boundary in speculative mode (the dispatched
#                  program is the widened verify step)
#   admit_dispatch bucketed prefill admits queued behind the scan
#   extend_dispatch chunked-prefill extends queued behind the scan
#   host_sync      the device_get wall: the host waits here while the
#                  device executes everything dispatched above
#   wave_resolve   resolving earlier rounds' deferred admit firsts
#   deliver        walking emissions into callbacks / retirements
#   other          whatever no phase covered
#
# and the profiler keeps three things from that ONE set of boundaries
# (enter() costs one perf_counter read, so it is ALWAYS ON):
#
#   sums     wall seconds per phase (phase_stats(), the bench's
#            lat_llama_phase_* fields, serving_phase_seconds_total)
#   a ring   one plain tuple per committed round (ROUND_RECORD), the
#            newest RING_ROUNDS of them: a stalled round dissolves in
#            a mean and stands out here.  round_log() hands the ring
#            to a reader that holds no reference to the decoder.
#   spans    jax.profiler.TraceAnnotations (SPAN_ROUND around the
#            round, SPANS[phase] inside it), which record only while a
#            profiler session runs and then sit on /host:CPU on the
#            clock of the device planes: a trace can say which phase
#            the device waited for.
#
# Host wall time says nothing about device bytes: no phase carries a
# bandwidth.  What the device did inside host_sync is the trace's to
# say (PERF.md, layers).
#
# Opt-in deep capture: arm_trace() opens a jax.profiler trace window
# (XLA-level timeline) for `duration` seconds, armed by environment
# (AIKO_PROFILE_TRACE=<logdir>, AIKO_PROFILE_TRACE_S=<seconds>) or
# programmatically — the HealthAggregator's on_alert hook can arm it,
# so an SLO breach captures the device timeline of the very next
# rounds.  jax imports lazily: observe/ stays importable without
# touching the accelerator stack.

from __future__ import annotations

import collections
import os
import time
import weakref

from .metrics import MetricsRegistry, default_registry

__all__ = ["PhaseProfiler", "PHASES", "ROUND_FIELDS", "ROUND_RECORD",
           "SPAN_ROUND", "SPANS",
           "arm_trace", "round_log", "slow_round", "trace_state"]

PHASES = ("plan", "scan_dispatch", "spec_verify", "admit_dispatch",
          "extend_dispatch", "host_sync", "wave_resolve", "deliver",
          "other")

# the same phases as a profiler session sees them: admit and extend are
# one span, and so are the wave resolve and the walk
SPAN_ROUND = "aiko.decoder.round"
SPANS = {"plan": "aiko.decoder.plan",
         "scan_dispatch": "aiko.decoder.dispatch_step",
         "spec_verify": "aiko.decoder.dispatch_step",
         "admit_dispatch": "aiko.decoder.dispatch_prefill",
         "extend_dispatch": "aiko.decoder.dispatch_prefill",
         "host_sync": "aiko.decoder.sync",
         "wave_resolve": "aiko.decoder.deliver",
         "deliver": "aiko.decoder.deliver"}

# one ring record, a plain tuple in this order: `rounds` is the decoder's
# stats["rounds"] at commit, `t0` the round's begin on perf_counter,
# `gap_s` the time since the previous committed round ended, and
# `idle_before` says that the decoder had nothing to do at that end
ROUND_FIELDS = ("seq", "rounds", "t0", "gap_s", "idle_before", "wall_s") \
    + PHASES + ("num_steps", "slots", "prefill_tokens", "pending")
# the whole record: ROUND_FIELDS, then what later PRs added.  The
# benchmark's reader zips a record with ROUND_FIELDS and its test
# builds records of exactly that length (neither file is this repo's
# to edit outside a benchmark PR), so ROUND_FIELDS stays what PR 24
# made it and a new field goes behind it: `attend_width`, the
# positions the round's step built its views and attended at (the
# dense cache's time extent; 0 for a round that ran no step); then what
# stood ahead of the round's step on the device and how deep its own
# pieces read (ISSUE 36), each counted where the program is dispatched:
# `prefill_ahead`, the prompt tokens of the admit and extend programs
# dispatched since the step before this one was (0 for a round that ran
# no step: the tokens wait for the next that does), `prefill_pieces`, the
# admit and extend programs this round dispatched (a program, not a
# row), and `prefill_prefix_tokens`, over the rows of those programs the
# positions already in the cache that the row's attention reads (an
# extend row's offset, 0 for a cold admit)
ROUND_RECORD = ROUND_FIELDS + ("attend_width", "prefill_ahead",
                               "prefill_pieces", "prefill_prefix_tokens")
RING_ROUNDS = 8192              # over ten minutes of 100 ms rounds

# a round is slow when it and the gap before it took longer than both
SLOW_ROUND_FLOOR_S = 0.5
SLOW_ROUND_FACTOR = 5.0

_profilers: weakref.WeakValueDictionary = weakref.WeakValueDictionary()


def round_log(name: str | None = None) -> list:
    """The ring of the profiler registered under `name`, oldest record
    first; with no name, of the one profiler the process has."""
    if name is None:
        if len(_profilers) != 1:
            raise LookupError(f"round_log() needs a name: the process has "
                              f"profilers {sorted(_profilers)}")
        name = next(iter(_profilers))
    profiler = _profilers.get(name)
    if profiler is None:
        raise LookupError(f"no profiler named {name!r}; "
                          f"there are {sorted(_profilers)}")
    return list(profiler.ring)


def slow_round(record: tuple, mean_s: float | None) -> str | None:
    """What to log about a round that stood still, or None: its
    sequence number and every phase's milliseconds.  `mean_s` is the
    running mean round BEFORE this one; a round that follows an idle
    decoder is nobody's stall."""
    seq, _, _, gap_s, idle_before, wall_s = record[:6]
    took = gap_s + wall_s
    if idle_before or mean_s is None or took <= max(
            SLOW_ROUND_FLOOR_S, SLOW_ROUND_FACTOR * mean_s):
        return None
    phases = " ".join(f"{phase}={1e3 * seconds:.1f}" for phase, seconds
                      in zip(PHASES, record[6:6 + len(PHASES)]))
    return (f"slow round seq={seq}: {1e3 * took:.1f} ms against a mean of "
            f"{1e3 * mean_s:.1f} ms (gap before it {1e3 * gap_s:.1f} ms); "
            f"phase ms: {phases}")


_annotation = None


def _annotation_class():
    """jax.profiler.TraceAnnotation, imported at the first round and
    not with this module."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


# -- jax.profiler capture window ---------------------------------------------

_trace = {"armed": False, "active": False, "logdir": None,
          "until": 0.0, "duration": 3.0, "captures": 0, "error": None}


def arm_trace(logdir: str, duration: float = 3.0) -> None:
    """Arm a one-shot jax.profiler capture window: the next profiled
    round starts the trace, and it stops `duration` seconds later."""
    _trace["armed"] = True
    _trace["logdir"] = str(logdir)
    _trace["duration"] = float(duration)


def trace_state() -> dict:
    return dict(_trace)


def _env_arm() -> None:
    logdir = os.environ.get("AIKO_PROFILE_TRACE", "")
    if logdir:
        arm_trace(logdir,
                  float(os.environ.get("AIKO_PROFILE_TRACE_S", "3.0")))


_env_arm()


def _trace_tick() -> None:
    """Advance the capture window state machine (called once per
    committed round — zero cost when nothing is armed)."""
    if not (_trace["armed"] or _trace["active"]):
        return
    now = time.perf_counter()
    if _trace["armed"] and not _trace["active"]:
        _trace["armed"] = False
        try:
            import jax
            jax.profiler.start_trace(_trace["logdir"])
            _trace["active"] = True
            _trace["until"] = now + _trace["duration"]
        except Exception as exc:    # profiler unavailable: disarm, note
            _trace["error"] = repr(exc)
        return
    if _trace["active"] and now >= _trace["until"]:
        try:
            import jax
            jax.profiler.stop_trace()
            _trace["captures"] += 1
        except Exception as exc:
            _trace["error"] = repr(exc)
        _trace["active"] = False


class PhaseProfiler:
    """Per-round wall-time attribution into named phases.

    Usage (the pump loop's shape):

        profiler.begin_round()             # the round begins in "plan"
        ...planning...
        profiler.enter("scan_dispatch")    # "plan" ends here
        ...dispatch scan...
        profiler.enter("admit_dispatch")
        ...
        profiler.commit_round(...)  # or abandon_round() for idle ticks

    enter(name) charges the time since the previous boundary to the
    phase that was open and opens `name`, as a sum, as a field of the
    round's record and as a span of a profiler session; commit folds
    the staged phases into the accumulators and appends the record to
    the ring.  abandon_round() discards them — idle pump ticks must not
    dilute the attribution, and leave no record."""

    def __init__(self, name: str = "decoder",
                 registry: MetricsRegistry | None = None):
        self.name = name
        self.rounds = 0
        self.wall_s = 0.0
        self.phase_s = {phase: 0.0 for phase in PHASES}
        self.ring: collections.deque = collections.deque(maxlen=RING_ROUNDS)
        self.idle = True        # nothing was live when the last round ended
        self._seq = 0
        self._t0 = 0.0
        self._last = 0.0
        self._end = None        # when the last committed round ended
        self._phase = "plan"
        self._staged = dict.fromkeys(PHASES, 0.0)
        self._round_span = self._span = self._span_name = None
        registry = registry or default_registry()
        self._seconds_counters = {
            phase: registry.counter(
                "serving_phase_seconds_total",
                "decode-round wall seconds by phase",
                labels={"decoder": name, "phase": phase})
            for phase in PHASES}
        _profilers[name] = self

    @property
    def seq(self) -> int:
        """The `seq` that the open round's commit_round will write: the
        one counter a request's journey is joined to the ring by.  Read
        inside a round (the decoder does, where it hands over a token:
        a round that hands one over is committed); between rounds it
        is the number the next one will take."""
        return self._seq + 1

    # -- the hot-path API (one perf_counter read each) ---------------------
    def begin_round(self) -> None:
        """Open the round; it begins in "plan"."""
        self._close_spans()             # of a round that raised half way
        self._round_span = _annotation_class()(SPAN_ROUND)
        self._round_span.__enter__()
        self._t0 = self._last = time.perf_counter()
        for name in PHASES:
            self._staged[name] = 0.0
        self._open("plan")

    def enter(self, phase: str) -> None:
        now = time.perf_counter()
        self._staged[self._phase] += now - self._last
        self._last = now
        self._open(phase)

    def _open(self, phase: str) -> None:
        self._phase = phase
        span_name = SPANS.get(phase)
        if span_name != self._span_name:
            if self._span is not None:
                self._span.__exit__(None, None, None)
            self._span = None
            if span_name is not None:
                self._span = _annotation_class()(span_name)
                self._span.__enter__()
            self._span_name = span_name

    def _close_spans(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
        if self._round_span is not None:
            self._round_span.__exit__(None, None, None)
        self._round_span = self._span = self._span_name = None

    def abandon_round(self) -> None:
        self._close_spans()
        # idle ticks still advance the capture window: a trace armed
        # by an alert must STOP on schedule even if decode work
        # ceases right after the breach (load shed/collapsed) —
        # otherwise the capture buffers unboundedly and the artifact
        # never finalizes
        _trace_tick()

    def commit_round(self, rounds: int = 0, num_steps: int = 0,
                     slots: int = 0, prefill_tokens: int = 0,
                     pending: int = 0, attend_width: int = 0,
                     prefill_ahead: int = 0, prefill_pieces: int = 0,
                     prefill_prefix_tokens: int = 0) -> tuple:
        """Fold the round into the sums and the ring; returns its
        record (ROUND_RECORD)."""
        now = time.perf_counter()
        staged = self._staged
        staged[self._phase] += now - self._last
        self._close_spans()
        total = now - self._t0
        for phase, dt in staged.items():
            if dt:
                self.phase_s[phase] += dt
                self._seconds_counters[phase].inc(dt)
        self.rounds += 1
        self.wall_s += total
        self._seq += 1
        record = (self._seq, rounds, self._t0,
                  0.0 if self._end is None else self._t0 - self._end,
                  self.idle, total, *staged.values(),
                  num_steps, slots, prefill_tokens, pending,
                  attend_width, prefill_ahead, prefill_pieces,
                  prefill_prefix_tokens)
        self.ring.append(record)
        self._end, self.idle = now, False
        _trace_tick()
        return record

    # -- reporting ----------------------------------------------------------
    def reset(self) -> None:
        """Zero the sums; the ring keeps its records."""
        self.rounds = 0
        self.wall_s = 0.0
        for phase in self.phase_s:
            self.phase_s[phase] = 0.0

    def attributed_fraction(self) -> float:
        """Fraction of committed round wall time carrying a NAMED
        phase (1 - other/wall) — the bench acceptance number."""
        if self.wall_s <= 0:
            return 0.0
        return max(0.0, 1.0 - self.phase_s["other"] / self.wall_s)

    def phase_stats(self) -> dict:
        """{"rounds", "wall_s", "attributed_frac", "phases": {name:
        {"s", "frac", "ms_per_round"}}} — phases with no time are
        omitted (speculative vs plain mode each uses its own dispatch
        phase)."""
        phases = {}
        for phase in PHASES:
            seconds = self.phase_s[phase]
            if seconds <= 0.0:
                continue
            phases[phase] = {
                "s": seconds,
                "frac": seconds / self.wall_s if self.wall_s > 0
                else 0.0,
                "ms_per_round": seconds * 1000.0 / self.rounds
                if self.rounds else 0.0,
            }
        return {"rounds": self.rounds, "wall_s": self.wall_s,
                "attributed_frac": self.attributed_fraction(),
                "phases": phases}
