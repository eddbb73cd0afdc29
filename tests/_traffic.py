"""Seeded traffic and the tick loop shared by the serving scenario
tests (overload, multitenant, kv-memory, fleet kill, disaggregation).

Everything here counts in scheduler TICKS: one pass of the loop is one
``step()`` of the engine or router under test, a request's TTFT is the
number of ticks from its submission to its first host-visible token
(the stream feed), and nothing reads a wall clock. A trace is a list of
``(tick, request)`` built up front from a seeded generator, so the same
arguments always yield the same burst."""

from dataclasses import dataclass, field
from typing import Dict, List

from apex_tpu.observability import percentile


def poisson_burst_trace(rng, ticks, base_rate, make_request,
                        burst_start=None, burst_end=None, burst_factor=1):
    """Per tick, ``Poisson(base_rate)`` arrivals - ``burst_factor`` x
    inside ``[burst_start, burst_end)`` - each made by
    ``make_request(tick, k)`` (``k`` the arrival's index in the trace).
    ``make_request`` may return a request or a zero-argument factory
    of one (a fleet run writes the terminal status onto the object, so
    a trace served twice hands out fresh ones)."""
    trace, k = [], 0
    for tick in range(ticks):
        burst = burst_start is not None and burst_start <= tick < burst_end
        rate = base_rate * (burst_factor if burst else 1)
        for _ in range(int(rng.poisson(rate))):
            trace.append((tick, make_request(tick, k)))
            k += 1
    return trace


class TickClock:
    """The clock to inject into an engine whose deadlines and waits
    should count in ticks: it reads the tick :func:`drive` is on."""
    now = 0.0

    def __call__(self):
        return self.now


@dataclass
class Drive:
    """What one pass of :func:`drive` saw."""
    submit: Dict[str, int] = field(default_factory=dict)
    token_ticks: Dict[str, List[int]] = field(default_factory=dict)
    accepted: List[str] = field(default_factory=list)
    shed: List[object] = field(default_factory=list)   # refused at the door
    stalls: int = 0     # ticks with work and no progress
    ticks: int = 0

    @property
    def ttft(self):
        return {u: t[0] - self.submit[u]
                for u, t in self.token_ticks.items()}

    def ttft_p99(self, uids=None):
        """TTFT p99 in ticks, of all requests or of ``uids`` (those of
        them that produced a token)."""
        ttft = self.ttft
        return percentile([ttft[u] for u in (ttft if uids is None else uids)
                           if u in ttft], 99)

    @property
    def itl(self):
        """Host-visible gaps between one request's consecutive tokens
        (tokens surfacing in one tick are 0 apart)."""
        return [b - a for t in self.token_ticks.values()
                for a, b in zip(t, t[1:])]


def drive(server, trace, clock=None, before_step=None, after_step=None):
    """Tick ``server`` (an ``InferenceEngine`` or a ``FleetRouter``)
    through ``trace`` until the trace is submitted and the work done.
    Arrivals go through ``try_add`` (a refusal is a shed at the door);
    ``clock``, the :class:`TickClock` the server was built with, is set
    to each tick as it starts; ``before_step(tick, seen)`` runs after
    the tick's arrivals and before its ``step()`` - the place to kill
    or drain a replica, to abort a request; ``after_step(tick, seen)``
    after the tick's tokens are recorded."""
    seen = Drive()
    i = 0
    while i < len(trace) or server.has_work:
        if clock is not None:
            clock.now = float(seen.ticks)
        while i < len(trace) and trace[i][0] <= seen.ticks:
            req = trace[i][1]
            req = req() if callable(req) else req
            if server.try_add(req):
                seen.submit[req.uid] = seen.ticks
                seen.accepted.append(req.uid)
            else:
                seen.shed.append(req)
            i += 1
        if before_step is not None:
            before_step(seen.ticks, seen)
        had_work = server.has_work
        if not server.step() and had_work:
            seen.stalls += 1
        for uid, tok, _last in server.pop_stream_events():
            if tok >= 0 and uid in seen.submit:
                seen.token_ticks.setdefault(uid, []).append(seen.ticks)
        if after_step is not None:
            after_step(seen.ticks, seen)
        seen.ticks += 1
    return seen
