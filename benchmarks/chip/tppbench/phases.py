"""The program's phase spans in a profiler trace, and what they say.

The serving engine marks its phases with ``jax.profiler.TraceAnnotation``
spans named ``serve.*``: ``serve.step`` around a decode step, with the
children ``serve.flush``, ``serve.score``, ``serve.score.wait``,
``serve.select``, ``serve.tables``, ``serve.dispatch``,
``serve.tokens.wait``, ``serve.policy`` and ``serve.interval``;
``serve.prefill`` with ``serve.prefill.forward`` and
``serve.prefill.write``; ``serve.finish``. A wait span (a host read of a
device result) ends in ``.wait``. The profiler records them on the host
plane, on the clock of the device planes, while it is active.

``tppbench.trace.events`` keeps only the harness's ``bench.*`` spans; this
module reads the program's, with their stats, and answers:

* a span's self time (its duration less what its children cover), and the
  time its wait spans cover;
* device idle time inside a span;
* idle gaps, each named by the innermost span that covers its middle: a
  ``serve.*`` span where one covers it, else the harness's span, else
  ``host.other``. On a trace with no ``serve.*`` span this is exactly
  ``Trace.idle_gaps``. A gap that runs across phases goes whole to the
  one at its middle; ``idle_by_phase`` splits it among them instead;
* five readings of the decode step, placement and prefill layers
  (``readings``).

Spans are taken to be properly nested, as spans of one thread are.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from tppbench.trace import DEVICE_PREFIX, Event, Trace, _union

PREFIX = "serve."
STEP = "serve.step"
PREFILL = "serve.prefill"
WAIT_SUFFIX = ".wait"
# TPP's host work: staged copies, selection and touch, the policy, the interval
PLACEMENT = ("serve.flush", "serve.select", "serve.policy", "serve.interval")


@dataclasses.dataclass(frozen=True)
class Span(Event):
    stats: tuple = ()  # (key, value) pairs of the annotation


def spans(path: str) -> List[Span]:
    """The program's ``serve.*`` spans of one xplane file, with their stats."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append(Span(plane.name, line.name, e.name, int(e.start_ns),
                                    int(e.duration_ns), tuple(e.stats)))
    return out


class Nest:
    """Nested spans in start order, each with its parent's index."""

    def __init__(self, evs: Sequence[Event]) -> None:
        self.spans = sorted(evs, key=lambda e: (e.start, -e.dur))
        self.starts = [e.start for e in self.spans]
        self.parent: List[int] = []
        open_: List[int] = []
        for i, e in enumerate(self.spans):
            while open_ and self.spans[open_[-1]].end <= e.start:
                open_.pop()
            self.parent.append(open_[-1] if open_ else -1)
            open_.append(i)

    def innermost(self, t: int) -> Optional[Event]:
        """The innermost span with ``start <= t < end``, or None. Every span
        that covers ``t`` is the last span starting by ``t`` or one of its
        ancestors."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and t >= self.spans[i].end:
            i = self.parent[i]
        return self.spans[i] if i >= 0 else None

    def inside(self, i: int) -> range:
        """Indices of the spans nested in span ``i``."""
        return range(i + 1, bisect.bisect_left(self.starts, self.spans[i].end, lo=i + 1))

    def covered_ns(self, i: int, match: Callable[[str], bool] = lambda n: True) -> int:
        """Time inside span ``i`` that its nested spans named to ``match`` cover."""
        s = self.spans[i]
        return sum(min(e, s.end) - max(b, s.start) for b, e in _union(
            (self.spans[j].start, self.spans[j].end) for j in self.inside(i)
            if match(self.spans[j].name)))

    def own(self, i: int) -> List[Tuple[int, int]]:
        """The intervals of span ``i`` that none of its nested spans covers."""
        out, cur, end = [], self.spans[i].start, self.spans[i].end
        for a, b in _union((self.spans[j].start, self.spans[j].end)
                           for j in self.inside(i)) + [(end, end)]:
            if a > cur:
                out.append((cur, a))
            cur = max(cur, b)
        return out

    def self_ns(self, i: int) -> int:
        return sum(b - a for a, b in self.own(i))


class Phases:
    """A trace's device work set against the program's phase spans."""

    def __init__(self, trace: Trace, program: Sequence[Span]) -> None:
        self.trace = trace
        self.program = Nest(program)
        self.harness = Nest(trace.spans)
        self._busy: Dict[str, tuple] = {}  # chip -> (intervals, starts, ends, prefix sums)

    def named(self, name: str) -> List[int]:
        return [i for i, s in enumerate(self.program.spans) if s.name == name]

    # -- device time inside spans -------------------------------------- #
    def _busy_of(self, chip: str):
        if chip not in self._busy:
            busy = _union((o.start, o.end) for o in self.trace.ops if o.plane == chip)
            acc = [0]
            for s, e in busy:
                acc.append(acc[-1] + e - s)
            self._busy[chip] = (busy, [s for s, _ in busy], [e for _, e in busy], acc)
        return self._busy[chip]

    def busy_ns(self, lo: int, hi: int) -> float:
        """Device busy time inside ``[lo, hi)``, per chip, averaged over chips."""
        if not self.trace.chips or hi <= lo:
            return 0.0
        total = 0
        for chip in self.trace.chips:
            busy, starts, ends, acc = self._busy_of(chip)
            i = bisect.bisect_right(ends, lo)  # first interval ending after lo
            j = bisect.bisect_left(starts, hi, lo=i)  # first starting at or after hi
            if j <= i:
                continue
            total += acc[j] - acc[i]
            total -= max(0, lo - busy[i][0])  # clip the first and last intervals
            total -= max(0, busy[j - 1][1] - hi)
        return total / len(self.trace.chips)

    def idle_ns(self, span: Event) -> float:
        return span.dur - self.busy_ns(span.start, span.end)

    def idle_gaps(self, window: Tuple[int, int], n: int = 10) -> List[List]:
        """Idle device time inside ``window`` (ns), summed by the innermost
        span that covers the middle of each gap."""
        acc: Dict[str, float] = collections.defaultdict(float)
        chips = self.trace.chips
        for chip in chips:
            busy = self._busy_of(chip)[0]
            edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
            for s, e in zip(edges[::2], edges[1::2]):
                s, e = max(s, window[0]), min(e, window[1])
                if e <= s:
                    continue
                mid = (s + e) // 2
                span = self.program.innermost(mid) or self.harness.innermost(mid)
                acc[span.name if span else "host.other"] += (e - s) / 1e9 / len(chips)
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_by_phase(self) -> List[List]:
        """Device idle time inside each span's own time (its duration less
        its nested spans), summed by name, in s. Unlike ``idle_gaps``, a gap
        that runs across several phases is split among them."""
        if not self.trace.chips:
            return []
        acc: Dict[str, float] = collections.defaultdict(float)
        for i, s in enumerate(self.program.spans):
            acc[s.name] += sum(b - a - self.busy_ns(a, b) for a, b in self.program.own(i)) / 1e9
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])]

    # -- readings ------------------------------------------------------ #
    def step_host_ms(self) -> Optional[float]:
        """Mean over decode steps of the step's host time: its duration less
        the host reads it waits in, in ms."""
        steps = self.named(STEP)
        if not steps:
            return None
        p = self.program
        return sum(p.spans[i].dur - p.covered_ns(i, lambda n: n.endswith(WAIT_SUFFIX))
                   for i in steps) / len(steps) / 1e6

    def step_idle_ms(self) -> Optional[float]:
        """Mean device idle time inside a decode step, in ms."""
        steps = self.named(STEP)
        if not steps or not self.trace.chips:
            return None
        return sum(self.idle_ns(self.program.spans[i]) for i in steps) / len(steps) / 1e6

    def placement_host_ms_per_step(self) -> Optional[float]:
        """Self time of TPP's host phases (``PLACEMENT``) from the first decode
        step's start to the last one's end, per decode step, in ms. A flush
        that a prefill's write makes between two steps counts too."""
        steps = self.named(STEP)
        if not steps:
            return None
        p = self.program
        lo, hi = p.spans[steps[0]].start, p.spans[steps[-1]].end
        total = sum(p.self_ns(i) for i, s in enumerate(p.spans)
                    if s.name in PLACEMENT and lo <= s.start and s.end <= hi)
        return total / len(steps) / 1e6

    def prefill_idle_pct(self) -> Optional[float]:
        """Device idle time inside prefills over their duration, in %."""
        pre = [self.program.spans[i] for i in self.named(PREFILL)]
        total = sum(s.dur for s in pre)
        if not total or not self.trace.chips:
            return None
        return 100.0 * sum(self.idle_ns(s) for s in pre) / total

    def events_per_step(self) -> List[int]:
        """``serve.*`` spans inside each decode step, itself included."""
        return [1 + len(self.program.inside(i)) for i in self.named(STEP)]


def local_access_pct(fast: int, slow: int) -> Optional[float]:
    """Share of attended pages that sat in the fast tier, in %."""
    return 100.0 * fast / (fast + slow) if fast + slow else None


def readings(phases: Phases, access: Optional[Tuple[int, int]] = None) -> Dict[str, float]:
    """The five readings that are not None: ``access`` is the window's
    (Δ``access_fast``, Δ``access_slow``)."""
    out = {"step_host_ms": phases.step_host_ms(), "step_idle_ms": phases.step_idle_ms(),
           "placement_host_ms_per_step": phases.placement_host_ms_per_step(),
           "prefill_idle_pct": phases.prefill_idle_pct(),
           "local_access_pct": local_access_pct(*access) if access else None}
    return {k: v for k, v in out.items() if v is not None}
