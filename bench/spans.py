"""Host spans on the device's clock: what the host was doing while the chip
sat idle, named after the innermost span.

The program's own spans nest inside the benchmark's: ``engine.step``
(the benchmark's, around ``ServeEngine.step``) holds ``engine.expire``,
``engine.admit`` (with ``engine.prefill``, ``engine.prefill_wait`` and
``engine.fetch``), ``engine.decode``, ``engine.decode_wait``,
``engine.fetch`` and ``engine.retire``.  An idle
stretch of the chip is named after the span that is innermost over most
of it: ``Trace.idle_gaps`` names it after the span with the largest
overlap, which for nested spans is always the outermost.

The host and device clocks of a trace differ by a small offset.  It is
estimated as the median, over executions, of the end of a wait span less
the end of the program execution it waited for (the wait blocks until
the program has ended and copies nothing, so the two differ by the
clocks' offset and the host's wake-up alone), and subtracted from the host spans before they
are compared with the device's idle stretches.  Nothing else reads it:
the window, busy time and every other metric stay on the trace's clocks.
"""

from __future__ import annotations

import bisect
import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace

OUTSIDE = "host:outside-spans"
# (wait span, program it waits for), per kind of cell
WAITS = {"serve_closed": ("engine.decode_wait", "jit_decode_step"),
         "train": ("train.wait", None)}  # None: the run's step program

Span = Tuple[str, float, float]


def clock_offset_ns(t: trace.Trace, wait_span: str, module: str) -> Optional[float]:
    """Median of (end of each ``wait_span``) - (end of the execution of a
    ``module`` program that ended nearest to it), in ns; None without a
    pair."""
    ends = sorted(e for n, _, e in t.chips[0].modules
                  if trace.module_name(n).startswith(module))
    diffs = []
    for name, _, we in t.host_spans:
        if name != wait_span or not ends:
            continue
        i = bisect.bisect_left(ends, we)
        near = min(ends[max(i - 1, 0):i + 1], key=lambda x: abs(we - x))
        diffs.append(we - near)
    return statistics.median(diffs) if diffs else None


def shifted(spans: Sequence[Span], offset_ns: float) -> List[Span]:
    """Host spans moved onto the device's clock."""
    return [(n, s - offset_ns, e - offset_ns) for n, s, e in spans]


def innermost_cover(gap: trace.Interval, spans: Sequence[Span]) -> Dict[str, float]:
    """ns of ``gap`` under each span that is innermost there (the
    shortest span covering a point), ``OUTSIDE`` where none covers it."""
    lo, hi = gap
    over = [(n, max(s, lo), min(e, hi), e - s) for n, s, e in spans
            if n != trace.WINDOW_SPAN and s < hi and e > lo]
    cuts = sorted({lo, hi, *(s for _, s, _, _ in over), *(e for _, _, e, _ in over)})
    out: Dict[str, float] = defaultdict(float)
    for a, b in zip(cuts, cuts[1:]):
        cover = [(length, n) for n, s, e, length in over if s <= a and e >= b]
        out[min(cover)[1] if cover else OUTSIDE] += b - a
    return dict(out)


def idle_stretches(t: trace.Trace) -> List[trace.Interval]:
    """The window's stretches with no op running on the first chip."""
    busy = trace.merge(trace.clip([(s, e) for _, s, e in t.chips[0].ops], t.window))
    gaps, cur = [], t.window[0]
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t.window[1]:
        gaps.append((cur, t.window[1]))
    return gaps


def idle_by_span(t: trace.Trace, offset_ns: float = 0.0,
                 within: Optional[str] = None) -> Dict[str, float]:
    """Idle seconds of the window under each innermost host span, with the
    host spans moved by ``offset_ns``; with ``within``, only the idle time
    inside spans of that name."""
    spans = sorted((x for x in shifted(t.host_spans, offset_ns) if x[0] != trace.WINDOW_SPAN),
                   key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    longest = max((e - s for _, s, e in spans), default=0.0)
    outer = trace.merge((s, e) for n, s, e in spans if n == within) if within else None
    out: Dict[str, float] = defaultdict(float)
    for gap in idle_stretches(t):
        pieces = trace.clip(outer, gap) if outer is not None else [gap]
        for piece in pieces:
            i = bisect.bisect_left(starts, piece[0] - longest)
            j = bisect.bisect_right(starts, piece[1])
            for name, ns in innermost_cover(piece, spans[i:j]).items():
                out[name] += ns * 1e-9
    return dict(out)


def idle_gaps(t: trace.Trace, offset_ns: float = 0.0, n: int = 10) -> List[list]:
    """The longest idle stretches, each named after the span innermost
    over most of it (host spans moved by ``offset_ns``); as in
    ``Trace.idle_gaps``, time under no span names a stretch only where no
    span covers any of it, so un-nested spans name stretches as there."""
    spans = shifted(t.host_spans, offset_ns)
    gaps = sorted(idle_stretches(t), key=lambda g: g[0] - g[1])[:n]
    out = []
    for g in gaps:
        cover = innermost_cover(g, spans)
        named = {k: v for k, v in cover.items() if k != OUTSIDE} or cover
        out.append([max(named, key=named.get), (g[1] - g[0]) * 1e-9])
    return out


def notes(t: trace.Trace, kind: str, module: Optional[str] = None,
          within: Optional[str] = None) -> List[str]:
    """The run's clock offset, its idle time per innermost span and its
    longest idle stretches so named, as lines for the run's notes."""
    wait, prog = WAITS.get(kind, (None, None))
    prog = prog or module
    off = clock_offset_ns(t, wait, prog) if wait and prog else None
    lines = [f"host-to-device clock offset: "
             + (f"{off * 1e-3:.1f} us (median over {wait} ends less {prog} ends)"
                if off is not None else "not estimated (no wait span paired)")]
    off = off or 0.0
    idle = idle_by_span(t, off, within=within)
    total = sum(idle.values())
    where = f" inside {within}" if within else ""
    lines.append(f"idle seconds per innermost host span{where} "
                 f"({total:.6f} s in all):")
    for name, s in sorted(idle.items(), key=lambda r: -r[1]):
        lines.append(f"  {name} {s:.6f} ({100.0 * s / total if total else 0:.1f}%)")
    lines.append("longest idle stretches by innermost span: "
                 + ", ".join(f"{n} {s * 1e3:.3f} ms" for n, s in idle_gaps(t, off)))
    return lines
