"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb`` with one plane per TPU
(``/device:TPU:<n>``) and one for the host (``/host:CPU``), on one clock.
A TPU plane's ``XLA Ops`` line holds every operation the core ran, named
by its HLO text (``%fusion.70 = f32[...] fusion(...), kind=kOutput, ...``);
its ``XLA Modules`` line holds every program execution
(``jit_step_fn(<fingerprint>)``).  The benchmark's own spans
(``jax.profiler.TraceAnnotation``) are host events; ``bench.window``
bounds the traced window.

- busy: the union of the op intervals of a chip inside the window;
- idle share: 1 - busy / window;
- an idle gap: a stretch of the window with no op running, named after
  the benchmark span that covers most of it (what the host was doing).
"""

from __future__ import annotations

import glob
import os
import re
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
_OP_NAME = re.compile(r"^%?([A-Za-z0-9_.\-]+)\s*=\s*(\S+)")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
# control flow: an event that spans the ops of its body, which have events
# of their own
CONTAINERS = ("while", "conditional", "call")
_KIND = re.compile(r"kind=(k\w+)")

Interval = Tuple[float, float]  # (start_ns, end_ns)


@dataclass
class ChipTrace:
    ops: List[Tuple[str, float, float]] = field(default_factory=list)  # name, start, end
    modules: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Trace:
    window: Interval
    chips: Dict[int, ChipTrace]
    host_spans: List[Tuple[str, float, float]]

    # -- device activity ------------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self, chip: int) -> float:
        return union_length(
            clip([(s, e) for _, s, e in self.chips[chip].ops], self.window)
        ) * 1e-9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(c) for c in self.chips) / len(self.chips)

    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s() / self.window_s

    def module_runs(self, prefix: str, chip: int = 0) -> List[Interval]:
        """Executions of programs whose name starts with ``prefix``,
        inside the window."""
        lo, hi = self.window
        return [
            (s, e) for n, s, e in self.chips[chip].modules
            if module_name(n).startswith(prefix) and s >= lo and e <= hi
        ]

    def op_time_s(self, names: Iterable[str], modules: Sequence[Interval],
                  chip: int = 0) -> float:
        """Total device time of the ops named in ``names`` (instruction
        names) that ran inside one of ``modules``."""
        wanted = set(names)
        spans = sorted(modules)
        total = 0.0
        for n, s, e in self.chips[chip].ops:
            if instruction(n) in wanted and _inside(spans, s, e):
                total += e - s
        return total * 1e-9

    # -- breakdown --------------------------------------------------------------

    def top_ops(self, n: int = 10, chip: int = 0) -> List[List]:
        """Device operations that took most time in the window, by
        instruction and program."""
        lo, hi = self.window
        mods = sorted(self.chips[chip].modules, key=lambda m: m[1])
        tot: Counter = Counter()
        for name, s, e in self.chips[chip].ops:
            if s >= lo and e <= hi and opcode(name) not in CONTAINERS:
                tot[f"{_module_at(mods, s)}:{short_op(name)}"] += e - s
        return [[k, v * 1e-9] for k, v in tot.most_common(n)]

    def idle_gaps(self, n: int = 10, chip: int = 0) -> List[List]:
        """The longest stretches with no op running, each named after the
        benchmark span that covers most of it."""
        busy = merge(clip([(s, e) for _, s, e in self.chips[chip].ops], self.window))
        gaps, cur = [], self.window[0]
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < self.window[1]:
            gaps.append((cur, self.window[1]))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.host_activity(g), (g[1] - g[0]) * 1e-9] for g in gaps[:n]]

    def host_activity(self, gap: Interval) -> str:
        best, best_overlap = "host:outside-spans", 0.0
        for name, s, e in self.host_spans:
            if name == WINDOW_SPAN:
                continue
            ov = min(e, gap[1]) - max(s, gap[0])
            if ov > best_overlap:
                best, best_overlap = name, ov
        return best


# -- intervals ----------------------------------------------------------------


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def union_length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _inside(spans: Sequence[Interval], s: float, e: float) -> bool:
    for a, b in spans:  # few executions per trace: a scan is enough
        if a <= s and e <= b:
            return True
    return False


def _module_at(mods, t: float) -> str:
    for n, s, e in mods:
        if s <= t <= e:
            return module_name(n)
    return "?"


# -- names --------------------------------------------------------------------


def instruction(op_event_name: str) -> str:
    """``%fusion.70 = f32[...] fusion(...)`` -> ``fusion.70``."""
    m = _OP_NAME.match(op_event_name)
    return m.group(1) if m else op_event_name


def opcode(op_event_name: str) -> str:
    """``%fusion.70 = (f32[4], f32[2]) fusion(...)`` -> ``fusion``."""
    rhs = op_event_name.split("=", 1)[-1]
    m = _OPCODE.search(rhs)
    return m.group(1) if m else ""


def short_op(op_event_name: str) -> str:
    """Instruction name, opcode, fusion kind and the first shape."""
    m = _OP_NAME.match(op_event_name)
    if not m:
        return op_event_name[:80]
    k = _KIND.search(op_event_name)
    shape = m.group(2).lstrip("(").rstrip(",")
    return f"{m.group(1)} {opcode(op_event_name)} {shape}" + (f" {k.group(1)}" if k else "")


def module_name(module_event_name: str) -> str:
    """``jit_step_fn(9677080685342000563)`` -> ``jit_step_fn``."""
    return module_event_name.split("(", 1)[0]


# -- matmul ops of a compiled program ---------------------------------------------

_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*[^=]*?\s([\w\-]+)\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
MATMUL_OPCODES = ("convolution", "dot")
# the repository's Pallas matmul and attention kernels, by kernel name
PALLAS_MATMUL_KERNELS = ("matmul_nt_direct", "matmul_nn", "matmul_tnn_fused",
                         "matmul_bnt", "matmul_bnn", "attention_fused")


def matmul_instructions(hlo_text: str) -> set:
    """Names of the instructions of a compiled HLO module that multiply
    matrices: ``convolution`` and ``dot`` ops, fusions whose computation
    holds one, and custom calls into the repository's Pallas matmul and
    attention kernels."""
    body_has_mm: Dict[str, bool] = defaultdict(bool)
    calls: Dict[str, str] = {}
    custom: set = set()
    comp = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and "=" not in line.split("{", 1)[0]:
            comp = m.group(1)
            continue
        mi = _INSTR.match(line)
        if not mi:
            continue
        name, opcode = mi.group(1), mi.group(2)
        if opcode in MATMUL_OPCODES:
            body_has_mm[comp] = True
            body_has_mm["@" + name] = True
        elif opcode == "fusion":
            mc = _CALLS.search(line)
            if mc:
                calls[name] = mc.group(1)
        elif opcode == "custom-call" and any(k in line for k in PALLAS_MATMUL_KERNELS):
            custom.add(name)
    out = {n for n in body_has_mm if n.startswith("@")}
    out = {n[1:] for n in out} | custom
    out |= {n for n, c in calls.items() if body_has_mm.get(c)}
    return out


# -- reading ------------------------------------------------------------------------


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, chips: Optional[Sequence[int]] = None) -> Trace:
    """Read a trace: the op and module events of each TPU plane and the
    benchmark's host spans.  The window is the ``bench.window`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev = re.compile(r"^/device:TPU:(\d+)$")
    chip_traces: Dict[int, ChipTrace] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        m = dev.match(plane.name)
        if m:
            idx = int(m.group(1))
            if chips is not None and idx not in chips:
                continue
            ct = chip_traces.setdefault(idx, ChipTrace())
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ct.ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                              for e in line.events]
                elif line.name == "XLA Modules":
                    ct.modules = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                  for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if _is_bench_span(e.name):
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace {path} holds no {WINDOW_SPAN!r} span")
    if not chip_traces:
        raise ValueError(f"trace {path} holds no TPU plane")
    return Trace(window=windows[0], chips=chip_traces, host_spans=spans)


def _is_bench_span(name: str) -> bool:
    return name.startswith(("bench.", "train.", "engine.", "gen."))
