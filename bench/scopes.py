"""Device time per dispatch scope: the op, OpKey and arm each device op ran for.

The program wraps each executed dispatch arm in a named scope
(``repro.core.engine.scope_name``)::

    dispatch.<op>.<m>x<n>x<k>[.g<g>].d<dsize>.<arm>

e.g. ``dispatch.NT.4096x26752x4096.d4.XLA_NT`` or
``dispatch.BNT.3072x2048x64.g24.d4.XLA_BNT``.  JAX writes the scope into
each instruction's ``op_name`` (``jvp(<scope>)/dot_general`` forward,
``transpose(jvp(<scope>))/dot_general`` backward; the unfused ATTN plan
nests its BNT and BNN scopes inside its own).  An op belongs to the
innermost scope of its ``op_name``.

The device trace names each op's ``op_name``: the TPU planes' ``XLA Ops``
event metadata carry it as the ``tf_op`` stat (``op_names_in_trace``).
That reader decodes the ``.xplane.pb`` wire format itself because
``jax.profiler.ProfileData``, which ``trace.load`` uses, gives each event
its own stats (offset, duration) but not its metadata's stats, where
``tf_op`` and ``program_id`` live.
On the TPU a fusion's metadata is its product's where it holds one, and a
Pallas kernel's is its scope's (checked on the compiled v5e programs in
``tests/test_tpu_compile.py``), so each op is attributed to the arm that
ran it.

``instruction_times`` sums device time per instruction within a
program's executions, as ``Trace.op_time_s`` does, and ``scope_times``
sums it per scope.
"""

from __future__ import annotations

import os
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import counts, trace

SCOPE = re.compile(
    r"dispatch\.([A-Z]+)\.(\d+)x(\d+)x(\d+)(?:\.g(\d+))?\.d(\d+)\.([A-Za-z0-9_]+(?:@[0-9x]+)?)"
)


@dataclass(frozen=True)
class Scope:
    op: str
    m: int
    n: int
    k: int
    g: int
    dsize: int
    arm: str

    @property
    def key(self) -> str:
        """``m x n x k``, with ``x g`` for grouped ops, and ``d<dsize>``."""
        g = f"x{self.g}" if self.g != 1 else ""
        return f"{self.m}x{self.n}x{self.k}{g}.d{self.dsize}"


def innermost(op_name: str) -> Optional[Scope]:
    """The innermost dispatch scope named in an ``op_name``, or None."""
    last = None
    for last in SCOPE.finditer(op_name or ""):
        pass
    if last is None:
        return None
    op, m, n, k, g, d, arm = last.groups()
    return Scope(op, int(m), int(n), int(k), int(g or 1), int(d), arm)


# -- op_name from the device trace -----------------------------------------------


def _varint(b, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} in a trace")
        yield f, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_names_in_trace(path: str) -> Dict[Tuple[int, str], str]:
    """(program id, instruction) -> ``op_name`` of every op the TPU planes
    of an ``.xplane.pb`` describe (the ``tf_op`` stat of the ``XLA Ops``
    event metadata; its ``program_id`` stat names the program).  Read from
    the protobuf wire format: ``XSpace.planes`` (1); ``XPlane.name`` (2),
    ``event_metadata`` (4) and ``stat_metadata`` (5), maps whose entries
    hold a key (1) and a value (2); ``XEventMetadata.name`` (2) and
    ``stats`` (5); ``XStat.metadata_id`` (1) and its value, an integer (3,
    4), a string (5) or a reference to a ``stat_metadata`` name (7)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out: Dict[Tuple[int, str], str] = {}
    for field, plane in _fields(data):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, pv in _fields(plane):
            if pf == 2:
                name = _text(pv)
            elif pf == 4:
                events.append(pv)
            elif pf == 5:
                for ef, ev in _fields(pv):
                    if ef == 2:
                        sm = dict(_fields(ev))
                        stat_names[sm.get(1, 0)] = _text(sm.get(2, b""))
        if not name.startswith("/device:TPU:"):
            continue
        for entry in events:
            meta = next((v for f, v in _fields(entry) if f == 2), None)
            if meta is None:
                continue
            ev_name, stats = "", {}
            for ef, ev in _fields(meta):
                if ef == 2:
                    ev_name = _text(ev)
                elif ef == 5:
                    st = dict(_fields(ev))
                    label = stat_names.get(st.get(1))
                    if label == "tf_op":
                        stats[label] = _text(st[5]) if 5 in st else stat_names.get(st.get(7), "")
                    elif label == "program_id":
                        stats[label] = st.get(3, st.get(4))
            if "tf_op" in stats and "program_id" in stats:
                # ``op_name:op_type``; JAX leaves the type empty
                out[(stats["program_id"], trace.instruction(ev_name))] = \
                    stats["tf_op"].rsplit(":", 1)[0]
    return out


def program_id(module_event_name: str) -> Optional[int]:
    """``jit_step_fn(9677080685342000563)`` -> 9677080685342000563."""
    m = re.search(r"\((\d+)\)$", module_event_name)
    return int(m.group(1)) if m else None


def step_op_names(t: trace.Trace, path: str, module: str) -> Dict[str, str]:
    """Instruction -> ``op_name`` for the programs named ``module`` in the
    trace ``t`` read from ``path``."""
    ids = {program_id(n) for n, _, _ in t.chips[0].modules
           if trace.module_name(n).startswith(module)}
    return {instr: op for (pid, instr), op in op_names_in_trace(path).items() if pid in ids}


# -- device time per scope -------------------------------------------------------------


def instruction_times(t: trace.Trace, runs: Sequence[trace.Interval]) -> Dict[str, float]:
    """Seconds of device time per instruction of the ops that ran inside
    one of ``runs``; control-flow containers, whose bodies' ops have
    events of their own, are left out."""
    spans = sorted(runs)
    out: Dict[str, float] = defaultdict(float)
    for name, s, e in t.chips[0].ops:
        if trace.opcode(name) not in trace.CONTAINERS and trace._inside(spans, s, e):
            out[trace.instruction(name)] += (e - s) * 1e-9
    return dict(out)


def scope_times(instr_times: Dict[str, float],
                op_names: Dict[str, str]) -> Dict[Optional[Scope], float]:
    """``instruction_times`` summed per innermost scope (None: unscoped)."""
    out: Dict[Optional[Scope], float] = defaultdict(float)
    for instr, v in instr_times.items():
        out[innermost(op_names.get(instr, ""))] += v
    return dict(out)


def table(times: Dict[Optional[Scope], float], steps: int, step_s: float) -> List[str]:
    """The per-OpKey table of a traced step: op, OpKey, arm, ms per step
    and share of the step's device time, largest first, with the unscoped
    ops last."""
    rows = sorted(((s, v) for s, v in times.items() if s is not None), key=lambda r: -r[1])
    lines = [f"{'op':<5s} {'OpKey (m x n x k [x g], dsize)':<32s} {'arm':<30s} "
             f"{'ms/step':>9s} {'share':>7s}"]
    for s, v in rows + [(None, times.get(None, 0.0))]:
        op, key, arm = (s.op, s.key, s.arm) if s else ("-", "unscoped", "-")
        lines.append(f"{op:<5s} {key:<32s} {arm:<30s} {v / steps * 1e3:9.3f} "
                     f"{100.0 * v / (steps * step_s):6.2f}%")
    return lines


# -- the step's required NT products --------------------------------------------------


def required_nt(c: dict, w: dict) -> List[counts.Work]:
    """The NT products (``y = x W^T``) one training step requires: each
    FCN layer's forward; each LM layer's seven dense projections and the
    tied head."""
    eb = np.dtype(c["dtype"]).itemsize
    if c["family"] == "fcn":
        dims = [c["input_dim"], *c["hidden"], c["output_dim"]]
        return [counts.gemm(w["batch"], o, i, eb) for i, o in zip(dims[:-1], dims[1:])]
    if c["family"] == "lm":
        d, h, kv, dh, f = c["d_model"], c["n_heads"], c["n_kv"], c["d_head"], c["d_ff"]
        T = w["batch"] * w["seq"]
        dense = [(h * dh, d), (kv * dh, d), (kv * dh, d), (d, h * dh), (f, d), (f, d), (d, f)]
        return ([counts.gemm(T, o, i, eb) for o, i in dense] * c["n_layers"]
                + [counts.gemm(T, c["vocab"], d, eb)])
    raise ValueError(f"no NT products known for family {c['family']!r}")


# -- one traced training run ----------------------------------------------------------


def reduce_train(run) -> Optional[dict]:
    """Per-scope device time of a traced training run's step program,
    computed once per run (kept in ``run.counters["scopes"]``) with its
    notes.  None where the run has no trace of the step program or the
    trace names no op."""
    k, t = run.counters, run.trace_data
    if k.get("kind") != "train" or t is None or not k.get("step_module"):
        return None
    if "scopes" in k:
        return k["scopes"]
    k["scopes"] = None
    runs = t.module_runs(k["step_module"])
    try:
        path = trace.find_xplane(os.path.join(run.out_dir, "trace"))
    except FileNotFoundError:
        return None
    if not runs:
        return None
    try:
        names = step_op_names(t, path, k["step_module"])
    except (ValueError, IndexError) as e:  # a trace this reader cannot decode
        run.notes.append(f"no op names read from {path}: {type(e).__name__}: {e}")
        return None
    if not names:
        return None
    if not any(innermost(op) for op in names.values()):
        # the scopes live in metadata, which JAX's persistent compile cache
        # keys by only where the program asks it to (``repro.core.engine``)
        run.notes.append(f"no op of {k['step_module']} carries a dispatch scope: "
                         "its executable likely came from a compile cache filled "
                         "by a program without scopes")
        return None
    per_instr = instruction_times(t, runs)
    times = scope_times(per_instr, names)
    step_s = sum(e - s for s, e in runs) * 1e-9 / len(runs)
    unscoped_mm = sorted(((n, per_instr[n]) for n in k.get("matmul_ops", ())
                          if n in per_instr and innermost(names.get(n, "")) is None),
                         key=lambda r: -r[1])
    k["scopes"] = {"times": times, "steps": len(runs), "step_s": step_s}
    run.notes.append(f"device time per dispatch scope, {k['step_module']}, "
                     f"{len(runs)} traced steps of {step_s * 1e3:.3f} ms:")
    run.notes.extend("  " + line for line in table(times, len(runs), step_s))
    if unscoped_mm:
        run.notes.append("matmul ops under no dispatch scope (ms/step, op_name):")
        run.notes.extend(f"  {n} {v / len(runs) * 1e3:.3f} {names.get(n, '')!r}"
                         for n, v in unscoped_mm[:20])
    return k["scopes"]

