"""Reduce a JAX profiler trace (``*.xplane.pb``) to what the metrics need.

* The window is the host span the benchmark wraps around the traced part
  of a run (``WINDOW_SPAN``), on the host's ``python`` line.
* A device is a ``/device:TPU:<n>`` plane.  Its busy time is the union of
  the intervals of the events on its ``XLA Ops`` line that fall in the
  window; program time is the summed duration of its ``XLA Modules``
  events by program name (the jitted function's name, ``jit_<fn>``, with
  any ``(<id>)`` suffix dropped); op time is the summed duration of
  ``XLA Ops`` events by name.
* An idle gap is a stretch of the window in which no op ran on the
  device.  It is named after the innermost span, on the benchmark's own
  host thread, that covers its middle: a ``bench.*`` span of the
  benchmark, or a JAX or program span inside it.
* The device's clock is put on the host's: a program cannot start on the
  device before the host has begun to enqueue it (``DoEnqueueProgram``,
  matched to the device's program by ``run_id``), so the device timeline
  is shifted by the largest lead of an enqueue's start over its program's
  start (1.58-1.67 ms on the recorded trace).  The enqueue's end is no
  bound: the device may start a program before its enqueue returns, and a
  host that stalls inside one would shift the timeline by the stall.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
ENQUEUE_SPAN = "DoEnqueueProgram"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Device:
    busy_s: float
    module_s: Dict[str, float]
    module_n: Dict[str, int]
    op_s: Dict[str, float]
    op_text: Dict[str, str]                  # op name -> its HLO text
    gaps: List[Tuple[float, float]]          # (start_ns, end_ns), longest first


@dataclasses.dataclass
class Reduction:
    window_s: float
    devices: Dict[int, Device]
    idle_gaps: List[Tuple[str, float]]       # (host span, seconds)

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        used = [d.busy_s for d in self.devices.values() if d.busy_s > 0]
        return sum(used) / len(used) if used else 0.0

    def program_s(self, name_part: str) -> float:
        """Device seconds of programs whose name contains ``name_part``,
        summed over devices."""
        return sum(s for d in self.devices.values()
                   for n, s in d.module_s.items() if name_part in n)

    def program_count(self, name_part: str) -> int:
        return sum(c for d in self.devices.values()
                   for n, c in d.module_n.items() if name_part in n)

    def op_s(self, name_part: str) -> float:
        """Device seconds of ops whose name contains ``name_part``."""
        return sum(s for d in self.devices.values()
                   for n, s in d.op_s.items() if name_part in n)

    def op_texts(self, name_part: str) -> List[str]:
        """HLO texts of the ops whose name contains ``name_part``."""
        return [t for d in self.devices.values()
                for n, t in d.op_text.items() if name_part in n]

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        tot: Dict[str, float] = collections.Counter()
        for d in self.devices.values():
            for name, s in d.op_s.items():
                tot[name] += s
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def _program_name(name: str) -> str:
    return name.split("(")[0].strip()


def op_name(text: str) -> str:
    """``%name: opcode`` from an op event's HLO text, with the custom-call
    target for custom calls (``tpu_custom_call`` is a Pallas kernel)."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text
    rest = _after_result_type(rest)
    opcode = rest.strip().split("(")[0]
    target = re.search(r'custom_call_target="([^"]+)"', text)
    if target:
        opcode += " " + target.group(1)
    return f"{name.strip()}: {opcode}"


def _after_result_type(rest: str) -> str:
    """An HLO instruction's text after `` = ``, from its opcode on."""
    if rest.startswith("("):                 # tuple-shaped result
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return rest[i + 1:]
    return rest.partition(" ")[2]


def operand_spaces(text: str) -> List[int]:
    """Memory space of each operand in an op's HLO text: the ``S(n)`` of
    its layout, 0 (HBM) where none is given; 1 is the core's VMEM."""
    _, sep, rest = text.partition(" = ")
    rest = _after_result_type(rest) if sep else ""
    start = rest.find("(")
    if start < 0:
        return []
    operands, depth, cur = [], 0, ""
    for ch in rest[start + 1:]:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            if depth == 0:
                break
            depth -= 1
        if ch == "," and depth == 0:
            operands.append(cur)
            cur = ""
        else:
            cur += ch
    if cur.strip():
        operands.append(cur)
    return [int(m.group(1)) if (m := re.search(r"S\((\d+)\)", o)) else 0
            for o in operands]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce_planes(planes, n_gaps: int = 10) -> Reduction:
    """``planes``: the ``ProfileData.planes`` of one trace."""
    host_spans = []
    window = None
    devices_raw = {}
    enqueued = {}                            # run_id -> host enqueue start
    for plane in planes:
        m = _DEVICE.match(plane.name)
        if plane.name == "/host:CPU":
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    if ev.name == WINDOW_SPAN and window is None:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                        host_spans = spans       # the benchmark's thread
                    elif ev.name == ENQUEUE_SPAN:
                        run = _stat(ev, "run_id")
                        if run is not None:
                            enqueued[run] = ev.start_ns
                    elif ev.duration_ns > 0 and not ev.name.startswith("$"):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
        elif m:
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            devices_raw[int(m.group(1))] = lines
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    lead = [enqueued[r] - ev.start_ns
            for lines in devices_raw.values()
            for ev in lines.get(MODULES_LINE, [])
            for r in [_stat(ev, "run_id")] if r in enqueued]
    shift = max(lead) if lead else 0
    lo, hi = window[0] - shift, window[1] - shift   # on the device clock
    devices = {}
    all_gaps = []
    for idx, lines in devices_raw.items():
        ops, op_text = [], {}
        for ev in lines.get(OPS_LINE, []):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e > lo and s < hi:
                n = op_name(ev.name)
                ops.append((s, e, n))
                op_text.setdefault(n, ev.name)
        busy = _union([_clip(s, e, lo, hi) for s, e, _ in ops])
        busy_ns = sum(e - s for s, e in busy)
        op_s: Dict[str, float] = collections.Counter()
        for s, e, n in ops:
            cs, ce = _clip(s, e, lo, hi)
            op_s[n] += (ce - cs) * 1e-9
        mod_s: Dict[str, float] = collections.Counter()
        mod_n: Dict[str, int] = collections.Counter()
        for ev in lines.get(MODULES_LINE, []):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= lo or s >= hi:
                continue
            cs, ce = _clip(s, e, lo, hi)
            name = _program_name(ev.name)
            mod_s[name] += (ce - cs) * 1e-9
            mod_n[name] += 1
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        devices[idx] = Device(busy_s=busy_ns * 1e-9, module_s=dict(mod_s),
                              module_n=dict(mod_n), op_s=dict(op_s),
                              op_text=op_text, gaps=gaps)
        if busy_ns:
            all_gaps.extend(gaps)
    all_gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_host_span_at((s + e) // 2 + shift, host_spans),
              (e - s) * 1e-9)
             for s, e in all_gaps[:n_gaps]]
    return Reduction(window_s=(hi - lo) * 1e-9, devices=devices,
                     idle_gaps=named)


def _stat(ev, key):
    for k, v in getattr(ev, "stats", ()):
        if k == key:
            return str(v)
    return None


def _host_span_at(t_ns, spans) -> str:
    """Innermost (shortest) host span covering ``t_ns``."""
    best: Optional[Tuple[int, str]] = None
    for s, e, name in spans:
        if s <= t_ns < e and name != WINDOW_SPAN:
            if best is None or e - s < best[0]:
                best = (e - s, name)
    return best[1] if best else "no host span"


def reduce_file(path: str, n_gaps: int = 10) -> Reduction:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, n_gaps)
