"""Named scopes on the device: which layer kind each compiled op runs for.

The served programs open a ``jax.named_scope`` per program part and layer
kind (``SCOPES``).  XLA keeps the scope path in each HLO instruction's
``metadata op_name`` (``jit(decode_loop)/while/body/layers/while/body/
attention/spectral/dot_general``), but the profiler's device trace names
an op by its instruction alone (``%fusion.12``), which a recompile
renames.  Two pieces join them:

* ``program_scopes(compiled)`` parses a compiled program's optimized HLO
  text into instruction name -> ``op_name`` path (the op->scope map),
  with each instruction's result type, which tells apart the programs of
  one name (the prefill buckets) in a trace.  It
  reads the ``jax.stages.Compiled`` the engines already hold, so it works
  for an executable loaded from the persistent compile cache too, and it
  runs only when asked, off the serving path
  (``ContinuousEngine.op_scopes()``).
* ``device_seconds(planes, programs)`` reduces a recorded profiler trace
  to the device seconds of each program's leaf ops by layer kind: an op
  belongs to the ``XLA Modules`` event that encloses it on the device's
  line, and is looked up in that program's map.

Leaf ops exclude ``while``, ``conditional`` and ``call``: on the device
line these enclose the ops of their bodies, whose time they would count
twice.  See docs/observability.md, "Spans and scopes on the profiler's
clock".
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, Iterable, List, Optional, Tuple

# The scope names the model and the serve programs open, outermost first
# where they nest (``layers/attention/spectral``).
SCOPES = ("embed", "layers", "attention", "mlp", "moe", "spectral", "dense",
          "kv_write", "final_norm", "lm_head", "sample", "health")
UNSCOPED = "unscoped"
CONTROL_OPCODES = ("while", "conditional", "call")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_DEVICE = re.compile(r"^/device:[A-Z]+:(\d+)$")


def kinds(path: str) -> Tuple[str, ...]:
    """The scope names in an ``op_name`` path, outermost first."""
    return tuple(c for c in path.split("/") if c in SCOPES)


def top_scope(path: Optional[str]) -> str:
    """The outermost scope of a path, ``UNSCOPED`` where it has none."""
    ks = kinds(path) if path else ()
    return ks[0] if ks else UNSCOPED


def _split_type(rest: str) -> Tuple[str, str]:
    """An HLO instruction's text after `` = ``: (result type, the rest)."""
    if rest.startswith("("):                 # tuple-shaped result
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return rest[:i + 1], rest[i + 1:]
    head, _, tail = rest.partition(" ")
    return head, tail


def parse_hlo(text: str) -> Dict:
    """``{"module": name, "ops": {instruction: op_name}, "types":
    {instruction: result type}}`` of an HLO text; instructions without an
    ``op_name`` have a type and no entry in ``ops``."""
    out: Dict = {"module": "", "ops": {}, "types": {}}
    for line in text.splitlines():
        if not out["module"]:
            m = _MODULE.match(line)
            if m:
                out["module"] = m.group(1)
                continue
        m = _INSTR.match(line)
        if m:
            name, rest = m.groups()
            out["types"][name] = _split_type(rest)[0]
            op = _OP_NAME.search(rest)
            if op:
                out["ops"][name] = op.group(1)
    return out


def program_scopes(compiled) -> Dict:
    """The op->scope map of one compiled program (``parse_hlo`` of its
    optimized HLO): ``module`` is the program's name in a trace
    (``jit_decode_loop``)."""
    return parse_hlo(compiled.as_text())


# ---------------------------------------------------------------------------
# A recorded trace, by layer kind
# ---------------------------------------------------------------------------
def _op_event(event_name: str) -> Tuple[str, str, str]:
    """(instruction, result type, opcode) of an op event's HLO text
    (``%w.3 = (s32[], f32[8]) while(...)``)."""
    m = _INSTR.match(event_name)
    if not m:
        return event_name, "", ""
    typ, rest = _split_type(m.group(2))
    return m.group(1), typ, rest.strip().split("(")[0]


def _pick(module: str, ops: Iterable[Tuple[str, str]],
          programs: List[Dict]) -> Optional[Dict]:
    """The map of one module event's program: among the maps of that
    module name, the one in which most of the event's ops have their
    instruction name with the same result type.  (The trace's program id
    is no attribute of the executable a program can read.)"""
    same = [p for p in programs if p["module"] == module]
    if len(same) < 2:
        return same[0] if same else None
    ops = list(ops)
    return max(same, key=lambda p: sum(p["types"].get(n) == t
                                       for n, t in ops))


def device_seconds(planes, programs: List[Dict],
                   window_ns: Optional[Tuple[int, int]] = None) -> Dict:
    """Leaf-op device seconds of a trace's programs, by layer kind.

    ``planes``: ``jax.profiler.ProfileData.planes``; ``programs``: maps
    from ``program_scopes``; ``window_ns``: keep only module events that
    start inside it, on the device's clock.  Returns, per module name::

        {"runs": module events, "leaf_s": seconds of leaf ops,
         "top": {outermost scope: s}, "under": {scope: s of ops with it
         anywhere in their path}, "unscoped_ops": {op: s}}

    summed over devices.  Ops outside every module event are left out.
    """
    out: Dict[str, Dict] = {}
    for plane in planes:
        if not _DEVICE.match(plane.name):
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for ev in lines.get("XLA Modules", []))
        if window_ns is not None:
            mods = [m for m in mods if window_ns[0] <= m[0] < window_ns[1]]
        starts = [m[0] for m in mods]
        per_mod: Dict[int, List] = collections.defaultdict(list)
        for ev in lines.get("XLA Ops", []):
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            if i < 0 or ev.start_ns >= mods[i][1]:
                continue
            name, typ, opcode = _op_event(ev.name)
            per_mod[i].append((name, typ, opcode, ev.duration_ns * 1e-9))
        for i, (_, _, ev_name) in enumerate(mods):
            module = ev_name.partition("(")[0].strip()
            ops = per_mod.get(i, [])
            prog = _pick(module, ((n, t) for n, t, _, _ in ops), programs)
            scope_of = prog["ops"] if prog else {}
            d = out.setdefault(module, {
                "runs": 0, "leaf_s": 0.0,
                "top": collections.Counter(), "under": collections.Counter(),
                "unscoped_ops": collections.Counter()})
            d["runs"] += 1
            for name, _, opcode, s in ops:
                if opcode in CONTROL_OPCODES:
                    continue
                path = scope_of.get(name)
                d["leaf_s"] += s
                top = top_scope(path)
                d["top"][top] += s
                for k in set(kinds(path or "")):
                    d["under"][k] += s
                if top == UNSCOPED:
                    d["unscoped_ops"][name] += s
    for d in out.values():
        for k in ("top", "under", "unscoped_ops"):
            d[k] = dict(d[k])
    return out


def device_seconds_file(path: str, programs: List[Dict], **kw) -> Dict:
    """``device_seconds`` of a ``*.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return device_seconds(ProfileData.from_file(path).planes, programs, **kw)
