"""Device time of the train step's parts, from the scopes on its operations.

The program wraps each part of the step in a ``jax.named_scope`` whose
name is one of ``repro.obs.scopes.ALL``; XLA keeps it in the ``op_name``
metadata of every HLO instruction the part lowers to.  The profiler stores
each program's optimized HLO module in the trace's ``/host:metadata``
plane: one event metadata per program, named like the program's events on
the device's ``XLA Modules`` line, with the module as its ``Hlo Proto``
stat.  So, for the device operations of the traced window (clipped to it
as ``trace_reduce.clip`` clips them):

- an operation's instruction is the first word of its name
  (``%fusion.12 = ...``), and its program the one its own stats name
  (``hlo_module`` and ``program_id``: the CPU's operations carry them, and
  the CPU rehearsal of a traced run reads them as a device's), else the
  device's ``XLA Modules`` event that encloses it;
- leaf operations alone are counted: a ``while``, ``conditional`` or
  ``call`` contains operations that the trace lists on their own;
- an operation's scope is the innermost name of the vocabulary on its
  ``op_name`` path (``transpose(jvp(...))/.../attention/dot_general`` is
  ``attention``); where that path names none (XLA leaves some fusions
  without an ``op_name``), the one scope that the instructions it calls
  agree on; an operation under none is unscoped.

The operations are read as ``trace_reduce.read_xplane`` reads them.
``jax.profiler.ProfileData`` gives the planes' events but not the metadata
plane's stats, so the modules are read here from the XSpace file with the
standard library (the protobuf wire format).  Where the trace holds no
module, or no operation carries a scope, there is nothing to read: the
scope metrics are left out, and the log says why.

The host planes also hold the program's ``data.next_batch`` spans, each
with the calling thread's CPU time inside it as its ``cpu_ms`` stat: the
host's own work, without the wait for a free slot in the device's queue
that the span's length holds once the queue is full.
"""

from __future__ import annotations

import bisect
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import harness
import trace_reduce

METADATA_PLANE = "/host:metadata"
MODULES_LINE = "XLA Modules"
HLO_STAT = "Hlo Proto"
CONTAINERS = ("while", "conditional", "call")   # hold other operations
NEXT_BATCH_SPAN = "data.next_batch"
NEXT_BATCH_CPU = "cpu_ms"               # its stat: the thread's CPU time

Event = Tuple[str, float, float]        # (name, start_ns, end_ns)
Instruction = Tuple[str, str, Tuple[int, ...]]  # opcode, op_name, calls
# an HLO module: its instructions by name, and each computation's
# instruction names by computation id
Module = Tuple[Dict[str, Instruction], Dict[int, List[str]]]


# --- protobuf wire format --------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def message(buf) -> Dict[int, List[Any]]:
    """One protobuf message's fields by number, in order: an int for a
    varint or fixed-width field, a ``memoryview`` for a length-delimited
    one (a string, bytes, a nested message or a packed list)."""
    buf = memoryview(buf)
    fields: Dict[int, List[Any]] = {}
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + width], "little"), i + width
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        fields.setdefault(key >> 3, []).append(value)
    return fields


def _text(fields: Dict[int, List[Any]], number: int) -> str:
    return bytes(fields[number][-1]).decode() if number in fields else ""


def _ints(fields: Dict[int, List[Any]], number: int) -> Tuple[int, ...]:
    """A repeated integer field, packed or not."""
    out: List[int] = []
    for v in fields.get(number, []):
        if isinstance(v, int):
            out.append(v)
            continue
        i = 0
        while i < len(v):
            x, i = _varint(v, i)
            out.append(x)
    return tuple(out)


# XSpace.planes 1; XPlane.name 2, .event_metadata 4, .stat_metadata 5 (map
# entries: key 1, value 2); XEventMetadata.name 2, .stats 5;
# XStatMetadata.name 2; XStat.metadata_id 1, .bytes_value 6.
# HloProto.hlo_module 1; HloModuleProto.computations 3;
# HloComputationProto.instructions 2, .id 5; HloInstructionProto.name 1,
# .opcode 2, .metadata 7 (OpMetadata.op_name 2), .called_computation_ids 38.

def _map_values(plane: Dict[int, List[Any]], number: int) -> List[Any]:
    return [message(entry)[2][0] for entry in plane.get(number, [])]


def hlo_module(hlo_proto) -> Module:
    """The instructions of a serialized ``HloProto`` and its computations."""
    module = message(message(hlo_proto)[1][0])
    insts: Dict[str, Instruction] = {}
    comps: Dict[int, List[str]] = {}
    for raw in module.get(3, []):
        comp = message(raw)
        names = comps.setdefault(comp.get(5, [0])[0], [])
        for inst in comp.get(2, []):
            f = message(inst)
            meta = message(f[7][0]) if 7 in f else {}
            names.append(_text(f, 1))
            insts[names[-1]] = (_text(f, 2), _text(meta, 2), _ints(f, 38))
    return insts, comps


def read_modules(path: str) -> Dict[str, Module]:
    """The optimized HLO modules a trace file holds, by program name (as
    the device's ``XLA Modules`` events name the program)."""
    with open(path, "rb") as f:
        space = message(f.read())
    out: Dict[str, Module] = {}
    for raw in space.get(1, []):
        plane = message(raw)
        if _text(plane, 2) != METADATA_PLANE:
            continue
        stat_names = {message(v).get(1, [0])[0]: _text(message(v), 2)
                      for v in _map_values(plane, 5)}
        for ev in _map_values(plane, 4):
            ev = message(ev)
            for stat in ev.get(5, []):
                stat = message(stat)
                if (stat_names.get(stat.get(1, [0])[0]) == HLO_STAT
                        and 6 in stat):
                    out[_text(ev, 2)] = hlo_module(stat[6][0])
    return out


# --- from the trace to device time by scope ---------------------------------

def scope_of(op_name: str, vocabulary: Sequence[str]) -> Optional[str]:
    """The innermost vocabulary name on an ``op_name`` path, or None."""
    found = [p for p in re.split(r"[/()]", op_name) if p in vocabulary]
    return found[-1] if found else None


def instruction_scope(module: Module, name: str, vocabulary: Sequence[str],
                      memo: Dict[str, Optional[str]]) -> Optional[str]:
    """The scope of one instruction of ``module``: its own ``op_name``'s,
    else the one scope that the instructions it calls agree on."""
    if name not in memo:
        insts, comps = module
        memo[name] = None                       # a call cycle reads None
        _, op_name, calls = insts[name]
        scope = scope_of(op_name, vocabulary)
        if scope is None and calls:
            inner = {instruction_scope(module, i, vocabulary, memo)
                     for c in calls for i in comps.get(c, [])} - {None}
            scope = inner.pop() if len(inner) == 1 else None
        memo[name] = scope
    return memo[name]


def read_profile(path: str) -> Dict[str, Any]:
    """Where the trace names operations' programs: ``{"runs": {plane:
    [Event]}}``, each device plane's ``XLA Modules`` events, and
    ``{"tagged": {(name, start_ns): program}}``, the host events whose
    stats name their program; and ``{"next_batch": [(start_ns, end_ns,
    cpu_ms)]}``, the host's ``data.next_batch`` spans (``cpu_ms`` None
    where the span carries no such stat)."""
    from jax.profiler import ProfileData

    runs: Dict[str, List[Event]] = {}
    tagged: Dict[Tuple[str, float], str] = {}
    batches: List[Tuple[float, float, Optional[float]]] = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(trace_reduce.DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name == MODULES_LINE:
                runs.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns, e.end_ns) for e in line.events)
            elif not device:
                for e in line.events:
                    st = dict(e.stats)
                    if "hlo_module" in st and "program_id" in st:
                        tagged[(e.name, e.start_ns)] = (
                            f"{st['hlo_module']}({st['program_id']})")
                    elif e.name == NEXT_BATCH_SPAN:
                        batches.append((e.start_ns, e.end_ns,
                                        st.get(NEXT_BATCH_CPU)))
    return {"runs": runs, "tagged": tagged, "next_batch": batches}


def by_scope(ops: Iterable[Event], runs: Sequence[Event],
             tagged: Dict[Tuple[str, float], str], modules: Dict[str, Module],
             vocabulary: Sequence[str], lo: float = float("-inf"),
             hi: float = float("inf")) -> Dict[Optional[str], float]:
    """Device ns in [lo, hi] of the leaf operations by scope (``None``:
    unscoped).  ``runs``: the device's ``XLA Modules`` events; ``tagged``:
    the programs that operations' own stats name."""
    runs = sorted(runs, key=lambda e: e[1])
    starts = [s for _, s, _ in runs]
    memos: Dict[str, Dict[str, Optional[str]]] = {p: {} for p in modules}
    out: Dict[Optional[str], float] = {}
    for name, s, e in ops:
        inside = min(e, hi) - max(s, lo)        # as trace_reduce.clip
        if inside <= 0:
            continue
        inst = name.split(" ", 1)[0].lstrip("%")
        program = tagged.get((name, s))
        k = bisect.bisect_right(starts, s) - 1
        if program is None and k >= 0 and s < runs[k][2]:
            program = runs[k][0]
        scope = None
        if program in modules and inst in modules[program][0]:
            if modules[program][0][inst][0] in CONTAINERS:
                continue
            scope = instruction_scope(modules[program], inst, vocabulary,
                                      memos[program])
        out[scope] = out.get(scope, 0.0) + inside
    return out


def summarize(trace: Dict[str, Any], profile: Dict[str, Any],
              modules: Dict[str, Module], lo: float,
              hi: float, vocabulary: Sequence[str]) -> Dict[str, Any]:
    """Leaf-op device seconds in [lo, hi] by scope, averaged over the
    device planes (a scope that the traced programs hold and no operation
    of the window ran under reads 0), and the ``data.next_batch`` spans
    that start there as ``(ms long, cpu_ms)``.  ``trace`` is
    ``trace_reduce.read_xplane``'s, ``profile`` :func:`read_profile`'s."""
    n = max(len(trace["devices"]), 1)
    scoped: Dict[Optional[str], float] = {
        scope_of(op_name, vocabulary): 0.0 for insts, _ in modules.values()
        for _, op_name, _ in insts.values()}
    for plane, ops in trace["devices"].items():
        ns = by_scope(ops, profile["runs"].get(plane, []),
                      profile["tagged"], modules, vocabulary, lo, hi)
        for k, v in ns.items():
            scoped[k] = scoped.get(k, 0.0) + v / n * 1e-9
    unscoped = scoped.pop(None, 0.0)
    leaf = unscoped + sum(scoped.values())
    batches = [((e - s) * 1e-6, cpu) for s, e, cpu in profile["next_batch"]
               if lo <= s < hi]
    return {"scope_s": scoped, "unscoped_s": unscoped, "leaf_s": leaf,
            "unscoped_share": unscoped / leaf if leaf else None,
            "next_batch": batches}


def _read(run) -> Optional[Dict[str, Any]]:
    try:
        from repro.obs.scopes import ALL as vocabulary
    except ImportError:
        harness.log("scopes: the program names no scopes (repro.obs.scopes)")
        vocabulary = ()
    path = trace_reduce.newest_xplane(run.trace_dir)
    if path is None:
        harness.log(f"scopes: no trace under {run.trace_dir}")
        return None
    trace = trace_reduce.read_xplane(path)
    bounds = trace_reduce.window_bounds(trace, trace_reduce.WINDOW_SPAN)
    if bounds is None:
        harness.log(f"scopes: no {trace_reduce.WINDOW_SPAN} span in {path}")
        return None
    modules = read_modules(path)
    out = summarize(trace, read_profile(path), modules, bounds[0],
                    bounds[1], vocabulary)
    scopes = ", ".join(f"{k} {v:.4f} s" for k, v in
                       sorted(out["scope_s"].items(), key=lambda x: -x[1]))
    batches = ", ".join(f"{ms:.2f}" + ("" if cpu is None else f"/{cpu:.2f}")
                        for ms, cpu in out["next_batch"])
    harness.log(f"scopes: {len(modules)} HLO modules; leaf ops "
                f"{out['leaf_s']:.4f} s in the window: {scopes or 'none'}; "
                f"unscoped {out['unscoped_s']:.4f} s (share "
                f"{out['unscoped_share']}); {NEXT_BATCH_SPAN} spans, ms "
                f"long/{NEXT_BATCH_CPU}: {batches or 'none'}")
    if not modules:
        harness.log("scopes: the trace holds no HLO module")
    elif not any(out["scope_s"].values()):
        harness.log("scopes: no operation carries a scope (a program "
                    "compiled without them, or loaded from a compile cache "
                    "whose key leaves the scopes out)")
    return out


def of(run) -> Optional[Dict[str, Any]]:
    """What the run's trace shows by scope, read once and kept on
    ``run.read``; None when the run was not traced."""
    if not run.trace:
        return None
    if "scopes" not in run.read:
        run.read["scopes"] = _read(run)
    return run.read["scopes"]


def scope_ms(run, scope: str) -> Optional[float]:
    """Device ms a traced step of the leaf operations under ``scope``;
    None when the traced programs hold no such scope, or when no operation
    of the window carries any scope."""
    out = of(run)
    steps = run.read.get("traced_steps")
    if (not out or not steps or scope not in out["scope_s"]
            or not any(out["scope_s"].values())):
        return None
    return 1e3 * out["scope_s"][scope] / steps
