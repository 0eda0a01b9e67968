"""What the program under test writes into the profiler's trace, for the
per-layer metrics that read it.

``trace_reduce`` reads a trace through ``jax.profiler.ProfileData``,
which gives each event its name and times but not the stats of its
metadata, and so not the named scope (``tf_op``) that a device op ran
under. ``load`` decodes the ``.xplane.pb`` itself, with a schema of the
few XSpace and HLO messages it reads, and keeps three lists:

- ``modules``: ``[name, start_ns, duration_ns]`` of each program run on
  the first accelerator (its "XLA Modules" line), named
  ``jit_<function>(<fingerprint>)``;
- ``ops``: ``[name, start_ns, duration_ns, scope]`` of each operation on
  that accelerator ("XLA Ops"), ``scope`` being the scope the op is
  charged to (``charged_scopes``), such as
  ``jit(serve_decode_span)/while/body/sampler/sort``;
- ``host``: the benchmark's (``bench.*``) and the engine's (``serve.*``)
  host spans.

An op's scope is its ``tf_op`` stat, the ``op_name`` of its HLO
instruction, where the program's tracing gave it one. The compiler makes
instructions of its own: the TPU compiler rewrites the sampler's
keep-mask scatter into a sort and a fusion, and a cumulative sum into
reduce-windows, none with an ``op_name``, and the trace then shows the
enclosing loop's scope (``jit(serve_decode_span)/while``). Such an op is
charged to the common path of the program scopes it is computed from,
read from the optimized HLO that the trace holds for each program
(``/host:metadata``, stat ``Hlo Proto``).

Times are on the clock that ``ProfileData`` and ``trace_reduce`` use:
the line's ``timestamp_ns`` plus the event's offset. A program that
writes no such names or spans gives empty lists, and the metrics that
read them then report nothing.
"""
from __future__ import annotations

import bisect
import functools
import os
import sys
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

from bench import trace_reduce
from bench.run import TRACE_DIR

Event = Tuple[str, float, float]                 # name, start_ns, dur_ns

MODULE_LINE = "XLA Modules"
OP_LINE = trace_reduce.DEVICE_LINE
HOST_PREFIXES = ("bench.", "serve.")
SCOPE_STAT = "tf_op"
HLO_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
# instructions whose events hold other ops' events: never charged a scope
CONTAINERS = ("while", "conditional", "call")
STEP_SPAN = "serve.step"

@functools.cache
def _messages():
    """Message classes for the parts of tsl's ``xplane.proto`` and xla's
    ``hlo.proto`` read here (field numbers as there; a map is read as its
    repeated entries), by message name."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    msgs = {
        "XStat": [("metadata_id", 1, F.TYPE_INT64, None),
                  ("double_value", 2, F.TYPE_DOUBLE, None),
                  ("uint64_value", 3, F.TYPE_UINT64, None),
                  ("int64_value", 4, F.TYPE_INT64, None),
                  ("str_value", 5, F.TYPE_STRING, None),
                  ("bytes_value", 6, F.TYPE_BYTES, None),
                  ("ref_value", 7, F.TYPE_UINT64, None)],
        "XEvent": [("metadata_id", 1, F.TYPE_INT64, None),
                   ("offset_ps", 2, F.TYPE_INT64, None),
                   ("duration_ps", 3, F.TYPE_INT64, None)],
        "XLine": [("name", 2, F.TYPE_STRING, None),
                  ("timestamp_ns", 3, F.TYPE_INT64, None),
                  ("events", 4, F.TYPE_MESSAGE, "XEvent")],
        "XEventMetadata": [("id", 1, F.TYPE_INT64, None),
                           ("name", 2, F.TYPE_STRING, None),
                           ("stats", 5, F.TYPE_MESSAGE, "XStat")],
        "XStatMetadata": [("id", 1, F.TYPE_INT64, None),
                          ("name", 2, F.TYPE_STRING, None)],
        "EventMetadataEntry": [("key", 1, F.TYPE_INT64, None),
                               ("value", 2, F.TYPE_MESSAGE,
                                "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, F.TYPE_INT64, None),
                              ("value", 2, F.TYPE_MESSAGE, "XStatMetadata")],
        "XPlane": [("name", 2, F.TYPE_STRING, None),
                   ("lines", 3, F.TYPE_MESSAGE, "XLine"),
                   ("event_metadata", 4, F.TYPE_MESSAGE,
                    "EventMetadataEntry"),
                   ("stat_metadata", 5, F.TYPE_MESSAGE, "StatMetadataEntry")],
        "XSpace": [("planes", 1, F.TYPE_MESSAGE, "XPlane")],
        # xla's hlo.proto and xla_data.proto
        "OpMetadata": [("op_name", 2, F.TYPE_STRING, None)],
        "ShapeProto": [("element_type", 2, F.TYPE_INT32, None),
                       ("dimensions", 3, F.TYPE_INT64, None),
                       ("tuple_shapes", 4, F.TYPE_MESSAGE, "ShapeProto")],
        "HloInstructionProto": [("name", 1, F.TYPE_STRING, None),
                                ("opcode", 2, F.TYPE_STRING, None),
                                ("shape", 3, F.TYPE_MESSAGE, "ShapeProto"),
                                ("metadata", 7, F.TYPE_MESSAGE,
                                 "OpMetadata"),
                                ("id", 35, F.TYPE_INT64, None),
                                ("operand_ids", 36, F.TYPE_INT64, None),
                                ("called_computation_ids", 38,
                                 F.TYPE_INT64, None)],
        "HloComputationProto": [("name", 1, F.TYPE_STRING, None),
                                ("instructions", 2, F.TYPE_MESSAGE,
                                 "HloInstructionProto"),
                                ("id", 5, F.TYPE_INT64, None)],
        "HloModuleProto": [("name", 1, F.TYPE_STRING, None),
                           ("computations", 3, F.TYPE_MESSAGE,
                            "HloComputationProto")],
        "HloProto": [("hlo_module", 1, F.TYPE_MESSAGE, "HloModuleProto")],
    }
    repeated = {("XLine", "events"), ("XEventMetadata", "stats"),
                ("XPlane", "lines"), ("XPlane", "event_metadata"),
                ("XPlane", "stat_metadata"), ("XSpace", "planes"),
                ("ShapeProto", "dimensions"), ("ShapeProto", "tuple_shapes"),
                ("HloInstructionProto", "operand_ids"),
                ("HloInstructionProto", "called_computation_ids"),
                ("HloComputationProto", "instructions"),
                ("HloModuleProto", "computations")}
    for mname, fields in msgs.items():
        m = fdp.message_type.add(name=mname)
        for fname, num, ftype, tname in fields:
            f = m.field.add(name=fname, number=num, type=ftype,
                            label=(F.LABEL_REPEATED
                                   if (mname, fname) in repeated
                                   else F.LABEL_OPTIONAL))
            if tname:
                f.type_name = f".bench_xplane.{tname}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return {m: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"bench_xplane.{m}"))
        for m in ("XSpace", "HloProto", "HloModuleProto")}


def _line_events(line, names) -> List[Event]:
    t0 = float(line.timestamp_ns)
    return [(names[e.metadata_id], t0 + e.offset_ps * 1e-3,
             e.duration_ps * 1e-3) for e in line.events]


def _program_path(op_name: str):
    """The path of a scope the program's tracing wrote (``jit(<fn>)/...``),
    or None for a name the compiler made or none."""
    return tuple(op_name.split("/")) if op_name.startswith("jit(") else None


def charged_scopes(module) -> Dict[str, str]:
    """For each op of an ``HloModuleProto`` that runs as an event of its
    own (not inside a fusion, not a loop or call) and whose ``op_name``
    is not a program scope: the longest path common to the program
    scopes it is computed from (its operands', through operands that
    have none either). A constant counts as having none: the compiler
    shares one constant among every user of its value, and it keeps the
    scope of whichever came first. Ops that derive from no program scope
    (of parameters and constants alone) are left out."""
    ins = {i.id: i for c in module.computations for i in c.instructions}
    own = {k: (None if i.opcode == "constant"
               else _program_path(i.metadata.op_name))
           for k, i in ins.items()}
    fused = {c for i in ins.values() if i.opcode == "fusion"
             for c in i.called_computation_ids}
    derived: Dict[int, tuple] = {}

    def path(k):
        return own[k] if own[k] is not None else derived[k]

    out = {}
    for comp in module.computations:
        if comp.id in fused:
            continue
        for root in comp.instructions:
            if own[root.id] is not None or root.opcode in CONTAINERS:
                continue
            stack = [root.id]
            while stack:                     # operands before their users
                k = stack[-1]
                todo = [o for o in ins[k].operand_ids
                        if own[o] is None and o not in derived]
                if todo:
                    stack.extend(todo)
                    continue
                stack.pop()
                paths = [p for p in map(path, ins[k].operand_ids) if p]
                derived[k] = (tuple(os.path.commonprefix(paths))
                              if paths else None)
            if derived[root.id]:
                out[root.name] = "/".join(derived[root.id])
    return out


def _module_scopes(space) -> Dict[str, Dict[str, str]]:
    """``charged_scopes`` of each program whose HLO the trace holds, by
    the program's name as its module events give it."""
    out = {}
    hlo = _messages()["HloProto"]
    for plane in space.planes:
        if plane.name != HLO_PLANE:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        for e in plane.event_metadata:
            for st in e.value.stats:
                if stat_names.get(st.metadata_id) == HLO_STAT:
                    out[e.value.name] = charged_scopes(
                        hlo.FromString(st.bytes_value).hlo_module)
    return out


def _instruction(op: str) -> str:
    """The HLO instruction's name in an op event's name
    (``%fusion.3 = f32[8]{0} fusion(...)`` or ``fusion.3``)."""
    return op.lstrip("%").split(" ", 1)[0]


def load(path: str) -> Dict[str, list]:
    space = _messages()["XSpace"].FromString(Path(path).read_bytes())
    out: Dict[str, list] = {"modules": [], "ops": [], "host": []}
    # the first accelerator, as trace_reduce.load picks it
    devs = sorted((p for p in space.planes if p.name.startswith("/device:")
                   and any(ln.name == OP_LINE for ln in p.lines)),
                  key=lambda p: p.name)
    if devs:
        plane = devs[0]
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        names, scopes = {}, {}
        for e in plane.event_metadata:
            md = e.value
            names[e.key] = md.name
            for st in md.stats:
                if stat_names.get(st.metadata_id) == SCOPE_STAT:
                    scopes[e.key] = (st.str_value
                                     or stat_names.get(st.ref_value, ""))
        for line in plane.lines:
            if line.name == MODULE_LINE:
                out["modules"] = _line_events(line, names)
        charged = _module_scopes(space)
        mods = sorted((s, s + d, n) for n, s, d in out["modules"])
        starts = [m[0] for m in mods]
        memo: Dict[Tuple[str, int], str] = {}

        def scope(mid: int, t: float) -> str:
            i = bisect.bisect_right(starts, t) - 1
            mod = mods[i][2] if i >= 0 and t < mods[i][1] else ""
            if (mod, mid) not in memo:
                memo[mod, mid] = charged.get(mod, {}).get(
                    _instruction(names[mid]), scopes.get(mid, ""))
            return memo[mod, mid]

        for line in plane.lines:
            if line.name == OP_LINE:
                t0 = float(line.timestamp_ns)
                out["ops"] = [(names[e.metadata_id], t0 + e.offset_ps * 1e-3,
                               e.duration_ps * 1e-3,
                               scope(e.metadata_id, t0 + e.offset_ps * 1e-3))
                              for e in line.events]
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        names = {e.key: e.value.name for e in plane.event_metadata}
        for line in plane.lines:
            out["host"].extend(ev for ev in _line_events(line, names)
                               if ev[0].startswith(HOST_PREFIXES))
    return out


def of(run):
    """The run's program trace: ``run.program`` where a test set it, else
    read once from the run's trace directory; None for an untraced run."""
    if getattr(run, "program", None) is None and run.trace is not None:
        paths = sorted(TRACE_DIR.rglob("*.xplane.pb"))
        run.program = load(str(paths[-1])) if paths else None
    return getattr(run, "program", None)


# --------------------------------------------------------------------------
# reductions
# --------------------------------------------------------------------------

def log(*a):
    print(*a, file=sys.stderr, flush=True)


def window(pt: dict) -> Tuple[float, float]:
    return trace_reduce.window(pt["host"])


def module_seconds(pt: dict, lo: float, hi: float,
                   program: str) -> Tuple[float, int]:
    """Device seconds inside [lo, hi] of the runs of ``jit_<program>``,
    and how many runs overlap it."""
    want = f"jit_{program}("
    runs = [(s, s + d) for n, s, d in pt["modules"] if n.startswith(want)]
    t = sum(b - a for a, b in trace_reduce.union(runs, lo, hi)) * 1e-9
    return t, sum(1 for a, b in runs if a < hi and b > lo)


def module_split(pt: dict, lo: float, hi: float) -> Dict[str, float]:
    """Device seconds inside [lo, hi] of each program, by its name
    without the fingerprint, and ``unnamed``: busy time in which no
    ``jit_serve_*`` program ran (eager operations, staging)."""
    out: Dict[str, float] = {}
    for n, s, d in pt["modules"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            k = n.split("(", 1)[0]
            out[k] = out.get(k, 0.0) + (b - a) * 1e-9
    named = [(s, s + d) for n, s, d in pt["modules"]
             if n.startswith("jit_serve_")]
    busy = [(s, s + d) for _, s, d, _ in pt["ops"]]
    # busy time outside every named program
    out["unnamed"] = idle_inside(named, busy, lo, hi)
    return out


def in_scope(scope: str, program: str, part: str) -> bool:
    """Whether a ``tf_op`` scope (``jit(<program>)/.../<part>/...:<op>``)
    lies in ``program`` under the named scope ``part``."""
    path = scope.split(":", 1)[0].split("/")
    return path[0] == f"jit({program})" and part in path[1:]


def scope_seconds(pt: dict, lo: float, hi: float, program: str,
                  part: str) -> float:
    """Device seconds inside [lo, hi] in which an op of ``program`` ran
    under the named scope ``part`` (the union: ops that hold others count
    once)."""
    ivs = [(s, s + d) for _, s, d, sc in pt["ops"]
           if in_scope(sc, program, part)]
    return sum(b - a for a, b in trace_reduce.union(ivs, lo, hi)) * 1e-9


def idle_inside(device: Sequence[Tuple[float, float]],
                spans: Sequence[Tuple[float, float]], lo: float,
                hi: float) -> float:
    """Seconds inside [lo, hi] that lie in one of ``spans`` and in no
    ``device`` interval."""
    busy = trace_reduce.union(device, lo, hi)
    ends = [b for _, b in busy]
    tot = 0.0
    for a, b in trace_reduce.union(spans, lo, hi):
        tot += b - a
        i = bisect.bisect_right(ends, a)
        while i < len(busy) and busy[i][0] < b:
            tot -= min(busy[i][1], b) - max(busy[i][0], a)
            i += 1
    return tot * 1e-9


def host_spans(pt: dict, name: str) -> List[Tuple[float, float]]:
    return [(s, s + d) for n, s, d in pt["host"] if n == name]


def stats_delta(run, key: str) -> int:
    """How much the engine counter ``key`` grew over the traced steps
    (``Step.stats`` is the engine's ``stats`` after each step)."""
    a, b = run.trace_steps
    if b <= a:
        return 0
    before = run.steps[a - 1].stats.get(key, 0) if a > 0 else 0
    return run.steps[b - 1].stats.get(key, 0) - before
