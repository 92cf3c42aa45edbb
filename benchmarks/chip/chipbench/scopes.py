"""Device time by the program's own scopes, and the program's host spans
with their arguments, from a profiler trace. An addition to
``reduce_trace``, whose readings it leaves as they are.

The program names its work. In the compiled decode step every HLO op
carries a JAX name stack in its ``op_name`` metadata: ``jit(step)/bind/…``
for the slices that bind the stacked weights and cache onto the graph,
``jit(step)/restack/…`` for the new cache, and ``jit(step)/<kind>/<node>/…``
for each graph op (``matmul/L3.q_proj``, ``cache_update/L3.k_cache_write``,
``ssm_decode/L0.ssm_decode``). A trace reports that stack as the ``tf_op``
stat of the op's event metadata, which ``jax.profiler.ProfileData`` does
not expose: :func:`op_scopes` reads it from the ``.xplane.pb`` with a
description of the few messages of ``xplane.proto`` it needs. A run whose
trace file is gone reads the same stacks from the compiled step's HLO
text (:func:`hlo_scopes`, :func:`compiled_step_scopes`).

``ContinuousBatcher`` writes host spans: ``step`` (args ``step``,
``live``, ``queued``) holding ``admit`` (``uid``) with its children
``prefill`` (``uid``, ``prompt_len``), ``first_token`` and ``slot_write``
(``uid``), then ``inputs``, ``decode`` and ``sample``. :func:`read` keeps
them with their arguments.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import re
import sys
import traceback
from typing import Any, Dict, List, Mapping, Optional, Sequence

from chipbench import reduce_trace

PROGRAM_SPANS = ("step", "admit", "prefill", "first_token", "slot_write", "inputs",
                 "decode", "sample")
#: admission and the phases inside it
ADMIT_SPANS = ("admit", "prefill", "first_token", "slot_write")


@dataclasses.dataclass
class ScopedOp:
    name: str
    start: float    # ns
    end: float      # ns
    scope: str      # scope path (``scope_path``); "" where the op has none


@dataclasses.dataclass
class Span:
    name: str
    start: float    # ns
    end: float      # ns
    args: Dict[str, Any]


@functools.lru_cache(maxsize=1)
def _xspace_class():
    """The ``XSpace`` message, described down to what ``op_scopes`` reads:
    planes, their event and stat metadata, and the stats on event
    metadata (field numbers as in ``tsl/profiler/protobuf/xplane.proto``).
    Fields it leaves out are kept as unknown fields, so a parsed space
    serializes back whole."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="xplane_subset.proto", package="xps",
                                            syntax="proto3")

    def message(name, fields, parent=None):
        m = (parent.nested_type if parent else fd.message_type).add(name=name)
        for number, fname, ftype, repeated, tname in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if tname:
                f.type_name = tname
        return m

    stat = message("XStat", [
        (1, "metadata_id", F.TYPE_INT64, False, None),
        (2, "double_value", F.TYPE_DOUBLE, False, None),
        (3, "uint64_value", F.TYPE_UINT64, False, None),
        (4, "int64_value", F.TYPE_INT64, False, None),
        (5, "str_value", F.TYPE_STRING, False, None),
        (6, "bytes_value", F.TYPE_BYTES, False, None),
        (7, "ref_value", F.TYPE_UINT64, False, None)])
    stat.oneof_decl.add(name="value")
    for f in stat.field[1:]:
        f.oneof_index = 0
    message("XEventMetadata", [
        (1, "id", F.TYPE_INT64, False, None),
        (2, "name", F.TYPE_STRING, False, None),
        (4, "display_name", F.TYPE_STRING, False, None),
        (5, "stats", F.TYPE_MESSAGE, True, ".xps.XStat")])
    message("XStatMetadata", [
        (1, "id", F.TYPE_INT64, False, None),
        (2, "name", F.TYPE_STRING, False, None)])
    plane = message("XPlane", [
        (1, "id", F.TYPE_INT64, False, None),
        (2, "name", F.TYPE_STRING, False, None),
        (4, "event_metadata", F.TYPE_MESSAGE, True, ".xps.XPlane.EventMetadataEntry"),
        (5, "stat_metadata", F.TYPE_MESSAGE, True, ".xps.XPlane.StatMetadataEntry")])
    for entry, value in (("EventMetadataEntry", ".xps.XEventMetadata"),
                         ("StatMetadataEntry", ".xps.XStatMetadata")):
        m = message(entry, [(1, "key", F.TYPE_INT64, False, None),
                            (2, "value", F.TYPE_MESSAGE, False, value)], parent=plane)
        m.options.map_entry = True
    message("XSpace", [(1, "planes", F.TYPE_MESSAGE, True, ".xps.XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("xps.XSpace"))


def instruction(name: str) -> str:
    """An op event's HLO instruction name: ``%fusion.12 = f32[8] …`` ->
    ``fusion.12``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _program_id(module_name: str) -> Optional[int]:
    m = re.search(r"\((\d+)\)\s*$", module_name)
    return int(m.group(1)) if m else None


def xspace(path: str):
    """The ``.xplane.pb`` at ``path``, parsed (``_xspace_class``)."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def op_scopes(path: str, program: str = "step") -> Dict[str, str]:
    """``{instruction name: tf_op}`` of the ops of the programs jitted
    from a function called ``program``, from the device planes' event
    metadata of the ``.xplane.pb`` at ``path``. An op without a
    ``tf_op`` is left out."""
    space = xspace(path)
    out: Dict[str, str] = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:") or plane.name.startswith("/device:CUSTOM"):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        ids = {_program_id(md.name) for md in plane.event_metadata.values()
               if " = " not in md.name and reduce_trace.same_program(md.name, program)}
        for md in plane.event_metadata.values():
            stats = {}
            for st in md.stats:
                kind = st.WhichOneof("value")
                v = getattr(st, kind) if kind else None
                stats[stat_names.get(st.metadata_id)] = (
                    stat_names.get(v) if kind == "ref_value" else v)
            if stats.get("tf_op") and stats.get("program_id") in ids:
                out[md.display_name or instruction(md.name)] = stats["tf_op"]
    return out


_HLO_OP = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+) = [^\n]*?\bmetadata=\{[^}\n]*?\bop_name="([^"]*)"', re.M)


def hlo_scopes(text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` from a compiled HLO module's text
    (``jax.stages.Compiled.as_text()``)."""
    return dict(_HLO_OP.findall(text))


def _is_jit(part: str) -> bool:
    return part.startswith("jit(")


def scope_path(tf_op: str) -> str:
    """The scope path of an op: its name stack without the primitive
    that ends it (``jit(step)/bind/squeeze:`` -> ``jit(step)/bind``).
    Where XLA fused ops of several stacks (``;``-joined), the scope they
    share if it names more than the jitted function, else the first
    one's."""
    if ":" in tf_op:
        tf_op = tf_op[:tf_op.rindex(":")]
    stacks = [s.split("/")[:-1] for s in tf_op.split(";") if s]
    if not stacks:
        return ""
    common = stacks[0]
    for s in stacks[1:]:
        n = 0
        while n < min(len(common), len(s)) and common[n] == s[n]:
            n += 1
        common = common[:n]
    if not any(not _is_jit(p) for p in common):
        common = stacks[0]
    return "/".join(common)


def in_scope(path: str, scope: str) -> bool:
    """Whether the scope path holds ``scope`` (one or more ``/``-joined
    names) as whole consecutive parts."""
    parts, want = path.split("/"), scope.split("/")
    return any(parts[i:i + len(want)] == want for i in range(len(parts) - len(want) + 1))


def top_scope(path: str) -> str:
    """The program's outermost scope inside the jitted function:
    ``jit(step)/matmul/L3.q_proj/jit(launch)/matmul_tile`` -> ``matmul``;
    "" where the op carries only primitive names."""
    parts = path.split("/")
    return parts[1] if len(parts) > 1 and not _is_jit(parts[1]) else ""


def program_ops(summary: reduce_trace.Summary, program: str) -> List[reduce_trace.Event]:
    """The device ops that started inside an execution of the programs
    jitted from a function called ``program``."""
    execs = sorted((m.start, m.end) for m in summary.modules
                   if reduce_trace.same_program(m.name, program))
    starts = [s for s, _ in execs]
    out = []
    for ev in summary.ops:
        i = bisect.bisect_right(starts, ev.start) - 1
        if i >= 0 and ev.start < execs[i][1]:
            out.append(ev)
    return out


#: an operand that is one of the decode step's arguments, as XLA names
#: them after the step's parameters (``params__embed__.1``,
#: ``cache__l0____k__.1``)
_STEP_ARG = re.compile(r"(?:params|cache)__")
#: the operands of an op, in its HLO text (not ``calls=%…`` and the like)
_OPERAND = re.compile(r"(?<![\w=])%([\w.\-]+)")


def tag(summary: reduce_trace.Summary, scopes: Mapping[str, str],
        program: str = "step") -> List[ScopedOp]:
    """The ops of ``program`` in the window, each with its scope path
    from ``scopes`` (``{instruction name: tf_op}``).

    An op XLA made without any name stack (a copy, an async start or
    done, a fusion it rewrote) takes its scope from its operands, first
    to last: ``bind`` for one of the step's arguments (the tied head's
    layout copy, the copies that keep the donated cache readable), else
    the scope of the op that produced it, found the same way."""
    evs = program_ops(summary, program)
    text = {instruction(ev.name): ev.name.split(" = ", 1)[-1] for ev in evs}
    done: Dict[str, str] = {}

    def scope_of(op: str, seen: frozenset) -> str:
        if op not in done:
            tf_op = scopes.get(op, "")
            path = scope_path(tf_op) if tf_op else ""
            if not _is_jit(path):   # no name stack: XLA's own
                path = ""
                for arg in _OPERAND.findall(text[op]):
                    if _STEP_ARG.match(arg):
                        path = f"jit({program})/bind"
                    elif arg in text and arg not in seen:
                        path = top_scope(scope_of(arg, seen | {op})) and done[arg]
                    if path:
                        break
            done[op] = path
        return done[op]

    return [ScopedOp(ev.name, ev.start, ev.end, scope_of(instruction(ev.name), frozenset()))
            for ev in evs]


def scope_ns(ops: Sequence[ScopedOp], *scopes: str) -> float:
    """Summed device time of the ops inside any of ``scopes``."""
    return sum(op.end - op.start for op in ops
               if any(in_scope(op.scope, s) for s in scopes))


def by_scope(ops: Sequence[ScopedOp]) -> List[list]:
    """Device seconds by top scope (``top_scope``), largest first; ops
    with none under ``""``."""
    tot: Dict[str, float] = {}
    for op in ops:
        k = top_scope(op.scope)
        tot[k] = tot.get(k, 0.0) + (op.end - op.start)
    return [[k, v * 1e-9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])]


def outermost(spans: Sequence, name: str) -> list:
    """The spans called ``name`` that lie inside no other span of that
    name (a caller may wrap the program's span in one of its own)."""
    out: list = []
    for sp in sorted((s for s in spans if s.name == name), key=lambda s: (s.start, -s.end)):
        if not out or sp.start >= out[-1].end:
            out.append(sp)
    return out


def idle_ms_per_span(summary: reduce_trace.Summary, names: Sequence[str]) -> Optional[float]:
    """Device idle milliseconds that fell inside spans ``names`` (each
    gap goes to the innermost span covering it, as in
    ``reduce_trace.idle_gaps``), per outermost span ``names[0]``."""
    n = len(outermost(summary.spans, names[0]))
    if not n:
        return None
    idle = sum(sec for label, sec in reduce_trace.idle_gaps(summary) if label in names)
    return idle * 1e3 / n


def host_spans(planes, names: Sequence[str], window) -> List[Span]:
    """The host spans called one of ``names``, clipped to ``window``,
    with their arguments."""
    out = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    s, t = max(e.start_ns, window[0]), min(e.start_ns + e.duration_ns, window[1])
                    if t > s:
                        out.append(Span(e.name, s, t, dict(e.stats)))
    return sorted(out, key=lambda sp: sp.start)


@dataclasses.dataclass
class Trace:
    summary: reduce_trace.Summary   # spans: the program's, by name only
    ops: List[ScopedOp]             # the decode step's ops, with scope paths
    spans: List[Span]               # the program's spans, with arguments
    steps: int                      # executions of the decode step


def read(path: str, program: str = "step") -> Trace:
    """Everything above from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)   # each ``.planes`` iterates once
    summary = reduce_trace.reduce_planes(data.planes, PROGRAM_SPANS)
    ops = tag(summary, op_scopes(path, program), program)
    return Trace(summary=summary, ops=ops,
                 spans=host_spans(data.planes, PROGRAM_SPANS, summary.window),
                 steps=reduce_trace.program_ns(summary, program)[1])


def compiled_step_text(engine) -> Optional[str]:
    """The HLO text of the engine's compiled decode step, lowered again at
    the shapes of the window's batcher (``engine.bench_batcher``); JAX's
    caches hold the program, so nothing compiles again. None where it
    cannot be had: a reader then finds nothing, and the run goes on."""
    import jax.numpy as jnp

    fn = engine.decode_fn()
    batcher = getattr(engine, "bench_batcher", None)
    if batcher is None or not hasattr(fn, "lower"):
        return None
    tok = jnp.zeros((engine.batch_size,), jnp.int32)
    try:
        return fn.lower(engine.params, batcher.cache, tok, tok).compile().as_text()
    except Exception:   # noqa: BLE001 - a metric reader must not end the run
        traceback.print_exc(file=sys.stderr)
        return None


def compiled_step_scopes(engine) -> Dict[str, str]:
    """``{instruction name: op_name}`` of the engine's compiled decode
    step (``compiled_step_text``); empty where there is none."""
    text = compiled_step_text(engine)
    return hlo_scopes(text) if text else {}


def step_name(run) -> str:
    return getattr(run.engine.decode_fn(), "__name__", "step")


def step_ops(run) -> Optional[List[ScopedOp]]:
    """The traced decode step's device ops with their scope paths, read
    once per run. The scope map is ``run.scope_map`` where the run
    carries one (a recorded trace), else the compiled step's."""
    if run.trace is None:
        return None
    cached = getattr(run, "_step_ops", None)
    if cached is None:
        scopes = getattr(run, "scope_map", None)
        if scopes is None:
            scopes = compiled_step_scopes(run.engine)
        cached = tag(run.trace, scopes, step_name(run)) if scopes else []
        run._step_ops = cached
    return cached


def ms_per_step(run, *scopes: str) -> Optional[float]:
    """Device milliseconds in ``scopes`` per traced execution of the
    decode step; None where the trace has no such scope."""
    ops = step_ops(run)
    if not ops:
        return None
    ns = scope_ns(ops, *scopes)
    n = reduce_trace.program_ns(run.trace, step_name(run))[1]
    return ns * 1e-6 / n if ns > 0 and n else None
