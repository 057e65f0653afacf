"""Reduction of a profiler trace (``.xplane.pb``) to device busy time, op
times by class, and idle gaps labelled by what the host was doing.

On a TPU the trace has one plane per chip, ``/device:TPU:<n>``, whose line
``XLA Modules`` holds one event per program execution (``jit_model(<id>)``)
and whose line ``XLA Ops`` holds the HLO instructions the core ran, each
named by its HLO text (``%fusion.20 = bf16[...] fusion(...), kind=kOutput,
...``).  A fusion's name does not say what it computes, so ops are classed
from the compiled program's HLO text: ``mxu`` for convolutions, dots, and
fusions or Pallas calls that contain them; ``move`` for instructions and
fusions that only copy, slice, pad, concatenate, transpose, broadcast or
cast; ``other`` for the rest (pools, norms, softmax, elementwise).  Loops
(``while``, ``conditional``, ``call``) are left out of op times, since their
bodies' ops are listed one by one.  Each op also takes the program scope
(``jax.named_scope``) its instruction's ``op_name`` names, so that device
time can be read by the program's own layers.  The host plane holds the
run's ``TraceAnnotation`` spans, on the same clock.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW = "bench.window"
# host spans, innermost first: a gap is labelled by the first that covers it
LABELS = ("fn", "engine.step", "wait")
MXU = {"convolution", "dot"}
MOVE = {"copy", "copy-start", "copy-done", "slice", "dynamic-slice", "dynamic-update-slice",
        "pad", "concatenate", "transpose", "reshape", "bitcast", "broadcast", "convert",
        "bitcast-convert", "reverse", "async-start", "async-done", "async-update"}
NEUTRAL = {"parameter", "constant", "tuple", "get-tuple-element", "iota"}
NESTING = {"while", "conditional", "call"}
TOP = 10
# JAX's own frames in an op_name, beside jitted calls (``jit(relu)``) and
# einsum specs (``bhnm,bmhd->bnhd``); none of them is a program scope
FRAMES = {"while", "body", "cond", "closed_call", "checkpoint", "remat", "pjit"}
UNSCOPED = "(unscoped)"

_INSTR = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")
_TYPED = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+) = ([a-z]\w*)\[")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)=\{?%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_JIT = re.compile(r"jit\(.*\)")


def op_name(event_name: str) -> str:
    """``fusion.20`` from ``%fusion.20 = bf16[...] fusion(...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """``jit_model`` from ``jit_model(2689850245132967268)``."""
    return event_name.split("(", 1)[0]


def _computations(hlo_text: str) -> dict[str, list[tuple[str, str, list[str], str]]]:
    """``name, opcode, called computations, text`` of every instruction of a
    compiled module's HLO text, by computation."""
    comps: dict[str, list[tuple[str, str, list[str], str]]] = {}
    cur = None
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.endswith("{") and " -> " in s and " = " not in s:
            head = s.split()[1] if s.startswith("ENTRY") else s.split()[0]
            cur = comps.setdefault(head.lstrip("%"), [])
        elif s == "}":
            cur = None
        elif cur is not None:
            m = _INSTR.match(s)
            if m:
                cur.append((m[1], m[2], _CALLS.findall(s), s))
    return comps


def classify(hlo_text: str) -> dict[str, str]:
    """Class (``mxu``, ``move`` or ``other``) of every instruction of a
    compiled module's HLO text, by name."""
    comps = _computations(hlo_text)
    deep: dict[str, set[str]] = {}

    def opcodes(comp: str) -> set[str]:
        if comp not in deep:
            deep[comp] = set()
            out = set()
            for _, op, calls, _ in comps.get(comp, []):
                out.add(op)
                for c in calls:
                    out |= opcodes(c)
            deep[comp] = out
        return deep[comp]

    classes = {}
    for instrs in comps.values():
        for name, op, calls, text in instrs:
            inner = set().union(*(opcodes(c) for c in calls)) if calls else set()
            if op in MXU or inner & MXU or (op == "custom-call" and "tpu_custom_call" in text):
                classes[name] = "mxu"
            elif op == "custom-call":
                classes[name] = "move" if "ConcatBitcast" in text else "other"
            elif op in NESTING:
                classes[name] = "loop"
            elif op in MOVE or (op == "fusion" and inner <= MOVE | NEUTRAL):
                classes[name] = "move"
            else:
                classes[name] = "other"
    return classes


def scope(op_name: str | None) -> str:
    """The program scope an ``op_name`` names: ``attn`` from
    ``jit(model)/while/body/closed_call/checkpoint/attn/dot_general``.  The
    last component (the primitive) goes, and so do JAX's frames, jitted
    calls and einsum specs; ``(unscoped)`` where nothing is left."""
    parts = (op_name or "").split("/")[:-1]
    kept = [p for p in parts if p not in FRAMES and not _JIT.fullmatch(p) and "->" not in p]
    return "/".join(kept) or UNSCOPED


def scopes(hlo_text: str) -> dict[str, str]:
    """Program scope of every instruction of a compiled module's HLO text,
    by name, from its ``op_name``.  An instruction with no ``op_name`` of its
    own (a fusion, a call) takes that of the root of the computation it
    calls."""
    comps = _computations(hlo_text)
    instrs = {i[0]: i for body in comps.values() for i in body}
    root = {c: i[0] for c, body in comps.items() for i in body if i[3].startswith("ROOT")}
    named: dict[str, str | None] = {}

    def op_name_of(name: str) -> str | None:
        if name not in named:
            named[name] = None  # a computation that calls itself names nothing
            _, _, calls, text = instrs[name]
            m = _OP_NAME.search(text)
            if m:
                named[name] = m[1]
            elif calls and calls[0] in root:
                named[name] = op_name_of(root[calls[0]])
        return named[name]

    return {name: scope(op_name_of(name)) for name in instrs}


def operand_types(hlo_text: str) -> list[tuple[str, tuple[str, ...]]]:
    """``(name, element types of its operands)`` of every convolution and dot
    of a compiled module's HLO text.  An operand is named (``%fusion.13``),
    with or without its type printed beside it; its type is that of the
    instruction or parameter of that name."""
    types: dict[str, str] = {}
    found: list[tuple[str, list[str]]] = []
    for line in hlo_text.splitlines():
        s = line.strip()
        m = _TYPED.match(s)
        if not m:
            continue
        types[m[1]] = m[2]
        op = _INSTR.match(s)
        if op and op[2] in MXU:
            args, depth = s[op.end():], 1
            for i, ch in enumerate(args):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    args = args[:i]
                    break
            found.append((m[1], _OPERAND.findall(args)))
    return [(name, tuple(types.get(a, "?") for a in args)) for name, args in found]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(profile, hlo: dict[str, str] | None = None) -> dict | None:
    """Busy and window seconds, op seconds by module and class and by module
    and program scope, executions per module, the ops that took most time,
    and the idle gaps by host span.
    Op times and executions count the program runs wholly inside the window;
    busy time is the union of all op intervals, clipped to it.
    ``profile`` is a ``jax.profiler.ProfileData``; ``hlo`` maps a module name
    to its compiled HLO text.  ``None`` when the trace holds no device op or
    no window span."""
    hosts, devices = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            hosts.append(plane)
    spans = defaultdict(list)
    for plane in hosts:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW or ev.name in LABELS:
                    spans[ev.name].append((ev.start_ns, ev.start_ns + ev.duration_ns))
    if not spans[WINDOW] or not devices:
        return None
    w0, w1 = spans[WINDOW][0]
    classes = {m: classify(t) for m, t in (hlo or {}).items()}
    scoped = {m: scopes(t) for m, t in (hlo or {}).items()}
    label_spans = {k: sorted(spans[k]) for k in LABELS}

    busy_total, op_s, scope_s, runs, by_op, gaps = 0.0, defaultdict(float), defaultdict(float), \
        defaultdict(int), defaultdict(float), defaultdict(float)
    n_ops = 0
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        mods = []
        for ev in (lines[MODULES_LINE].events if MODULES_LINE in lines else []):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if w0 <= s and e <= w1:
                runs[module_name(ev.name)] += 1
            mods.append((s, e, module_name(ev.name)))
        mods.sort()
        starts = [m[0] for m in mods]
        ivs = []
        for ev in (lines[OPS_LINE].events if OPS_LINE in lines else []):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= w0 or s >= w1:
                continue
            ivs.append((max(s, w0), min(e, w1)))
            j = bisect.bisect_right(starts, s) - 1
            if j < 0 or s >= mods[j][1] or mods[j][0] < w0 or mods[j][1] > w1:
                continue  # outside any program run wholly inside the window
            mod, name = mods[j][2], op_name(ev.name)
            cls = classes.get(mod, {}).get(name, "other")
            if cls == "loop" or name.split(".")[0] in NESTING:
                continue
            n_ops += 1
            op_s[(mod, cls)] += ev.duration_ns * 1e-9
            scope_s[(mod, scoped.get(mod, {}).get(name, UNSCOPED))] += ev.duration_ns * 1e-9
            by_op[f"{mod}/{name}"] += ev.duration_ns * 1e-9
        merged = _merge(ivs)
        busy_total += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps[_label(label_spans, (s + e) / 2)] += (e - s) * 1e-9
    if not n_ops:
        return None
    k = len(devices)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total * 1e-9 / k,
        "op_s": {f"{m}:{c}": v / k for (m, c), v in op_s.items()},
        "scope_s": {f"{m}:{c}": v / k for (m, c), v in scope_s.items()},
        "runs": {m: n / k for m, n in runs.items()},
        "device_ops": [[n, v / k] for n, v in sorted(by_op.items(), key=lambda x: -x[1])[:TOP]],
        "idle_gaps": [[n, v / k] for n, v in sorted(gaps.items(), key=lambda x: -x[1])[:TOP]],
    }


def _label(label_spans, t) -> str:
    for name in LABELS:
        sp = label_spans[name]
        j = bisect.bisect_right(sp, (t, float("inf"))) - 1
        if j >= 0 and sp[j][0] <= t <= sp[j][1]:
            return name
    return "harness"


def reduce_dir(trace_dir: str, hlo: dict[str, str] | None = None) -> dict | None:
    """``reduce`` of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        return None
    return reduce(ProfileData.from_file(paths[0]), hlo)
