"""Device time per named phase of the solve program, from a profiler
trace joined with the program's compiled HLO text.

On the TPU an ``XLA Ops`` event carries only its HLO instruction text:
no scope.  The library names its phases with ``util/trace.span``, whose
``jax.named_scope`` reaches the compiled program as each instruction's
``metadata={op_name="jit(solve)/slate.gesv/slate.getrf/slate.getrf/
panel/..."}``, and the trace's op events carry the same instruction
names.  So
:func:`table` joins the two:

- the executions, clock offset, clipping and coverage are
  ``benchmark/trace.py``'s (:func:`covered`), and each op counts its own
  time (``trace.own_times``);
- only the ops inside covered executions of the program that the text
  holds count (a second program of the window, such as the shift's
  ``jit_convert_element_type``, is left out);
- each op's own time goes to the innermost name of :data:`PHASES` in its
  ``op_name`` (or the one it inherits, :func:`instruction_scopes`); an
  op under a driver scope alone (``slate.getrs``) goes to
  ``<driver>/other``, one under no ``slate.`` scope to ``unscoped``, and
  one whose instruction the text lacks to ``unjoined``.

:func:`ms_per_solve` scales a phase's share of that op time by the
device's busy time per solve, as ``metrics/nonmatmul_ms.solve.py``
scales the non-matmul share.
"""

from __future__ import annotations

import bisect
import re

from benchmark import trace

PHASES = ("slate.getrf/panel", "slate.getrf/swap", "slate.getrf/trsm",
          "slate.getrf/gemm", "slate.getrf/health",
          "slate.potrf/herk", "slate.potrf/panel", "slate.potrf/health",
          "slate.getrs/permute",
          "slate.layout/to_dense", "slate.layout/from_dense")
UNSCOPED = "unscoped"
UNJOINED = "unjoined"
# the per-layer quantities the phases make, each the sum of its phases'
# milliseconds per solve (:func:`ms_per_solve`)
METRICS = {
    "panel_ms.solve": ("slate.getrf/panel", "slate.potrf/panel"),
    "update_ms.solve": ("slate.getrf/trsm", "slate.getrf/gemm",
                        "slate.potrf/herk"),
    "swap_ms.solve": ("slate.getrf/swap",),
    "layout_ms.solve": ("slate.layout/to_dense", "slate.layout/from_dense"),
}

_MODULE = re.compile(r"^HloModule (\S+?),?\s")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(?P<name>[^\s(]+) .*\{\s*$")
_LINE = re.compile(r"^\s*(?:ROOT )?%?(?P<inst>[^\s=]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="(?P<name>[^"]*)"')
_REF = re.compile(r"%([\w.-]+)")


def module_name(hlo_text: str) -> str:
    """``jit_solve`` for a text that starts ``HloModule jit_solve, ...``;
    the trace names each execution ``jit_solve(<fingerprint>)``."""
    m = _MODULE.match(hlo_text)
    if not m:
        raise ValueError("the text does not start with an HloModule line")
    return m.group(1)


def instruction_scopes(hlo_text: str) -> dict:
    """``{instruction name: (op_name, inherited)}`` over every
    instruction of the text.

    An instruction that the compiler made carries no ``op_name``: the
    loop a gather becomes, a layout copy.  It takes the scope of the
    instruction that calls its computation (a loop's body runs for the
    loop), else of the nearest instruction that reads its result, else
    of the nearest one it reads (``inherited`` is then true; ``""``
    where none has a scope).  An ``op_name`` outside every ``slate.``
    scope is the instruction's own and stays."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            cur = comps.setdefault(head["name"], [])
            continue
        m = _LINE.match(line)
        if m and cur is not None:
            body, _, meta = line[m.end():].partition(", metadata={")
            n = _OP_NAME.search("metadata={" + meta) if meta else None
            # a parameter runs nothing: it passes no scope on
            cur.append((m["inst"], n["name"] if n else "",
                        _REF.findall(body), " parameter(" in body))
    caller, users, operands, comp_of = {}, {}, {}, {}
    for comp, insts in comps.items():
        local = {inst for inst, *_ in insts}
        for inst, _, refs, _ in insts:
            comp_of[inst] = comp
            for r in refs:
                if r in comps:
                    caller.setdefault(r, inst)
                elif r in local:
                    users.setdefault(r, []).append(inst)
                    operands.setdefault(inst, []).append(r)
    own = {inst: name for insts in comps.values()
           for inst, name, *_ in insts}
    param = {inst for insts in comps.values()
             for inst, _, _, is_param in insts if is_param}

    def by_caller(inst):
        seen = set()
        while not own[inst] and comp_of[inst] in caller and inst not in seen:
            seen.add(inst)
            inst = caller[comp_of[inst]]
        return own[inst]

    scope = {inst: by_caller(inst) for inst in own}
    for insts in comps.values():        # printed in order: users come later
        for inst, *_ in reversed(insts):
            if not scope[inst] and inst not in param:
                scope[inst] = next((scope[u] for u in users.get(inst, ())
                                    if scope[u]), "")
        for inst, *_ in insts:
            if not scope[inst] and inst not in param:
                scope[inst] = next((scope[o] for o in operands.get(inst, ())
                                    if scope[o]), "")
    return {inst: (scope[inst], not own[inst] and bool(scope[inst]))
            for inst in own}


def phase_of(op_name: str, phases=PHASES) -> str:
    """The innermost span of ``phases`` in an ``op_name``, else the
    innermost driver scope's ``<driver>/other``, else ``unscoped``."""
    parts = op_name.split("/")
    found = UNSCOPED
    for i, part in enumerate(parts):
        if part.startswith("slate."):
            pair = "/".join(parts[i:i + 2])
            found = pair if pair in phases else f"{part}/other"
    return found


def covered(tr: dict, window_span: str = "bench.window", devices=None):
    """Per device, the window's covered executions ``(start, end, name)``
    and their ops ``(start, end, name, category)`` on the host clock,
    with each op's own time: the rules of ``trace.reduce``."""
    spans = [h for h in tr["host"] if h[2] == window_span]
    if not spans:
        raise ValueError(f"no host span {window_span!r} in the trace")
    w0, wd, _ = max(spans, key=lambda h: h[1])
    w1 = w0 + wd
    ids = sorted(tr["devices"]) if devices is None else list(devices)
    shift = trace.clock_offset(tr, w0, w1, ids)
    out = {}
    for dev in ids:
        d = tr["devices"].get(dev, {"modules": [], "ops": []})
        mods = [(s + shift, du, *rest) for s, du, *rest in d["modules"]]
        ops_end = max((s + du + shift for s, du, *_ in d["ops"]),
                      default=w0)
        execs = sorted((s, s + du, name) for s, du, name in mods
                       if s >= w0 and s + du <= w1
                       and s + du <= ops_end + trace.COVER_SLACK * du + 2e4)
        lo = min((s for s, *_ in execs), default=w0)
        hi = max((e for _, e, _ in execs), default=w0)
        ops = trace._clip([(s + shift, du, *rest)
                           for s, du, *rest in d["ops"]], lo, hi)
        own, _ = trace.own_times(ops)
        out[dev] = (execs, ops, own)
    return out


def table(tr: dict, hlo_text: str, window_span: str = "bench.window",
          devices=None, top: int = 3) -> dict:
    """Per device, over the covered executions of the program
    ``hlo_text`` holds: the own op time (s) and op events of each
    instruction and of each phase, the op time joined to an instruction
    and the part of it whose scope was inherited.  ``spans``: the phases
    that the program's instructions name; ``top``: the longest
    instructions of each ``*/other``, ``unscoped`` and ``unjoined``."""
    prog = module_name(hlo_text)
    scopes = instruction_scopes(hlo_text)
    bucket = {inst: phase_of(name) for inst, (name, _) in scopes.items()}
    per, tops = {}, {}
    for dev, (execs, ops, own) in covered(tr, window_span, devices).items():
        mine = [(s, e) for s, e, name in execs
                if name.split("(", 1)[0] == prog]
        starts = [s for s, _ in mine]
        by_op = {}
        for (s, _, name, _), t in zip(ops, own):
            k = bisect.bisect_right(starts, s) - 1
            if k >= 0 and s < mine[k][1]:
                acc = by_op.setdefault(name, [0.0, 0])
                acc[0] += t / 1e9
                acc[1] += 1
        by_phase, joined_s, inherited_s = {}, 0.0, 0.0
        for name, (secs, events) in by_op.items():
            inst = name.split(" ", 1)[0].lstrip("%")
            b = bucket.get(inst, UNJOINED)
            acc = by_phase.setdefault(b, [0.0, 0])
            acc[0] += secs
            acc[1] += events
            if b != UNJOINED:
                joined_s += secs
                inherited_s += secs if scopes[inst][1] else 0.0
            if b in (UNSCOPED, UNJOINED) or b.endswith("/other"):
                named = tops.setdefault(b, {})
                named[name] = named.get(name, 0.0) + secs
        per[dev] = {"executions": len(mine),
                    "op_s": sum(secs for secs, _ in by_op.values()),
                    "joined_s": joined_s, "inherited_s": inherited_s,
                    "phases": by_phase, "instructions": by_op}
    return {"program": prog, "devices": per,
            "spans": sorted(set(bucket.values()) - {UNSCOPED}),
            "top": {b: sorted(v.items(), key=lambda kv: -kv[1])[:top]
                    for b, v in sorted(tops.items())}}


def ms_by_phase(tab: dict, red: dict, solves: int) -> dict:
    """Milliseconds per solve of every phase in ``tab``: its share of the
    covered op time times the device's busy time (``trace.reduce``'s
    ``busy_s``), averaged over the devices that recorded ops, over
    ``solves``.  The phases sum to the busy time per solve."""
    devs = [(dev, d) for dev, d in tab["devices"].items() if d["op_s"] > 0]
    if not solves or not devs:
        return {}
    out = {}
    for dev, d in devs:
        scale = red["devices"][dev]["busy_s"] / d["op_s"]
        for b, (secs, _) in d["phases"].items():
            out[b] = out.get(b, 0.0) + 1e3 * secs * scale / (
                len(devs) * solves)
    return out


def ms_per_solve(tab: dict, red: dict, solves: int, names) -> float | None:
    """Milliseconds per solve of the phases ``names`` together; None
    where the program names none of them (a program without the spans)
    or nothing was recorded."""
    ms = ms_by_phase(tab, red, solves)
    if not ms or not set(names) & set(tab["spans"]):
        return None
    return sum(ms.get(n, 0.0) for n in names)


def events_per_execution(tab: dict) -> dict:
    """Op events per execution of the program, by phase, averaged over
    the devices."""
    devs = [d for d in tab["devices"].values() if d["executions"]]
    out = {}
    for d in devs:
        for b, (_, events) in d["phases"].items():
            out[b] = out.get(b, 0.0) + events / (d["executions"] * len(devs))
    return out


def idle_by_label(tr: dict, window_span: str = "bench.window",
                  devices=None) -> dict:
    """Seconds of the window in which the first device ran no program,
    by the innermost host span around each gap's middle (``none`` where
    no span but the window holds it): ``trace.reduce``'s idle gaps, all
    of them."""
    spans = [h for h in tr["host"] if h[2] == window_span]
    if not spans:
        raise ValueError(f"no host span {window_span!r} in the trace")
    w0, wd, _ = max(spans, key=lambda h: h[1])
    w1 = w0 + wd
    ids = sorted(tr["devices"]) if devices is None else list(devices)
    if not ids:
        return {}
    shift = trace.clock_offset(tr, w0, w1, ids)
    mods = [(s + shift, du) for s, du, *_ in
            tr["devices"].get(ids[0], {"modules": []})["modules"]]
    busy = trace.union((s, e) for s, e in trace._clip(mods, w0, w1))
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    out = {}
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            label = trace._label(tr["host"], (s + e) / 2, window_span)
            out[label] = out.get(label, 0.0) + (e - s) / 1e9
    return out
