"""Reduce a profiler trace to the numbers the per-layer readers take.

:func:`load_xspace` reads the ``.xplane.pb`` that ``jax.profiler``
writes, with ``jax.profiler.ProfileData``, into a plain form.  For each
``/device:TPU:<id>`` plane:

- ``modules``: the ``XLA Modules`` line, one event per execution of a
  compiled program, as ``[start_ns, dur_ns, name]``;
- ``ops``: the ``XLA Ops`` line as ``[start_ns, dur_ns, name, category]``.
  On the TPU an op event is named by its HLO instruction text (there is
  no scope or category stat); :func:`short_name` keeps the instruction,
  its result shape and opcode, and :func:`category` sorts it into
  ``matmul`` (a ``kOutput`` fusion, the TPU's fusion around a dot or
  convolution, or a bare ``convolution``/``dot``), ``collective``
  (all-reduce, all-gather, reduce-scatter, collective-permute,
  all-to-all, send/recv, by instruction name) and ``other``.

``host`` holds the host ``TraceAnnotation`` spans named ``bench.*`` and
``slate.*`` as ``[start_ns, dur_ns, name]``; the device clock is
aligned to the host's to about a millisecond.

The device's op buffer is finite: a program with very many small ops
(the recursive LU panel) fills it within a few executions, and later op
events are dropped while module events go on.  So :func:`reduce` takes
busy time from the module events (the union of program executions), and
every op-level number only from the executions whose ops were all
recorded (``modules_covered``).

Op events nest: a loop's event spans the events of its body.  Each op's
time in the categories and the top list is its own time, its span less
that of the ops it holds, so that no interval counts twice.
"""

from __future__ import annotations

import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "send", "recv",
               "collective-broadcast")
DISPATCH = "PJRT_LoadedExecutable_Execute"
COVER_SLACK = 0.01      # an execution's tail past its last op, at most
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_INSTR = re.compile(r"^%?(?P<inst>\S+) = (?P<type>.*?) ?"
                    r"(?P<op>[a-z][a-z0-9-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def category(name: str) -> str:
    inst = name.lstrip("%").split(" ", 1)[0]
    if inst.startswith(COLLECTIVES):
        return "collective"
    if "kind=kOutput" in name or inst.startswith(("convolution", "dot")):
        return "matmul"
    return "other"


def short_name(name: str) -> str:
    """``fusion.405 f32[20480,20480] fusion kLoop`` for an HLO text."""
    m = _INSTR.match(name)
    if not m:
        return name[:120]
    kind = re.search(r"kind=k(\w+)", name)
    typ = _LAYOUT.sub("", m["type"])
    typ = typ if len(typ) <= 60 else typ[:57] + "..."
    return " ".join(x for x in (m["inst"], typ, m["op"],
                                kind[1] if kind else "") if x)


def load_xspace(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host, dispatch = {}, [], []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] += [[float(e.start_ns),
                                        float(e.duration_ns), e.name]
                                       for e in line.events]
                elif line.name == "XLA Ops":
                    dev["ops"] += [[float(e.start_ns), float(e.duration_ns),
                                    short_name(e.name), category(e.name)]
                                   for e in line.events]
            devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("bench.", "slate.")):
                        host.append([float(ev.start_ns),
                                     float(ev.duration_ns), ev.name])
                    elif ev.name == DISPATCH:
                        dispatch.append(float(ev.start_ns))
    return {"devices": devices, "host": host, "dispatch": sorted(dispatch)}


def clock_offset(tr: dict, w0: float, w1: float, ids) -> float:
    """Nanoseconds to add to device times to put them on the host's
    clock.  The trace is taken around the window alone, so each recorded
    program execution ran inside it: the first no earlier than the
    window's first dispatch, the last no later than the window's end.
    The device clock stands where it keeps to both; a clock that puts
    the first execution before its dispatch is moved forward to it, and
    one that puts the last past the window's end is moved back to it.
    The two conversions of a run's trace can be seconds apart, so the
    offset has no bound of its own.  Where the executions together span
    more than the window (events from outside it), only the first rule
    holds."""
    mods = [m for dev in ids
            for m in tr["devices"].get(dev, {}).get("modules", [])]
    if not mods:
        return 0.0
    first = next((t for t in tr.get("dispatch", []) if t >= w0), w0)
    lo = first - min(m[0] for m in mods)
    hi = w1 - max(m[0] + m[1] for m in mods)
    shift = max(0.0, lo)
    return min(shift, hi) if lo <= hi else shift


# ------------------------------------------------------------ intervals


def union(intervals) -> list:
    """Merged, sorted ``[start, end]`` of the given intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def overlap(merged_a, merged_b) -> float:
    """Length of the intersection of two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(merged_a) and j < len(merged_b):
        s = max(merged_a[i][0], merged_b[j][0])
        e = min(merged_a[i][1], merged_b[j][1])
        if e > s:
            total += e - s
        if merged_a[i][1] < merged_b[j][1]:
            i += 1
        else:
            j += 1
    return total


def own_times(ops):
    """Each op's own time and whether it holds others: its span less the
    spans of the ops that lie wholly inside it, as a loop's body lies in
    the loop's event.  Ops that only overlap (an asynchronous collective
    beside a fusion) keep their spans.  ``ops`` are ``(start, end, ...)``."""
    own = [e - s for s, e, *_ in ops]
    leaf = [True] * len(ops)
    stack = []
    for i in sorted(range(len(ops)), key=lambda k: (ops[k][0], -ops[k][1])):
        s, e = ops[i][0], ops[i][1]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            own[stack[-1]] -= e - s
            leaf[stack[-1]] = False
        stack.append(i)
    return [max(0.0, t) for t in own], leaf


def _clip(ops, w0, w1):
    out = []
    for s, d, *rest in ops:
        e = min(s + d, w1)
        s = max(s, w0)
        if e > s:
            out.append((s, e, *rest))
    return out


def _label(host, t: float, skip: str) -> str:
    best = None
    for s, d, name in host:
        if name != skip and s <= t <= s + d:
            if best is None or d < best[1]:
                best = (s, d, name)
    return best[2] if best else "none"


def reduce(tr: dict, window_span: str = "bench.window",
           devices=None, top: int = 10) -> dict:
    spans = [h for h in tr["host"] if h[2] == window_span]
    if not spans:
        raise ValueError(f"no host span {window_span!r} in the trace")
    w0, wd, _ = max(spans, key=lambda h: h[1])
    w1 = w0 + wd
    ids = sorted(tr["devices"]) if devices is None else list(devices)
    shift = clock_offset(tr, w0, w1, ids)
    per, totals, busy0 = {}, {}, []
    for dev in ids:
        d = tr["devices"].get(dev, {"modules": [], "ops": []})
        mods = [(s + shift, du, *rest) for s, du, *rest in d["modules"]]
        busy = union((s, e) for s, e, *_ in _clip(mods, w0, w1))
        ops_end = max((s + du + shift for s, du, *_ in d["ops"]),
                      default=w0)
        # executions wholly inside the window whose ops all made it in
        covered = [(s, s + du) for s, du, *_ in mods
                   if s >= w0 and s + du <= w1
                   and s + du <= ops_end + COVER_SLACK * du + 2e4]
        lo = min((s for s, _ in covered), default=w0)
        hi = max((e for _, e in covered), default=w0)
        ops = _clip([(s + shift, du, *rest) for s, du, *rest in d["ops"]],
                    lo, hi)
        own, leaf = own_times(ops)
        by_cat = {"matmul": 0.0, "collective": 0.0, "other": 0.0}
        for (_, _, _, cat), t in zip(ops, own):
            by_cat[cat] += t
        coll = union((s, e) for s, e, _, cat in ops if cat == "collective")
        compute = union((s, e) for (s, e, _, cat), lf in zip(ops, leaf)
                        if lf and cat != "collective")
        progs = {}
        for s, e, name in _clip(mods, w0, w1):
            c, t = progs.get(name, (0, 0.0))
            progs[name] = (c + 1, t + e - s)
        per[dev] = {"busy_s": length(busy) / 1e9,
                    "modules": len(_clip(mods, w0, w1)),
                    "modules_covered": len(covered),
                    "programs": [[k, c, t / 1e9] for k, (c, t) in sorted(
                        progs.items(), key=lambda kv: -kv[1][1])[:3]],
                    "matmul_s": by_cat["matmul"] / 1e9,
                    "collective_s": by_cat["collective"] / 1e9,
                    "other_s": by_cat["other"] / 1e9,
                    "collective_exposed_s": (length(coll)
                                             - overlap(coll, compute)) / 1e9,
                    "ops": len(ops)}
        for (_, _, name, _), t in zip(ops, own):
            totals[name] = totals.get(name, 0.0) + t / 1e9
        if not busy0:
            busy0 = busy
    edges = [w0] + [x for iv in busy0 for x in iv] + [w1]
    gaps = sorted(((e - s, s) for s, e in zip(edges[0::2], edges[1::2])
                   if e > s), reverse=True)[:top]
    n = max(len(ids), 1)
    return {"window_s": wd / 1e9,
            "clock_offset_s": shift / 1e9,
            "busy_s": sum(p["busy_s"] for p in per.values()) / n,
            "devices": per,
            "device_ops": [[k, v] for k, v in sorted(
                totals.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[_label(tr["host"], s + du / 2, window_span),
                           du / 1e9] for du, s in gaps]}
