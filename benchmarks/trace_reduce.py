"""From a profiler trace to numbers: device busy and idle time, time per
device operation and per kernel, and the longest idle gaps named by what the
host was doing.  The one reducer of the benchmark; per-layer readers take
their device numbers from what it returns.

Two steps, so that the arithmetic is testable without a chip:

* :func:`load_xplane` reads the ``.xplane.pb`` the JAX profiler wrote (with
  ``jax.profiler.ProfileData``, nothing but JAX) into a flat list of events
  ``{"plane", "line", "name", "start_ns", "dur_ns"}``;
* :func:`reduce_events` turns such a list into the reduction.

Device planes are ``/device:TPU:<id>``.  On each, the line ``XLA Ops`` holds
one event per executed HLO instruction: the union of their intervals is the
time in which an operation ran.  (``XLA Modules`` holds one event per program
execution and ``Steps`` the step markers; both overlap the ops and are not
added.)  Host annotations (``jax.profiler.TraceAnnotation``, which the repo's
``obs.span`` opens too) are events on the host plane's thread lines.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: a host annotation that names an idle gap: the dotted lower-case names of
#: ``obs.span`` (``bench.dispatch``, ``serve.tick``, ``async_ea.sync``), not
#: the runtime's own (``PjitFunction(step)``)
SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")

_HLO = re.compile(r"^%?([\w.\-]+)\s*=\s*(\(?[a-z0-9]+\[[^\]]*\])")


def short_op_name(raw: str) -> str:
    """An HLO instruction's name and output shape only: ``fusion.12 f32[8,1024]``
    from ``%fusion.12 = f32[8,1024]{1,0:T(8,128)} fusion(f32[...] %p0, ...)``.
    A name that is not HLO text stays as it is, cut to 96 characters."""
    m = _HLO.match(raw)
    if m:
        return f"{m.group(1)} {m.group(2).lstrip('(')}"
    return raw[:96]


def load_xplane(path: str, layout: dict | None = None) -> list[dict]:
    """The events the reducer reads.  ``layout``, if given, is filled with
    ``{plane name: {line name: events}}`` of the WHOLE file, for an earlier
    line of the run: what to look at when a trace reduces to nothing."""
    from jax.profiler import ProfileData
    events = []
    for plane in ProfileData.from_file(path).planes:
        if layout is not None:
            layout[plane.name] = {ln.name: sum(1 for _ in ln.events)
                                  for ln in plane.lines}
        device = DEVICE_PLANE.match(plane.name)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                events.append({"plane": plane.name, "line": line.name,
                               "name": ev.name,
                               "start_ns": float(ev.start_ns),
                               "dur_ns": float(ev.duration_ns)})
    return events


def _union(intervals):
    """Sorted, merged ``(start, end)`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _self_times(events):
    """``(event, self ns, is a leaf)`` for the events of one line: an
    operation that encloses others (a ``while`` around its body, a call
    around its callee) keeps only the time its children do not cover, so the
    per-operation times add up to the busy time and not to several times it,
    and only the leaves say when the device really ran something."""
    out, stack = [], []
    for ev in sorted(events, key=lambda e: (e["start_ns"], -e["dur_ns"])):
        end = ev["start_ns"] + ev["dur_ns"]
        while stack and stack[-1][1] <= ev["start_ns"]:
            stack.pop()
        if stack and end <= stack[-1][1]:
            stack[-1][2][1] -= ev["dur_ns"]
            stack[-1][2][2] = False
        rec = [ev, ev["dur_ns"], True]
        out.append(rec)
        stack.append((ev["start_ns"], end, rec))
    return [(ev, max(0.0, ns), leaf) for ev, ns, leaf in out]


def _host_span_at(spans, s, e):
    """The host annotation covering most of the gap ``[s, e]``; the
    innermost (shortest) among equals."""
    best, best_key = "host:no-span", (0.0, 0.0)
    for name, hs, he in spans:
        cover = min(e, he) - max(s, hs)
        if cover <= 0:
            continue
        key = (cover, -(he - hs))
        if key > best_key:
            best, best_key = name, key
    return best


def reduce_events(events, *, window_s: float, device_ids=None,
                  top: int = 10) -> dict:
    """The reduction of one traced window.

    ``busy_s``: seconds in which an operation ran, the union of the LEAF
    device operations' intervals (a ``while`` or a call spans its body's
    stalls too, so it does not count by itself), averaged over the devices
    used.  ``window_s``: the
    traced window by the host's clock, as given.  ``per_device``: busy
    seconds of each device.  ``ops``: ``{short name: [self seconds, count]}``
    summed over the FIRST device used (every chip of a data-parallel mesh
    runs the same program).  ``device_ops``: the ``top`` of those by time.
    ``idle_gaps``: the ``top`` idle gaps of that device, each named by the
    host annotation open during it, as ``[name, seconds]``; gaps with one
    name are NOT merged: the list shows the longest single stalls.
    """
    per_plane: dict[int, list] = {}
    host_spans = []
    for ev in events:
        m = DEVICE_PLANE.match(ev["plane"])
        if m:
            if ev["line"] == OPS_LINE and ev["dur_ns"] > 0:
                per_plane.setdefault(int(m.group(1)), []).append(ev)
        elif ev["plane"] == HOST_PLANE and SPAN_NAME.match(ev["name"]):
            host_spans.append((ev["name"], ev["start_ns"],
                               ev["start_ns"] + ev["dur_ns"]))
    ids = sorted(per_plane) if device_ids is None else \
        [i for i in device_ids if i in per_plane]
    if not ids:
        raise ValueError("the trace holds no device operation on the chips "
                         f"used (device planes found: {sorted(per_plane)})")
    per_device, timed, busy = {}, {}, {}
    for i in ids:
        timed[i] = _self_times(per_plane[i])
        busy[i] = _union((e["start_ns"], e["start_ns"] + e["dur_ns"])
                         for e, _, leaf in timed[i] if leaf)
        per_device[i] = sum(e - s for s, e in busy[i]) / 1e9
    first = ids[0]
    ops: dict[str, list] = {}
    for ev, self_ns, _ in timed[first]:
        rec = ops.setdefault(short_op_name(ev["name"]), [0.0, 0, 0.0])
        rec[0] += self_ns / 1e9
        rec[1] += 1
        rec[2] += ev["dur_ns"] / 1e9
    merged = busy[first]
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(merged, merged[1:])), reverse=True)[:top]
    return {
        "busy_s": sum(per_device.values()) / len(per_device),
        "window_s": float(window_s),
        "per_device": per_device,
        "first_device_busy_s": per_device[first],
        "ops": ops,
        "device_ops": [[n, v[0]] for n, v in sorted(
            ops.items(), key=lambda kv: -kv[1][0])[:top]],
        "idle_gaps": [[_host_span_at(host_spans, s, e), g / 1e9]
                      for g, s, e in gaps],
    }
