"""Reduction of a profiler trace (``.xplane.pb``) to the device numbers.

``summarize(trace, edge_count)`` reads one trace with
``jax.profiler.ProfileData`` and returns:

- ``window_s``: the length of the host span ``bench.window`` (the measured
  window), or of the device activity where that span is missing;
- ``busy_s``: per chip, the union of the intervals in which an operation
  ran (the "XLA Ops" line of each device plane), clipped to the window,
  averaged over the chips;
- ``push_s``: per chip, the device time of the phase-1 push's operations,
  averaged over the chips.  Until the program names its push, an op is the
  push's by kind: a leaf op (one that holds no other op, so not a ``while``)
  that is the Pallas kernel (``custom_call_target="tpu_custom_call"``) or
  that reads or writes an edge-length array (XLA's gather, scatter and the
  sort and masks around them).  ``push_ops`` lists the ops so attributed;
- ``device_ops``: the ten ops with the most self time (an op's time less
  the ops nested in it), by short label (``name opcode [kind]``);
- ``idle_gaps``: the ten longest stretches in which chip 0 ran nothing,
  each named by the innermost host span open on the window's thread at its
  midpoint (the benchmark's ``bench.*`` spans, or a deeper runtime span).

The XLA Ops line nests: a ``while`` op's event spans the ops of its body.
The busy union is unaffected; per-op times use self time.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
KERNEL = 'custom_call_target="tpu_custom_call"'
_DIMS = re.compile(r"\[([0-9,]+)\]")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_KIND = re.compile(r"kind=(k[A-Za-z]+)")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    push_s: float
    devices: int
    op_s: dict
    push_ops: list
    device_ops: list
    idle_gaps: list


def find_trace(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(files)}")
    return files[0]


def label(text: str) -> str:
    """``%fusion.15 = f32[..] fusion(..), kind=kCustom`` -> ``fusion.15
    fusion kCustom``; the Pallas kernel gets ``tpu_custom_call``."""
    name, _, rest = text.partition(" = ")
    parts = [name.lstrip("%")]
    op = _OPCODE.search(rest)
    if op:
        parts.append(op.group(1))
    kind = _KIND.search(rest)
    if kind:
        parts.append(kind.group(1))
    if KERNEL in rest:
        parts.append("tpu_custom_call")
    return " ".join(parts)


def is_push(text: str, edge_count: int) -> bool:
    if KERNEL in text:
        return True
    head = text.split(", calls=")[0].split(", to_apply=")[0]
    return any(int(d) >= edge_count for m in _DIMS.finditer(head)
               for d in m.group(1).split(",") if d)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(ops):
    """[(name, start, end)] -> [(name, start, end, self_ns, is_leaf)]."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    child = [0.0] * len(ops)
    leaf = [True] * len(ops)
    stack = []
    for i in order:
        _, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += e - s
            leaf[stack[-1]] = False
        stack.append(i)
    return [(n, s, e, (e - s) - child[i], leaf[i])
            for i, (n, s, e) in enumerate(ops)]


def _device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:")
            and not p.name.startswith("/device:CUSTOM")]


def _ops_line(plane):
    for ln in plane.lines:
        if ln.name == "XLA Ops":
            return ln
    return None


def summarize(trace, edge_count: int) -> Summary:
    """``trace``: a path to an ``.xplane.pb``, or a ``ProfileData``;
    ``edge_count``: the graph's edges, which mark the push's arrays."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(trace) if isinstance(trace, str) else trace
    window = main_line = None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name == WINDOW_SPAN:
                    window, main_line = (ev.start_ns, ev.end_ns), ln
                    break
            if window:
                break

    per_device = []
    for plane in _device_planes(pd):
        line = _ops_line(plane)
        if line is not None:
            ops = [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events]
            if ops:
                per_device.append(ops)
    if not per_device:
        raise ValueError("no device operations in the trace")
    if window is None:
        window = (min(o[1] for ops in per_device for o in ops),
                  max(o[2] for ops in per_device for o in ops))
    w0, w1 = window

    busy, push, op_s, push_ops, unions = [], [], {}, set(), []
    for ops in per_device:
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in ops
                   if e > w0 and s < w1]
        u = _union([(s, e) for _, s, e in clipped])
        unions.append(u)
        busy.append(sum(e - s for s, e in u))
        p = 0.0
        for n, s, e, self_ns, leaf in _self_times(clipped):
            key = label(n)
            op_s[key] = op_s.get(key, 0.0) + self_ns
            if leaf and is_push(n, edge_count):
                p += self_ns
                push_ops.add(key)
        push.append(p)
    nd = len(per_device)

    gaps, prev = [], w0
    for s, e in unions[0] + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    spans = [] if main_line is None else [
        (ev.start_ns, ev.end_ns, ev.name) for ev in main_line.events
        if ev.name != WINDOW_SPAN]

    def host_span(mid):
        open_ = [(e - s, n) for s, e, n in spans if s <= mid < e]
        return min(open_)[1] if open_ else "no host span"

    gaps.sort(key=lambda g: g[0] - g[1])
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return Summary(
        window_s=(w1 - w0) / 1e9,
        busy_s=sum(busy) / nd / 1e9,
        push_s=sum(push) / nd / 1e9,
        devices=nd,
        op_s={k: v / nd / 1e9 for k, v in op_s.items()},
        push_ops=sorted(push_ops),
        device_ops=[[n, v / nd / 1e9] for n, v in top],
        idle_gaps=[[host_span((s + e) / 2), (e - s) / 1e9]
                   for s, e in gaps[:10]],
    )
