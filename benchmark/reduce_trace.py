"""From a profiler trace to device busy time, idle share, the operations
that took most time and the longest idle gaps.

The arithmetic works on plain tuples, so it is checked on hand-made
intervals (`tests/test_harness.py`); `load` is the only part that needs
JAX, and runs in the process that holds the chip (`child.py`).

An event is `(name, start_ns, duration_ns)`. Device events are those of
the device planes' "XLA Ops" lines, under the kind of operation XLA named
them by (`op_kind`); host events are the host planes' spans, used only to
say what the host was doing during a gap.
"""

from __future__ import annotations

import glob
import os
import re

TOP = 10  # entries of each breakdown list (the contract's limit)
LABELLED = 50  # gaps, longest first, that get the host's label


def op_kind(name: str) -> str:
    """`%broadcast_multiply_fusion.304 = (s32[...` -> `broadcast_multiply_fusion`:
    the trace names an operation by its whole HLO line, kilobytes of it,
    and numbers every instance; the kernels have no names of their own
    yet, so the kind of fusion is the most a breakdown can say."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.clone|\.\d+)+$", "", head)


def union(events) -> list:
    """Merged [start, end) intervals in which at least one event ran."""
    merged = []
    for start, end in sorted((s, s + d) for _, s, d in events if d > 0):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def self_times(events) -> dict:
    """Seconds per operation name, a parent's time less its children's
    (a `while` holds the fusions of its body on the same line), so that
    the names add up to the busy time of one line."""
    out: dict = {}
    stack = []  # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0) + own

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return {name: ns / 1e9 for name, ns in out.items()}


def label_gap(start: int, end: int, host_events) -> str:
    """What the host was doing in [start, end): the host span that
    overlaps the gap longest, or a plain name where the trace has none."""
    best, best_ns = "host: no span in the trace", 0
    for name, s, d in host_events:
        overlap = min(end, s + d) - max(start, s)
        if overlap > best_ns:
            best, best_ns = name, overlap
    return best


def reduce_events(per_device: list, host_events=()) -> dict:
    """`per_device`: one list of device events per chip used. Returns
    busy seconds averaged over the chips, the span from the first to the
    last device event, the top operations by self time (summed over
    chips) and the longest gaps of the first chip. Empty when no
    operation ran on a device: the caller then reports nothing."""
    per_device = [events for events in per_device if events]
    if not per_device:
        return {}
    busy, ops = [], {}
    for events in per_device:
        busy.append(sum(end - start for start, end in union(events)) / 1e9)
        for name, seconds in self_times(events).items():
            ops[name] = ops.get(name, 0.0) + seconds
    # only the longest gaps are held against the host's spans: a pairing
    # leaves hundreds of thousands of sub-microsecond gaps between fusions
    merged = union(per_device[0])
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(merged, merged[1:])), reverse=True)
    by_label: dict = {}
    for ns, start, end in gaps[:LABELLED]:
        label = label_gap(start, end, host_events)
        by_label[label] = by_label.get(label, 0.0) + ns / 1e9
    if gaps[LABELLED:]:
        by_label[f"{len(gaps) - LABELLED} shorter gaps between operations"] \
            = sum(ns for ns, _, _ in gaps[LABELLED:]) / 1e9
    first = min(e[1] for events in per_device for e in events)
    last = max(e[1] + e[2] for events in per_device for e in events)
    return {
        "busy_s": sum(busy) / len(busy),
        "span_s": (last - first) / 1e9,
        "device_ops": _top(ops),
        "idle_gaps": _top(by_label),
        "n_gaps": len(gaps),
    }


def _top(table: dict) -> list:
    rows = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
    return [[name, seconds] for name, seconds in rows]


def load(trace_dir: str):
    """(per_device, host_events) of the newest `.xplane.pb` under
    `trace_dir`, or ([], []) when the profiler wrote none."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return [], []
    data = ProfileData.from_file(files[-1])
    per_device, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            events = [(op_kind(ev.name), int(ev.start_ns),
                       int(ev.duration_ns))
                      for line in plane.lines if line.name == "XLA Ops"
                      for ev in line.events]
            if events:
                per_device.append(events)
        elif plane.name.startswith("/host:"):
            host.extend((f"{line.name}: {ev.name}", int(ev.start_ns),
                         int(ev.duration_ns))
                        for line in plane.lines for ev in line.events)
    return per_device, host
