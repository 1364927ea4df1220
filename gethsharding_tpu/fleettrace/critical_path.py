"""Critical-path attribution over an assembled cross-process trace.

An assembled trace is a TREE: the RPC trace envelope parents every
handler span under the calling process's client span, the serving
pipeline parents its phase spans under the handler, so a fleet request
(actor -> frontend -> replica -> device) is one connected tree rooted
at the outermost client span. Walking it answers the question metrics
cannot: of the request's end-to-end wall time, how much was wire, how
much frontend routing/WFQ wait, how much replica queue wait vs batch
assembly vs device execution — and how much was duplicate work a hedge
threw away.

The attribution rule is SELF-TIME: each span contributes its duration
minus the union of its children's intervals (clipped to the span, so a
skewed child can't drive a negative), and every self-time lands in a
named segment keyed by the span-name vocabulary the instrumented
layers already emit. Self-times over a tree telescope, so the segment
table sums to the root's duration (small cross-clock skews and
post-parent overhangs like `future_wake` aside — the bench gate allows
10%). Hedge-wasted spans are CONCURRENT duplicate work, not wall time,
so they are reported beside the table, excluded from the sum identity.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

# segment vocabulary, in rough request order (display order for the
# /status section and the scripts/fleettrace_report.py table)
SEGMENTS = (
    "actor_queue",      # actor-side spans before the wire
    "wire",             # client-span time not covered by the remote handler
    "rpc_handler",      # JSON decode/encode + dispatch glue, both tiers
    "frontend_route",   # fleet/route + fleet/attempt self: WFQ wait, picks
    "queue_wait",       # replica admission queue
    "batch_assembly",   # replica micro-batcher coalescing window
    "device_dispatch",  # device execution (the span the paper is about)
    "future_wake",      # completion future wake latency
    "serving_other",    # serving/*/request self (should be ~0)
    "other",            # anything the vocabulary doesn't know
)

HEDGE_WASTED = "hedge_wasted"


def segment_for(name: str) -> str:
    """Map one span name to its attribution segment."""
    if name.endswith("/queue_wait"):
        return "queue_wait"
    if name.endswith("/batch_assembly"):
        return "batch_assembly"
    if name.endswith("/device_dispatch"):
        return "device_dispatch"
    if name.startswith(("sig/", "jax/")) or (
            name.startswith("serving/") and name.endswith("/dispatch")):
        # what lies under a request's device_dispatch span: the batch's
        # dispatch span, the backend's dispatch span and its stages
        return "device_dispatch"
    if name.endswith("/future_wake"):
        return "future_wake"
    if name == "fleet/hedge_wasted":
        return HEDGE_WASTED
    if name.startswith("rpc/client/"):
        return "wire"
    if name.startswith("rpc/"):
        return "rpc_handler"
    if name in ("fleet/route", "fleet/attempt"):
        return "frontend_route"
    if name.startswith("serving/"):
        return "serving_other"
    if name.startswith(("notary/", "proposer/", "actor/")):
        return "actor_queue"
    return "other"


def _covered(intervals: List[Tuple[float, float]], lo: float,
             hi: float) -> float:
    """Total length of the union of `intervals` clipped to [lo, hi]."""
    clipped = sorted((max(lo, s), min(hi, e)) for s, e in intervals
                     if e > lo and s < hi)
    total = 0.0
    cur_s = cur_e = None
    for s, e in clipped:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def attribute(spans: List[dict]) -> Optional[dict]:
    """Walk one trace's span records (collector-rebased, each dict
    carrying name/span/parent/start/end/tags and optionally pid) and
    return the segment table. None when there is nothing to attribute.

    Roots whose parent never arrived (a lossy source, a one-sided
    trace) are left out of the walk and surfaced as `orphan_spans` —
    presenting a truncated tree as a complete request is exactly the
    failure mode the drop accounting exists to prevent."""
    if not spans:
        return None
    by_id: Dict[int, dict] = {s["span"]: s for s in spans}
    children: Dict[Optional[int], List[dict]] = {}
    roots: List[dict] = []
    for s in spans:
        parent = s.get("parent")
        if parent is None or parent not in by_id:
            roots.append(s)
        else:
            children.setdefault(parent, []).append(s)
    # the MAIN root is the widest interval: the outermost client span
    # covers the whole request; orphaned subtrees are narrower
    root = max(roots, key=lambda s: s["end"] - s["start"])
    segments = {name: 0.0 for name in SEGMENTS}
    wasted = 0.0
    klass = None
    pids = set()
    reached = 0
    stack = [root]
    while stack:
        span = stack.pop()
        reached += 1
        if span.get("pid") is not None:
            pids.add(span["pid"])
        tags = span.get("tags") or {}
        if klass is None and "klass" in tags:
            klass = tags["klass"]
        kids = children.get(span["span"], ())
        stack.extend(kids)
        dur = span["end"] - span["start"]
        segment = segment_for(span["name"])
        if segment == HEDGE_WASTED:
            # concurrent duplicate work: full duration, outside the
            # wall-time identity
            wasted += max(0.0, dur)
            continue
        covered = _covered([(k["start"], k["end"]) for k in kids],
                           span["start"], span["end"])
        segments[segment] += max(0.0, dur - covered)
    return {
        "total_s": max(0.0, root["end"] - root["start"]),
        "root": root["name"],
        "segments": segments,
        "hedge_wasted_s": wasted,
        "klass": klass,
        "processes": len(pids),
        "spans": reached,
        "orphan_spans": len(spans) - reached,
    }
