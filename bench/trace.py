"""The reduction from a profiler trace to the numbers the per-layer metrics
read.

It reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` alone. A device plane (``/device:TPU:<n>``)
holds one line of operations (``XLA Ops``); the host planes hold the
harness's own spans (``bench.build``, ``bench.put``, ``bench.step``,
``bench.wait``, ``bench.read``), written with ``TraceAnnotation`` on the
same clock. From these:

- the traced window: from the first span's start to the last span's end;
- busy seconds per device: the union of its operations' intervals inside
  the window (mean over devices);
- each operation's device seconds, by name, and each kernel's by a name
  pattern; the ops line nests (a loop or a conditional holds the
  operations it runs), so these sums take the innermost operations only;
- a collective's exposed seconds: its intervals less the union of the
  device's other innermost operations;
- idle gaps: the spaces between busy intervals, each labelled by the
  harness span open on the host at the gap's middle.
"""
from __future__ import annotations

import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute)")
# "%name = <shape> opcode(...), custom_call_target=\"target\""
_INSTR = re.compile(r"^%?([\w.\-]+) = .*?[\s}\]]([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def opcode(name):
    """The HLO opcode of an ops-line event ("" if the name is not HLO)."""
    m = _INSTR.match(name)
    return m.group(2) if m else ""


def label(name):
    """A short label: the instruction, its opcode, a custom call's target."""
    m = _INSTR.match(name)
    if not m:
        return name[:80]
    t = _TARGET.search(name)
    return f"{m.group(1)} {m.group(2)}" + (f" {t.group(1)}" if t else "")


def _leaves(evs):
    """The events that hold no other event of the line (innermost)."""
    evs = sorted(evs, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (n, s, e) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt[1] >= e:
            out.append((n, s, e))
    return out


def _union(intervals, lo=None, hi=None):
    """Merged, sorted [start, end) intervals, clipped to [lo, hi]."""
    out = []
    for s, e in sorted(intervals):
        if lo is not None:
            s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(merged):
    return sum(e - s for s, e in merged)


def _subtract(a, b):
    """Length of merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _device_planes(pd):
    planes = [p for p in pd.planes if re.match(r"/device:TPU:\d+$", p.name)]
    return sorted(planes, key=lambda p: int(p.name.rsplit(":", 1)[1]))


def read_events(path):
    """(device ops, host spans): ``ops[i]`` is a list of (name, start_ns,
    end_ns) of device i's operations, ``spans`` of the harness's spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops = []
    for plane in _device_planes(pd):
        evs = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                evs += [(e.name, e.start_ns, e.end_ns) for e in line.events]
        ops.append(evs)
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    return ops, sorted(spans, key=lambda s: s[1])


def reduce_events(ops, spans, n_devices=None, top=10):
    """The numbers the readers use, from ``read_events``' output. Times in
    seconds."""
    if n_devices is not None:
        ops = ops[:n_devices]
    if not spans:
        raise ValueError("the trace holds none of the harness's spans")
    lo, hi = spans[0][1], max(e for _, _, e in spans)
    window = (hi - lo) * 1e-9
    by_name = defaultdict(float)
    busy, exposed, coll_s, gaps = [], [], [], []
    for evs in ops:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                  if min(e, hi) > max(s, lo)]
        leaves = _leaves(inside)
        for n, s, e in leaves:
            by_name[n] += (e - s) * 1e-9
        merged = _union([(s, e) for _, s, e in inside])
        busy.append(_length(merged) * 1e-9)
        is_coll = [bool(COLLECTIVE.match(opcode(n))) for n, _, _ in leaves]
        coll = _union([(s, e) for (_, s, e), c in zip(leaves, is_coll) if c])
        comp = _union([(s, e) for (_, s, e), c in zip(leaves, is_coll)
                       if not c])
        exposed.append(_subtract(coll, comp) * 1e-9)
        coll_s.append(_length(coll) * 1e-9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_span_at(spans, (s + e) / 2), (e - s) * 1e-9))
    n = max(len(ops), 1)
    gaps.sort(key=lambda g: -g[1])
    by_label = defaultdict(float)
    for k, v in by_name.items():
        by_label[label(k)] += v
    return {
        "window_s": window,
        "busy_s": sum(busy) / n,
        "busy_per_device": busy,
        "exposed_collective_s": sum(exposed) / n,
        "collective_s": sum(coll_s) / n,
        "op_seconds": {k: v / n for k, v in by_name.items()},
        "top_ops": [[k, v / n] for k, v in sorted(
            by_label.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[span, s] for span, s in gaps[:top]],
        "idle_by_span": _sum_by(gaps, n),
        "span_seconds": _sum_by([(name, (e - s) * 1e-9)
                                 for name, s, e in spans], 1),
        "span_counts": _count(spans),
        "n_devices": n,
    }


def _span_at(spans, t):
    """Name of the innermost harness span open at ``t`` ("none" if none)."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "none"


def _sum_by(pairs, n):
    out = defaultdict(float)
    for k, v in pairs:
        out[k] += v / n
    return dict(out)


def _count(spans):
    out = defaultdict(int)
    for name, _, _ in spans:
        out[name] += 1
    return dict(out)


def kernel_seconds(reduced, match):
    """Device seconds (mean over devices) of the innermost operations whose
    full name ``match`` accepts (a callable, or a regular expression that
    must be found in it); None where none ran."""
    if not callable(match):
        match = re.compile(match).search
    hits = [v for k, v in reduced["op_seconds"].items() if match(k)]
    return sum(hits) if hits else None


def reduce(path, n_devices=None):
    return reduce_events(*read_events(path), n_devices=n_devices)
