"""Reduction from a profiler trace to per-layer numbers.

The functions at the top work on plain ``(start_ns, end_ns)`` intervals and
``(name, start_ns, duration_ns)`` events, so the tests can feed them
hand-built events. :class:`Trace` reads a JAX ``.xplane.pb`` into such
events: device operations per device plane, and the benchmark's own host
spans (``jax.profiler.TraceAnnotation`` names that start with ``bench:``)
from the host plane, on the same clock.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

#: HLO op names of the collectives (all-to-all, all-gather, all-reduce,
#: reduce-scatter, collective-permute and their async halves)
COLLECTIVE_PREFIXES = ("all-to-all", "all-gather", "all-reduce",
                       "reduce-scatter", "collective-permute")

SPAN_PREFIX = "bench:"


def op_name(event_name: str) -> str:
    """The HLO instruction name of a TPU op event, whose name is the
    instruction's text (``%encode_fused.1 = u32[...] custom-call(...)``)."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged union of half-open intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    """Length of the union of ``intervals``."""
    return sum(e - s for s, e in union(intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """``intervals`` cut to the window [lo, hi)."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def exposed(target: Iterable[Interval], others: Iterable[Interval]) -> float:
    """Time inside the union of ``target`` during which none of ``others``
    runs: a collective's time not overlapped by other work."""
    tgt, oth = union(target), union(others)
    hidden, j = 0.0, 0
    for s, e in tgt:
        while j < len(oth) and oth[j][1] <= s:
            j += 1
        k = j
        while k < len(oth) and oth[k][0] < e:
            hidden += min(e, oth[k][1]) - max(s, oth[k][0])
            k += 1
    return sum(e - s for s, e in tgt) - hidden


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi) between the merged ``busy`` ones."""
    out, t = [], lo
    for s, e in union(clip(busy, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def group_by_name(events: Iterable[Tuple[str, float, float]]
                  ) -> Dict[str, Tuple[int, float]]:
    """name -> (count, summed duration) over ``(name, start, dur)``."""
    acc: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for name, _, dur in events:
        acc[name][0] += 1
        acc[name][1] += dur
    return {k: (int(c), d) for k, (c, d) in acc.items()}


def is_collective(name: str) -> bool:
    return name.lower().startswith(COLLECTIVE_PREFIXES)


def label_gaps(idle: Sequence[Interval],
               spans: Sequence[Tuple[str, float, float]],
               top: int = 10) -> List[Tuple[str, float]]:
    """The ``top`` longest idle gaps, each named by the host span that
    overlaps it most (``host idle`` where none does), in seconds."""
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
        best, best_ov = "host idle", 0.0
        for name, ss, sd in spans:
            ov = min(e, ss + sd) - max(s, ss)
            if ov > best_ov:
                best, best_ov = name, ov
        out.append((best, (e - s) * 1e-9))
    return out


class Trace:
    """Device operations and host spans of one traced window.

    ``ops[d]`` holds device ``d``'s operations as ``(name, start_ns,
    duration_ns)``, from the plane's ``XLA Ops`` line; ``spans`` the
    benchmark's host spans, their ``bench:`` prefix dropped."""

    def __init__(self, ops: Dict[str, List[Tuple[str, float, float]]],
                 spans: List[Tuple[str, float, float]],
                 window: Optional[Interval] = None):
        self.ops = ops
        self.spans = spans
        if window is None:
            pts = [(s, s + d) for evs in ops.values() for _, s, d in evs]
            pts += [(s, s + d) for _, s, d in spans]
            window = ((min(p[0] for p in pts), max(p[1] for p in pts))
                      if pts else (0.0, 0.0))
        self.window = window

    # -- reading ---------------------------------------------------------

    @classmethod
    def from_dir(cls, trace_dir: str, window_span: str = "window"
                 ) -> "Trace":
        paths = sorted(glob.glob(os.path.join(
            trace_dir, "**", "*.xplane.pb"), recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        return cls.from_file(paths[-1], window_span)

    @classmethod
    def from_file(cls, path: str, window_span: str = "window") -> "Trace":
        """Read ``path``, an ``.xplane.pb`` (gzipped where it ends in
        ``.gz``)."""
        import gzip

        import jax

        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rb") as f:
            pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
        ops: Dict[str, List[Tuple[str, float, float]]] = {}
        spans: List[Tuple[str, float, float]] = []
        for plane in pd.planes:
            if plane.name.startswith("/device:") and "TPU" in plane.name:
                evs = []
                for line in plane.lines:
                    if line.name != "XLA Ops":
                        continue
                    evs += [(op_name(e.name), float(e.start_ns),
                             float(e.duration_ns)) for e in line.events]
                if evs:
                    ops[plane.name] = evs
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            spans.append((e.name[len(SPAN_PREFIX):],
                                          float(e.start_ns),
                                          float(e.duration_ns)))
        win = [s for s in spans if s[0] == window_span]
        window = ((win[0][1], win[0][1] + win[0][2]) if win else None)
        return cls(ops, [s for s in spans if s[0] != window_span], window)

    # -- numbers ---------------------------------------------------------

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def device_ops(self, device: str) -> List[Tuple[str, float, float]]:
        lo, hi = self.window
        return [(n, max(s, lo), min(s + d, hi) - max(s, lo))
                for n, s, d in self.ops[device] if min(s + d, hi) > max(s, lo)]

    def busy_ns(self, device: str) -> float:
        return total((s, s + d) for _, s, d in self.device_ops(device))

    def mean_busy_ns(self) -> float:
        """Busy time of the window, averaged over the traced devices."""
        if not self.ops:
            return 0.0
        return sum(self.busy_ns(d) for d in self.ops) / len(self.ops)

    def op_ns(self, device: str, match) -> float:
        """Summed duration on ``device`` of the ops whose name ``match``
        accepts."""
        return sum(d for n, _, d in self.device_ops(device) if match(n))

    def mean_op_ns(self, match) -> float:
        if not self.ops:
            return 0.0
        return sum(self.op_ns(d, match) for d in self.ops) / len(self.ops)

    def mean_exposed_collective_ns(self) -> float:
        """Per device, the collectives' time during which no other op runs
        on it; averaged over the devices."""
        if not self.ops:
            return 0.0
        acc = 0.0
        for dev in self.ops:
            evs = self.device_ops(dev)
            coll = [(s, s + d) for n, s, d in evs if is_collective(n)]
            rest = [(s, s + d) for n, s, d in evs if not is_collective(n)]
            acc += exposed(coll, rest)
        return acc / len(self.ops)

    def top_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        """The ``top`` op names by device time (seconds, mean over the
        devices)."""
        acc: Dict[str, float] = defaultdict(float)
        for dev in self.ops:
            for name, (_, dur) in group_by_name(self.device_ops(dev)).items():
                acc[name] += dur / len(self.ops)
        return [(n, d * 1e-9) for n, d in
                sorted(acc.items(), key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """Longest idle gaps of the first device, by host span."""
        if not self.ops:
            return []
        dev = sorted(self.ops)[0]
        busy = [(s, s + d) for _, s, d in self.device_ops(dev)]
        return label_gaps(gaps(busy, *self.window), self.spans, top)

    def span_op_ns(self, span: str, match) -> Tuple[float, int]:
        """Summed duration of the first device's ops that ``match``
        accepts and that start inside a host span called ``span``, and
        the number of such spans in the window."""
        if not self.ops:
            return 0.0, 0
        lo, hi = self.window
        sp = sorted((s, s + d) for n, s, d in self.spans
                    if n == span and s >= lo and s + d <= hi)
        ops = sorted((s, d) for n, s, d in self.device_ops(sorted(self.ops)[0])
                     if match(n))
        acc, j = 0.0, 0
        for s, e in sp:
            while j < len(ops) and ops[j][0] < s:
                j += 1
            while j < len(ops) and ops[j][0] < e:
                acc += ops[j][1]
                j += 1
        return acc, len(sp)
