"""Reduction of a profiler trace to device times.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` (JAX alone).  On a TPU, each chip is a plane
``/device:TPU:<n>``; its line ``XLA Modules`` holds one event per run of a
compiled program (``jit__segment_impl(...)``) and its line ``XLA Ops`` one
event per operation, named by its HLO text (``%fusion.180 = bf16[...]
fusion(...)``), of which the reduction keeps the instruction's name
(``fusion.180``); a Pallas kernel is a custom call named after its
wrapper (``flash_decode_paged.9``).  A loop's operation (``while.42``)
spans the operations of its body, which are events too.  The host plane
carries the harness's own spans (``chipbench.*``), on the same clock.

Busy time is the union of the operation intervals; idle is the rest of the
traced window.  Everything is clipped to the span ``chipbench.window``.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."


@dataclasses.dataclass
class Events:
    names: list
    start: np.ndarray            # ns
    end: np.ndarray

    @classmethod
    def of(cls, rows):
        # an enclosing event sorts before what it encloses
        rows = sorted(rows, key=lambda r: (r[1], -r[2]))
        return cls([r[0] for r in rows],
                   np.array([r[1] for r in rows], np.float64),
                   np.array([r[1] + r[2] for r in rows], np.float64))


@dataclasses.dataclass
class Trace:
    ops: Events                  # one chip's operations
    modules: Events              # one chip's program runs
    spans: Events                # the harness's host spans
    t0: float                    # the window, ns
    t1: float

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_intervals(self, lo=None, hi=None) -> np.ndarray:
        """Merged (k, 2) intervals in which an operation ran, in [lo, hi)."""
        lo = self.t0 if lo is None else lo
        hi = self.t1 if hi is None else hi
        s = np.clip(self.ops.start, lo, hi)
        e = np.clip(self.ops.end, lo, hi)
        keep = e > s
        s, e = s[keep], e[keep]
        if not len(s):
            return np.zeros((0, 2))
        order = np.argsort(s, kind="stable")
        s, e = s[order], np.maximum.accumulate(e[order])
        new = np.concatenate([[True], s[1:] > e[:-1]])
        idx = np.flatnonzero(new)
        ends = np.concatenate([idx[1:] - 1, [len(s) - 1]])
        return np.stack([s[idx], e[ends]], axis=1)

    def busy_s(self, lo=None, hi=None) -> float:
        iv = self.busy_intervals(lo, hi)
        return float((iv[:, 1] - iv[:, 0]).sum()) * 1e-9

    def _busy_between(self, iv, cum, s, e) -> float:
        """Busy ns of the merged intervals ``iv`` within [s, e]."""
        i = int(np.searchsorted(iv[:, 1], s, side="right"))
        j = int(np.searchsorted(iv[:, 0], e, side="left"))
        if j <= i:
            return 0.0
        tot = cum[j] - cum[i]
        tot -= max(0.0, s - iv[i, 0])
        tot -= max(0.0, iv[j - 1, 1] - e)
        return float(tot)

    def module_s(self, prefix: str) -> float:
        """Device seconds of the runs of programs whose name starts with
        ``prefix`` (in the window)."""
        return self._sum(self.modules, lambda n: n.startswith(prefix))

    def op_s(self, prefix: str) -> float:
        """Device seconds of the operations whose instruction name starts
        with ``prefix`` (a kernel's wrapper name)."""
        return self._sum(self.ops, lambda n: n.startswith(prefix))

    def _sum(self, ev: Events, pred) -> float:
        tot = 0.0
        for n, s, e in zip(ev.names, ev.start, ev.end):
            if pred(n):
                tot += max(0.0, min(e, self.t1) - max(s, self.t0))
        return tot * 1e-9

    def spans_named(self, name: str) -> list[tuple[float, float]]:
        return [(s, e) for n, s, e in zip(self.spans.names, self.spans.start,
                                          self.spans.end) if n == name]

    def idle_in(self, name: str) -> tuple[float, int]:
        """Device-idle seconds inside the host spans ``name``, and how many
        such spans lie in the window."""
        iv = self.busy_intervals()
        cum = np.concatenate([[0.0], np.cumsum(iv[:, 1] - iv[:, 0])])
        idle, n = 0.0, 0
        for s, e in self.spans_named(name):
            s, e = max(s, self.t0), min(e, self.t1)
            if e <= s:
                continue
            n += 1
            idle += (e - s) - self._busy_between(iv, cum, s, e)
        return idle * 1e-9, n

    def self_s(self) -> dict:
        """Seconds of each operation by name, less the time of the
        operations nested in it (a loop's body), in the window."""
        tot: dict = {}
        stack: list = []        # [name, end, nested time, start] of open ops
        for n, s, e in zip(self.ops.names, self.ops.start, self.ops.end):
            s, e = max(s, self.t0), min(e, self.t1)
            if e <= s:
                continue
            while stack and stack[-1][1] <= s:
                pn, pe, pc, ps = stack.pop()
                tot[pn] = tot.get(pn, 0.0) + (pe - ps - pc)
            if stack:
                stack[-1][2] += e - s
            stack.append([n, e, 0.0, s])
        while stack:
            pn, pe, pc, ps = stack.pop()
            tot[pn] = tot.get(pn, 0.0) + (pe - ps - pc)
        return {n: v * 1e-9 for n, v in tot.items()}

    def top_ops(self, k: int = 10) -> list:
        return sorted(([n, v] for n, v in self.self_s().items()),
                      key=lambda x: -x[1])[:k]

    def idle_gaps(self, k: int = 10) -> list:
        """The longest idle gaps, each named by the host span that holds
        its midpoint (``other`` where none does)."""
        iv = self.busy_intervals()
        edges = np.concatenate([[self.t0], iv.ravel(), [self.t1]])
        gaps = edges.reshape(-1, 2)
        gaps = gaps[gaps[:, 1] > gaps[:, 0]]
        order = np.argsort(gaps[:, 0] - gaps[:, 1])[:k]
        starts = list(self.spans.start)
        out = []
        for g in gaps[order]:
            mid = (g[0] + g[1]) / 2
            name = "other"
            i = bisect.bisect_right(starts, mid)
            for j in range(i - 1, max(-1, i - 64), -1):
                n = self.spans.names[j]
                if self.spans.end[j] >= mid and n != SPAN_PREFIX + "window":
                    name = n[len(SPAN_PREFIX):]
                    break
            out.append([name, float(g[1] - g[0]) * 1e-9])
        return out


def xplane_file(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def instruction(event_name: str) -> str:
    """``%fusion.180 = bf16[...] fusion(...)`` -> ``fusion.180``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(path: str, device: int = 0) -> Trace:
    """The trace of chip ``device`` (averaging over chips is the caller's)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, mods, spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            if plane.name != f"{DEVICE_PREFIX}{device}":
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((instruction(ev.name), ev.start_ns,
                                ev.duration_ns) for ev in line.events)
                elif line.name == MODULES_LINE:
                    mods.extend((ev.name, ev.start_ns, ev.duration_ns)
                                for ev in line.events)
        else:
            for line in plane.lines:
                spans.extend((ev.name, ev.start_ns, ev.duration_ns)
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    window = [s for s in spans if s[0] == SPAN_PREFIX + "window"]
    if not window:
        raise ValueError(f"{path}: no {SPAN_PREFIX}window span")
    t0 = window[0][1]
    return from_rows(ops, mods, spans, (t0, t0 + window[0][2]))


def from_rows(ops, modules, spans, window) -> Trace:
    """A trace from ``(name, start_ns, duration_ns)`` rows and the window
    ``(t0, t1)`` in ns."""
    return Trace(Events.of(ops), Events.of(modules), Events.of(spans),
                 float(window[0]), float(window[1]))
