"""The program's own marks in a profiler trace, read beside ``trace.py``'s
reduction (which it takes as its base and leaves as it is).

- **Host spans** ``serving.*``: the engine's phases of each boundary
  (``serving.step`` and the spans nested in it), with their args
  (``serving.admit``: ``rows``, ``bucket``; ``serving.commit``: ``tokens``,
  ``steps``, ``rows``).  They sit on a host plane, on the device's clock.
- **Scope paths**: each device operation's HLO ``op_name`` metadata
  (``jit(_segment_impl)/jit(main)/while/body/attention/kv_write/scatter``),
  whose components include the program's ``jax.named_scope`` names
  (``attention``, ``mlp``, ``kv_write``, ``lm_head``, ``sample``).  A
  device event carries only its instruction's name, so the path is looked
  up in the HLO of the program run that holds the operation: the profiler
  stores each program's ``HloProto`` on its ``/host:metadata`` plane (stat
  ``Hlo Proto``), under the name the program's runs carry on the ``XLA
  Modules`` line (``jit__segment_impl(<id>)``).  ``ProfileData`` does not
  show a plane's metadata, so ``op_names`` reads the file's protobuf wire
  format for it (fields of ``xplane.proto`` and ``hlo.proto``).

On a trace of a program without these marks every reading is empty, and
the readers built on it return ``None``.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import sys

import numpy as np

from chipbench import trace

PREFIX = "serving."
HLO_PROTO = "Hlo Proto"


@dataclasses.dataclass
class Marks:
    base: trace.Trace            # the harness's reduction of the same file
    scopes: list                 # per operation of base.ops: its op_name
    serving: trace.Events        # the program's host spans
    args: list                   # their args, in the order of ``serving``

    # ------------------------------------------------------------ scopes
    @functools.cached_property
    def op_self_ns(self) -> np.ndarray:
        """Each operation's time in the window less that of the
        operations nested in it (a loop's body), in ns."""
        b = self.base
        s = np.clip(b.ops.start, b.t0, b.t1)
        e = np.clip(b.ops.end, b.t0, b.t1)
        own = e - s
        stack: list = []             # indices of the open operations
        for i in range(len(s)):
            if e[i] <= s[i]:
                continue
            while stack and e[stack[-1]] <= s[i]:
                stack.pop()
            if stack:
                own[stack[-1]] -= e[i] - s[i]
            stack.append(i)
        return np.maximum(own, 0.0)

    def in_runs(self, program: str) -> np.ndarray:
        """Whether each operation starts inside a run of a program whose
        name starts with ``program``."""
        mods = self.base.modules
        keep = [n.startswith(program) for n in mods.names]
        lo, hi = mods.start[keep], mods.end[keep]
        start = self.base.ops.start
        j = np.searchsorted(lo, start, side="right") - 1
        return (j >= 0) & (start < hi[np.maximum(j, 0)]) if len(lo) \
            else np.zeros(len(start), bool)

    def scope_s(self, program: str, scopes) -> float:
        """Self seconds of the operations under any of ``scopes`` (a
        component of their scope path), inside the runs of ``program``,
        in the window."""
        want = set(scopes)
        flag = {p: bool(want.intersection(p.split("/")))
                for p in set(self.scopes)}
        under = np.fromiter((flag[p] for p in self.scopes), bool,
                            len(self.scopes))
        if not under.any():
            return 0.0
        keep = under & self.in_runs(program)
        return float(self.op_self_ns[keep].sum()) * 1e-9

    # ------------------------------------------------------------- spans
    def serving_args(self, name: str) -> list:
        """The args of the spans ``name`` that start in the window."""
        b = self.base
        return [a for n, s, a in zip(self.serving.names, self.serving.start,
                                     self.args)
                if n == name and b.t0 <= s < b.t1]

    @functools.cached_property
    def _timeline(self) -> tuple:
        """Cut times and, between each two, where the host was: the
        innermost program span, else the harness's span (its name without
        ``chipbench.``, the window aside), else ``other``.  Spans of each
        kind nest, so a stack of each tells."""
        edges = []
        for kind, ev in ((0, self.base.spans), (1, self.serving)):
            for i, (n, s, e) in enumerate(zip(ev.names, ev.start, ev.end)):
                if n != trace.SPAN_PREFIX + "window":
                    edges += [(s, 1, kind, i), (e, 0, kind, i)]
        edges.sort()
        cuts, labels = [], []
        open_ = ([], [])
        for t, opening, kind, i in edges:
            if opening:
                open_[kind].append(i)
            else:
                open_[kind].remove(i)
            if open_[1]:
                name = self.serving.names[open_[1][-1]]
            elif open_[0]:
                name = self.base.spans.names[open_[0][-1]][
                    len(trace.SPAN_PREFIX):]
            else:
                name = "other"
            if cuts and cuts[-1] == t:
                labels[-1] = name
            else:
                cuts.append(t)
                labels.append(name)
        return np.array(cuts, np.float64), labels

    def where(self, t: float) -> str:
        """Where the host was at ``t`` (see ``_timeline``)."""
        cuts, labels = self._timeline
        i = int(np.searchsorted(cuts, t, side="right")) - 1
        return labels[i] if i >= 0 else "other"

    def _gaps(self) -> np.ndarray:
        b = self.base
        edges = np.concatenate([[b.t0], b.busy_intervals().ravel(), [b.t1]])
        gaps = edges.reshape(-1, 2)
        return gaps[gaps[:, 1] > gaps[:, 0]]

    def idle_gaps(self, k: int = 10) -> list:
        """The longest idle gaps, each named by where the host was at its
        midpoint: the innermost program span, else the harness's span as
        ``trace.Trace.idle_gaps`` names it."""
        gaps = self._gaps()
        return [[self.where((g[0] + g[1]) / 2), float(g[1] - g[0]) * 1e-9]
                for g in gaps[np.argsort(gaps[:, 0] - gaps[:, 1])[:k]]]

    def idle_by_span(self) -> dict:
        """Device-idle seconds in the window by where the host was (see
        ``_timeline``), largest first."""
        b = self.base
        cuts, labels = self._timeline
        t = np.unique(np.clip(np.concatenate([[b.t0, b.t1], cuts]),
                              b.t0, b.t1))
        iv = b.busy_intervals()
        cum = np.concatenate([[0.0], np.cumsum(iv[:, 1] - iv[:, 0])])
        i = np.maximum(np.searchsorted(iv[:, 0], t, side="right") - 1, 0)
        busy = cum[i] + np.clip(t - iv[i, 0], 0, iv[i, 1] - iv[i, 0]) \
            if len(iv) else np.zeros_like(t)
        idle = np.diff(t) - np.diff(busy)
        out: dict = {}
        for lo, ns in zip(t[:-1], idle):
            if ns > 0:
                name = self.where(lo)
                out[name] = out.get(name, 0.0) + float(ns) * 1e-9
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def ms_per(rec, program: str, scopes, span: str, arg: str) -> float | None:
    """Device ms under ``scopes`` in the runs of ``program`` over the sum
    of the ``arg`` of the window's ``span`` spans (``None`` where either
    is nothing)."""
    marks = of(rec)
    if marks is None:
        return None
    n = sum(a[arg] for a in marks.serving_args(span))
    t = marks.scope_s(program, scopes)
    return t / n * 1e3 if n and t > 0 else None


# ---------------------------------------------------------------- loading
def of(rec) -> Marks | None:
    """The marks of a record's traced window, read once from the trace file
    the run left in ``.chipbench/trace`` (``None`` without a trace, or
    where the file's metadata cannot be parsed: a reader of a per-layer
    metric reports nothing rather than end the run)."""
    if not hasattr(rec, "marks"):
        rec.marks = None
        from chipbench.run import OUT
        found = sorted(glob.glob(os.path.join(OUT, "trace", "**",
                                              "*.xplane.pb"), recursive=True))
        if rec.trace is not None and found:
            try:
                rec.marks = load(found[-1], rec.trace)
            except (ValueError, IndexError) as e:
                print(f"chipbench.scopes: {found[-1]}: {e!r}",
                      file=sys.stderr)
    return rec.marks


def load(path: str, base: trace.Trace) -> Marks:
    """Marks of the trace file ``path`` whose harness reduction is
    ``base`` (``trace.load(path)``)."""
    from jax.profiler import ProfileData
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            spans.extend((ev.name, ev.start_ns, ev.duration_ns,
                          dict(ev.stats)) for ev in line.events
                         if ev.name.startswith(PREFIX))
    with open(path, "rb") as f:
        names = op_names(f.read(), set(base.modules.names))
    return from_rows(base, scopes_of(base, names), spans)


def from_rows(base: trace.Trace, scopes: list, spans) -> Marks:
    """Marks from a base trace, a scope path per operation of
    ``base.ops`` and ``(name, start_ns, duration_ns, args)`` span rows."""
    rows = sorted(spans, key=lambda r: (r[1], -r[2]))
    return Marks(base, list(scopes), trace.Events.of([r[:3] for r in rows]),
                 [r[3] for r in rows])


def scopes_of(base: trace.Trace, names: dict) -> list:
    """The scope path of each operation of ``base.ops``: its instruction's
    ``op_name`` in the HLO of the program run that holds its start ("" for
    an operation outside any run or without metadata).  ``names`` is
    ``op_names``' map."""
    mods = base.modules
    j = np.searchsorted(mods.start, base.ops.start, side="right") - 1
    out = []
    for op, s, m in zip(base.ops.names, base.ops.start, j):
        table = names.get(mods.names[m]) if m >= 0 and s < mods.end[m] \
            else None
        out.append(table.get(op, "") if table else "")
    return out


# ------------------------------------------------- protobuf wire format
def _fields(buf: bytes, lo: int, hi: int):
    """(field number, value) of one message in ``buf[lo:hi]``; a
    length-delimited value is its (start, end) in ``buf``."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, val


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _str(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_names(xspace: bytes, wanted=None) -> dict:
    """``{program name: {instruction: op_name}}`` from the ``Hlo Proto``
    stats of a serialized XSpace, for the programs named in ``wanted``
    (all where ``None``).  XSpace.planes = 1; XPlane.event_metadata = 4,
    stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata.name = 2,
    stats = 5; XStatMetadata.id = 1, name = 2; XStat.metadata_id = 1,
    bytes_value = 6."""
    out: dict = {}
    for num, plane in _fields(xspace, 0, len(xspace)):
        if num != 1:
            continue
        events, ids = [], set()
        for f, v in _fields(xspace, *plane):
            if f == 4:
                events.append(v)
            elif f == 5:
                meta = {g: w for g, w in _fields(xspace, *v)}.get(2)
                meta = {g: w for g, w in _fields(xspace, *meta)} if meta \
                    else {}
                if 2 in meta and _str(xspace, meta[2]) == HLO_PROTO:
                    ids.add(meta.get(1, 0))
        for entry in events if ids else ():
            em = {g: w for g, w in _fields(xspace, *entry)}.get(2)
            if em is None:
                continue
            name, proto = None, None
            for h, w in _fields(xspace, *em):
                if h == 2:
                    name = _str(xspace, w)
                elif h == 5:
                    stat = {k: x for k, x in _fields(xspace, *w)}
                    if stat.get(1, 0) in ids and 6 in stat:
                        proto = stat[6]
            if proto is not None and (wanted is None or name in wanted):
                out[name] = _hlo_op_names(xspace, proto)
    return out


def _hlo_op_names(buf: bytes, span) -> dict:
    """``{instruction: op_name}`` of a serialized HloProto: hlo_module =
    1; HloModuleProto.computations = 3; HloComputationProto.instructions
    = 2; HloInstructionProto.name = 1, metadata = 7; OpMetadata.op_name =
    2."""
    out = {}
    for f, mod in _fields(buf, *span):
        for g, comp in _fields(buf, *mod) if f == 1 else ():
            for h, ins in _fields(buf, *comp) if g == 3 else ():
                if h != 2:
                    continue
                fields = {k: v for k, v in _fields(buf, *ins)
                          if k in (1, 7)}
                op = {m: w for m, w in _fields(buf, *fields[7])}.get(2) \
                    if 7 in fields else None
                if op is not None and 1 in fields:
                    out[_str(buf, fields[1])] = _str(buf, op)
    return out
