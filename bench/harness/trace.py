"""From a profiler trace to per-layer numbers.

The trace is JAX's ``.xplane.pb``. Each TPU is a plane ``/device:TPU:<k>``
whose line ``XLA Ops`` holds one event per executed HLO instruction, named
by the instruction's text (``%fusion.143 = f32[1100000,128]{...} fusion(...),
kind=kCustom, calls=...``); ``Async XLA Ops`` holds the asynchronous
starts (copies, collectives). Host threads are lines of ``/host:CPU``.

Op classes, read from that text alone (the rule was checked against the
optimized HLO of the CRITEO fused step for a v5e, where every ``kOutput``
fusion wraps a convolution and every ``kCustom`` fusion wraps a gather or a
scatter, and against one traced v5e step):

* ``collective``: all-gather, all-reduce, reduce-scatter, collective-permute
  and all-to-all instructions, with their ``-start``/``-done`` halves;
* ``matmul``: ``convolution`` and ``dot`` instructions and ``kOutput``
  fusions (XLA's TPU backend fuses into a matmul's output);
* ``embedding``: ``gather``, ``scatter`` and ``sort`` instructions and
  ``kCustom`` fusions (the TPU backend's gather and scatter emitters): the
  lookup, the segment sums, the queue and the row-sparse adagrad apply;
* ``other``: the rest (elementwise fusions, copies, reshapes).

Device busy time is the union of the ``XLA Ops`` intervals. A collective is
exposed where no non-collective op runs on that device.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
WINDOW = "bench:window"


@dataclasses.dataclass
class TraceData:
    # device plane name -> line name -> [(start_ns, dur_ns, text)]
    devices: dict
    # [(start_ns, dur_ns, name)] over every host thread
    host: list

    @staticmethod
    def from_xplane(path: str) -> "TraceData":
        from jax.profiler import ProfileData
        if os.path.isdir(path):
            found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                              recursive=True)
            if not found:
                raise FileNotFoundError(f"no .xplane.pb under {path}")
            path = sorted(found)[-1]
        pd = ProfileData.from_file(path)
        devices, host = {}, []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                lines = {}
                for line in plane.lines:
                    if line.name in (OPS_LINE, ASYNC_LINE):
                        lines[line.name] = [
                            (float(e.start_ns), float(e.duration_ns), e.name)
                            for e in line.events]
                devices[plane.name] = lines
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    host.extend((float(e.start_ns), float(e.duration_ns),
                                 e.name) for e in line.events)
        return TraceData(devices=devices, host=host)

    def to_json(self, path: str):
        with gzip.open(path, "wt") as f:
            json.dump({"devices": self.devices, "host": self.host}, f)

    @staticmethod
    def from_json(path: str) -> "TraceData":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return TraceData(
            devices={p: {ln: [tuple(e) for e in evs]
                         for ln, evs in lines.items()}
                     for p, lines in d["devices"].items()},
            host=[tuple(e) for e in d["host"]])


# -- reading one instruction ---------------------------------------------------

def parse_op(text: str) -> tuple[str, str, str, str]:
    """(instruction name, opcode, kind, output shape) of one op's text."""
    name = re.match(r"%?([\w.\-]+)", text)
    name = name.group(1) if name else text
    eq = text.find(" = ")
    if eq < 0:
        return name, "", "", ""
    rest = text[eq + 3:]
    if rest.startswith("("):
        depth, i = 0, 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, rest = rest[: i + 1], rest[i + 1:]
    else:
        sp = rest.find(" ")
        shape, rest = rest[:sp], rest[sp:]
    op = re.match(r"\s*([\w\-]+)\(", rest)
    kind = re.search(r"kind=(k\w+)", text)
    return (name, op.group(1) if op else "", kind.group(1) if kind else "",
            re.sub(r"\{[^}]*\}", "", shape))


def classify(text: str) -> str:
    name, op, kind, _ = parse_op(text)
    base = re.sub(r"-(start|done)$", "", op)
    stem = re.sub(r"[.\d]+$", "", name)
    if base in COLLECTIVES or any(stem.startswith(c) for c in COLLECTIVES):
        return "collective"
    if op in ("convolution", "dot") or (op == "fusion" and kind == "kOutput") \
            or "convolution" in stem:
        return "matmul"
    if op in ("gather", "scatter", "sort") or \
            (op == "fusion" and kind == "kCustom"):
        return "embedding"
    return "other"


def label(text: str) -> str:
    """A stable name for one kind of op: class, opcode and output shape."""
    name, op, kind, shape = parse_op(text)
    return f"{classify(text)} {op or name} {kind + ' ' if kind else ''}{shape}"


# -- interval arithmetic -------------------------------------------------------

def union(intervals) -> list:
    """Sorted disjoint [start, end) covering the given (start, end)s."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Disjoint sorted ``a`` minus disjoint sorted ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


# -- the reduction -------------------------------------------------------------

def reduce(td: TraceData, steps: int, top: int = 10) -> dict:
    """Per-device busy time and class times, exposed collective time, the
    ops that took most time, and idle gaps by what the host was doing.
    Times are in seconds; ``window_s`` is the host's ``bench:window`` span
    (or the span of the device ops where the trace lacks it)."""
    win = [h for h in td.host if h[2] == WINDOW]
    all_ops = [e for lines in td.devices.values()
               for e in lines.get(OPS_LINE, [])]
    if not all_ops:
        raise ValueError("the trace holds no device op")
    if win:
        lo, hi = win[0][0], win[0][0] + win[0][1]
    else:
        lo = min(e[0] for e in all_ops)
        hi = max(e[0] + e[1] for e in all_ops)
    window = hi - lo
    per_device = {}
    by_label = {}
    gaps_ns = []
    for plane, lines in sorted(td.devices.items()):
        ops = [e for e in lines.get(OPS_LINE, []) if e[0] < hi
               and e[0] + e[1] > lo]
        asyncs = [e for e in lines.get(ASYNC_LINE, []) if e[0] < hi
                  and e[0] + e[1] > lo]
        cls = {"matmul": 0.0, "embedding": 0.0, "collective": 0.0,
               "other": 0.0}
        compute, coll = [], []
        for s, d, text in ops:
            c = classify(text)
            cls[c] += d
            lab = label(text)
            by_label[lab] = by_label.get(lab, 0.0) + d
            (coll if c == "collective" else compute).append((s, s + d))
        for s, d, text in asyncs:
            if classify(text) == "collective":
                cls["collective"] += d
                coll.append((s, s + d))
        busy = clip(union([(s, s + d) for s, d, _ in ops]), lo, hi)
        exposed = subtract(clip(union(coll), lo, hi), union(compute))
        per_device[plane] = {
            "busy_s": length(busy) * 1e-9,
            "idle_share": 1.0 - length(busy) / window,
            "exposed_collective_s": length(exposed) * 1e-9,
            **{f"{k}_s": v * 1e-9 for k, v in cls.items()},
        }
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps_ns.extend((edges[i], edges[i + 1])
                       for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i])
    n = len(per_device)
    ops_top = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window * 1e-9,
        "steps": steps,
        "devices": per_device,
        "device_ops": [[k, v * 1e-9 / n] for k, v in ops_top],
        "idle_gaps": _attribute(gaps_ns, td.host, n, top),
    }


def _attribute(gaps, host, n_devices, top) -> list:
    """Each idle gap goes to the innermost host span (shortest, other than
    the window itself) that covers its midpoint."""
    spans = sorted((s, s + d, name) for s, d, name in host if name != WINDOW)
    totals = {}
    active, i = [], 0
    for s, e in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = 0.5 * (s + e)
        while i < len(spans) and spans[i][0] <= mid:
            active.append(spans[i])
            i += 1
        active = [a for a in active if a[1] >= mid]
        best = min(active, key=lambda a: a[1] - a[0]) if active else None
        key = best[2] if best else "no host span"
        totals[key] = totals.get(key, 0.0) + (e - s)
    out = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v * 1e-9 / n_devices] for k, v in out]
