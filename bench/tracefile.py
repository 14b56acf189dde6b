"""Reduction of a ``jax.profiler`` trace to the numbers the per-layer
metrics read: the traced window, device busy time (the union of the
intervals in which an op ran), the flush kernels' device time, and the
``breakdown`` of the result line.

Two stages: :func:`load` turns an ``.xplane.pb`` into plain event lists
(it needs JAX), and :class:`Trace` reduces those lists (it needs
nothing, so the tests run it on a recorded fixture).
"""
from __future__ import annotations

import glob
import os
import re

#: the host-side span around the traced stretch (``bench/run.py``)
WINDOW_SPAN = "bench.window"

_OPERAND = r"[^ ]* %[\w.\-]+"
#: the flush kernels, matched on their HLO signature: Mosaic kernels
#: appear in the trace as anonymous ``tpu_custom_call`` ops, so the
#: shapes are what tell them apart.  Both flush paths are here, so a
#: change of path still reads the same work.
FLUSH_KERNELS = {
    "dot_norms": re.compile(
        r"^%[\w.\-]+ = \(f32\[(?P<s>\d+),1\][^ ]*, f32\[(?P=s),1\][^ ]*, f32\[1,1\][^ ]*\) "
        r"custom-call\(f32\[(?P=s),(?P<d>\d+)\]" + _OPERAND + r", f32\[(?P=d)\]" + _OPERAND
        + r"\), custom_call_target=\"tpu_custom_call\""),
    "blend_reduce": re.compile(
        r"^%[\w.\-]+ = f32\[(?P<d>\d+)\][^ ]* custom-call\(f32\[(?P<s>\d+),(?P=d)\]" + _OPERAND
        + r", f32\[(?P=d)\]" + _OPERAND + r", f32\[(?P=s),1\]" + _OPERAND
        + r", f32\[(?P=s),1\]" + _OPERAND + r"\), custom_call_target=\"tpu_custom_call\""),
    "fused_flush": re.compile(
        r"^%[\w.\-]+ = \(f32\[(?P<d>\d+)\][^ ]*, f32\[(?P<s>\d+),1\][^ ]*, f32\[(?P=s),1\][^ ]*, "
        r"f32\[1,1\][^ ]*\) custom-call\(f32\[(?P=s),(?P=d)\]" + _OPERAND + r", f32\[(?P=d)\]"),
}
#: the kernels whose count is the number of flushes (one per flush path)
FLUSH_CALLS = ("dot_norms", "fused_flush")


def kernel_of(op_name: str) -> str | None:
    for label, pattern in FLUSH_KERNELS.items():
        if pattern.match(op_name):
            return label
    return None


def op_label(op_name: str) -> str:
    """A name that stays the same from run to run: the flush kernel's
    label, else the HLO instruction's name without its number."""
    k = kernel_of(op_name)
    if k:
        return k
    head = op_name.split(" = ", 1)[0]
    return re.sub(r"\.\d+$", "", head)


def load(trace_dir: str) -> dict:
    """Plain event lists from the newest ``.xplane.pb`` under a
    ``jax.profiler.trace`` directory: ``devices`` (one list of [name,
    start_ns, end_ns] per TPU, from its "XLA Ops" line) and ``host``
    ([name, start_ns, end_ns] of every host event)."""
    import jax

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [[e.name, e.start_ns, e.end_ns] for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.end_ns] for e in line.events]
    return {"devices": devices, "host": host}


def _union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


class Trace:
    """The reduced trace of one traced stretch."""

    def __init__(self, events: dict):
        self.host = events["host"]
        spans = [(a, b) for name, a, b in self.host if name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        self.t0, self.t1 = min(a for a, _ in spans), max(b for _, b in spans)
        self.devices = [
            [(n, max(a, self.t0), min(b, self.t1))
             for n, a, b in ops if b > self.t0 and a < self.t1]
            for ops in events["devices"]]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the traced devices."""
        per = [sum(b - a for a, b in _union((a, b) for _, a, b in ops)) for ops in self.devices]
        return sum(per) / len(per) / 1e9 if per else 0.0

    def kernel_s(self, labels) -> float:
        """Summed device seconds of the named flush kernels."""
        return sum(b - a for ops in self.devices for n, a, b in ops
                   if kernel_of(n) in labels) / 1e9

    def kernel_calls(self, labels) -> int:
        return sum(1 for ops in self.devices for n, _, _ in ops if kernel_of(n) in labels)

    def device_ops(self, top: int = 10) -> list:
        """The ops that took most device time, by self time: an op that
        encloses others (a loop) counts only the time none of them ran."""
        tot: dict[str, float] = {}
        for ops in self.devices:
            for n, dur in _self_times(ops):
                key = op_label(n)
                tot[key] = tot.get(key, 0.0) + dur / 1e9
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, top: int = 10) -> list:
        """The longest gaps in the first device's busy time inside the
        window, each named by the innermost host event that spans its
        middle, after the benchmark's own span around it."""
        if not self.devices:
            return []
        busy = _union((a, b) for _, a, b in self.devices[0])
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:top]:
            mid = (a + b) / 2
            inside = sorted((e - s, n) for n, s, e in self.host
                            if s <= mid <= e and n != WINDOW_SPAN)
            ours = [n for _, n in inside if n.startswith("bench.")]
            name = inside[0][1] if inside else "no host event"
            out.append([f"{ours[-1]} > {name}" if ours and ours[-1] != name else name,
                        (b - a) / 1e9])
        return out


def _self_times(ops):
    """(name, self ns) per op: its duration less that of the ops nested
    directly inside it on the same line."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    self_ns = [ops[i][2] - ops[i][1] for i in range(len(ops))]
    stack: list[int] = []
    for i in order:
        while stack and ops[stack[-1]][2] <= ops[i][1]:
            stack.pop()
        if stack and ops[i][2] <= ops[stack[-1]][2]:
            self_ns[stack[-1]] -= ops[i][2] - ops[i][1]
        stack.append(i)
    return [(ops[i][0], self_ns[i]) for i in range(len(ops))]
