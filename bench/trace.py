"""Reduction of a JAX profiler trace to device busy time, op times and
idle gaps.

`jax.profiler` writes an `.xplane.pb` under `<dir>/plugins/profile/<t>/`.
A TPU appears as planes named `/device:TPU:<n>`; the line `XLA Ops` of
each holds one event per operation run on that chip, on the same clock
as the host plane `/host:CPU`, whose lines hold the host's spans (the
harness's own `TraceAnnotation`s among them, all named `bench/...`). An
op event's name is its HLO text, `%<instruction>.<n> = <type>
<opcode>(...)`; the reduction keeps the instruction name without its
number (`jvp_jit_gather_spmm__`), so one kernel's calls share a name.
Loop and call ops (`while`, `conditional`, `call`) span the ops they run
and are left out: busy time is the union of the ops that do the work.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"
CONTAINERS = ("while", "conditional", "call")


def op_name(hlo: str) -> str:
    """`%jvp_jit_gather_spmm__.18 = f32[..] custom-call(..)` ->
    `jvp_jit_gather_spmm__`."""
    return re.sub(r"\.\d+$", "", hlo.split(" = ", 1)[0].strip().lstrip("%"))


@dataclass
class Summary:
    """What the metric readers take from one trace: per chip the device
    op events (name, start ns, duration ns), and the harness's host
    spans."""
    ops: List[List[Tuple[str, int, int]]]
    spans: List[Tuple[str, int, int]] = field(default_factory=list)

    @property
    def chips(self) -> int:
        return len(self.ops)

    def busy_s(self) -> float:
        """Seconds in which some op ran, per chip, averaged over chips."""
        return sum(_union_ns(ev) for ev in self.ops) / 1e9 / max(
            self.chips, 1)

    def op_seconds(self, pattern: Optional[str] = None) -> float:
        """Summed device seconds of the ops whose name matches `pattern`
        (a regular expression searched in the name; all ops if None),
        averaged over chips."""
        rx = re.compile(pattern) if pattern else None
        total = sum(d for ev in self.ops for (n, _, d) in ev
                    if rx is None or rx.search(n))
        return total / 1e9 / max(self.chips, 1)

    def top_ops(self, k: int = 10) -> List[List]:
        agg: Dict[str, int] = {}
        for ev in self.ops:
            for n, _, d in ev:
                agg[n] = agg.get(n, 0) + d
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
        return [[n, d / 1e9 / max(self.chips, 1)] for n, d in top]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The k longest gaps between ops on chip 0, each named by the
        innermost harness span around its middle ("host" where none)."""
        if not self.ops or not self.ops[0]:
            return []
        ev = sorted(self.ops[0], key=lambda e: e[1])
        gaps, end = [], ev[0][1] + ev[0][2]
        for _, s, d in ev[1:]:
            if s > end:
                gaps.append((s - end, end, s))
            end = max(end, s + d)
        gaps.sort(reverse=True)
        out = []
        for length, a, b in gaps[:k]:
            mid = (a + b) // 2
            inner = [sp for sp in self.spans if sp[1] <= mid < sp[1] + sp[2]]
            name = min(inner, key=lambda sp: sp[2])[0] if inner else "host"
            out.append([name, length / 1e9])
        return out


def _union_ns(events) -> int:
    total, cur_s, cur_e = 0, None, None
    for _, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def summarize(planes) -> Summary:
    """Summary from planes with `.name`, `.lines`; lines with `.name`,
    `.events`; events with `.name`, `.start_ns`, `.duration_ns` (what
    `jax.profiler.ProfileData` gives, or test doubles)."""
    ops, spans = [], []
    for plane in sorted(planes, key=lambda p: p.name):
        if DEVICE_PLANE.match(plane.name):
            ev = [(op_name(e.name), int(e.start_ns), int(e.duration_ns))
                  for ln in plane.lines if ln.name == OPS_LINE
                  for e in ln.events]
            ops.append([e for e in ev if e[0] not in CONTAINERS])
        elif plane.name == HOST_PLANE:
            spans += [(e.name, int(e.start_ns), int(e.duration_ns))
                      for ln in plane.lines for e in ln.events
                      if e.name.startswith(SPAN_PREFIX)]
    return Summary(ops=ops, spans=spans)


def load(trace_dir: Path) -> Summary:
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(str(find_xplane(trace_dir))).planes)
