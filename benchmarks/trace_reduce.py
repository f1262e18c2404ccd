"""From a profiler trace to numbers: the benchmark's own reducer.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain tuples (per device the operations of its ``XLA Ops`` line, from the
host the benchmark's own spans). ``reduce`` turns those into busy time,
idle gaps, time per ``grace/`` stage and exposed collective time. The two
are apart so that the arithmetic can be checked on hand-made intervals.

A device operation's stage is the rightmost ``grace/<stage>`` in the
``op_name`` the program's ``jax.named_scope`` left in the compiled text
(``scopes_of``), looked up by the operation's HLO name.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter"
    r"|collective-broadcast)")
STAGE = re.compile(r"grace/[a-z_]+")
# The transform's own stages: what compression costs on the critical path.
TRANSFORM_STAGES = ("grace/compensate", "grace/compress", "grace/decompress",
                    "grace/memory_update")
HOST_SPAN_PREFIX = "bench/"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# A TPU trace names a device operation by its whole HLO instruction,
# "%fusion.24 = (f32[256]...) fusion(...), kind=kOutput, ...".
INSTRUCTION = re.compile(r"^%?([\w.\-]+)")
# Shorter pauses between two device operations are the device's own
# hand-over, not idleness worth a name.
GAP_FLOOR_NS = 2_000


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: float        # ns
    dur: float          # ns

    @property
    def end(self):
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: dict       # device id -> [Op], sorted by start
    host: list          # [Op] of the benchmark's own spans


def scopes_of(compiled_text: str) -> dict:
    """HLO instruction name -> ``op_name`` metadata, from a compiled
    program's text."""
    out = {}
    for m in re.finditer(
            r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"",
            compiled_text, re.M):
        out.setdefault(m.group(1), m.group(2))
    return out


def stage_of(op_name: str | None) -> str | None:
    found = STAGE.findall(op_name or "")
    return found[-1] if found else None


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"{len(files)} xplane files under {trace_dir}")
    return files[0]


def load(path: str, rehearsal: bool = False) -> Trace:
    """Read a trace file. On a TPU the device operations are the events of
    each ``/device:TPU:<n>`` plane's ``XLA Ops`` line. ``rehearsal`` (a CPU
    run, which has no device plane) takes the host events that carry an
    ``hlo_op`` instead, so that the same reduction can be rehearsed."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[int(m.group(1))] = [
                        Op(INSTRUCTION.match(e.name).group(1), e.start_ns,
                           e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        host.append(Op(e.name, e.start_ns, e.duration_ns))
                    elif rehearsal and e.duration_ns > 0:
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            devices.setdefault(
                                int(stats.get("device_ordinal", 0)),
                                []).append(
                                Op(e.name, e.start_ns, e.duration_ns))
    for ops in devices.values():
        ops.sort(key=lambda o: (o.start, -o.dur))
    host.sort(key=lambda o: o.start)
    return Trace(devices, host)


def union(intervals) -> list:
    """Merge ``(start, end)`` pairs into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """The part of the disjoint sorted intervals ``a`` that no interval of
    the disjoint sorted ``b`` covers."""
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


def self_times(ops: list) -> list:
    """``(op, self_ns)``: an operation's duration minus what the operations
    nested inside it cover (a ``while`` or a ``call`` holds its body's
    operations on the same line). ``ops`` sorted by start, outer first."""
    out, stack = [], []          # stack of [op, covered_ns]

    def close():
        op, covered = stack.pop()
        out.append((op, max(op.dur - covered, 0.0)))

    for op in ops:
        while stack and op.start >= stack[-1][0].end:
            close()
        if stack:
            stack[-1][1] += min(op.end, stack[-1][0].end) - op.start
        stack.append([op, 0.0])
    while stack:
        close()
    return out


def reduce(trace: Trace, steps: int, scopes: dict) -> dict:
    """The numbers the per-layer readers take, each a mean over the devices
    traced. Times in seconds over the whole traced window unless named
    ``per_step``."""
    if not trace.devices or not all(trace.devices.values()):
        raise RuntimeError("no operation ran on a device in the trace")
    n = len(trace.devices)
    busy = window = exposed = 0.0
    stages, by_op, gaps = {}, {}, []
    for ops in trace.devices.values():
        covered = union((o.start, o.end) for o in ops)
        busy += total(covered)
        window += covered[-1][1] - covered[0][0]
        for (_, e), (s, _) in zip(covered, covered[1:]):
            if s - e >= GAP_FLOOR_NS:
                mid = (s + e) / 2
                during = next((h.name for h in trace.host
                               if h.start <= mid < h.end), "no_bench_span")
                gaps.append((during, s - e))
        selfs = self_times(ops)
        compute = union((o.start, o.end) for o, self_ns in selfs
                        if not COLLECTIVE.match(o.name) and self_ns == o.dur)
        coll = union((o.start, o.end) for o in ops
                     if COLLECTIVE.match(o.name))
        exposed += total(subtract(coll, compute))
        for op, self_ns in selfs:
            stage = stage_of(scopes.get(op.name)) or "unattributed"
            stages[stage] = stages.get(stage, 0.0) + self_ns
            key = f"{op.name}@{stage}"
            by_op[key] = by_op.get(key, 0.0) + self_ns
    per_step = 1e-9 / n / steps
    gap_names = {}
    for name, ns in gaps:
        gap_names[name] = max(gap_names.get(name, 0.0), ns)
    return {
        "devices": n, "steps": steps,
        "busy_s": busy * 1e-9 / n, "window_s": window * 1e-9 / n,
        "step_device_s": busy * per_step,
        "collective_exposed_s_per_step": exposed * per_step,
        "stage_s_per_step": {k: v * per_step for k, v in stages.items()},
        "grace_s_per_step": sum(stages.get(s, 0.0)
                                for s in TRANSFORM_STAGES) * per_step,
        # seconds per step, the ten largest
        "device_ops": _top({k: v * per_step for k, v in by_op.items()}),
        # the longest single gap under each host span, seconds
        "idle_gaps": _top({k: v * 1e-9 for k, v in gap_names.items()}),
    }


def _top(table: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]
