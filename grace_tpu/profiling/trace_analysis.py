"""Profiler-trace analysis: attribute device time to pipeline stages.

The write side of observability has existed since PR 2: every pipeline
stage runs under a canonical ``grace/...`` scope
(:mod:`grace_tpu.telemetry.scopes`), so ``jax.profiler`` traces carry the
stage vocabulary in their op names. This module is the READ side: it parses
a profiler artifact back into spans and answers the questions the ROADMAP's
perf arc is blocked on —

* **where did the step's device time go, per stage?** Each device span is
  attributed to a canonical stage via the same longest-prefix match the
  static auditor uses (:func:`grace_tpu.telemetry.scopes.match_stage`), and
  charged its *self* time (child spans subtracted), so the per-stage table
  sums exactly to the total device time;
* **compute vs collective split** — op-name classification of the XLA
  collective families (all-gather/all-reduce/all-to-all/collective-permute/
  reduce-scatter and their fusion spellings);
* **overlap fraction** — the share of collective time hidden under
  concurrent compute on the same device, computed from interval unions of
  the *device* timelines (NOT host wall-clock: host timing can neither see
  that a collective ran under the backward pass nor avoid counting dispatch
  gaps — see IMPLEMENTING.md "Per-link wire model & overlap"). This is the
  before/after number ROADMAP item 2 (bucketed overlap, Pallas fusion)
  needs, and the measured answer to the projection model's documented
  "assumes NO overlap" caveat;
* **step-time percentiles** from the trace's step markers.

Two input formats, one span model:

* ``*.trace.json.gz`` / ``*.json`` — the Chrome-trace-format export every
  ``jax.profiler.trace`` capture writes. Fully supported.
* ``*.xplane.pb`` — the raw XSpace protobuf. Decoded with a small
  schema-pinned reader (:data:`_XPLANE_SCHEMA`; pure stdlib, mirroring the
  hand-encoded protos of :class:`~grace_tpu.telemetry.sinks.TensorBoardSink`)
  — best effort against the stable upstream field numbering.

Everything here is pure host-side stdlib + numpy: it runs on a CPU-only box
with no devices, against a checked-in canned trace
(``tests/data/perf_trace.json.gz``), which is how the whole module is
tested and how ``tools/perf_report.py`` gates CI.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import struct
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from grace_tpu.telemetry.scopes import match_stage

__all__ = ["Span", "TraceAnalysis", "load_trace_events", "parse_chrome_trace",
           "parse_xplane", "analyze_trace", "analyze_spans",
           "hlo_scope_map", "enrich_spans",
           "interval_union_us", "overlap_us", "find_latest_trace",
           "UNATTRIBUTED", "STEP_LANE"]

# Stage bucket for device spans outside the grace/... vocabulary (the model
# forward/backward XLA fusions that run under no named scope, framework
# infeed, etc.). Kept explicit so the stage table still sums to the total.
UNATTRIBUTED = "(unattributed)"

# Lane (thread) name the XLA profiler uses for per-step markers.
STEP_LANE = "Steps"

# Op-name substrings that mark a device span as wire time. XLA spells the
# collectives with dashes in HLO op names (all-gather.3, collective-permute-
# start) and jax spells the primitives with underscores in scope names —
# match both. "Fusion" never matches: a fused collective keeps its
# collective op name as a prefix in XLA naming.
_COLLECTIVE_TOKENS = (
    "all-gather", "all_gather", "all-reduce", "all_reduce", "allreduce",
    "all-to-all", "all_to_all", "collective-permute", "collective_permute",
    "ppermute", "reduce-scatter", "reduce_scatter", "psum",
    "collective-broadcast", "send-done", "recv-done",
)


@dataclasses.dataclass(frozen=True)
class Span:
    """One complete event on one timeline: ``[ts, ts+dur)`` microseconds."""

    name: str
    ts: float                 # µs since trace epoch
    dur: float                # µs
    device: str = ""          # process name, e.g. "/device:TPU:0"
    lane: str = ""            # thread name, e.g. "XLA Ops" / "Steps"
    scope: str = ""           # extra scope path (args metadata), if any

    @property
    def end(self) -> float:
        return self.ts + self.dur

    def stage(self) -> str:
        """Canonical grace stage of this span (name first, scope second)."""
        return match_stage(self.name) or match_stage(self.scope)

    def is_collective(self) -> bool:
        text = f"{self.name} {self.scope}".lower()
        return any(tok in text for tok in _COLLECTIVE_TOKENS)


# ---------------------------------------------------------------------------
# Chrome trace format (trace.json.gz)
# ---------------------------------------------------------------------------

def parse_chrome_trace(doc: Mapping) -> List[Span]:
    """Chrome-trace-format dict → spans, with pid/tid names resolved from
    the ``process_name``/``thread_name`` metadata events."""
    events = doc.get("traceEvents", [])
    pid_names: Dict[object, str] = {}
    tid_names: Dict[Tuple[object, object], str] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        args = e.get("args") or {}
        if e.get("name") == "process_name":
            pid_names[e.get("pid")] = str(args.get("name", e.get("pid")))
        elif e.get("name") == "thread_name":
            tid_names[(e.get("pid"), e.get("tid"))] = str(
                args.get("name", e.get("tid")))
    spans: List[Span] = []
    for e in events:
        if e.get("ph") != "X" or not e.get("dur"):
            continue
        pid, tid = e.get("pid"), e.get("tid")
        args = e.get("args") or {}
        # named_scope metadata surfaces in different arg keys across
        # profiler versions (long_name carries the full HLO metadata path).
        scope = " ".join(str(v) for k, v in sorted(args.items())
                         if isinstance(v, str)
                         and k in ("name", "long_name", "tf_op", "scope",
                                   "hlo_op", "group_name"))
        spans.append(Span(name=str(e.get("name", "")),
                          ts=float(e["ts"]), dur=float(e["dur"]),
                          device=pid_names.get(pid, f"pid {pid}"),
                          lane=tid_names.get((pid, tid), f"tid {tid}"),
                          scope=scope))
    return spans


# ---------------------------------------------------------------------------
# XSpace protobuf (xplane.pb) — schema-pinned minimal decoder
# ---------------------------------------------------------------------------

# Field numbers of the upstream xplane.proto messages this reader walks.
# ONE table shared with the test-side encoder (tests/test_profiling.py
# round-trips a hand-built XSpace through it), so reader and fixture can
# never disagree; against real captures it is best-effort on the stable
# upstream numbering.
_XPLANE_SCHEMA = {
    "XSpace": {"planes": 1},
    "XPlane": {"id": 1, "name": 2, "lines": 3, "event_metadata": 4,
               "stat_metadata": 5},
    "XLine": {"id": 1, "name": 2, "timestamp_ns": 3, "events": 4,
              "duration_ps": 9, "display_id": 10, "display_name": 11},
    "XEvent": {"metadata_id": 1, "offset_ps": 2, "duration_ps": 3,
               "stats": 4},
    "XEventMetadata": {"id": 1, "name": 2, "display_name": 4},
    "XStat": {"metadata_id": 1, "str_value": 5},
    "map_entry": {"key": 1, "value": 2},
}


def _iter_proto_fields(buf: bytes):
    """Yield (field_number, wire_type, value) over one serialized message.
    Varints yield ints; length-delimited yield bytes; fixed widths ints."""
    i, n = 0, len(buf)
    while i < n:
        tag = 0
        shift = 0
        while True:
            b = buf[i]
            i += 1
            tag |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                break
        field, wire = tag >> 3, tag & 0x7
        if wire == 0:                      # varint
            val = 0
            shift = 0
            while True:
                b = buf[i]
                i += 1
                val |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
            yield field, wire, val
        elif wire == 2:                    # length-delimited
            ln = 0
            shift = 0
            while True:
                b = buf[i]
                i += 1
                ln |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
            yield field, wire, buf[i:i + ln]
            i += ln
        elif wire == 1:                    # 64-bit
            yield field, wire, struct.unpack("<Q", buf[i:i + 8])[0]
            i += 8
        elif wire == 5:                    # 32-bit
            yield field, wire, struct.unpack("<I", buf[i:i + 4])[0]
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire} "
                             f"(field {field}) — not an XSpace?")


def _proto_dict(buf: bytes) -> Dict[int, list]:
    out: Dict[int, list] = {}
    for field, _wire, val in _iter_proto_fields(buf):
        out.setdefault(field, []).append(val)
    return out


def _first(d: Dict[int, list], field: int, default=None):
    vals = d.get(field)
    return vals[0] if vals else default


def hlo_scope_map(data: bytes) -> Dict[str, str]:
    """Instruction-name → grace-scope joins harvested from the serialized
    HLO protos an xplane's ``/host:metadata`` plane embeds.

    Some runtimes (XLA:CPU notably) export execution events under bare HLO
    instruction names (``all-gather.11``, ``copy.203``) with no op-name
    metadata — the ``named_scope`` paths live only inside the HLO proto's
    per-instruction ``metadata.op_name``. Rather than pin the full
    HloModuleProto schema, this walks every nested message generically and
    pairs each message's field-1 identifier (the instruction name, by HLO
    proto convention) with the nearest descendant string containing
    ``grace/`` — exactly the vocabulary :func:`match_stage` consumes, so a
    mis-paired non-grace string can never pollute attribution. Best-effort
    by construction: an empty map just leaves spans unattributed.
    """
    out: Dict[str, str] = {}

    def walk(buf: bytes, owner: Optional[str], depth: int) -> None:
        if depth > 40:
            return
        try:
            fields = _proto_dict(buf)
        except Exception:
            return
        name, name_bytes = owner, None
        v = fields.get(1)
        if v and isinstance(v[0], bytes) and 0 < len(v[0]) < 128:
            try:
                s = v[0].decode()
                if s and s.isascii() and all(c.isalnum() or c in "._-"
                                             for c in s):
                    name, name_bytes = s, v[0]
            except UnicodeDecodeError:
                pass
        for vals in fields.values():
            for val in vals:
                if not isinstance(val, bytes) or val is name_bytes \
                        or b"grace/" not in val:
                    continue
                try:
                    txt = val.decode()
                except UnicodeDecodeError:
                    txt = None
                if txt is not None and "grace/" in txt and len(txt) < 512 \
                        and "\n" not in txt:
                    if name is not None:
                        out.setdefault(name, txt)
                else:
                    walk(val, name, depth + 1)

    walk(data, None, 0)
    return out


def enrich_spans(spans: List[Span],
                 scope_map: Mapping[str, str]) -> List[Span]:
    """Attach scopes from an instruction-name → scope map
    (:func:`hlo_scope_map`) to spans that attribute to no stage yet.
    An existing scope is appended to, not replaced (Chrome CPU exports
    stuff the bare op name into ``args.name``, which carries no stage);
    spans already attributable or finding no mapping pass through."""
    if not scope_map:
        return spans
    return [dataclasses.replace(
                s, scope=f"{s.scope} {scope_map[s.name]}".strip())
            if not s.stage() and s.name in scope_map else s
            for s in spans]


def parse_xplane(data: bytes) -> List[Span]:
    """Serialized XSpace → spans (device = plane name, lane = line name).
    When the space embeds HLO protos carrying ``grace/`` op names (the
    XLA:CPU layout), spans are enriched via :func:`hlo_scope_map`."""
    S = _XPLANE_SCHEMA
    spans: List[Span] = []
    space = _proto_dict(data)
    for plane_buf in space.get(S["XSpace"]["planes"], []):
        plane = _proto_dict(plane_buf)
        device = _first(plane, S["XPlane"]["name"], b"").decode(
            "utf-8", "replace")
        ev_meta: Dict[int, str] = {}
        for entry_buf in plane.get(S["XPlane"]["event_metadata"], []):
            entry = _proto_dict(entry_buf)
            key = _first(entry, S["map_entry"]["key"], 0)
            md_buf = _first(entry, S["map_entry"]["value"], b"")
            md = _proto_dict(md_buf)
            name = _first(md, S["XEventMetadata"]["name"], b"")
            ev_meta[int(key)] = name.decode("utf-8", "replace")
        for line_buf in plane.get(S["XPlane"]["lines"], []):
            line = _proto_dict(line_buf)
            lane = (_first(line, S["XLine"]["display_name"])
                    or _first(line, S["XLine"]["name"], b"")).decode(
                        "utf-8", "replace")
            base_ns = int(_first(line, S["XLine"]["timestamp_ns"], 0))
            for ev_buf in line.get(S["XLine"]["events"], []):
                ev = _proto_dict(ev_buf)
                md_id = int(_first(ev, S["XEvent"]["metadata_id"], 0))
                offset_ps = int(_first(ev, S["XEvent"]["offset_ps"], 0))
                dur_ps = int(_first(ev, S["XEvent"]["duration_ps"], 0))
                if dur_ps <= 0:
                    continue
                spans.append(Span(
                    name=ev_meta.get(md_id, f"event {md_id}"),
                    ts=base_ns * 1e-3 + offset_ps * 1e-6,   # → µs
                    dur=dur_ps * 1e-6,
                    device=device, lane=lane))
    if b"grace/" in data and not any(s.stage() for s in spans):
        spans = enrich_spans(spans, hlo_scope_map(data))
    return spans


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------

def load_trace_events(path: str) -> List[Span]:
    """Load spans from a profiler artifact, dispatching on the filename
    (``.json``/``.json.gz`` → Chrome trace; ``.pb``/``.xplane`` → XSpace)."""
    lower = path.lower()
    if lower.endswith(".pb") or ".xplane" in lower:
        with open(path, "rb") as f:
            return parse_xplane(f.read())
    opener = gzip.open if lower.endswith(".gz") else open
    with opener(path, "rt") as f:
        return parse_chrome_trace(json.load(f))


def find_latest_trace(logdir: str) -> Optional[str]:
    """Newest profiler artifact under ``logdir`` (the layout
    ``jax.profiler.trace`` writes: ``plugins/profile/<run>/…``)."""
    paths = []
    for pattern in ("**/*.trace.json.gz", "**/*.xplane.pb"):
        paths.extend(glob.glob(os.path.join(logdir, pattern),
                               recursive=True))
    return max(paths, key=os.path.getmtime) if paths else None


# ---------------------------------------------------------------------------
# interval math (all µs)
# ---------------------------------------------------------------------------

def interval_union_us(intervals: Iterable[Tuple[float, float]]
                      ) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into a disjoint sorted union."""
    ivs = sorted((s, e) for s, e in intervals if e > s)
    out: List[Tuple[float, float]] = []
    for s, e in ivs:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _measure(union: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in union)


def overlap_us(a: Sequence[Tuple[float, float]],
               b: Sequence[Tuple[float, float]]) -> float:
    """Measure of the intersection of two interval unions (each already
    disjoint + sorted, as :func:`interval_union_us` returns)."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _self_times(spans: List[Span]) -> List[float]:
    """Self time of each span (dur minus time covered by nested children on
    the same timeline). Chrome-trace complete events on one thread nest
    properly; a malformed partial overlap clamps at zero rather than going
    negative. Per-stage sums of self time add up exactly to the union
    measure of the lane — the invariant that makes the stage table sum to
    the total."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i].ts, -spans[i].dur))
    child = [0.0] * len(spans)
    stack: List[int] = []
    for i in order:
        s = spans[i]
        while stack and s.ts >= spans[stack[-1]].end - 1e-9:
            stack.pop()
        if stack:
            child[stack[-1]] += s.dur
        stack.append(i)
    return [max(0.0, spans[i].dur - child[i]) for i in range(len(spans))]


# ---------------------------------------------------------------------------
# analysis
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TraceAnalysis:
    """Stage attribution + overlap + step stats of one profiler trace."""

    path: Optional[str]
    n_spans: int
    devices: List[str]
    device_lanes_detected: bool
    total_us: float                       # total device self time
    stage_us: Dict[str, float]            # canonical stage → self µs
    compute_us: float
    collective_us: float
    overlap_us: float                     # collective ∩ compute, device time
    step_times_us: List[float]

    @property
    def overlap_fraction(self) -> Optional[float]:
        """Share of collective device time hidden under concurrent compute
        on the same device; None when the trace has no collective time."""
        if self.collective_us <= 0.0:
            return None
        return self.overlap_us / self.collective_us

    def step_percentiles_ms(self) -> Optional[Dict[str, float]]:
        if not self.step_times_us:
            return None
        arr = np.asarray(self.step_times_us) * 1e-3
        return {"n": len(self.step_times_us),
                "mean_ms": float(arr.mean()),
                "p50_ms": float(np.percentile(arr, 50)),
                "p90_ms": float(np.percentile(arr, 90)),
                "p99_ms": float(np.percentile(arr, 99)),
                "max_ms": float(arr.max())}

    def as_dict(self) -> dict:
        return {
            "trace": self.path,
            "n_spans": self.n_spans,
            "devices": self.devices,
            "device_lanes_detected": self.device_lanes_detected,
            "total_device_ms": round(self.total_us * 1e-3, 6),
            "stages_ms": {k: round(v * 1e-3, 6)
                          for k, v in sorted(self.stage_us.items(),
                                             key=lambda kv: -kv[1])},
            "compute_ms": round(self.compute_us * 1e-3, 6),
            "collective_ms": round(self.collective_us * 1e-3, 6),
            "overlap_ms": round(self.overlap_us * 1e-3, 6),
            "overlap_fraction": (None if self.overlap_fraction is None
                                 else round(self.overlap_fraction, 6)),
            "step_times": self.step_percentiles_ms(),
        }

    def render(self) -> str:
        out = []
        dev = ", ".join(self.devices) or "(no device lanes — all spans)"
        out.append(f"devices: {dev}")
        out.append(f"spans: {self.n_spans}   total device time: "
                   f"{self.total_us / 1e3:.3f} ms")
        out.append("")
        out.append(f"  {'stage':<28s}{'ms':>12s}{'share':>9s}")
        for name, us in sorted(self.stage_us.items(), key=lambda kv: -kv[1]):
            share = us / self.total_us if self.total_us else 0.0
            out.append(f"  {name:<28s}{us / 1e3:>12.3f}{share:>8.1%}")
        out.append(f"  {'TOTAL':<28s}{self.total_us / 1e3:>12.3f}"
                   f"{'100.0%':>9s}")
        out.append("")
        out.append(f"  compute: {self.compute_us / 1e3:.3f} ms   "
                   f"collective: {self.collective_us / 1e3:.3f} ms")
        if self.overlap_fraction is None:
            out.append("  overlap: n/a (no collective time in trace)")
        else:
            out.append(
                f"  overlap: {self.overlap_us / 1e3:.3f} ms of collective "
                f"time hidden under compute — overlap fraction "
                f"{self.overlap_fraction:.1%} (device timelines; the tuner's "
                "projection model assumes 0%)")
        sp = self.step_percentiles_ms()
        if sp:
            out.append(f"  steps: n={sp['n']}  mean {sp['mean_ms']:.3f} ms  "
                       f"p50 {sp['p50_ms']:.3f}  p90 {sp['p90_ms']:.3f}  "
                       f"p99 {sp['p99_ms']:.3f}  max {sp['max_ms']:.3f}")
        return "\n".join(out)


def _is_device(name: str) -> bool:
    low = name.lower()
    return "/device:" in low or "tpu" in low or "gpu" in low


def analyze_spans(spans: List[Span],
                  path: Optional[str] = None) -> TraceAnalysis:
    """Attribute a span list. Device lanes are processes named like
    ``/device:TPU:0``; when the trace marks none (some CPU captures), every
    lane is analyzed and the result says so. The ``Steps`` lane provides
    step-time samples and is excluded from op attribution (its markers
    *cover* the ops; charging both would double-count)."""
    device_spans = [s for s in spans if _is_device(s.device)]
    detected = bool(device_spans)
    if not detected:
        device_spans = list(spans)
    step_times = [s.dur for s in device_spans if s.lane == STEP_LANE]
    op_spans = [s for s in device_spans if s.lane != STEP_LANE]

    by_lane: Dict[Tuple[str, str], List[Span]] = {}
    for s in op_spans:
        by_lane.setdefault((s.device, s.lane), []).append(s)

    stage_us: Dict[str, float] = {}
    total = 0.0
    coll_by_device: Dict[str, List[Tuple[float, float]]] = {}
    comp_by_device: Dict[str, List[Tuple[float, float]]] = {}
    for (device, _lane), lane_spans in by_lane.items():
        selfs = _self_times(lane_spans)
        for s, self_us in zip(lane_spans, selfs):
            stage = s.stage() or UNATTRIBUTED
            stage_us[stage] = stage_us.get(stage, 0.0) + self_us
            total += self_us
            bucket = (coll_by_device if s.is_collective()
                      else comp_by_device)
            bucket.setdefault(device, []).append((s.ts, s.end))

    collective = overlap = compute = 0.0
    for device in set(coll_by_device) | set(comp_by_device):
        cu = interval_union_us(coll_by_device.get(device, []))
        pu = interval_union_us(comp_by_device.get(device, []))
        collective += _measure(cu)
        compute += _measure(pu)
        overlap += overlap_us(cu, pu)

    return TraceAnalysis(
        path=path,
        n_spans=len(spans),
        devices=sorted({s.device for s in device_spans}),
        device_lanes_detected=detected,
        total_us=total,
        stage_us=stage_us,
        compute_us=compute,
        collective_us=collective,
        overlap_us=overlap,
        step_times_us=step_times)


def analyze_trace(path: str) -> TraceAnalysis:
    """Load + analyze one profiler artifact (see :func:`load_trace_events`);
    pass a directory to analyze its newest capture. A Chrome-trace export
    whose op names carry no grace scopes (the XLA:CPU layout) is enriched
    from a sibling ``xplane.pb``'s embedded HLO metadata when one exists."""
    if os.path.isdir(path):
        found = find_latest_trace(path)
        if found is None:
            raise FileNotFoundError(
                f"no *.trace.json.gz / *.xplane.pb under {path}")
        path = found
    spans = load_trace_events(path)
    if not any(s.stage() for s in spans):
        siblings = glob.glob(os.path.join(os.path.dirname(path),
                                          "*.xplane.pb"))
        if siblings:
            with open(siblings[0], "rb") as f:
                spans = enrich_spans(spans, hlo_scope_map(f.read()))
    return analyze_spans(spans, path=path)
