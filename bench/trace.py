"""Reduction of a profiler trace to per-op device time, busy share and idle
gaps.

The harness wraps every scheduler op in ``jax.named_scope(<op name>)``; the
compiler keeps that path in each instruction's ``op_name`` metadata.  A
device event is attributed to the innermost op scope in its path, taken from
the event's own ``tf_op`` / ``long_name`` stat where the trace carries one,
else from the compiled module text (instruction name -> op_name).  Events
under no op scope are ``unattributed``.  Nested events on one line (a loop
or conditional around its body) count only their self time.

Host spans (``jax.profiler.TraceAnnotation``) name what the host was doing:
``dispatch``, ``block_until_ready``, ``count_live`` inside ``window``.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

HOST_SPANS = ("dispatch", "block_until_ready", "count_live")
WINDOW_SPAN = "window"
UNATTRIBUTED = "unattributed"
MIN_GAP_NS = 1000   # shorter gaps are the rounding of adjacent events

_EVENT = re.compile(r"^%?([\w.\-]+)(?:\s*=|$)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                    r"metadata=\{[^}]*op_name=\"([^\"]*)\"")


def hlo_op_names(hlo_text: str) -> dict:
    """Instruction name -> op_name metadata, from a module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def innermost_scope(path: str, scopes) -> str:
    for part in reversed(path.split("/")):
        if part in scopes:
            return part
    return UNATTRIBUTED


def merged(intervals):
    """(start, end) intervals merged where they overlap, in order."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    return sum(e - s for s, e in merged(intervals))


def self_times(events):
    """Self time of each (start, end) event on one line: its length less
    that of the events nested inside it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [e - s for s, e in events]
    stack = []
    for i in order:
        s, e = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


@dataclasses.dataclass
class Trace:
    """A traced window, reduced.

    ops:   (line, name, start_ns, end_ns, scope) per device event
    host:  (name, start_ns, end_ns) per host span of ours
    window: (start_ns, end_ns) of the ``window`` span
    """

    ops: list
    host: list
    window: tuple
    n_devices: int
    op_freq: dict
    first_step: int
    steps: int

    # ---------------------------------------------------------- loading

    @classmethod
    def load(cls, trace_dir: str, op_freq: dict, hlo_text: str,
             first_step: int, steps: int) -> "Trace":
        from jax.profiler import ProfileData

        paths = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        if not paths:
            raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
        raw = raw_events(ProfileData.from_file(paths[-1]))
        return cls.reduce(raw, op_freq, hlo_op_names(hlo_text),
                          first_step, steps)

    @classmethod
    def reduce(cls, raw: dict, op_freq: dict, hlo_names: dict,
               first_step: int, steps: int) -> "Trace":
        """From :func:`raw_events`' plain form (also what test data holds)."""
        ops = []
        for dev, line, name, start, dur, path in raw["device"]:
            scope = innermost_scope(path, op_freq)
            if scope == UNATTRIBUTED:
                scope = innermost_scope(
                    hlo_names.get(name.lstrip("%"), ""), op_freq)
            ops.append((f"{dev}/{line}", name, start, start + dur, scope))
        host = [(n, s, s + d) for n, s, d in raw["host"]
                if n in HOST_SPANS or n == WINDOW_SPAN]
        wins = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
        if wins:
            window = (min(s for s, _ in wins), max(e for _, e in wins))
        else:
            window = (min(o[2] for o in ops), max(o[3] for o in ops))
        return cls(ops=ops, host=[h for h in host if h[0] != WINDOW_SPAN],
                   window=window, n_devices=max(len(raw["devices"]), 1),
                   op_freq=op_freq, first_step=first_step, steps=steps)

    # ------------------------------------------------------- quantities

    def _clipped(self, s, e):
        lo, hi = self.window
        return max(s, lo), min(e, hi)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _by_device(self):
        out = {}
        for line, name, s, e, scope in self.ops:
            out.setdefault(line.split("/")[0], []).append((s, e))
        return out

    @property
    def busy_s(self) -> float:
        """Union of device-busy intervals in the window, mean over chips."""
        per = [union_length([iv for iv in (self._clipped(s, e) for s, e in v)
                             if iv[1] > iv[0]])
               for v in self._by_device().values()]
        return sum(per) / max(len(per), 1) * 1e-9

    def scope_seconds(self) -> dict:
        """Device self time per op scope, summed over lines, mean over
        chips."""
        lines = {}
        for op in self.ops:
            lines.setdefault(op[0], []).append(op)
        out = {}
        for ops in lines.values():
            own = self_times([(s, e) for _, _, s, e, _ in ops])
            for (_, _, s, e, scope), t in zip(ops, own):
                out[scope] = out.get(scope, 0.0) + t * 1e-9
        return {k: v / self.n_devices for k, v in out.items()}

    def has_scope(self, scope: str) -> bool:
        return any(op[4] == scope for op in self.ops)

    def firings(self, scope: str) -> int:
        """Steps of the window on which the op fires."""
        k = self.op_freq.get(scope, 0)
        if k <= 0:
            return 0
        return sum(1 for t in range(self.first_step, self.first_step + self.steps)
                   if t % k == 0)

    def idle_gaps(self):
        """(host span open at the gap, seconds) per device-idle gap of a
        microsecond or more in the window, longest first; a gap on any chip
        counts."""
        gaps = []
        for ivs in self._by_device().values():
            busy = merged([self._clipped(s, e) for s, e in ivs])
            edges = [self.window[0]] + [x for iv in busy for x in iv] + [self.window[1]]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e - s >= MIN_GAP_NS:
                    gaps.append((self.host_span_at((s + e) / 2), (e - s) * 1e-9))
        return sorted(gaps, key=lambda g: -g[1])

    def host_span_at(self, t: float) -> str:
        inside = [(e - s, n) for n, s, e in self.host if s <= t <= e]
        return min(inside)[1] if inside else "host"

    def breakdown(self) -> dict:
        ops = sorted(self.scope_seconds().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops[:10]],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps()[:10]]}


def instruction(event_name: str) -> str:
    """The instruction name of a device event, whose name may be the whole
    instruction text (``%fusion.3 = f32[8] fusion(...)``)."""
    m = _EVENT.match(event_name.strip())
    return m.group(1) if m else event_name


def raw_events(profile) -> dict:
    """The profile as plain lists: device op events with their scope path
    where the trace carries one, and every host event."""
    device, host, devices = [], [], []
    for plane in profile.planes:
        lines = [(line.name, list(line.events)) for line in plane.lines]
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = ([l for l in lines if l[0] == "XLA Ops"]
                   or [l for l in lines if l[0] not in ("XLA Modules", "Steps")])
            if not any(events for _, events in ops):
                continue
            devices.append(plane.name)
            for name, events in ops:
                for ev in events:
                    stats = dict(ev.stats)
                    path = stats.get("tf_op") or stats.get("long_name") or ""
                    device.append((plane.name, name, instruction(ev.name),
                                   ev.start_ns, ev.duration_ns, str(path)))
        elif plane.name.startswith("/host:"):
            for _, events in lines:
                host.extend((ev.name, ev.start_ns, ev.duration_ns)
                            for ev in events)
    return {"device": device, "host": host, "devices": devices}
