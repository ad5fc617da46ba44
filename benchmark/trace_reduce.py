"""From the profiler's trace to numbers: device busy and idle time, the
operations that took most of it, collectives and their exposed part, and
idle gaps named by what the host was doing.

Two steps, so that the arithmetic can be checked on a small recorded trace
kept with the tests:

* :func:`load_xplane` reads an ``.xplane.pb`` with nothing but JAX into a
  plain structure ``{"planes": [{"name", "lines": [{"name", "events":
  [[name, start_ns, duration_ns], ...]}]}]}`` (host planes keep only the
  span names asked for, by name or by prefix, or they would be most of the
  file);
* :func:`reduce` turns that structure into a :class:`Reduced`.

What a TPU trace looks like (read by hand on a v5e, jax 0.9.0): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
executed program, named ``jit_step(<hash>)``), ``XLA Ops`` (one event per HLO
operation, named by its whole HLO line, ``%fusion.67 = ...``) and ``Async XLA
Ops`` (an asynchronous operation from its start to its done); host threads
are lines of the plane ``/host:CPU``, where a ``jax.profiler.TraceAnnotation``
appears under its own name. All on one clock, in nanoseconds from the start
of the trace.
"""

from __future__ import annotations

import bisect
import dataclasses
import re

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"  # start-to-done spans of asynchronous operations
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute|"
    r"collective-broadcast|psum",
    re.IGNORECASE,
)
NS = 1e-9


def load_xplane(path, host_names=(), host_prefixes=()) -> dict:
    """Read a profiler ``.xplane.pb`` into the plain structure above. Lines
    of a device plane are kept whole; of any other plane only events whose
    name is in ``host_names`` or starts with one of ``host_prefixes``."""
    from jax.profiler import ProfileData

    keep, prefixes = set(host_names), tuple(host_prefixes)
    planes = []
    for plane in ProfileData.from_file(str(path)).planes:
        device = DEVICE_PLANE.fullmatch(plane.name) is not None
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, ASYNC_LINE, MODULES_LINE):
                continue
            events = [
                [short_name(e.name), float(e.start_ns), float(e.duration_ns)]
                for e in line.events
                if device or e.name in keep or e.name.startswith(prefixes)
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def short_name(name: str) -> str:
    """``%fusion.67 = (u32[...]) fusion(...)`` -> ``fusion.67``."""
    return name.split(" = ", 1)[0].lstrip("%")


def union_seconds(intervals) -> float:
    return sum(e - s for s, e in merge(intervals)) * NS


def merge(intervals) -> list:
    """Sorted, disjoint cover of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def subtract(intervals, cover) -> list:
    """The parts of merged ``intervals`` that merged ``cover`` leaves bare."""
    out = []
    starts = [c[0] for c in cover]
    for s, e in intervals:
        i = max(0, bisect.bisect_right(starts, s) - 1)
        at = s
        while i < len(cover) and cover[i][0] < e:
            cs, ce = cover[i]
            if ce > at:
                if cs > at:
                    out.append([at, min(cs, e)])
                at = max(at, ce)
            i += 1
        if at < e:
            out.append([at, e])
    return out


def self_times(ops) -> list:
    """``(name, self_ns)`` per operation: its duration less that of the
    operations nested inside it (a ``while`` spans its body's)."""
    out = []
    stack = []  # [name, end, self_ns]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, _end, own = stack.pop()
            out.append((name, own))

    for name, start, end in sorted(ops, key=lambda o: (o[1], -o[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    close(float("inf"))
    return out


@dataclasses.dataclass
class Reduced:
    """One traced window, reduced. Times in seconds; ``ops`` per chip are
    ``(module/op name, start_ns, end_ns)`` clipped to the window."""

    window: tuple  # (start_ns, end_ns)
    ops: dict  # chip -> list of (name, start_ns, end_ns)
    async_ops: dict  # chip -> the same, start to done of asynchronous operations
    host_spans: list  # (name, start_ns, end_ns)

    @property
    def window_seconds(self) -> float:
        return (self.window[1] - self.window[0]) * NS

    @property
    def chips(self) -> list:
        return sorted(self.ops)

    def busy_seconds(self, chip, modules=None) -> float:
        """Seconds in which an operation ran on ``chip``; with ``modules``,
        an operation of one of these programs (``jit_step``) only."""
        return union_seconds(
            (s, e) for n, s, e in self.ops[chip]
            if modules is None or n.split("/", 1)[0] in modules
        )

    def mean_busy_seconds(self) -> float:
        return sum(self.busy_seconds(c) for c in self.chips) / len(self.chips)

    def max_busy_seconds(self, modules=None) -> float:
        return max(self.busy_seconds(c, modules) for c in self.chips)

    def max_kernel_seconds(self, kernel: str) -> float:
        """Device seconds of the operations named ``kernel`` in any program (a
        Pallas kernel's ``name``, which its operations carry numbered:
        ``chacha_rounds.1``), on the chip with most."""
        return max(
            sum(e - s for n, s, e in self.ops[chip] if n.rpartition("/")[2].startswith(kernel))
            for chip in self.chips
        ) * NS

    def idlest_chip(self):
        return min(self.chips, key=self.busy_seconds)

    def idle_share(self) -> float:
        """1 - busy / window on the chip that idles most."""
        return 1.0 - self.busy_seconds(self.idlest_chip()) / self.window_seconds

    def top_operations(self, n: int) -> list:
        """The operations that took most device time in the window: self
        seconds by ``module/op``, averaged over the chips."""
        total = {}
        for chip in self.chips:
            for name, own in self_times(self.ops[chip]):
                total[name] = total.get(name, 0.0) + own
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * NS / len(self.chips)] for name, ns in ranked]

    def collective_seconds(self) -> tuple:
        """``(collective, exposed)`` seconds on the chip with most
        collective time: a collective from its start to its done, exposed
        where no other operation ran on that chip meanwhile."""
        best = (0.0, 0.0)
        for chip in self.chips:
            every = self.ops[chip] + self.async_ops.get(chip, [])
            coll = merge((s, e) for n, s, e in every if COLLECTIVE.search(n))
            rest = merge((s, e) for n, s, e in self.ops[chip] if not COLLECTIVE.search(n))
            total = sum(e - s for s, e in coll) * NS
            exposed = sum(e - s for s, e in subtract(coll, rest)) * NS
            if total > best[0]:
                best = (total, exposed)
        return best

    def idle_gaps_by_span(self, n: int) -> list:
        """Idle seconds of the idlest chip, by the benchmark's span the host
        was in: the innermost span where they nest, ``-`` outside any."""
        chip = self.idlest_chip()
        busy = merge((s, e) for _n, s, e in self.ops[chip])
        gaps = subtract([list(self.window)], busy)
        outer = merge((s, e) for name, s, e in self.host_spans if name == "round")
        inner = {}
        for name, s, e in self.host_spans:
            if name != "round":
                inner.setdefault(name, []).append((s, e))
        total = {}
        covered = []
        for name, intervals in inner.items():
            cover = merge(intervals)
            covered.extend(cover)
            left = sum(e - s for s, e in subtract(gaps, cover))
            total[name] = sum(e - s for s, e in gaps) - left
        in_children = merge(covered)
        bare = subtract(gaps, in_children)
        outside = sum(e - s for s, e in subtract(bare, outer))
        total["round"] = sum(e - s for s, e in bare) - outside
        total["-"] = outside
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * NS] for name, ns in ranked]


def reduce(raw: dict, span_names, span_prefixes=()) -> Reduced | None:
    """The plain structure -> :class:`Reduced`, its host spans those named
    in ``span_names`` or by a prefix; ``None`` where it holds no device plane
    with operations."""
    keep, prefixes = set(span_names), tuple(span_prefixes)
    host_spans = []
    devices = {}
    for plane in raw["planes"]:
        match = DEVICE_PLANE.fullmatch(plane["name"])
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        if match is None:
            for events in lines.values():
                host_spans.extend(
                    (n, s, s + d) for n, s, d in events
                    if n in keep or n.startswith(prefixes)
                )
        elif lines.get(OPS_LINE):
            devices[int(match.group(1))] = lines
    if not devices:
        return None
    rounds = [(s, e) for n, s, e in host_spans if n == "round"]
    if rounds:
        window = (min(s for s, _ in rounds), max(e for _, e in rounds))
    else:
        every = [ev for lines in devices.values() for ev in lines[OPS_LINE]]
        window = (min(s for _n, s, _d in every), max(s + d for _n, s, d in every))
    ops, async_ops = {}, {}
    for chip, lines in devices.items():
        modules = sorted(
            (s, s + d, re.sub(r"\(\d+\)$", "", n))
            for n, s, d in lines.get(MODULES_LINE, ())
        )
        starts = [m[0] for m in modules]

        def clipped(events):
            out = []
            for name, start, duration in events:
                s, e = max(start, window[0]), min(start + duration, window[1])
                if e <= s:
                    continue
                i = bisect.bisect_right(starts, start) - 1
                if i >= 0 and start < modules[i][1]:
                    name = f"{modules[i][2]}/{name}"
                out.append((name, s, e))
            return out

        ops[chip] = clipped(lines[OPS_LINE])
        async_ops[chip] = clipped(lines.get(ASYNC_LINE, ()))
    return Reduced(window=window, ops=ops, async_ops=async_ops, host_spans=host_spans)
