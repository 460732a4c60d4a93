"""From a profiler trace to the numbers per-layer metrics read.

``load_xplane`` turns the profiler's ``.xplane.pb`` into plain records: the
device operations (start, duration, name, the named-scope path XLA kept in
their metadata, chip) and the host spans the benchmark opened around its
window and calls.  ``TraceData`` reduces those records; it is what the
tests feed from a small recorded trace.

A layer's time is the device time of the operations whose scope path holds
its scope (``ppo/rollout``, ``env/``, ...); its self time leaves out the
operations that also sit under a named child scope.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench/window"
CALL_SPAN = "bench/call"


@dataclasses.dataclass
class Op:
    start_ns: float
    dur_ns: float
    name: str  # HLO instruction
    scope: str  # named-scope path from the instruction's metadata
    chip: int
    category: str = ""  # HLO opcode


@dataclasses.dataclass
class Span:
    start_ns: float
    dur_ns: float
    name: str


# a device op event is named by its HLO instruction's text:
# "%fusion.12 = f32[...] fusion(...), kind=..., calls=..."
_INSTR = re.compile(r"^%?([^\s=]+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r"^\s*(?:ROOT )?%?([^\s=]+) = .*?op_name=\"([^\"]*)\"")
# ops whose time is that of the ops they run: counting them would count twice
CONTAINERS = {"while", "conditional", "call"}


def scope_map(hlo_text: str) -> dict[str, str]:
    """HLO instruction name -> the named-scope path in its metadata, from a
    compiled program's text (``Compiled.as_text()``)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def parse_op_name(text: str) -> tuple[str, str]:
    """(instruction name, opcode) of a device op event's name."""
    m = _INSTR.match(text)
    if not m:
        return text, ""
    rest = text[m.end():]
    op = _OPCODE.search(rest)
    return m.group(1), op.group(1) if op else ""


def load_xplane(path: str, scopes: dict[str, str]) -> tuple[list[Op], list[Span]]:
    """Device ops of every TPU plane's ``XLA Ops`` line (without control-flow
    containers), each with its scope path from ``scopes``, and host spans
    named ``bench/...``, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    instr, opcode = parse_op_name(e.name)
                    if opcode in CONTAINERS:
                        continue
                    ops.append(Op(e.start_ns, e.duration_ns, instr, scopes.get(instr, ""), chip, opcode))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench/"):
                        spans.append(Span(e.start_ns, e.duration_ns, e.name))
    return ops, spans


def save_records(path: str, ops: list[Op], spans: list[Span], max_ops: int) -> None:
    """Write the window's first ``max_ops`` device ops and the host spans as
    gzipped JSON, with the window span cut to end at the last op kept: a
    small recorded trace for the reducer's tests."""
    import gzip
    import json

    win = next(s for s in spans if s.name == WINDOW_SPAN)
    kept = sorted((o for o in ops if o.start_ns >= win.start_ns), key=lambda o: o.start_ns)[:max_ops]
    end = max(o.start_ns + o.dur_ns for o in kept)
    cut = [Span(s.start_ns, min(s.start_ns + s.dur_ns, end) - s.start_ns, s.name) for s in spans if s.start_ns < end]
    with gzip.open(path, "wt") as f:
        json.dump({"ops": [dataclasses.astuple(o) for o in kept], "spans": [dataclasses.astuple(s) for s in cut]}, f)


def load_records(path: str) -> tuple[list[Op], list[Span]]:
    import gzip
    import json

    with gzip.open(path, "rt") as f:
        d = json.load(f)
    return [Op(*o) for o in d["ops"]], [Span(*s) for s in d["spans"]]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class TraceData:
    """Reductions over the device ops inside the benchmark's window span."""

    def __init__(self, ops: list[Op], spans: list[Span]):
        windows = [s for s in spans if s.name == WINDOW_SPAN]
        if not windows:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
        w = windows[0]
        self.t0, self.t1 = w.start_ns, w.start_ns + w.dur_ns
        self.spans = spans
        self.ops = [o for o in ops if o.start_ns < self.t1 and o.start_ns + o.dur_ns > self.t0]
        self.chips = sorted({o.chip for o in self.ops}) or [0]

    @property
    def window_ns(self) -> float:
        return self.t1 - self.t0

    def _clip(self, o: Op) -> float:
        return min(o.start_ns + o.dur_ns, self.t1) - max(o.start_ns, self.t0)

    def busy_ns(self) -> float:
        """Union of op intervals in the window, averaged over the chips."""
        total = 0.0
        for c in self.chips:
            iv = [(max(o.start_ns, self.t0), min(o.start_ns + o.dur_ns, self.t1)) for o in self.ops if o.chip == c]
            total += sum(b - a for a, b in _union(iv))
        return total / len(self.chips)

    def time_ns(self, pred) -> float | None:
        """Device time of the ops ``pred`` accepts, averaged over the chips;
        None where no op is accepted."""
        sel = [o for o in self.ops if pred(o)]
        if not sel:
            return None
        return sum(self._clip(o) for o in sel) / len(self.chips)

    def scope_ns(self, scope: str, exclude: tuple[str, ...] = ()) -> float | None:
        """Device time under ``scope`` (a path component prefix such as
        ``ppo/rollout`` or ``env/``), leaving out ops also under ``exclude``."""
        return self.time_ns(lambda o: in_scope(o.scope, scope) and not any(in_scope(o.scope, x) for x in exclude))

    def top_ops(self, n: int = 10) -> list[list]:
        """The device operations that took most time, grouped by their
        innermost benchmark-known scope and XLA op name stem."""
        acc: dict[str, float] = {}
        for o in self.ops:
            key = f"{layer_of(o.scope)}:{o.category}"
            acc[key] = acc.get(key, 0.0) + self._clip(o) / len(self.chips)
        return [[k, v / 1e9] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The longest stretches of the window with no op on chip 0, named by
        the host span open at the gap's midpoint."""
        c = self.chips[0]
        busy = _union([(max(o.start_ns, self.t0), min(o.start_ns + o.dur_ns, self.t1)) for o in self.ops if o.chip == c])
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        out = []
        for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = 0.5 * (a + b)
            open_ = [s for s in self.spans if s.start_ns <= mid <= s.start_ns + s.dur_ns and s.name != WINDOW_SPAN]
            host = open_[-1].name if open_ else "host between calls"
            out.append([f"{host} at +{(a - self.t0) / 1e6:.3f} ms", (b - a) / 1e9])
        return out


def in_scope(path: str, scope: str) -> bool:
    """Whether ``scope`` starts a component of ``path``.  Components are
    separated by ``/``, and a transformation wraps the ones inside it:
    under ``vmap`` the env's ``env/reward`` reads ``vmap(vmap(env/reward))``."""
    return re.search(r"(?:^|[/(])" + re.escape(scope), path) is not None


def layer_of(path: str) -> str:
    """Innermost benchmark-known scope of an op (``env/charge_cars``,
    ``wrap/AutoReset``, ``ppo/update``, ...) or ``other``."""
    found = re.findall(r"(?:^|[/(])((?:env|wrap|ppo|eval)/[A-Za-z0-9_]+)", path)
    return found[-1] if found else "other"
