"""The program's own spans and scopes in a profiler trace, and the
per-layer numbers they give.

:mod:`trace_reduce` keeps the benchmark's ``bench.*`` spans. This module
reads, over the same ``bench.window``, the program's ``repro.*`` host spans
(``repro.utils.spans``: the event loop, the engine's result, batch
assembly, dispatch, aggregation and evaluation), labels the device's
longest idle gaps by the innermost span of either prefix, and splits the
device time of the cohort train step's programs (``jit_step``,
``jit_train_scan``) twice: by the step scope each op was traced under
(``unpack``, ``grad``, ``pack``, ``optimizer``), and by the model's own
``jax.named_scope`` s below it (:func:`model_scope_of`), whose names are
read from the paths and listed nowhere here.

A TPU trace's op events carry no ``op_name``: an op's scope comes from the
``op_name`` metadata of the compiled program's text (:func:`hlo_op_scopes`),
keyed by the op's name and result shape, with which the event's text
starts. An event's ``tf_op`` stat, where a trace has one (the slimmed
recording of ``tests/record_trace_spans.py`` keeps the scope there), is
read first. On a trace of a program without these spans and scopes each
number reads ``None``.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

from trace_reduce import WINDOW_SPAN, _enclosing, _label, _stats, \
    program_name, union_ns

PREFIXES = ("bench.", "repro.")
STEP_PROGRAMS = re.compile(r"^jit_(step|train_scan)$")
SCOPES = ("unpack", "grad", "pack", "optimizer")
GLUE = ("unpack", "pack")
ASSEMBLE = "repro.engine.assemble"
DISPATCH = "repro.engine.dispatch"
EVENT = "repro.sim.event"
ENGINE_CALLS = ("repro.engine.result", "repro.engine.aggregate",
                "repro.engine.evaluate")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%[\w.\-]+")


# what JAX itself writes on an ``op_name`` path around a model's scopes:
# the labels of control flow and rematerialisation, and the names of the
# primitives that nest others
JAX_LABELS = frozenset((
    "while", "body", "cond", "body_pred", "checkpoint",
    "rematted_computation", "closed_call", "core_call", "remat", "remat2",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "custom_lin", "pjit", "scan", "shard_map", "run_state", "pallas_call"))
_BRANCH = re.compile(r"^branch_\d+_fun$")
_WRAPPED = re.compile(r"^([\w.\-]+)\((.*)\)$")


@dataclass(frozen=True)
class OpScope:
    """Where an op of a step program was traced: its step scope (one of
    :data:`SCOPES`, or ``""``) and the path of the model's own scopes
    below it (``"/"``-joined, outermost first; ``""`` where none)."""
    step: str = ""
    model: str = ""


def scope_of(op_name: str) -> str:
    """The first step scope on an ``op_name`` path, else ``""``."""
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return ""


def _scope_name(part: str) -> str:
    """The ``jax.named_scope`` name a path component stands for, else
    ``""``. A transform wraps the scope it entered (``vmap(jvp(attn))``,
    ``transpose(jvp())``): the innermost name counts. A nested jit's
    function (``jit(relu)``), a step scope and a label of JAX's own are
    no model scope."""
    while True:
        m = _WRAPPED.match(part)
        if m is None or m.group(1) in ("jit", "pmap"):
            break
        part = m.group(2)
    if not part or _WRAPPED.match(part) or part in SCOPES or \
            part in JAX_LABELS or _BRANCH.match(part):
        return ""
    return part


def model_scope_of(op_name: str) -> str:
    """The path of the model's own scopes on an ``op_name`` path below its
    first step scope, outermost first, without the op's primitive; ``""``
    where there is no step scope or no model scope under it. The TPU
    compiler gives an op it made of several ops their names joined by
    ``;`` (``.../pack/reshape;jit(step)/grad/.../reshape``): the op takes
    the step scope of the first name that has one, as :func:`scope_of`
    reads it, and the model scopes of that same name."""
    parts = op_name.split("/")
    for i, part in enumerate(parts):
        if part in SCOPES:
            rest = parts[i + 1:]
            end = next((j for j, p in enumerate(rest) if ";" in p),
                       len(rest) - 1)
            return "/".join(n for n in map(_scope_name, rest[:end]) if n)
    return ""


def op_scope(op_name: str) -> OpScope:
    return OpScope(scope_of(op_name), model_scope_of(op_name))


def op_key(text: str) -> str:
    """``%name = <result shape>`` of an HLO instruction's text, the part
    an op's trace event and the compiled program's text share."""
    text = text.strip()
    if text.startswith("ROOT "):
        text = text[5:]
    head, sep, rest = text.partition(" = ")
    if not sep:
        return head
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(c, 0)
            if depth == 0:
                return f"{head} = {rest[:i + 1]}"
        return f"{head} = {rest}"
    return f"{head} = {rest.split(' ', 1)[0]}"


def _program_scopes(text: str) -> Dict[str, OpScope]:
    """Op key -> :class:`OpScope` of one compiled program. An op the
    compiler made (a layout copy, the halves of an async copy, the writes
    it turns a concatenation into) carries no ``op_name``. It takes the
    scope of the ops it feeds, the earliest in the step's order; where the
    data it moves comes from a scope two steps before that, it takes the
    scope between them (writes from ``grad`` into a buffer ``optimizer``
    reads are ``pack``), with no model scope; an op that feeds nothing
    takes its operands' scope. An op that the compiler merged from ops of
    several scopes (a fusion) carries the ``op_name`` of one of them, its
    root, and takes that op's step and model scope. The model scope
    always comes from the op the step scope came from, so the step scopes
    read as they would alone."""
    keys, own, deps = {}, {}, {}
    for line in text.splitlines():
        if " = " not in line or not line.startswith("  "):
            continue
        key = op_key(line)
        name = key.split(" = ")[0]
        m = _OP_NAME.search(line)
        keys[name] = key
        own[name] = op_scope(m.group(1)) if m else OpScope()
        deps[name] = _OPERAND.findall(line.split(" = ", 1)[1])
    order = {s: i for i, s in enumerate(SCOPES)}

    def rank(o: OpScope) -> int:
        return order[o.step]

    before, users = {}, {}
    for name in own:                       # the text lists operands first
        before[name] = own[name] if own[name].step else max(
            (before[d] for d in deps[name]
             if d in before and before[d].step),
            key=rank, default=OpScope())
        for d in deps[name]:
            users.setdefault(d, []).append(name)
    scope: Dict[str, OpScope] = {}
    for name in reversed(list(own)):
        after = min((scope[u] for u in users.get(name, ())
                     if u in scope and scope[u].step),
                    key=rank, default=OpScope())
        came = before[name]
        if own[name].step or not after.step:
            scope[name] = own[name] if own[name].step else came
        elif came.step and rank(after) - rank(came) > 1:
            scope[name] = OpScope(SCOPES[rank(came) + 1])
        else:
            scope[name] = after
    return {keys[n]: s for n, s in scope.items()}


def hlo_op_scopes(texts: Iterable[str]) -> Dict[str, OpScope]:
    """Op key (:func:`op_key`) and bare op name -> :class:`OpScope`, from
    the ``as_text()`` of compiled programs. A bare name that different
    programs give different step scopes is left out; one they give the
    same step scope but different model scopes keeps the step scope
    alone."""
    keyed: Dict[str, OpScope] = {}
    bare: Dict[str, set] = {}
    for text in texts:
        for key, scope in _program_scopes(text).items():
            keyed[key] = scope
            bare.setdefault(key.split(" = ")[0], set()).add(scope)
    for name, seen in bare.items():
        if len({o.step for o in seen}) == 1:
            keyed[name] = next(iter(seen)) if len(seen) == 1 else \
                OpScope(next(iter(seen)).step)
    return keyed


def _event_scope(ev, scopes: Dict[str, OpScope]) -> OpScope:
    tf_op = _stats(ev).get("tf_op")
    if tf_op:
        return op_scope(str(tf_op))
    key = op_key(ev.name)
    return scopes.get(key, scopes.get(key.split(" = ")[0], OpScope()))


def _merge(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap_ns(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclass
class ProgramTrace:
    window: Tuple[float, float]
    devices: int
    spans: List[Tuple[str, float, float]] = field(default_factory=list)
    idle: List[List[Tuple[float, float]]] = field(default_factory=list)
    scope_ns: Dict[str, float] = field(default_factory=dict)
    # the step programs' device ns by the path of the model's scopes
    # (:attr:`OpScope.model`; ``""``: under none)
    model_scope_ns: Dict[str, float] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def step_ns(self) -> float:
        """Device ns of the step programs' ops in the window."""
        return sum(self.scope_ns.values())

    def under_ns(self, scope: str) -> float:
        """Device ns of the step programs' ops under the model scope
        ``scope``, at any depth of their path."""
        return sum(ns for path, ns in self.model_scope_ns.items()
                   if scope in path.split("/"))

    def under_share(self, scope: str) -> Optional[float]:
        """Percent of the step programs' device time under the model scope
        ``scope``; None where no op is under it."""
        ns = self.under_ns(scope)
        return 100.0 * ns / self.step_ns if ns else None

    def union(self, *names: str) -> List[Tuple[float, float]]:
        """The named spans, clipped to the window and merged."""
        lo, hi = self.window
        return _merge((max(s, lo), min(e, hi)) for n, s, e in self.spans
                      if n in names and e > lo and s < hi)

    def total_ns(self, name: str) -> float:
        """Summed length of the named spans inside the window."""
        lo, hi = self.window
        return sum(min(e, hi) - max(s, lo) for n, s, e in self.spans
                   if n == name and e > lo and s < hi)

    def count(self, name: str) -> int:
        lo, hi = self.window
        return sum(1 for n, s, e in self.spans
                   if n == name and s >= lo and e <= hi)

    @property
    def idle_ns(self) -> float:
        """Device idle time in the window, mean over devices."""
        return sum(e - s for d in self.idle for s, e in d) / self.devices

    def idle_within_ns(self, *names: str) -> float:
        """Device idle time inside the union of the named spans, mean over
        devices."""
        u = self.union(*names)
        return sum(_overlap_ns(d, u) for d in self.idle) / self.devices


def reduce_program(planes,
                   op_scopes: Optional[Dict[str, OpScope]] = None,
                   n_gaps: int = 10) -> Optional[ProgramTrace]:
    """``planes`` as ``jax.profiler.ProfileData`` gives them; ``op_scopes``
    from :func:`hlo_op_scopes` for traces whose op events carry no
    ``tf_op``. None when the trace holds no window span or no device
    operation."""
    op_scopes = op_scopes or {}
    spans, devices = [], []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIXES):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows or not devices:
        return None
    lo, hi = windows[0]
    idle, scope_ns, model_ns = [], {}, {}
    known: Dict[str, OpScope] = {}         # op text -> scope
    for lines in devices:
        modules = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns,
             program_name(ev.name))
            for ev in (lines["XLA Modules"].events
                       if "XLA Modules" in lines else ()))
        starts = [m[0] for m in modules]
        busy = []
        for ev in lines["XLA Ops"].events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= lo or s >= hi:
                continue
            s, e = max(s, lo), min(e, hi)
            busy.append((s, e))
            prog = program_name(str(_stats(ev).get("hlo_module", ""))) or \
                _enclosing(modules, starts, ev.start_ns)
            if STEP_PROGRAMS.search(prog):
                scope = known.get(ev.name)
                if scope is None:
                    scope = known[ev.name] = _event_scope(ev, op_scopes)
                scope_ns[scope.step] = scope_ns.get(scope.step, 0.0) + e - s
                model_ns[scope.model] = model_ns.get(scope.model, 0.0) + e - s
        t, gaps = lo, []
        for s, e in _merge(busy) + [(hi, hi)]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        idle.append(gaps)
    longest = sorted(idle[0], key=lambda g: g[0] - g[1])[:n_gaps]
    return ProgramTrace(
        window=(lo, hi), devices=len(devices), spans=spans, idle=idle,
        scope_ns=scope_ns, model_scope_ns=model_ns,
        # labelled by the innermost span of either prefix
        gaps=[(_label(spans, (s + e) / 2), e - s) for s, e in longest])


# ------------------------------------------------------------------ numbers


@dataclass
class Counters:
    """The traced session's counts: ``flushes``, ``jobs_run``, ``rounds``
    from the engine and session, and the engine's ``jobs_served`` and
    ``batch_bytes_h2d`` (``None`` where the engine has no such
    counter)."""
    flushes: int = 0
    jobs_run: int = 0
    rounds: int = 0
    jobs_served: Optional[int] = None
    batch_bytes_h2d: Optional[int] = None


def assembly_ms_per_flush(t: ProgramTrace, c: Counters):
    """Host ms in ``repro.engine.assemble`` per vmapped group."""
    ns = t.total_ns(ASSEMBLE)
    return ns / c.flushes / 1e6 if ns and c.flushes else None


def dispatch_ms_per_flush(t: ProgramTrace, c: Counters):
    """Host ms in ``repro.engine.dispatch`` per vmapped group."""
    ns = t.total_ns(DISPATCH)
    return ns / c.flushes / 1e6 if ns and c.flushes else None


def idle_in_assembly_share(t: ProgramTrace, c: Counters):
    """Share of the device's idle time that falls inside batch assembly."""
    if not t.count(ASSEMBLE) or not t.idle_ns:
        return None
    return 100.0 * t.idle_within_ns(ASSEMBLE) / t.idle_ns


def loop_self_share(t: ProgramTrace, c: Counters):
    """Share of the window in event handlers but outside the engine's
    calls: the event loop, the protocol and the network model."""
    events = t.union(EVENT)
    if not events:
        return None
    calls = t.union(*ENGINE_CALLS)
    self_ns = union_ns(events + calls, *t.window) - union_ns(calls, *t.window)
    return 100.0 * self_ns / t.window_ns


def train_glue_share(t: ProgramTrace, c: Counters):
    """Share of the cohort step's device time under ``unpack`` or
    ``pack``."""
    total = t.step_ns
    if not total or not any(t.scope_ns.get(s) for s in SCOPES):
        return None
    return 100.0 * sum(t.scope_ns.get(s, 0.0) for s in GLUE) / total


def scope_coverage(t: ProgramTrace) -> Optional[float]:
    """Share of the cohort step's device time that some scope names."""
    total = t.step_ns
    if not total:
        return None
    return 100.0 * sum(t.scope_ns.get(s, 0.0) for s in SCOPES) / total


def jobs_served_share(t: ProgramTrace, c: Counters):
    """Results answered from a flush over jobs trained in flushes."""
    if c.jobs_served is None or not c.jobs_run:
        return None
    return 100.0 * c.jobs_served / c.jobs_run


def h2d_mb_per_round(t: ProgramTrace, c: Counters):
    """MB (1e6 bytes) of batches copied to the device per round."""
    if not c.batch_bytes_h2d or not c.rounds:
        return None
    return c.batch_bytes_h2d / 1e6 / c.rounds


READERS = {f.__name__: f for f in (
    assembly_ms_per_flush, dispatch_ms_per_flush, idle_in_assembly_share,
    loop_self_share, train_glue_share, jobs_served_share, h2d_mb_per_round)}


def session_counters(stats) -> Counters:
    """The counters of a session the harness ran (``SessionStats``)."""
    return Counters(flushes=stats.flushes, jobs_run=stats.jobs,
                    rounds=stats.rounds, jobs_served=stats.jobs_served,
                    batch_bytes_h2d=stats.batch_bytes_h2d)


def read(name: str, window) -> Optional[float]:
    """Number ``name`` of a window's traced session (the harness's
    ``Window``): its program reduction and its session's counters; None
    where the window has no trace."""
    if window.program is None or window.traced is None:
        return None
    return READERS[name](window.program, session_counters(window.traced))


def numbers(t: ProgramTrace, c: Counters) -> Dict[str, Optional[float]]:
    """Each of the seven numbers, ``None`` where the trace or the counters
    hold nothing to read."""
    return {name: read(t, c) for name, read in READERS.items()}
