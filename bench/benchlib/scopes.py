"""Reduction of a profile to device time by program scope and device idle
time by program span.

The program names its device work with ``jax.named_scope``
(``repro.obs.scopes.SCOPES``: ``dit.self_attn``, ``lp.stitch``, ...)
and its host work with spans (``batch.*``, ``denoise.*``,
``snapshot.*``) that ``jax.profiler.TraceAnnotation`` puts on the
profile's host plane.  A device op event carries only its instruction's
text, so the scope of each op comes from the program:
``LPStepCompiler.programs()`` gives each step program's module name and
optimized HLO, ``repro.obs.scopes.scope_map`` the scope of each
instruction in it, and the ``XLA Modules`` line of the profile says
which module each op ran in.  That line names a module ``name(id)``; the
id is the profiler's and no executable exposes it (on a v5e the
runtime's fingerprint is an unrelated 32-byte digest), so modules are
matched by name.

``read`` takes what ``tracefile.read`` leaves out (the module line and
the program's spans); the rest is interval arithmetic on nanoseconds,
free of the profiler so the tests can drive it with hand-made events.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

from .tracefile import DEVICE_PREFIX, Event, gaps

MODULES_LINE = "XLA Modules"
PROGRAM_PREFIXES = ("batch.", "denoise.", "snapshot.")
UNSCOPED = "unscoped"
NO_SPAN = "host"
_MODULE_ID = re.compile(r"\(\d+\)$")

# {module name: [{instruction: scope} of each executable of that name]}
ScopeMaps = Dict[str, List[Dict[str, str]]]


def read(path: str) -> Tuple[Dict[int, List[Event]], List[Event]]:
    """The ``XLA Modules`` events of each TPU (named ``module(id)``) and
    the program's host spans, from a ``.xplane.pb``."""
    from jax.profiler import ProfileData

    modules: Dict[int, List[Event]] = {}
    program: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX):
            tail = name[len(DEVICE_PREFIX):]
            if not tail.isdigit():
                continue
            mods = modules.setdefault(int(tail), [])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    mods.extend(Event(e.name, e.start_ns, e.end_ns)
                                for e in line.events)
        elif name.startswith("/host:"):
            for line in plane.lines:
                program.extend(Event(e.name, e.start_ns, e.end_ns)
                               for e in line.events
                               if e.name.startswith(PROGRAM_PREFIXES))
    return modules, program


def scope_maps(programs) -> ScopeMaps:
    """``LPStepCompiler.programs()`` -> the scope maps by module name."""
    from repro.obs.scopes import scope_map

    out: ScopeMaps = defaultdict(list)
    for name, text in programs:
        out[name].append(scope_map(text))
    return dict(out)


def _lookup(maps: ScopeMaps, module: str):
    """The scope of each instruction of a module event ``name(id)``: what
    every executable of that name agrees on (two may share one: jit
    compiles the first rotation dim twice on a mesh, for an input off
    the mesh and one on it); an op on which they differ is unscoped."""
    found = maps.get(_MODULE_ID.sub("", module), [])

    def agreed(op):
        seen = {scopes.get(op) for scopes in found}
        return seen.pop() if len(seen) == 1 else None
    return agreed


def device_scopes(ops: Sequence[Event], modules: Sequence[Event],
                  maps: ScopeMaps, lo: float, hi: float) -> Dict[str, float]:
    """Self seconds of one device's ops inside [lo, hi] by the scope that
    owns them (an op's time less that of the ops inside it, as
    ``tracefile.device_summary`` counts it), with an ``unscoped`` row for
    ops of other programs and instructions no scope claims."""
    mods = sorted(modules, key=lambda e: e.start)
    out: Dict[str, float] = defaultdict(float)
    lookups: Dict[str, object] = {}
    j, current = 0, None
    for e in sorted(ops, key=lambda e: e.start):
        if not (e.start >= lo and e.end <= hi):
            continue
        while j < len(mods) and mods[j].start <= e.start:
            current = mods[j]
            j += 1
        scope = None
        if current is not None and e.end <= current.end:
            if current.name not in lookups:
                lookups[current.name] = _lookup(maps, current.name)
            scope = lookups[current.name](e.op)
        out[scope or UNSCOPED] += (e.dur - e.inner) * 1e-9
    return dict(out)


def idle_by_span(ops: Sequence[Event], program: Sequence[Event],
                 lo: float, hi: float) -> Dict[str, float]:
    """Seconds of one device's idle time in [lo, hi] by the innermost
    (latest opened) program span covering each instant of it, ``host``
    where none does.  A gap that crosses a span boundary is split."""
    out: Dict[str, float] = defaultdict(float)
    spans = sorted(program, key=lambda e: e.start)
    for a, b in gaps([(e.start, e.end) for e in ops], lo, hi):
        covering = [e for e in spans if e.start < b and e.end > a]
        cuts = sorted({a, b} | {t for e in covering
                                for t in (e.start, e.end) if a < t < b})
        for s, t in zip(cuts, cuts[1:]):
            inner = [e for e in covering if e.start <= s and e.end >= t]
            name = inner[-1].name if inner else NO_SPAN
            out[name] += (t - s) * 1e-9
    return dict(out)


def readings(scopes: Dict[str, float], idle: Dict[str, float],
             steps: int) -> Dict[str, float]:
    """Milliseconds per denoise step served of the first device:
    self-attention, FFN, LP's own work (windows, stitch, exchange,
    update) and idle time inside ``snapshot.record`` spans."""
    if not steps:
        return {}
    per = 1e3 / steps
    return {
        "dit.self_attn_ms": scopes.get("dit.self_attn", 0.0) * per,
        "dit.ffn_ms": scopes.get("dit.ffn", 0.0) * per,
        "lp.overhead_ms": sum(v for k, v in scopes.items()
                              if k.startswith("lp.")) * per,
        "engine.snapshot_idle_ms": idle.get("snapshot.record", 0.0) * per,
    }
