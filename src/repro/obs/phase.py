"""Phase telemetry: timing spans, compile/execute splits, memory watermarks.

A ``PhaseRecorder`` collects named ``Span``s (wall-clock segments tagged
``host``/``compile``/``execute``) and free-form notes (chunk plans, memory
watermarks vs the 256 MB chunk budget).  It is installed per-scope through a
contextvar (``use_recorder``); instrumented call sites — ``core.sweep``,
``fabric.bringup``, ``benchmarks.common.timed_steady`` — look it up with
``current_recorder()`` and do *nothing* when none is installed, so the
uninstrumented path stays a plain function call with zero overhead and zero
behavior change.

``measured_call`` is the compile/execute splitter: it AOT-lowers a jitted
function (``fn.lower(*args, **kwargs).compile()``), records the compile span
and the compiled program's memory watermarks (``memory_analysis()``), then
executes the compiled artifact with only the *dynamic* arguments (JAX's AOT
contract: static args are baked into the lowered program and must be omitted
from the compiled call).  A compiler error propagates: telemetry never turns
a program the backend refuses into a plain re-run.
"""
from __future__ import annotations

import contextlib
import contextvars
import time
from dataclasses import dataclass, field
from typing import Any

__all__ = [
    "PhaseRecorder",
    "Span",
    "current_recorder",
    "measured_call",
    "note",
    "span",
    "use_recorder",
]


@dataclass
class Span:
    """One timed segment: ``kind`` is ``host``/``compile``/``execute``."""

    name: str
    kind: str
    ms: float
    extra: dict = field(default_factory=dict)


class PhaseRecorder:
    """Collects spans and notes for one run scope (a benchmark module, a
    smoke run, a test).  Not thread-safe; one recorder per scope.

    measure_memory: opt into the AOT lower/compile/execute split in
    ``measured_call`` (it changes dispatch — one extra compile-cache-miss
    cost on first call — so benchmark steady-state timing keeps it off).
    """

    def __init__(self, *, measure_memory: bool = False):
        self.spans: list[Span] = []
        self.notes: list[dict] = []
        self.measure_memory = bool(measure_memory)
        self._open: list[str] = []

    # -- spans ------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, kind: str = "host", **extra):
        self._open.append(name)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            ms = (time.perf_counter() - t0) * 1e3
            self._open.pop()
            self.spans.append(Span(name=name, kind=kind, ms=ms, extra=extra))

    @property
    def current(self) -> str | None:
        """Innermost open span name (what was executing *right now*) —
        the SIGALRM handler of ``benchmarks.run`` reads this to attribute
        a timeout to the phase it interrupted."""
        return self._open[-1] if self._open else None

    def current_path(self) -> str | None:
        """Full open-span stack as ``outer/inner`` (None when idle)."""
        return "/".join(self._open) if self._open else None

    # -- notes ------------------------------------------------------------
    def note(self, name: str, **fields):
        self.notes.append({"name": name, **fields})

    def memory(self, name: str, nbytes: int, budget: int | None = None):
        """Record a compiled-memory watermark, optionally vs a budget."""
        rec: dict[str, Any] = {"bytes": int(nbytes)}
        if budget:
            rec["budget"] = int(budget)
            rec["frac"] = float(nbytes) / float(budget)
        self.note(f"memory.{name}", **rec)

    # -- aggregation ------------------------------------------------------
    def phase_fields(self) -> dict[str, dict]:
        """Aggregate spans by name -> {kind, ms, count} (benchmark-record
        payload: stable keys, summed durations)."""
        out: dict[str, dict] = {}
        for s in self.spans:
            slot = out.setdefault(s.name, {"kind": s.kind, "ms": 0.0, "count": 0})
            slot["ms"] += s.ms
            slot["count"] += 1
        for slot in out.values():
            slot["ms"] = round(slot["ms"], 3)
        return out

    def memory_fields(self) -> list[dict]:
        return [n for n in self.notes if n["name"].startswith("memory.")]


_CURRENT: contextvars.ContextVar[PhaseRecorder | None] = contextvars.ContextVar(
    "repro_obs_phase_recorder", default=None
)


def current_recorder() -> PhaseRecorder | None:
    return _CURRENT.get()


@contextlib.contextmanager
def use_recorder(rec: PhaseRecorder):
    tok = _CURRENT.set(rec)
    try:
        yield rec
    finally:
        _CURRENT.reset(tok)


@contextlib.contextmanager
def span(name: str, kind: str = "host", **extra):
    """Module-level span: records into the installed recorder, or no-ops."""
    rec = _CURRENT.get()
    if rec is None:
        yield
    else:
        with rec.span(name, kind, **extra):
            yield


def note(name: str, **fields):
    """Module-level note: records into the installed recorder, or no-ops."""
    rec = _CURRENT.get()
    if rec is not None:
        rec.note(name, **fields)


def measured_call(
    label: str,
    fn,
    args: tuple,
    kwargs: dict,
    *,
    dynamic_args: tuple,
    dynamic_kwargs: dict | None = None,
    budget: int | None = None,
):
    """Call a jitted ``fn``, splitting compile from execute when asked.

    Without an installed recorder (or with ``measure_memory`` off) this is
    exactly ``fn(*args, **kwargs)`` — identical dispatch, identical caching.
    With memory measurement on, the call is AOT-split: ``lower + compile``
    under a ``compile`` span (recording ``memory_analysis`` watermarks vs
    ``budget``), then the compiled artifact runs under an ``execute`` span
    with the *dynamic* args only (statics are baked into the program).
    """
    rec = _CURRENT.get()
    if rec is None:
        return fn(*args, **kwargs)
    if not rec.measure_memory:
        with rec.span(label, kind="execute"):
            return fn(*args, **kwargs)
    dynamic_kwargs = dynamic_kwargs or {}
    lower = getattr(fn, "lower", None)
    if lower is None:  # not a jitted callable: nothing to split
        rec.note(f"{label}.aot_fallback", error="callable has no .lower")
        with rec.span(label, kind="execute"):
            return fn(*args, **kwargs)
    # Compiler errors propagate: a program the backend refuses is a failure.
    with rec.span(f"{label}:compile", kind="compile"):
        compiled = lower(*args, **kwargs).compile()
    mem = compiled.memory_analysis()
    if mem is not None:
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes"):
            val = getattr(mem, attr, None)
            if val is not None:
                rec.memory(
                    f"{label}.{attr.split('_size')[0]}",
                    int(val),
                    budget=budget if attr == "temp_size_in_bytes" else None,
                )
    with rec.span(f"{label}:execute", kind="execute"):
        out = compiled(*dynamic_args, **dynamic_kwargs)
        import jax

        return jax.block_until_ready(out)
