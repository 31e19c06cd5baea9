"""Phase telemetry: timing spans, engine stage names, compile/execute
splits, memory watermarks.

Spans reach the profiler trace.  ``span`` (and ``PhaseRecorder.span``)
opens a ``jax.profiler.TraceAnnotation`` of the span's name, so a
``jax.profiler`` trace shows each span on the host timeline, beside the
device ops it dispatched; counters the span collects (its keyword
arguments, what the body adds to the dict it yields, and ``compiles``, the
executables compiled or loaded inside it) become the annotation's
arguments.  With neither a profiler running nor a recorder installed a
span costs a flag check.

``stage`` names a stage of the jitted engine with ``jax.named_scope``: the
name (``arb.<stage>``, one of ``STAGES``) lands in the ``op_name`` metadata
of every HLO op the stage lowers to, and from there on the device ops of a
profiler trace.  Scopes change metadata only, never results.  The
persistent compile cache's key strips that metadata by default, so an entry
compiled from another tree would be served with that tree's op names;
importing this module keys the cache on metadata as well.

A ``PhaseRecorder`` collects the same spans as ``Span`` records
(wall-clock segments tagged ``host``/``compile``/``execute``) plus
free-form notes (chunk plans, memory watermarks vs the 256 MB chunk
budget).  It is installed per-scope through a contextvar (``use_recorder``);
instrumented call sites — ``core.sweep``, ``fabric.bringup``,
``benchmarks.common.timed_steady`` — look it up with ``current_recorder()``
and record nothing into it when none is installed; the program's results
are the same either way.

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

import jax

__all__ = [
    "STAGES",
    "STAGE_PREFIX",
    "PhaseRecorder",
    "Span",
    "compile_count",
    "current_recorder",
    "measured_call",
    "note",
    "span",
    "stage",
    "use_recorder",
]

#: Prefix of every engine stage scope, which sets them apart from the name
#: stack parts JAX adds itself (``jit(...)``, ``while``, ``vmap(...)``).
STAGE_PREFIX = "arb."

#: The engine's stages, by where they sit:
#:   instantiate  ``instantiate`` of a system batch (``core/api.py``)
#:   ideal        ideal-policy scoring (``_ideal_success``/``_ideal_min_tr``)
#:   matching     bipartite matching entries (``core/matching.py``, kernel ops)
#:   table_build  search-table build (``_build_tables``)
#:   arbitrate    a scheme's arbiter (relation search + SSM, seq, protocol)
#:   classify     outcome classification and the AFP/CAFP/min-TR reductions
#:   chunk_loop   ``chunked_map``'s pad, reshape and ``lax.map`` plumbing
STAGES = ("instantiate", "ideal", "matching", "table_build", "arbitrate",
          "classify", "chunk_loop")

# Stage names live in op metadata, which the compile cache's key leaves out
# unless told otherwise.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = 0


def _on_duration(event: str, duration: float, **_) -> None:
    global _compiles
    if event == _COMPILE_EVENT:
        _compiles += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_count() -> int:
    """Executables compiled or loaded from the persistent cache so far in
    this process (one ``jax.monitoring`` listener counts them)."""
    return _compiles


def stage(name: str):
    """``jax.named_scope`` of the engine stage ``name`` (one of ``STAGES``)."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; stages: {STAGES}")
    return jax.named_scope(STAGE_PREFIX + name)


_TRACE_ME = jax.profiler.TraceAnnotation


class _Annotated:
    """A profiler annotation ``name`` around the body; on exit ``args`` and
    the count of compilations inside (``compiles``) become its arguments.
    A class, not a generator, for the dispatch path's sake."""

    __slots__ = ("_ann", "_args", "_compiles0")

    def __init__(self, name: str, args: dict):
        self._ann = _TRACE_ME(name)
        self._args = args

    def __enter__(self) -> dict:
        self._compiles0 = _compiles
        self._ann.__enter__()
        return self._args

    def __exit__(self, *exc) -> None:
        self._args["compiles"] = _compiles - self._compiles0
        if self._ann.is_enabled():
            self._ann.set_metadata(**self._args)
        self._ann.__exit__(*exc)


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
        """Time the body as a ``Span``, under a profiler annotation of the
        same name; yields the dict of counters (``extra``, whatever the body
        adds, and ``compiles``), kept as ``Span.extra`` and the annotation's
        arguments."""
        self._open.append(name)
        t0 = time.perf_counter()
        try:
            with _Annotated(name, extra) as args:
                yield args
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


def span(name: str, kind: str = "host", **extra):
    """Module-level span: a profiler annotation, recorded into the installed
    recorder as well.  A context manager yielding the span's counter dict
    (see ``PhaseRecorder.span``).  With neither a recorder nor a running
    profiler it only yields the dict: it sits on every request's path."""
    rec = _CURRENT.get()
    if rec is not None:
        return rec.span(name, kind, **extra)
    if not _TRACE_ME.is_enabled():   # nobody looks: no annotation to fill
        return contextlib.nullcontext(extra)
    return _Annotated(name, extra)


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
        return jax.block_until_ready(out)
