"""Low-overhead host-side span tracer: SWIFT's per-task tic/toc for XLA.

SWIFT instruments every task with per-core tic/toc timestamps and reads the
resulting task-timeline plots to find load imbalance and dead time
(arXiv:1606.02738 §4). On an XLA substrate the "task" is a phase program
dispatch, and the complication is asynchrony: a jitted call returns before
the device work finishes, so a naive ``perf_counter`` pair times the
*dispatch*, not the work. Host-scheduled ladders therefore pair spans with
explicit :meth:`Tracer.fence` calls (``jax.block_until_ready`` — only when
tracing is enabled) so device work is attributed to the phase that launched
it. The observer effect is the fence itself: tracing serialises dispatch
against completion, which is exactly what a task plot needs and exactly
what a production run doesn't. The device-scheduled path never fences: its
spans time the host's own phases, and the one span in which the host waits
on the device (``wait``) is where it already blocks. Either way tracing
changes *no computed value* and triggers *no extra compiles* (asserted in
``tests/test_observability.py``).

Design constraints:

* **Disabled must be free.** Engines are instrumented unconditionally and
  hold :data:`NULL_TRACER` by default; its ``span()`` returns one shared
  no-op context manager (no allocation, no clock read, no annotation) and
  ``fence()`` is a pass. The enabled path is a clock read, a profiler
  annotation and a NamedTuple append per span (< 5 µs median, asserted).
* **One clock for both timelines.** An enabled span also opens
  ``jax.profiler.TraceAnnotation("engine:<name>")``, so any profile of the
  program shows its phases on the host line, nested over the runtime's
  events and on the device trace's clock; the recorded :class:`Span` keeps
  the ``perf_counter`` interval for the Chrome-trace export and the
  observer.
* **Spans carry task attrs**, SWIFT-style: rank, cycle, sub-step, time-bin
  level, pair bucket, live pair count, active-particle fraction — whatever
  the call site knows. ``units`` is the conventional attr for the task's
  asymptotic work (live pairs, shipped slots), consumed by the
  measured-cost feedback into :class:`~repro.core.cost_model.CostModel`.
* **Collective phases appear on every participating rank's row**
  (``span(..., ranks=...)``) — one shard_map program is one task on each
  rank's timeline, like SWIFT's send/recv tasks on each core's row.

This module imports jax only on first use of an enabled span or a fence,
so the observability layer stays importable (and its CLI can set
``XLA_FLAGS``) before jax loads.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Union

# jax.profiler.TraceAnnotation, bound when the first enabled tracer is made
_TraceAnnotation = None


class Span(NamedTuple):
    """One closed tic/toc interval on one rank's timeline."""
    name: str
    rank: int
    t0: float                       # perf_counter seconds
    t1: float
    attrs: Optional[Dict[str, Any]]

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


class _ActiveSpan:
    """Context manager of one in-flight span.

    Also the ``timed()`` result: ``elapsed`` is always measured (the
    engines' ``stats["wall"]`` comes from it); the profiler annotation and
    the recording into the tracer happen only when one is attached. A span
    given ``ranks`` records its interval on each of those ranks' rows; a
    mapping of rank to attrs adds those attrs on that rank's row alone.
    """

    __slots__ = ("_tracer", "name", "rows", "attrs", "t0", "elapsed",
                 "_ann")

    def __init__(self, tracer: Optional["Tracer"], name: str,
                 rows: Dict[int, Optional[Dict[str, Any]]],
                 attrs: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self.name = name
        self.rows = rows            # rank -> attrs of that row alone
        self.attrs = attrs
        self.t0 = 0.0
        self.elapsed = 0.0
        self._ann = None

    def set(self, **attrs) -> None:
        """Add attrs known only inside the span (e.g. a bucket size)."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs.update(attrs)

    def __enter__(self) -> "_ActiveSpan":
        if self._tracer is not None:
            self._ann = _TraceAnnotation("engine:" + self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self.elapsed = t1 - self.t0
        tr = self._tracer
        if tr is not None:
            self._ann.__exit__(*exc)
            a = self.attrs
            for r, own in self.rows.items():
                tr._spans.append(Span(self.name, r, self.t0, t1,
                                      {**(a or {}), **own} if own else a))
        return False


class _NoopSpan:
    """The disabled-path context manager: shared, stateless, free."""

    __slots__ = ()
    elapsed = 0.0

    def set(self, **attrs) -> None:
        pass

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOOP_SPAN = _NoopSpan()


class Tracer:
    """Collects :class:`Span` records for one run (all ranks, one stream).

    ``t_origin`` anchors the run's timeline; exported traces report µs
    since this origin so per-rank rows line up in one Perfetto view.
    """

    enabled = True

    def __init__(self, t_origin: Optional[float] = None):
        global _TraceAnnotation
        if self.enabled and _TraceAnnotation is None:
            from jax.profiler import TraceAnnotation as _TraceAnnotation
        self._spans: List[Span] = []
        self.t_origin = (time.perf_counter() if t_origin is None
                         else float(t_origin))
        # ambient attrs merged into every span — engines park loop state
        # here (cycle, sub-step) so leaf call sites (e.g. a transport's
        # exchange) inherit it without plumbing arguments through layers
        self.ctx: Dict[str, Any] = {}

    def _merge(self, attrs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        if self.ctx:
            merged = dict(self.ctx)
            merged.update(attrs)
            return merged
        return attrs or None

    # ------------------------------------------------------------ recording
    def span(self, name: str, rank: int = 0,
             ranks: Union[Iterable[int], Dict[int, Dict], None] = None,
             **attrs) -> _ActiveSpan:
        """``with tracer.span("density", rank=r, units=npairs): ...``

        ``ranks=range(n)`` records the one interval on every listed rank's
        row instead of ``rank``'s — how one collective program (an
        exchange, a fused sub-step) shows up as a task on each rank's
        timeline. ``ranks={row: {"request_id": ...}}`` also gives each row
        attrs of its own."""
        if ranks is None:
            rows = {rank: None}
        elif isinstance(ranks, dict):
            rows = ranks
        else:
            rows = dict.fromkeys(ranks)
        return _ActiveSpan(self, name, rows, self._merge(attrs))

    def timed(self, name: str, rank: int = 0, **attrs) -> _ActiveSpan:
        """A span whose ``elapsed`` the caller consumes (wall-clock stats).

        On :data:`NULL_TRACER` this still measures — it is the one shared
        timing helper behind every quadrant's ``stats["wall"]``.
        """
        return _ActiveSpan(self, name, {rank: None}, self._merge(attrs))

    # -------------------------------------------------------------- fencing
    def fence(self, value: Any) -> Any:
        """``jax.block_until_ready`` — attribute in-flight device work to
        the enclosing span. No-op on :data:`NULL_TRACER`, so tracing-off
        keeps the engines' fully-asynchronous dispatch. Host-scheduled
        ladders only: the device-scheduled path never fences."""
        import jax
        return jax.block_until_ready(value)

    # -------------------------------------------------------------- reading
    @property
    def spans(self) -> List[Span]:
        return self._spans

    def clear(self) -> None:
        self._spans.clear()

    def ranks(self) -> List[int]:
        return sorted({s.rank for s in self._spans})


class NullTracer(Tracer):
    """The default, disabled tracer: recording is free, fencing is off."""

    enabled = False

    def __init__(self):
        super().__init__(t_origin=0.0)

    def span(self, name: str, rank: int = 0,
             ranks: Union[Iterable[int], Dict[int, Dict], None] = None,
             **attrs) -> _NoopSpan:
        return _NOOP_SPAN

    def timed(self, name: str, rank: int = 0, **attrs) -> _ActiveSpan:
        return _ActiveSpan(None, name, {}, None)

    def fence(self, value: Any) -> Any:
        return value


NULL_TRACER = NullTracer()
