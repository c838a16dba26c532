"""Transport subsystem: bucketed exchange buffers + compiled-program reuse.

SWIFT's communication is "just another task": data ships the moment it is
ready and consumers defer until it arrives. On an XLA device mesh the
equivalent discipline is that the *exchange program* must be compiled once
and reused for every sub-step, no matter how many cut-cell rows happen to be
active — recompiling per message size would serialise the whole ladder on
the compiler. This module provides the generic machinery for that:

* :func:`next_pow2` / :class:`BucketPolicy` — power-of-two bucket sizing
  with grow/shrink **hysteresis**: growth is immediate (correctness), but a
  bucket only shrinks after the demand has sat at half a bucket or less for
  ``shrink_patience`` consecutive fits. Demand oscillating around a
  power-of-two boundary therefore costs at most one recompile per crossing,
  not one per sub-step.
* :class:`CompileProbe` / :class:`ProgramCache` — the compile-count probe:
  every jitted program is registered by name, and ``total_compiles()``
  reports the true number of XLA compilations (via the jit cache), so tests
  can assert "at most one compile per (program, bucket)".
* :class:`ShipSlots` + :func:`pack_rounds` / :func:`pack_allgather` — the
  host-side image of one exchange: which (source row → destination row)
  copies each rank-to-rank edge carries, packed into bucket-padded index
  tables for the device program.
* :class:`HostTransport` — the host-mediated wire (numpy row copies between
  the ranks' jitted phase programs); the reference semantics every
  device-collective lowering must reproduce bit-for-bit.
* :class:`TransferProbe` / :class:`ResidentBuffers` — the residency layer:
  per-field accounting of every byte the engine moves across the
  host↔device boundary (split into cycle-*boundary* traffic — scatter and
  gather — and *intra-cycle* traffic), and the named stacked device buffers
  the device-resident cycle scan keeps on the mesh for a segment. The
  transfer probe is the ``CompileProbe`` of the wire: tests assert the
  scan's intra-segment traffic is **empty**.
* :func:`make_transport` — factory over ``"host" | "collective"`` (the
  collective implementation lives in ``repro.sph.collectives``; imported
  lazily so this layer stays free of SPH specifics).
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax.numpy as jnp

from ..observability.tracer import NULL_TRACER

TRANSPORTS = ("host", "collective")
RESIDENCIES = ("host", "device")


def schedule_of(residency: str, schedule: Optional[str] = None) -> str:
    """Who drives a cycle's sub-step ladder, which the residency decides:
    the host loop for host-resident state, the device cycle scan for
    device-resident state. ``schedule``, where given, must agree."""
    want = "device" if residency == "device" else "host"
    if schedule is None or schedule == want:
        return want
    if schedule not in ("host", "device"):
        raise ValueError(f"schedule must be 'host' or 'device', "
                         f"got {schedule!r}")
    if schedule == "host":
        raise ValueError(
            "residency='device' always runs the device cycle scan: the "
            "host-scheduled fused sub-step ladder is gone, so "
            "schedule='host' needs residency='host'")
    raise ValueError(
        "schedule='device' derives the sub-step schedule from the "
        "device-resident bins array; it requires residency='device'")


# the dynamical per-particle state of the time-bin engine: the arrays whose
# intra-cycle host↔device movement the device-resident cycle scan
# eliminates. ``bins`` is deliberately *not* here — it is the schedule
# (1 int32/particle), which the TransferProbe counts separately.
DYNAMIC_STATE_FIELDS = ("pos", "vel", "mass", "u", "h", "mask", "accel",
                        "dudt", "rho", "omega", "t_start", "time")


def next_pow2(n: int) -> int:
    """Smallest power of two ≥ max(n, 1)."""
    p = 1
    while p < max(int(n), 1):
        p *= 2
    return p


class BucketPolicy:
    """Per-stream power-of-two bucket sizing with grow/shrink hysteresis.

    ``fit(key, n)`` returns the bucket to pad stream ``key``'s current
    demand ``n`` to. Growth (n > bucket) snaps immediately to
    ``next_pow2(n)``. Shrinking is damped: only after ``shrink_patience``
    consecutive fits with ``next_pow2(n) ≤ bucket / 2`` does the bucket
    halve (one level per event, so a demand collapse walks down one
    power of two at a time). The result: each power-of-two crossing of the
    demand costs at most one bucket change — and therefore at most one
    compile of any program keyed by the bucket.
    """

    def __init__(self, *, min_bucket: int = 1, shrink_patience: int = 4):
        self.min_bucket = next_pow2(min_bucket)
        self.shrink_patience = int(shrink_patience)
        self._bucket: Dict[object, int] = {}
        self._below: Dict[object, int] = {}
        self.events: List[Tuple[object, int, int]] = []   # (key, old, new)

    def current(self, key) -> Optional[int]:
        return self._bucket.get(key)

    def fit(self, key, n: int) -> int:
        need = max(next_pow2(n), self.min_bucket)
        cur = self._bucket.get(key)
        if cur is None:
            self._bucket[key] = need
            self._below[key] = 0
            return need
        if need > cur:                                   # grow: immediate
            self.events.append((key, cur, need))
            self._bucket[key] = need
            self._below[key] = 0
            return need
        if need <= cur // 2:
            # need ≥ min_bucket, so the halved bucket is always legal
            # here — no separate floor guard, and at the floor itself
            # (cur == min_bucket) this branch can never be entered.
            self._below[key] = self._below[key] + 1
            if self._below[key] >= self.shrink_patience:
                new = cur // 2
                self.events.append((key, cur, new))
                self._bucket[key] = new
                # re-earn the patience at the new size: without this
                # reset, a stream sitting just under the *new* half-
                # bucket boundary would halve again on the very next
                # fit, churning one recompile per fit on a collapse.
                self._below[key] = 0
                return new
        else:
            self._below[key] = 0
        return self._bucket[key]


class _SignatureCountingProgram:
    """Fallback compile counter for callables without a jit cache.

    Wraps a program that exposes no ``_cache_size`` (not produced by
    ``jax.jit``, or an older/newer jax without that private hook) and
    counts the distinct flattened call signatures — pytree structure plus
    per-leaf (shape, dtype) — which is exactly the key a jit cache would
    compile per. The count is an upper bound on true compiles but, unlike
    the old silent ``-1``, it is monotone, non-negative, and agrees with
    the jit cache for shape-keyed programs.
    """

    __slots__ = ("_fn", "_signatures", "__wrapped__")

    def __init__(self, fn):
        self._fn = fn
        self.__wrapped__ = fn
        self._signatures = set()

    def __call__(self, *args, **kwargs):
        try:
            import jax
            leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
            sig = (treedef, tuple(
                (getattr(x, "shape", None),
                 str(getattr(x, "dtype", type(x).__name__)))
                for x in leaves))
            self._signatures.add(sig)
        except Exception:
            self._signatures.add(("<unflattenable>",))
        return self._fn(*args, **kwargs)

    def _cache_size(self) -> int:
        return len(self._signatures)


class CompileProbe:
    """Registry of jitted programs with true compile counts.

    ``register(name, fn)`` tracks a ``jax.jit``-wrapped callable;
    ``counts()`` reads each program's jit cache size — the number of
    distinct XLA compilations actually performed — so tests can assert the
    bucketing bounds recompiles without guessing from shapes. A callable
    without a jit cache is detected *at registration* and wrapped in a
    :class:`_SignatureCountingProgram` (with a :class:`RuntimeWarning`),
    so ``counts()`` never reports the old silent ``-1``.
    """

    def __init__(self):
        self._fns: Dict[str, object] = {}

    def register(self, name: str, fn):
        if not callable(getattr(fn, "_cache_size", None)):
            warnings.warn(
                f"compile probe: program {name!r} exposes no jit cache "
                "(_cache_size); counting distinct call signatures instead — "
                "compile counts for this program are an upper bound",
                RuntimeWarning, stacklevel=2)
            fn = _SignatureCountingProgram(fn)
        self._fns[name] = fn
        return fn

    def counts(self) -> Dict[str, int]:
        return {name: int(fn._cache_size()) for name, fn in self._fns.items()}

    def total_compiles(self) -> int:
        return sum(max(c, 0) for c in self.counts().values())


class ProgramCache:
    """Build-once cache of compiled exchange programs, keyed by the static
    exchange signature (bucket, rounds, field shapes). Each build is
    registered with the probe so its XLA compiles are counted."""

    def __init__(self, probe: Optional[CompileProbe] = None):
        self.probe = probe or CompileProbe()
        self._programs: Dict[object, Callable] = {}
        self.builds = 0

    def get(self, key, builder: Callable[[], Callable]) -> Callable:
        if key not in self._programs:
            prog = builder()
            self.probe.register(f"program:{key}", prog)
            self._programs[key] = prog
            self.builds += 1
        return self._programs[key]

    @property
    def keys(self):
        return set(self._programs)


class TransferProbe:
    """Host↔device transfer accounting, CompileProbe-style.

    Every byte the engine moves across the host boundary is ``record``-ed
    under a field name, tagged as cycle-``boundary`` traffic (the scatter at
    cycle start / gather at cycle end) or intra-cycle traffic. Tests assert
    the residency discipline on the *measured* ledger instead of trusting
    the control flow: the device-resident cycle scan must show no
    intra-cycle bytes at all, none for any :data:`DYNAMIC_STATE_FIELDS`
    entry in particular.
    """

    def __init__(self):
        self.boundary_bytes: Dict[str, int] = {}
        self.intra_bytes: Dict[str, int] = {}
        self.intra_events: Dict[str, int] = {}
        self.boundary_events: Dict[str, int] = {}

    def record(self, fname: str, nbytes: int, *, boundary: bool) -> None:
        book = self.boundary_bytes if boundary else self.intra_bytes
        book[fname] = book.get(fname, 0) + int(nbytes)
        events = self.boundary_events if boundary else self.intra_events
        events[fname] = events.get(fname, 0) + 1

    def intra_state_bytes(
            self, fields: Sequence[str] = DYNAMIC_STATE_FIELDS) -> int:
        """Intra-cycle bytes of dynamical state — 0 on the resident path."""
        return sum(self.intra_bytes.get(f, 0) for f in fields)

    def total_bytes(self) -> int:
        return (sum(self.boundary_bytes.values())
                + sum(self.intra_bytes.values()))

    def stats(self) -> Dict[str, object]:
        return {"boundary_bytes": dict(self.boundary_bytes),
                "boundary_events": dict(self.boundary_events),
                "intra_bytes": dict(self.intra_bytes),
                "intra_state_bytes": self.intra_state_bytes(),
                "total_bytes": self.total_bytes()}


class ResidentBuffers:
    """Named stacked device buffers of the device-resident cycle scan.

    Holds one ``(nranks, …)`` mesh-sharded array per state field for the
    duration of a cycle. The only mutation path is :meth:`update` with the
    outputs of a compiled program (a device→device handoff, no transfer);
    host access goes through :meth:`put` / :meth:`pull`, which record their
    bytes with the :class:`TransferProbe` — so the ledger is complete by
    construction as long as the engine never touches ``arrays`` directly.
    """

    def __init__(self, probe: TransferProbe):
        self.probe = probe
        self.arrays: Dict[str, object] = {}

    def put(self, name: str, host_array: np.ndarray, place: Callable,
            *, boundary: bool = True) -> None:
        """Upload a host array through ``place`` (e.g. a device_put with a
        mesh sharding) and record the bytes."""
        self.probe.record(name, host_array.nbytes, boundary=boundary)
        self.arrays[name] = place(host_array)

    def pull(self, name: str, *, boundary: bool = True,
             index: Optional[object] = None) -> np.ndarray:
        """Materialise a buffer (or an indexed slice of it) on host —
        pull only what the caller consumes; the ledger records the
        actually-transferred bytes."""
        arr = self.arrays[name]
        out = np.asarray(arr if index is None else arr[index])
        self.probe.record(name, out.nbytes, boundary=boundary)
        return out

    def update(self, mapping: Dict[str, object]) -> None:
        """Adopt compiled-program outputs (stays on device: no transfer)."""
        self.arrays.update(mapping)

    def __getitem__(self, name: str):
        return self.arrays[name]


# ---------------------------------------------------------------- ship slots
@dataclass
class ShipSlots:
    """One exchange's copies, grouped by rank-to-rank edge.

    ``edges[(src, dst)]`` lists (src_row, dst_row) pairs: the source rank's
    extended-state row to read and the destination rank's row to overwrite.
    Rows are unique per destination (each replica row has one owner), so
    copy order is irrelevant.
    """
    edges: Dict[Tuple[int, int], List[Tuple[int, int]]] = \
        field(default_factory=dict)

    def add(self, src: int, dst: int, src_row: int, dst_row: int) -> None:
        self.edges.setdefault((src, dst), []).append((src_row, dst_row))

    @property
    def total(self) -> int:
        return sum(len(v) for v in self.edges.values())

    @property
    def max_edge_slots(self) -> int:
        return max((len(v) for v in self.edges.values()), default=0)

    def max_rank_exports(self, nranks: int) -> int:
        out = [0] * nranks
        for (s, _d), v in self.edges.items():
            out[s] += len(v)
        return max(out, default=0)

    def max_rank_imports(self, nranks: int) -> int:
        out = [0] * nranks
        for (_s, d), v in self.edges.items():
            out[d] += len(v)
        return max(out, default=0)


def pack_rounds(rounds: Sequence[Sequence[Tuple[int, int]]],
                slots: ShipSlots, nranks: int, bucket: int
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Bucket-padded index tables for a ppermute-rounds exchange.

    Returns ``(pack_rows, unpack_rows, unpack_valid)``, each
    ``(nranks, R, bucket)``: in round ``t`` rank ``r`` sends the rows
    ``pack_rows[r, t]`` (0-padded) and, if it is the round's destination,
    writes the received slots ``k`` with ``unpack_valid[r, t, k] > 0`` into
    rows ``unpack_rows[r, t, k]``. Each round is a partial permutation
    (``core.comm_planner.ppermute_rounds``), so sender and receiver agree on
    slot order by construction.
    """
    scheduled = {e for rnd in rounds for e in rnd}
    missing = set(slots.edges) - scheduled
    if missing:
        raise ValueError(
            f"ship slots on edges {sorted(missing)} absent from the round "
            f"schedule — transport.prepare() did not run for this plan")
    R = max(len(rounds), 1)
    pack = np.zeros((nranks, R, bucket), dtype=np.int32)
    unpack = np.zeros((nranks, R, bucket), dtype=np.int32)
    valid = np.zeros((nranks, R, bucket), dtype=np.float32)
    for t, rnd in enumerate(rounds):
        for (s, d) in rnd:
            pairs = slots.edges.get((s, d), ())
            if len(pairs) > bucket:
                raise ValueError(
                    f"edge ({s}->{d}) ships {len(pairs)} rows > bucket "
                    f"{bucket}")
            for k, (srow, drow) in enumerate(pairs):
                pack[s, t, k] = srow
                unpack[d, t, k] = drow
                valid[d, t, k] = 1.0
    return pack, unpack, valid


def pack_allgather(slots: ShipSlots, nranks: int, bucket_out: int,
                   bucket_in: int
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Bucket-padded index tables for the all-gather fallback.

    Every rank contributes one export buffer of ``bucket_out`` rows
    (``pack_rows``); after the gather each rank reads slot
    ``unpack_src[r, k]`` of the flattened ``(nranks * bucket_out)`` buffer
    into row ``unpack_rows[r, k]`` where ``unpack_valid[r, k] > 0``.
    """
    pack = np.zeros((nranks, bucket_out), dtype=np.int32)
    unpack_src = np.zeros((nranks, bucket_in), dtype=np.int32)
    unpack_rows = np.zeros((nranks, bucket_in), dtype=np.int32)
    valid = np.zeros((nranks, bucket_in), dtype=np.float32)
    out_n = [0] * nranks
    in_n = [0] * nranks
    for (s, d) in sorted(slots.edges):
        for (srow, drow) in slots.edges[(s, d)]:
            k = out_n[s]
            if k >= bucket_out:
                raise ValueError(
                    f"rank {s} exports {k + 1} rows > bucket {bucket_out}")
            pack[s, k] = srow
            out_n[s] += 1
            m = in_n[d]
            if m >= bucket_in:
                raise ValueError(
                    f"rank {d} imports {m + 1} rows > bucket {bucket_in}")
            unpack_src[d, m] = s * bucket_out + k
            unpack_rows[d, m] = drow
            valid[d, m] = 1.0
            in_n[d] += 1
    return pack, unpack_src, unpack_rows, valid


# ---------------------------------------------------------------- transports
class Transport:
    """One exchange step: owner rows → replica rows across ranks.

    ``fields`` is a list of per-rank array lists (``fields[f][r]`` has the
    extended row layout on rank ``r``); the returned structure is the same
    with the destination rows of every slot overwritten by the source rank's
    values, bit-for-bit. Implementations must be pure copies — all transport
    lowerings produce identical states by construction.
    """

    kind = "abstract"
    # observability hook: rebound to the run's tracer by the engine when
    # SimulationSpec(observe=True); an exchange is SWIFT's send/recv task
    # and shows up on every participating rank's timeline row
    tracer = NULL_TRACER

    def prepare(self, edges: Sequence[Tuple[int, int]]) -> None:
        """New decomposition: the rank-to-rank export edge list changed."""

    def exchange(self, slots: ShipSlots, fields: List[List],
                 stream: str = "substep",
                 label: Optional[str] = None) -> List[List]:
        """``stream`` names the demand stream for bucket sizing: exchanges
        with systematically different volumes (activity-restricted
        sub-steps vs the full-cut cycle sync) must not share a bucket, or
        the hysteresis would churn once per cycle. ``label`` names the
        traced span (e.g. ``"exchange1"``/``"exchange2"``) — engine
        position of this exchange in the sub-step, not its bucket
        stream."""
        raise NotImplementedError

    def stats(self) -> Dict[str, object]:
        return {"kind": self.kind}


class HostTransport(Transport):
    """Host-mediated wire: numpy row copies between jitted phase programs.

    ``host_bytes`` counts what this wire costs beyond the copies
    themselves: every exchanged field makes a device→host→device round
    trip of its *full* per-rank arrays (not just the shipped rows) — the
    overhead the device-resident cycle scan exists to eliminate.
    """

    kind = "host"

    def __init__(self):
        self.host_bytes = 0
        self.exchanges = 0

    def exchange(self, slots: ShipSlots, fields: List[List],
                 stream: str = "substep",
                 label: Optional[str] = None) -> List[List]:
        nranks = max(len(f) for f in fields)
        with self.tracer.span(label or "exchange", ranks=range(nranks),
                              stream=stream, units=slots.total, kind="host",
                              collective=1):
            arrays = [[np.array(fr) for fr in f] for f in fields]
            self.host_bytes += 2 * sum(a.nbytes for f in arrays for a in f)
            self.exchanges += 1
            for (s, d), pairs in slots.edges.items():
                for (srow, drow) in pairs:
                    for f in range(len(arrays)):
                        arrays[f][d][drow] = arrays[f][s][srow]
            return [[jnp.asarray(arrays[f][r]) for r in range(nranks)]
                    for f in range(len(arrays))]

    def stats(self) -> Dict[str, object]:
        return {"kind": self.kind, "exchanges": self.exchanges,
                "host_bytes": self.host_bytes}


def make_transport(kind: str, *, nranks: int,
                   probe: Optional[CompileProbe] = None,
                   mode: str = "auto") -> Transport:
    """Build a transport: ``"host"`` (numpy copies) or ``"collective"``
    (shard_map + ppermute/all_gather over bucketed buffers; needs
    ``nranks`` addressable devices)."""
    if kind == "host":
        return HostTransport()
    if kind == "collective":
        from ..sph.collectives import CollectiveTransport
        return CollectiveTransport(nranks=nranks, probe=probe, mode=mode)
    raise ValueError(f"transport must be one of {TRANSPORTS}, got {kind!r}")
