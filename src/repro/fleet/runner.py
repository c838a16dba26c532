"""Fleet runner: many simulations as one batched, signature-grouped program.

SWIFT's scheduling insight applied one level up: the unit of work is a whole
*simulation request*, and the hardware stays saturated by always dispatching
the largest ready batch of shape-compatible requests as ONE compiled
program. The pieces:

* **Batched entry points.** Requests in the ``("global", "local")``
  quadrant are served by a single jitted program per (signature,
  shape, batch-bucket): the engine's ``step`` vmapped over a new leading
  **fleet axis**, and — when the process has a device mesh — wrapped in
  ``shard_map`` over that axis, so a batch of B independent simulations
  shards B/ndev-per-device across the mesh with zero cross-device traffic.
  Per-request CFL time-steps ride along as a ``(B,)`` vector. Entry points
  live in a :class:`~repro.distributed.transport.ProgramCache` and their
  compile counts are ledgered by :class:`CompileProbe` — at most one XLA
  compile per (signature, shape, bucket), no matter how arrival sizes
  wobble (the batcher's no-shrink buckets).
* **Lockstep semantics = sequential semantics.** Batched execution mirrors
  the single-run engine exactly: same eager per-member init, same host
  re-binning cadence (``rebin_every``), same CFL policy — so each lane of
  a vmapped batch (``fleet_devices=1``) is **bitwise identical** to the
  same spec run alone (``tests/test_fleet.py``). Sharding the fleet axis
  across devices keeps the math but not the bits: per-device SPMD
  partitioning reassociates the pair-sum reductions, so the sharded path's
  contract is ulp-level (``allclose``), asserted with a tight tolerance.
  A lane whose cell capacity diverges mid-run (rare re-bin overflow) falls
  off the batch and finishes sequentially; correctness is never traded for
  batching.
* **Sequential fallback.** Quadrants whose host control flow is
  data-dependent per request (time-bin ladders, distributed backends) are
  served one-by-one but still signature-grouped: the engine layer's shared
  jit programs (``engine.shared_step_program`` /
  ``timebins.shared_timebin_programs``) make N same-signature requests
  cost one compile, not N.
* **Pooled result transfers.** Finished lanes are pulled through a
  :class:`TransferBufferPool` (the SHARK-Engine idiom): bounded, reused
  host buffers per (shape, dtype) instead of a fresh allocation per
  request result.
* **Per-request tracing.** With ``observe=True`` every dispatch is
  recorded on each member request's own timeline row with a
  ``request_id`` attr, so one fleet trace shows every user's run on the
  shared Perfetto timeline (``export_trace``).
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..distributed.transport import CompileProbe, ProgramCache
from ..observability.tracer import NULL_TRACER, Tracer
from ..sph.api import SimulationSpec, build_simulation, make_ic
from .batcher import Batch, SignatureBatcher
from .queue import FleetRequest, FleetResult, RequestQueue, RequestState


# ------------------------------------------------------------- result pool
class TransferBufferPool:
    """Reusable host buffers for device→host result pulls.

    ``take(src)`` copies a device (or host) array into a pooled numpy
    buffer of matching (shape, dtype), allocating only on pool miss;
    ``give(buf)`` returns a buffer to its bucket. Serving keeps result
    memory bounded by the number of *inflight* results, not the number of
    requests ever served.
    """

    def __init__(self):
        self._free: Dict[Tuple[tuple, str], List[np.ndarray]] = {}
        self.hits = 0
        self.misses = 0

    def take(self, src) -> np.ndarray:
        a = np.asarray(src)
        key = (a.shape, str(a.dtype))
        bucket = self._free.get(key)
        if bucket:
            buf = bucket.pop()
            self.hits += 1
        else:
            buf = np.empty(a.shape, a.dtype)
            self.misses += 1
        np.copyto(buf, a)
        return buf

    def give(self, buf: np.ndarray) -> None:
        self._free.setdefault((buf.shape, str(buf.dtype)), []).append(buf)

    def stats(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "resident": sum(len(v) for v in self._free.values())}


# ---------------------------------------------------------- batched members
@dataclass(eq=False)
class _Member:
    """One request's host-side engine bookkeeping inside a batch."""
    req: FleetRequest
    box: tuple                      # the cube's edge, thrice
    n: int
    gspec: Any
    cells: Any
    pairs: Any
    perm: np.ndarray
    state: Any                      # SPHState (host-side numpy leaves ok)
    steps_done: int = 0
    steps_since_rebin: int = 0
    done: bool = False

    @property
    def shape_key(self) -> tuple:
        return (self.gspec.dims, self.cells.mass.shape[1],
                self.box, int(np.asarray(self.pairs.ci).shape[0]))


def _build_member(req: FleetRequest) -> _Member:
    """Host-side admission of one request: IC → grid → cells → initial
    state, exactly the single-run engine's construction path (eager
    ``init_state`` so lane 0 of a batch is bitwise the single run)."""
    from ..sph.cellgrid import (bin_particles, box_lengths, build_pair_list,
                                choose_grid)
    from ..sph.engine import init_state
    spec = req.spec
    ic = make_ic(spec.scenario, **dict(spec.scenario_params))
    if np.ndim(ic["box"]) != 0:
        raise ValueError(
            f"the fleet batches cubic boxes of one edge length; scenario "
            f"{spec.scenario!r} has a box of three, {tuple(ic['box'])!r}: "
            f"run it through build_simulation")
    box = box_lengths(ic["box"])
    n = len(ic["pos"])
    gspec = choose_grid(box, float(np.max(ic["h"])), n,
                        capacity_margin=spec.capacity_margin)
    cells, perm = bin_particles(gspec, np.asarray(ic["pos"]),
                                np.asarray(ic["vel"]), np.asarray(ic["mass"]),
                                np.asarray(ic["u"]), np.asarray(ic["h"]))
    if cells.mass.shape[1] != gspec.capacity:
        object.__setattr__(gspec, "capacity", cells.mass.shape[1])
    pairs = build_pair_list(gspec)
    state = init_state(cells, pairs, spec.physics)
    return _Member(req=req, box=box, n=n, gspec=gspec, cells=cells,
                   pairs=pairs, perm=perm, state=state)


def _rebin_member(m: _Member) -> None:
    """The engine's host re-bin: unbin → re-bin → fresh eager init."""
    from ..sph.cellgrid import bin_particles, build_pair_list, unbin
    from ..sph.engine import init_state
    flat = unbin(m.state.cells, m.perm, m.n)
    m.cells, m.perm = bin_particles(m.gspec, flat["pos"], flat["vel"],
                                    flat["mass"], flat["u"], flat["h"])
    if m.cells.mass.shape[1] != m.gspec.capacity:
        object.__setattr__(m.gspec, "capacity", m.cells.mass.shape[1])
    m.pairs = build_pair_list(m.gspec)
    fresh = init_state(m.cells, m.pairs, m.req.spec.physics)
    m.state = fresh._replace(time=m.state.time)
    m.steps_since_rebin = 0


def _flat_result(state_cells, perm: np.ndarray, n: int, time: float,
                 steps: int, wall: float, *, batched: bool,
                 batch_size: int = 1, bucket: int = 1,
                 pool: Optional[TransferBufferPool] = None) -> FleetResult:
    """Final state → user-facing flat particle arrays + host diagnostics."""
    from ..sph.cellgrid import unbin
    flat = unbin(state_cells, perm, n)
    if pool is not None:
        flat = {k: (pool.take(v) if isinstance(v, np.ndarray) else v)
                for k, v in flat.items()}
    m = flat["mass"]
    v = flat["vel"]
    ke = 0.5 * float(np.sum(m * np.sum(v * v, axis=-1)))
    ie = float(np.sum(m * flat["u"]))
    mom = np.sum(m[:, None] * v, axis=0)
    return FleetResult(particles=flat, energy=ke + ie, momentum=mom,
                       t=float(time), steps=steps, wall=wall,
                       batched=batched, batch_size=batch_size, bucket=bucket)


# ------------------------------------------------------------------ runner
class FleetRunner:
    """Request-driven serving loop over signature-grouped batches."""

    def __init__(self, *, max_batch: int = 64, max_inflight: int = 1024,
                 fleet_devices: Optional[int] = None, observe: bool = False,
                 flight_dir: Optional[str] = None):
        import jax
        if fleet_devices is None:
            ndev = len(jax.devices())
            # the fleet axis must divide every power-of-two bucket
            fleet_devices = ndev if ndev & (ndev - 1) == 0 else 1
        self.fleet_devices = int(fleet_devices)
        self.queue = RequestQueue(max_inflight=max_inflight)
        self.batcher = SignatureBatcher(max_batch=max_batch,
                                        min_bucket=self.fleet_devices)
        self.probe = CompileProbe()
        self.programs = ProgramCache(self.probe)
        self.pool = TransferBufferPool()
        self.tracer: Tracer = Tracer() if observe else NULL_TRACER
        self.row_names: Dict[int, str] = {}
        self.batches_run = 0
        self.sequential_runs = 0
        self.particle_steps = 0         # Σ particles × steps actually served
        # per-request terminal-status counter: every request the runner
        # retires lands here exactly once (done/failed/expired) — the
        # metric that makes dead lanes visible, not just absent
        self.terminal_status: Dict[str, int] = {}
        # where expired-sweep post-mortem bundles go (None = no dumps)
        self.flight_dir = flight_dir
        self.flight_dumps: List[str] = []

    # ----------------------------------------------------------- frontend
    def submit(self, spec: SimulationSpec, *, n_steps: int = 1,
               deadline: Optional[float] = None,
               request_id: Optional[str] = None,
               callback: Optional[Callable[[FleetRequest], None]] = None
               ) -> FleetRequest:
        # visible sweep before admission: expired requests get their
        # terminal count / timeline span / flight bundle here, not only
        # when a later drain() claims (queue.submit also sweeps, but this
        # runs first so the runner's accounting sees every expiry)
        self._sweep_expired(self.queue.expire())
        req = self.queue.submit(spec, n_steps=n_steps, deadline=deadline,
                                request_id=request_id, callback=callback)
        self.row_names[req.row] = req.request_id
        return req

    def poll(self) -> Dict[str, Any]:
        """Deadline sweep + fleet stats without claiming any work."""
        self._sweep_expired(self.queue.expire())
        return self.stats()

    def drain(self) -> List[FleetRequest]:
        """Serve until the queue is empty; returns the finished requests.

        The deadline sweep runs *visibly*: expired requests get a terminal
        status count, a zero-length ``expired`` span on their own timeline
        row, and (when ``flight_dir`` is set) a post-mortem bundle — a
        dead lane must show up in the metrics, not just go missing."""
        served: List[FleetRequest] = []
        while True:
            self._sweep_expired(self.queue.expire())
            ready = self.queue.take_ready()
            if not ready:
                break
            for batch in self.batcher.form(ready):
                self._run_batch(batch)
                served.extend(batch.requests)
                for r in batch.requests:
                    self._count_terminal(r)
        return served

    def _count_terminal(self, req: FleetRequest) -> None:
        key = req.state.value
        self.terminal_status[key] = self.terminal_status.get(key, 0) + 1

    def _sweep_expired(self, expired: List[FleetRequest]) -> None:
        if not expired:
            return
        tr = self.tracer
        for r in expired:
            self._count_terminal(r)
            if tr.enabled:
                # an instant marker on the request's row
                with tr.span("expired", rank=r.row, request_id=r.request_id,
                             deadline=r.deadline, error=str(r.error)):
                    pass
        if self.flight_dir is not None:
            from ..observability.flight import FlightRecorder
            path = FlightRecorder().dump(
                self.flight_dir,
                reason=f"expired-{expired[0].request_id}",
                cycle=self.batches_run,
                spans=self.tracer.spans[-256:],
                row_names=self.row_names,
                extra={"expired": [r.request_id for r in expired]})
            self.flight_dumps.append(path)

    # ---------------------------------------------------------- dispatch
    def _run_batch(self, batch: Batch) -> None:
        spec = batch.requests[0].spec
        quadrant = (spec.integrator, spec.backend)
        try:
            if quadrant == ("global", "local") and not spec.physics.use_pallas:
                self._run_batched_global(batch)
            else:
                self._run_sequential(batch)
        except Exception as e:
            for r in batch.requests:
                if r.state is RequestState.RUNNING:
                    self.queue.fail(r, e)
            raise
        finally:
            self.batches_run += 1

    # ----------------------------------------------- batched global×local
    def _ndev_for(self, bucket: int) -> int:
        """Devices the fleet axis shards over for this bucket (1 = vmap)."""
        if bucket % self.fleet_devices == 0 and bucket >= self.fleet_devices:
            return self.fleet_devices
        return 1

    def _shard_fleet(self, tree, ndev: int):
        """Pin the stacked state to the fleet-axis sharding the entry
        points expect — from the *first* call, so a state that stays
        device-resident between steps (rebin_every > 1) presents one input
        sharding to the jit cache, not unsharded-then-sharded (which would
        compile every program twice)."""
        if ndev <= 1:
            return tree
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        from ..distributed.mesh_utils import ranks_mesh
        mesh = ranks_mesh(ndev, axis="fleet")
        return jax.device_put(tree, NamedSharding(mesh, P("fleet")))

    def _entry_points(self, sig_key: str, shape_key: tuple, bucket: int,
                      spec: SimulationSpec):
        """(step, cfl) programs for one (signature, shape, bucket) cell."""
        import jax
        import jax.numpy as jnp
        from ..sph.engine import cfl_timestep_particles, step
        ndev = self._ndev_for(bucket)
        box = shape_key[2]
        cfg = spec.physics

        def build_step():
            f = jax.vmap(functools.partial(step, box=box, cfg=cfg),
                         in_axes=(0, None, 0))
            if ndev > 1:
                from jax import shard_map
                from jax.sharding import PartitionSpec as P
                from ..distributed.mesh_utils import ranks_mesh
                mesh = ranks_mesh(ndev, axis="fleet")
                f = shard_map(f, mesh=mesh,
                              in_specs=(P("fleet"), P(), P("fleet")),
                              out_specs=P("fleet"))
            return jax.jit(f)

        def build_cfl():
            def one(state):
                return jnp.min(cfl_timestep_particles(state, cfg))
            f = jax.vmap(one)
            if ndev > 1:
                from jax import shard_map
                from jax.sharding import PartitionSpec as P
                from ..distributed.mesh_utils import ranks_mesh
                mesh = ranks_mesh(ndev, axis="fleet")
                f = shard_map(f, mesh=mesh, in_specs=(P("fleet"),),
                              out_specs=P("fleet"))
            return jax.jit(f)

        step_fn = self.programs.get(
            ("fleet_step", sig_key, shape_key, bucket, ndev), build_step)
        cfl_fn = self.programs.get(
            ("fleet_cfl", sig_key, shape_key, bucket, ndev), build_cfl)
        return step_fn, cfl_fn

    def _run_batched_global(self, batch: Batch) -> None:
        """Serve a ("global", "local") batch as one vmapped/sharded program.

        Splits by concrete shape key (members whose grid/capacity differ
        cannot stack); each shape group gets its own bucket from the
        no-shrink policy and its own cached entry points.
        """
        members = [_build_member(r) for r in batch.requests]
        groups: Dict[tuple, List[_Member]] = {}
        for m in members:
            groups.setdefault(m.shape_key, []).append(m)
        for shape_key, group in groups.items():
            if len(groups) == 1:
                bucket = batch.bucket            # the batcher's sizing holds
            else:
                bucket = self.batcher.policy.fit(
                    (batch.signature_key, shape_key), len(group))
            self._run_shape_group(batch.signature_key, shape_key, bucket,
                                  group)

    def _stack(self, group: List[_Member], bucket: int):
        """Members' states → one stacked pytree with a leading fleet axis
        (padding lanes replicate member 0; their outputs are discarded)."""
        import jax
        import jax.numpy as jnp
        idx = list(range(len(group))) + [0] * (bucket - len(group))
        return jax.tree_util.tree_map(
            lambda *leaves: jnp.stack([jnp.asarray(leaves[i]) for i in idx]),
            *[m.state for m in group])

    def _run_shape_group(self, sig_key: str, shape_key: tuple, bucket: int,
                         group: List[_Member]) -> None:
        import jax
        import jax.numpy as jnp
        tr = self.tracer
        spec = group[0].req.spec
        step_fn, cfl_fn = self._entry_points(sig_key, shape_key, bucket, spec)
        ndev = self._ndev_for(bucket)
        stacked = self._shard_fleet(self._stack(group, bucket), ndev)
        pairs = group[0].pairs
        max_steps = max(m.req.n_steps for m in group)
        t_start = time.perf_counter()
        for n in range(max_steps):
            # one batched program: a task on every live request's row
            rows = ({m.req.row: {"request_id": m.req.request_id}
                     for m in group if not m.done} if tr.enabled else None)
            with tr.span("fleet_step", ranks=rows,
                         signature=sig_key, step=n, batch=len(group),
                         bucket=bucket):
                if spec.dt is not None:
                    dts = jnp.full((bucket,), float(spec.dt),
                                   stacked.cells.pos.dtype)
                else:
                    dts = cfl_fn(stacked).astype(stacked.cells.pos.dtype)
                stacked = step_fn(stacked, pairs, dts)
                tr.fence(stacked.cells.pos)
            self.particle_steps += sum(m.n for m in group if not m.done)
            # lockstep host bookkeeping, mirroring engine.Simulation.run
            finish, rebin = [], False
            for i, m in enumerate(group):
                if m.done:
                    continue
                m.steps_done += 1
                m.steps_since_rebin += 1
                if m.steps_done >= m.req.n_steps:
                    finish.append(i)
                elif m.steps_since_rebin >= m.req.spec.rebin_every:
                    rebin = True
            if finish or (rebin and n < max_steps - 1):
                # pull lanes to host once; finish and/or re-bin from it
                host = jax.tree_util.tree_map(np.asarray, stacked)
                for i in finish:
                    m = group[i]
                    m.done = True
                    lane = jax.tree_util.tree_map(lambda a, i=i: a[i], host)
                    wall = time.perf_counter() - t_start
                    res = _flat_result(
                        lane.cells, m.perm, m.n, lane.time, m.steps_done,
                        wall, batched=True, batch_size=len(group),
                        bucket=bucket, pool=self.pool)
                    self.queue.complete(m.req, res)
                if rebin and n < max_steps - 1:
                    for i, m in enumerate(group):
                        if m.done:
                            continue
                        m.state = jax.tree_util.tree_map(
                            lambda a, i=i: a[i], host)
                        if m.steps_since_rebin >= m.req.spec.rebin_every:
                            _rebin_member(m)
                            if m.shape_key != shape_key:
                                # capacity grew: this lane can no longer
                                # stack — finish it off-batch, correctness
                                # over batching
                                self._finish_member_sequentially(m)
                    if any(not m.done for m in group):
                        stacked = self._shard_fleet(
                            self._stack_mixed(group, bucket), ndev)
            if all(m.done for m in group):
                break

    def _stack_mixed(self, group: List[_Member], bucket: int):
        """Re-stack after a host pull/re-bin: live lanes carry their member
        state (re-binned, or as pulled), done/fallen lanes pad with a live
        lane's state (their outputs are never read again)."""
        import jax
        import jax.numpy as jnp
        states = [None if m.done else m.state for m in group]
        anchor = next(s for s in states if s is not None)
        lanes = [s if s is not None else anchor for s in states]
        lanes += [anchor] * (bucket - len(lanes))
        return jax.tree_util.tree_map(
            lambda *leaves: jnp.stack([jnp.asarray(x) for x in leaves]),
            *lanes)

    def _finish_member_sequentially(self, m: _Member) -> None:
        """A lane that fell off its batch (shape divergence) finishes on the
        shared single-run engine path from its current exact state."""
        import jax.numpy as jnp
        from ..sph.engine import cfl_timestep, shared_step_program
        tr = self.tracer
        spec = m.req.spec
        step_fn = shared_step_program(m.box, spec.physics)
        t_start = time.perf_counter()
        while m.steps_done < m.req.n_steps:
            if spec.dt is not None:
                dt = float(spec.dt)
            else:
                dt = float(cfl_timestep(m.state, spec.physics))
            with tr.span("fleet_step", rank=m.req.row,
                         request_id=m.req.request_id, sequential=1):
                m.state = step_fn(m.state, m.pairs,
                                  jnp.asarray(dt, m.state.cells.pos.dtype))
                tr.fence(m.state.cells.pos)
            m.steps_done += 1
            m.steps_since_rebin += 1
            self.particle_steps += m.n
            if m.steps_since_rebin >= spec.rebin_every \
                    and m.steps_done < m.req.n_steps:
                _rebin_member(m)
        m.done = True
        self.sequential_runs += 1
        res = _flat_result(m.state.cells, m.perm, m.n, m.state.time,
                           m.steps_done, time.perf_counter() - t_start,
                           batched=False, pool=self.pool)
        self.queue.complete(m.req, res)

    # -------------------------------------------------- sequential fallback
    def _run_sequential(self, batch: Batch) -> None:
        """Quadrants without a batched lowering (time-bin ladders,
        distributed backends): serve per request, signature-grouped so the
        shared engine programs compile once for the whole group."""
        tr = self.tracer
        for req in batch.requests:
            t_start = time.perf_counter()
            try:
                with tr.span("fleet_run", rank=req.row,
                             request_id=req.request_id,
                             signature=batch.signature_key,
                             quadrant=f"{req.spec.integrator}/"
                                      f"{req.spec.backend}"):
                    sim = build_simulation(req.spec)
                    for _ in range(req.n_steps):
                        sim.step()
                    res = self._sequential_result(
                        sim, req, time.perf_counter() - t_start)
            except Exception as e:
                self.queue.fail(req, e)
                continue
            self.sequential_runs += 1
            self.queue.complete(req, res)

    def _sequential_result(self, sim, req: FleetRequest,
                           wall: float) -> FleetResult:
        eng = getattr(sim, "engine", sim)
        state = getattr(eng, "state", None)
        cells = getattr(state, "cells", None)
        perm = getattr(eng, "perm", None)
        n = getattr(eng, "n", None)
        self.particle_steps += (n or 0) * req.n_steps
        if cells is not None and perm is not None and n is not None:
            return _flat_result(cells, perm, n, sim.time, req.n_steps, wall,
                                batched=False, pool=self.pool)
        e, p = sim.diagnostics()
        return FleetResult(particles={}, energy=e, momentum=p, t=sim.time,
                           steps=req.n_steps, wall=wall, batched=False)

    # ------------------------------------------------------------- reading
    def compile_counts(self) -> Dict[str, int]:
        return self.probe.counts()

    def assert_compile_discipline(self) -> None:
        """≤1 XLA compile per (signature, shape, bucket) entry point."""
        bad = {k: c for k, c in self.probe.counts().items() if c > 1}
        if bad:
            raise AssertionError(
                f"fleet entry points recompiled: {bad} — batch bucketing "
                f"or shape keying is leaking shapes")

    def stats(self) -> Dict[str, Any]:
        return {"queue": self.queue.stats(),
                "terminal_status": dict(self.terminal_status),
                "flight_dumps": list(self.flight_dumps),
                "batches": self.batches_run,
                "sequential_runs": self.sequential_runs,
                "particle_steps": self.particle_steps,
                "programs": len(self.programs.keys),
                "compiles": self.probe.total_compiles(),
                "buckets": dict(self.batcher.policy._bucket),
                "pool": self.pool.stats(),
                "fleet_devices": self.fleet_devices}

    def export_trace(self, path: str) -> Dict[str, Any]:
        """Chrome-trace of the fleet timeline: one row per request, every
        span attributed to its ``request_id``."""
        from ..observability.sinks import write_chrome_trace
        return write_chrome_trace(path, self.tracer.spans,
                                  self.tracer.t_origin,
                                  process_name="repro.fleet",
                                  row_names=self.row_names)


def sequential_reference(spec: SimulationSpec, n_steps: int) -> FleetResult:
    """The single-simulation serving path for parity checks and baselines:
    ``build_simulation`` + ``step()`` × n, result in the same flat layout
    as the fleet's (bitwise-comparable per request)."""
    t0 = time.perf_counter()
    sim = build_simulation(spec)
    for _ in range(n_steps):
        sim.step()
    eng = sim.engine
    return _flat_result(eng.state.cells, eng.perm, eng.n, sim.time, n_steps,
                        time.perf_counter() - t0, batched=False)
