"""Fleet serving contracts: signatures, batching, parity, compile counts.

The subsystem's three load-bearing claims, asserted:

* **Grouping is sound.** A frozen spec hashes/compares by *content*
  (scenario_params insertion order is canonicalised away), program
  signatures separate shape from value (different blast energies batch
  together; different lattice sides do not).
* **Batching is invisible.** A batched fleet of N heterogeneous requests
  produces, per request, *bitwise* the particles of N sequential
  single-simulation runs (the vmap path; scenario fixtures reused from
  ``test_conformance``).
* **Compiles are bounded.** Wobbling arrival sizes (3, 7, 5, 8) cost one
  XLA compile per (signature, batch-bucket) — counted by ``CompileProbe``
  from the jit caches, not inferred.
"""

import numpy as np
import pytest
import jax

from test_conformance import SCENARIOS, requires4

from repro.fleet import (AdmissionError, FleetRunner, RequestState,
                         SignatureBatcher, TransferBufferPool,
                         sequential_reference, split_scenario_params)
from repro.sph import SimulationSpec, SPHConfig


def _spec(scenario, **overrides):
    """A global×local spec from the conformance fixtures (which pin the
    timebin fields; the fleet's batched quadrant ignores those)."""
    kw = dict(SCENARIOS[scenario])
    kw.pop("dt_max", None)
    kw.pop("max_depth", None)
    params = dict(kw.pop("scenario_params"))
    params.update(overrides.pop("scenario_params", {}))
    kw.update(overrides)
    return SimulationSpec(scenario_params=params, **kw)


# ------------------------------------------------------------- signatures
class TestSpecHashing:
    def test_insertion_order_canonicalised(self):
        a = SimulationSpec(scenario="sedov",
                           scenario_params={"n_side": 5, "e0": 1.0,
                                            "seed": 3})
        b = SimulationSpec(scenario="sedov",
                           scenario_params={"seed": 3, "n_side": 5,
                                            "e0": 1.0})
        assert a == b
        assert hash(a) == hash(b)
        assert a.program_signature() == b.program_signature()
        assert a.signature_key() == b.signature_key()

    def test_spec_usable_as_dict_key(self):
        a = SimulationSpec(scenario_params={"x": 1, "y": 2})
        b = SimulationSpec(scenario_params={"y": 2, "x": 1})
        assert len({a: 0, b: 1}) == 1

    def test_params_mapping_still_reads_like_a_dict(self):
        a = SimulationSpec(scenario_params={"n_side": 5, "seed": 3})
        assert a.scenario_params["n_side"] == 5
        assert dict(a.scenario_params) == {"n_side": 5, "seed": 3}

    def test_value_params_share_signature(self):
        a = _spec("sedov", scenario_params={"e0": 1.0, "seed": 0})
        b = _spec("sedov", scenario_params={"e0": 2.5, "seed": 9})
        assert a.signature_key() == b.signature_key()

    def test_shape_params_split_signature(self):
        a = _spec("sedov")
        b = _spec("sedov", scenario_params={"n_side": 4})
        assert a.signature_key() != b.signature_key()

    def test_engine_fields_split_signature(self):
        a = _spec("sedov")
        assert a.signature_key() != \
            _spec("sedov", integrator="timebin").signature_key()
        assert a.signature_key() != \
            _spec("sedov",
                  physics=SPHConfig(alpha_visc=0.5)).signature_key()

    def test_split_scenario_params(self):
        shape, value = split_scenario_params(
            "sedov", {"n_side": 5, "e0": 2.0, "seed": 7})
        assert dict(shape) == {"n_side": 5}
        assert dict(value) == {"e0": 2.0, "seed": 7}


# ------------------------------------------------------------------ queue
class TestQueue:
    def test_admission_bounded(self):
        runner = FleetRunner(max_inflight=2, fleet_devices=1)
        runner.submit(_spec("sedov"))
        runner.submit(_spec("sedov"))
        with pytest.raises(AdmissionError):
            runner.submit(_spec("sedov"))

    def test_deadline_expiry_fires_callback(self):
        runner = FleetRunner(fleet_devices=1)
        seen = []
        req = runner.submit(_spec("sedov"), deadline=0.0,
                            callback=seen.append)
        import time
        time.sleep(0.01)
        dead = runner.queue.expire()
        assert dead == [req]
        assert req.state is RequestState.EXPIRED
        assert isinstance(req.error, TimeoutError)
        assert seen == [req]

    def test_duplicate_request_id_rejected(self):
        runner = FleetRunner(fleet_devices=1)
        runner.submit(_spec("sedov"), request_id="r1")
        with pytest.raises(ValueError):
            runner.submit(_spec("sedov"), request_id="r1")

    def test_expiry_fires_on_poll_without_claim(self):
        """The sweep-only-on-claim bug: a request whose deadline passes
        must reach EXPIRED (callback fired) from a pure status check —
        no take_ready/drain claim anywhere in the sequence."""
        runner = FleetRunner(fleet_devices=1, observe=True)
        seen = []
        req = runner.submit(_spec("sedov"), deadline=0.0,
                            callback=seen.append)
        import time
        time.sleep(0.01)
        stats = runner.poll()
        assert req.state is RequestState.EXPIRED
        assert isinstance(req.error, TimeoutError)
        assert seen == [req]
        assert stats["queue"]["expired"] == 1
        assert runner.terminal_status == {"expired": 1}
        # and the expiry left a visible span on the request's row
        assert [s for s in runner.tracer.spans if s.name == "expired"]

    def test_expiry_fires_on_next_submit(self):
        """A later submission is also a front-door entry: it sweeps the
        stale request out (freeing its admission slot) before admitting."""
        runner = FleetRunner(max_inflight=1, fleet_devices=1)
        seen = []
        stale = runner.submit(_spec("sedov"), deadline=0.0,
                              callback=seen.append)
        import time
        time.sleep(0.01)
        # at max_inflight=1 this would raise AdmissionError if the
        # overdue request still held its slot
        fresh = runner.submit(_spec("sedov"))
        assert stale.state is RequestState.EXPIRED
        assert seen == [stale]
        assert fresh.state is RequestState.QUEUED
        assert runner.terminal_status == {"expired": 1}


# ---------------------------------------------------------------- batcher
class TestBatcher:
    def _reqs(self, n, **overrides):
        from repro.fleet import RequestQueue
        q = RequestQueue()
        return [q.submit(_spec("sedov", **overrides)) for _ in range(n)]

    def test_groups_by_signature(self):
        from repro.fleet import RequestQueue
        q = RequestQueue()
        reqs = [q.submit(_spec("sedov")), q.submit(_spec("kelvin_helmholtz")),
                q.submit(_spec("sedov", scenario_params={"e0": 3.0}))]
        batches = SignatureBatcher().form(reqs)
        assert len(batches) == 2
        assert [b.size for b in batches] == [2, 1]

    def test_buckets_never_shrink(self):
        b = SignatureBatcher()
        sizes = [bb.bucket for bb in (b.form(self._reqs(7))
                                      + b.form(self._reqs(3))
                                      + b.form(self._reqs(5)))]
        assert sizes == [8, 8, 8]       # grew to 8, never back down

    def test_bucket_divisible_by_mesh(self):
        b = SignatureBatcher(min_bucket=4)
        (batch,) = b.form(self._reqs(3))
        assert batch.bucket == 4 and batch.pad == 1

    def test_max_batch_chunks(self):
        b = SignatureBatcher(max_batch=4)
        batches = b.form(self._reqs(10))
        assert [bb.size for bb in batches] == [4, 4, 2]


# ------------------------------------------------------- batched execution
def _served_ok(reqs):
    assert all(r.state is RequestState.DONE for r in reqs), \
        [(r.request_id, r.error) for r in reqs]


class TestBatchedParity:
    """Batched fleet == N sequential runs, bitwise, on the vmap path."""

    def test_heterogeneous_fleet_bitwise(self):
        specs = [
            _spec("sedov", scenario_params={"e0": 1.0, "seed": 0}),
            _spec("sedov", scenario_params={"e0": 1.7, "seed": 1}),
            _spec("sedov", scenario_params={"e0": 0.6, "seed": 2}),
            _spec("kelvin_helmholtz",
                  scenario_params={"v_shear": 0.5, "seed": 0}),
            _spec("kelvin_helmholtz",
                  scenario_params={"v_shear": 0.8, "seed": 3}),
        ]
        runner = FleetRunner(fleet_devices=1)
        reqs = [runner.submit(s, n_steps=3) for s in specs]
        runner.drain()
        _served_ok(reqs)
        assert all(r.result.batched for r in reqs)
        for r in reqs:
            ref = sequential_reference(r.spec, r.n_steps)
            assert r.result.particles.keys() == ref.particles.keys()
            for k in r.result.particles:
                np.testing.assert_array_equal(
                    np.asarray(r.result.particles[k]),
                    np.asarray(ref.particles[k]),
                    err_msg=f"{r.request_id}: field {k} not bitwise")
            assert r.result.t == ref.t

    def test_heterogeneous_step_counts(self):
        """Members with different n_steps finish at their own horizon."""
        runner = FleetRunner(fleet_devices=1)
        reqs = [runner.submit(_spec("sedov",
                                    scenario_params={"e0": 1.0 + i,
                                                     "seed": i}),
                              n_steps=n)
                for i, n in enumerate([2, 4, 3])]
        runner.drain()
        _served_ok(reqs)
        for r, n in zip(reqs, [2, 4, 3]):
            assert r.result.steps == n
            ref = sequential_reference(r.spec, n)
            for k in r.result.particles:
                np.testing.assert_array_equal(
                    np.asarray(r.result.particles[k]),
                    np.asarray(ref.particles[k]))

    def test_box_of_three_lengths_is_refused(self):
        """The batched path stacks cubic grids of one edge length; a shock
        tube's 2 x 0.5 x 0.5 box is refused with a clear error."""
        spec = SimulationSpec(scenario="sodshock",
                              scenario_params={"n_side": 8})
        runner = FleetRunner(fleet_devices=1)
        req = runner.submit(spec, n_steps=1)
        with pytest.raises(ValueError, match="cubic boxes of one edge"):
            runner.drain()
        assert req.state is RequestState.FAILED

    def test_timebin_quadrant_served_sequentially(self):
        kw = dict(SCENARIOS["sedov"])
        spec = SimulationSpec(integrator="timebin", **kw)
        runner = FleetRunner(fleet_devices=1)
        req = runner.submit(spec, n_steps=1)
        runner.drain()
        _served_ok([req])
        assert not req.result.batched
        assert req.result.energy == pytest.approx(
            sequential_reference_timebin(spec).energy, rel=1e-5)


def sequential_reference_timebin(spec):
    """One time-bin cycle on the plain path, diagnostics only."""
    from repro.sph import build_simulation
    from repro.fleet.queue import FleetResult
    sim = build_simulation(spec)
    sim.step()
    e, p = sim.diagnostics()
    return FleetResult(particles={}, energy=e, momentum=p, t=sim.time,
                       steps=1, wall=0.0, batched=False)


# --------------------------------------------------------- compile counts
class TestCompileDiscipline:
    def test_wobbling_arrivals_one_compile_per_bucket(self):
        """Arrival waves of 3, 7, 5, 8 same-signature requests: buckets 4
        and 8 exist, so exactly two (step, cfl) entry-point pairs compile,
        each exactly once — wave sizes never reach the XLA compiler."""
        runner = FleetRunner(fleet_devices=1)
        i = 0
        for wave in (3, 7, 5, 8):
            for _ in range(wave):
                runner.submit(_spec("sedov",
                                    scenario_params={"seed": i,
                                                     "e0": 1.0 + 0.01 * i}),
                              n_steps=1)
                i += 1
            runner.drain()
        stats = runner.queue.stats()
        assert stats["done"] == 23
        counts = runner.compile_counts()
        step_programs = [k for k in counts if "fleet_step" in k]
        assert len(step_programs) == 2, counts      # buckets 4 and 8 only
        assert all(c == 1 for c in counts.values()), counts
        runner.assert_compile_discipline()
        assert set(runner.batcher.policy._bucket.values()) == {8}

    def test_second_same_signature_fleet_compiles_nothing(self):
        runner = FleetRunner(fleet_devices=1)
        for wave in (2, 2):
            for i in range(wave):
                runner.submit(_spec("kelvin_helmholtz",
                                    scenario_params={"seed": i}), n_steps=1)
            runner.drain()
        assert runner.programs.builds == 2          # one step + one cfl
        runner.assert_compile_discipline()


# ------------------------------------------------------------ result pool
class TestTransferPool:
    def test_buffers_reused_after_give(self):
        pool = TransferBufferPool()
        a = pool.take(np.arange(6, dtype=np.float32))
        assert pool.stats() == {"hits": 0, "misses": 1, "resident": 0}
        pool.give(a)
        b = pool.take(np.ones(6, dtype=np.float32))
        assert b is a                               # same buffer, new bytes
        assert b[0] == 1.0
        assert pool.stats()["hits"] == 1

    def test_shape_buckets_are_distinct(self):
        pool = TransferBufferPool()
        a = pool.take(np.zeros(4))
        pool.give(a)
        b = pool.take(np.zeros(5))
        assert b is not a
        assert pool.stats()["misses"] == 2


# ------------------------------------------------------------------ trace
class TestFleetTrace:
    def test_rows_named_by_request_id(self):
        from repro.observability.sinks import validate_chrome_trace
        runner = FleetRunner(fleet_devices=1, observe=True)
        reqs = [runner.submit(_spec("sedov",
                                    scenario_params={"seed": i}), n_steps=2)
                for i in range(2)]
        runner.drain()
        _served_ok(reqs)
        doc = runner.export_trace("/dev/null")
        assert validate_chrome_trace(doc) == []
        names = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
                 if e.get("name") == "thread_name"}
        assert set(names.values()) == {r.request_id for r in reqs}
        slices = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert slices and all(
            e["args"].get("request_id") in names.values() for e in slices)


# -------------------------------------------------------------- 4 devices
@requires4
class TestShardedFleet:
    def test_fleet_axis_sharded_over_mesh(self):
        """8 requests over 4 devices: the fleet axis shards 2 lanes per
        device; per-device SPMD partitioning reassociates pair-sum
        reductions, so the sharded contract is ulp-level, not bitwise."""
        runner = FleetRunner(fleet_devices=4)
        reqs = [runner.submit(_spec("sedov",
                                    scenario_params={"seed": i,
                                                     "e0": 1.0 + 0.1 * i}),
                              n_steps=2)
                for i in range(8)]
        runner.drain()
        _served_ok(reqs)
        assert all(r.result.batched and r.result.bucket == 8 for r in reqs)
        runner.assert_compile_discipline()
        for r in reqs:
            ref = sequential_reference(r.spec, r.n_steps)
            for k in r.result.particles:
                np.testing.assert_allclose(
                    np.asarray(r.result.particles[k]),
                    np.asarray(ref.particles[k]), rtol=1e-4, atol=1e-5,
                    err_msg=f"{r.request_id}: field {k}")


# ---------------------------------------------------- terminal visibility
class TestTerminalVisibility:
    def test_expired_sweep_counts_traces_and_dumps(self, tmp_path):
        from repro.observability.flight import validate_bundle
        runner = FleetRunner(fleet_devices=1, observe=True,
                             flight_dir=str(tmp_path))
        ok = runner.submit(_spec("sedov"), n_steps=1)
        import time
        dead = runner.submit(_spec("sedov"), n_steps=1, deadline=0.0)
        time.sleep(0.01)
        runner.drain()
        assert ok.state is RequestState.DONE
        assert dead.state is RequestState.EXPIRED
        # every lane's terminal state is counted, including the swept one
        assert runner.terminal_status == {"done": 1, "expired": 1}
        assert runner.stats()["terminal_status"] == runner.terminal_status
        # the sweep is a first-class span on the lane's timeline row
        spans = [s for s in runner.tracer.spans if s.name == "expired"]
        assert len(spans) == 1
        assert spans[0].attrs["request_id"] == dead.request_id
        assert "deadline" in spans[0].attrs["error"]
        # ...and produced one validated post-mortem bundle
        assert len(runner.flight_dumps) == 1
        manifest = validate_bundle(runner.flight_dumps[0])
        assert manifest["reason"].startswith("expired")
        assert manifest["expired"] == [dead.request_id]

    def test_no_flight_dump_without_flight_dir(self):
        runner = FleetRunner(fleet_devices=1)
        runner.submit(_spec("sedov"), n_steps=1, deadline=0.0)
        import time
        time.sleep(0.01)
        runner.drain()
        assert runner.terminal_status == {"expired": 1}
        assert runner.flight_dumps == []
