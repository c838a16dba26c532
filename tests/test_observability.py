"""Observability contract: tracing is free, faithful, and non-invasive.

Three claims pinned here, mirroring the conformance harness's discipline:

* **non-interference** — ``observe=True`` changes no number: traced runs
  are bitwise-identical to untraced runs (the fences only *wait*, they
  never reorder or recompute), and mint zero extra compiled programs.
* **fidelity** — the exported Chrome trace passes the schema validator,
  carries one row per rank with per-phase slices, and the per-cycle JSONL
  counters agree *exactly* (not approximately) with the engines' live
  ``TransferProbe``/``CompileProbe`` ledgers.
* **cost** — an enabled span costs < 5 µs median on CPU, and the
  ``CompileProbe`` fallback counts signatures instead of reporting ``-1``.
"""

import json
import time

import numpy as np
import pytest

from repro.core.cost_model import CostModel
from repro.observability import (METRICS_SCHEMA_VERSION, NULL_TRACER,
                                 ObserveSpec, Tracer, UMBRELLA_SPANS,
                                 chrome_trace, jsonify, read_metrics_jsonl,
                                 validate_chrome_trace, write_metrics_jsonl)
from repro.sph import SimulationSpec, SPHConfig, build_simulation

from test_conformance import (SCENARIOS, _assert_bitwise, _reference,
                              _timebin_spec, _trajectory)


# ----------------------------------------------------------- tracer basics
def test_span_records_attrs_and_ctx():
    tr = Tracer()
    tr.ctx["cycle"] = 3
    with tr.span("density", rank=1, units=64):
        pass
    tr.ctx.pop("cycle")
    with tr.span("force", rank=0):
        pass
    spans = tr.spans
    assert [s.name for s in spans] == ["density", "force"]
    assert spans[0].rank == 1 and spans[0].attrs["units"] == 64
    assert spans[0].attrs["cycle"] == 3          # ambient ctx merged in
    assert (spans[1].attrs or {}).get("cycle") is None   # only while set
    assert all(s.t1 >= s.t0 for s in spans)
    assert tr.ranks() == [0, 1]


def test_span_ranks_duplicates_collective_interval():
    tr = Tracer()
    with tr.span("exchange1", ranks=range(3), units=10, collective=1) as sp:
        sp.set(bucket=8)                          # known only inside
    spans = tr.spans
    assert [s.rank for s in spans] == [0, 1, 2]
    assert len({(s.t0, s.t1) for s in spans}) == 1   # same interval per rank
    assert all(s.attrs["collective"] == 1 and s.attrs["bucket"] == 8
               for s in spans)
    # a mapping of rank to attrs adds them on that rank's row alone
    tr.clear()
    with tr.span("fleet_step", ranks={4: {"request_id": "a"},
                                      7: {"request_id": "b"}}, step=2):
        pass
    assert [(s.rank, s.attrs) for s in tr.spans] == [
        (4, {"step": 2, "request_id": "a"}),
        (7, {"step": 2, "request_id": "b"})]
    assert len({(s.t0, s.t1) for s in tr.spans}) == 1


def test_null_tracer_is_inert_but_timed_measures():
    assert not NULL_TRACER.enabled
    with NULL_TRACER.span("x", rank=0):
        pass
    with NULL_TRACER.span("y", ranks=range(4)) as sp:
        sp.set(bucket=8)
    assert NULL_TRACER.fence("payload") == "payload"
    with NULL_TRACER.timed("wall") as sp:
        time.sleep(0.001)
    assert sp.elapsed >= 0.001                    # "wall" stats still work
    assert NULL_TRACER.spans == []


def test_enabled_span_opens_engine_annotation(monkeypatch):
    """Every enabled span/timed is a ``TraceAnnotation("engine:<name>")``
    nested as the spans are (one per span, whatever its ``ranks``); the
    null tracer opens none."""
    from repro.observability import tracer as tracer_mod
    opened = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            opened.append("/" + self.name)

    monkeypatch.setattr(tracer_mod, "_TraceAnnotation", Annotation)
    tr = Tracer()
    with tr.timed("cycle"):
        with tr.span("plan", ranks=range(4)):
            pass
    assert opened == ["engine:cycle", "engine:plan", "/engine:plan",
                      "/engine:cycle"]
    opened.clear()
    with NULL_TRACER.timed("cycle"):
        with NULL_TRACER.span("plan", ranks=range(4)):
            pass
    assert opened == []


def test_spans_reach_the_profilers_trace(tmp_path):
    """On a real profile the spans are host events of the profiler's own
    trace, on its clock, child inside parent."""
    import glob
    import jax
    from jax.profiler import ProfileData
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("rebin", ranks=range(2)):
            with tr.span("rebin.unbin"):
                pass
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {e.name: (e.start_ns, e.end_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events
              if e.name.startswith("engine:")}
    assert set(events) == {"engine:rebin", "engine:rebin.unbin"}
    (a, b), (c, d) = events["engine:rebin"], events["engine:rebin.unbin"]
    assert a <= c <= d <= b


def test_enabled_span_overhead_under_5us():
    tr = Tracer()
    n = 2000
    samples = []
    for _ in range(15):
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("bench", rank=0):
                pass
        samples.append((time.perf_counter() - t0) / n)
        tr.clear()
    samples.sort()
    assert samples[len(samples) // 2] < 5e-6, samples


# ------------------------------------------------------- chrome trace sink
def _toy_tracer() -> Tracer:
    tr = Tracer()
    for r in (0, 1):
        with tr.span("density", rank=r, units=8):
            pass
        with tr.span("force", rank=r):
            pass
    with tr.span("exchange1", ranks=range(2), collective=1):
        pass
    return tr


def test_chrome_trace_schema_valid_and_ordered():
    doc = chrome_trace(_toy_tracer().spans, process_name="toy")
    assert validate_chrome_trace(doc) == []
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [e["ts"] for e in xs] == sorted(e["ts"] for e in xs)
    assert {e["tid"] for e in xs} == {0, 1}
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert any(m["name"] == "thread_name" for m in metas)


def test_chrome_trace_validator_catches_tampering():
    doc = chrome_trace(_toy_tracer().spans)
    bad = json.loads(json.dumps(doc))
    bad["traceEvents"][-1]["dur"] = -1.0
    assert validate_chrome_trace(bad)
    bad = json.loads(json.dumps(doc))
    xs = [e for e in bad["traceEvents"] if e["ph"] == "X"]
    xs[0]["ts"], xs[-1]["ts"] = xs[-1]["ts"], xs[0]["ts"]
    assert validate_chrome_trace(bad)
    bad = json.loads(json.dumps(doc))
    bad["traceEvents"] = [e for e in bad["traceEvents"]
                          if e.get("name") != "thread_name"]
    assert validate_chrome_trace(bad)             # rank mapping lost


# ------------------------------------------------- spec coercion / wiring
def test_observe_spec_coercion():
    assert SimulationSpec().observe == ObserveSpec(enabled=False)
    assert SimulationSpec(observe=True).observe.enabled
    ospec = SimulationSpec(observe={"trace": False}).observe
    assert ospec.enabled and not ospec.trace and ospec.metrics
    with pytest.raises(ValueError, match="observe"):
        SimulationSpec(observe=3.14)


@pytest.mark.parametrize("integrator,backend", [
    ("global", "local"), ("timebin", "local"),
    ("global", "distributed"), ("timebin", "distributed")])
def test_every_quadrant_reports_wall_and_observes(integrator, backend):
    kw = dict(SCENARIOS["sedov"])
    kw.update(integrator=integrator, backend=backend, dt=0.004,
              observe=True)
    if backend == "distributed":
        kw.update(ranks=1)
    sim = build_simulation(SimulationSpec(**kw))
    stats = sim.step()
    assert stats["wall"] > 0.0
    assert sim.observer is not None
    rec = sim.observer.records[-1]
    assert rec["cycle"] == 0 and rec["wall"] == stats["wall"]
    assert sim.observer.tracer.spans          # something was traced


# ------------------------------------------------ bitwise non-interference
@pytest.mark.slow
@pytest.mark.parametrize("transport,residency",
                         [("host", "host"), ("collective", "device")])
def test_tracing_is_bitwise_invisible(transport, residency):
    """observe=True vs observe=False: identical trajectories, the fences
    only wait on values the untraced run computes anyway."""
    spec = _timebin_spec("sedov", backend="distributed", ranks=1,
                         transport=transport, residency=residency,
                         observe=True)
    got = _trajectory(build_simulation(spec))
    _assert_bitwise(got, _reference("sedov"),
                    f"traced/{transport}/{residency}")


@pytest.mark.slow
def test_tracing_is_bitwise_invisible_local_timebin():
    spec = _timebin_spec("sedov", observe=True)
    got = _trajectory(build_simulation(spec))
    _assert_bitwise(got, _reference("sedov"), "traced/local-timebin")


@pytest.mark.slow
def test_tracing_mints_no_extra_programs():
    base = _timebin_spec("sedov", backend="distributed", ranks=1,
                         transport="collective", residency="device")
    plain = build_simulation(base)
    traced = build_simulation(_timebin_spec(
        "sedov", backend="distributed", ranks=1, transport="collective",
        residency="device", observe=True))
    for _ in range(2):
        plain.step()
        traced.step()
    assert traced.engine.probe.total_compiles() \
        == plain.engine.probe.total_compiles()
    assert traced.engine.probe.counts() == plain.engine.probe.counts()


# ---------------------------------- device-scheduled cycle: host phases
# the spans that tile a device-scheduled cycle, in order, and the rebin's
DEVICE_PHASES = ("plan", "scatter", "tables", "launch", "wait", "gather",
                 "repartition", "rebin", "stats")
REBIN_PARTS = ("rebin.unbin", "rebin.bin_particles", "rebin.pair_list",
               "rebin.upload")


def _device_schedule_spec(ranks: int) -> SimulationSpec:
    return _timebin_spec("sedov", backend="distributed", ranks=ranks,
                         transport="collective", residency="device",
                         schedule="device", segment_cycles=1, max_depth=2,
                         observe=True)


_FOUR_RANK_SPANS = """
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{src!r}, {tests!r}]
import jax
jax.config.update("jax_default_matmul_precision", "float32")
from repro.sph import build_simulation
from test_observability import _device_schedule_spec
sim = build_simulation(_device_schedule_spec(4))
for _ in range(3):
    sim.step()
print(json.dumps([[s.name, s.rank, s.t0, s.t1]
                  for s in sim.observer.tracer.spans]))
"""


@pytest.fixture(scope="module")
def device_schedule_run():
    """A traced one-rank device-scheduled run of three cycles, with every
    ``jax.block_until_ready`` made inside ``_run_segment`` counted."""
    import jax
    sim = build_simulation(_device_schedule_spec(1))
    eng = sim.engine
    inside, fences = [], []
    real_fence, real_segment = jax.block_until_ready, eng._run_segment

    def fence(x):
        if inside:
            fences.append(x)
        return real_fence(x)

    def run_segment():
        inside.append(1)
        try:
            return real_segment()
        finally:
            inside.pop()

    jax.block_until_ready = fence
    eng._run_segment = run_segment
    try:
        for _ in range(3):
            sim.step()
    finally:
        jax.block_until_ready = real_fence
        del eng._run_segment
    return sim, fences


def _spans_of(sim_or_ranks):
    """(name, rank, t0, t1) of a run's spans: the one-rank run in this
    process, or a four-rank run on four emulated devices."""
    if not isinstance(sim_or_ranks, int):
        return [(s.name, s.rank, s.t0, s.t1)
                for s in sim_or_ranks.observer.tracer.spans]
    import os
    import subprocess
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    script = _FOUR_RANK_SPANS.format(src=os.path.join(here, "..", "src"),
                                     tests=here)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return [tuple(x) for x in json.loads(proc.stdout.splitlines()[-1])]


@pytest.mark.parametrize("ranks", [1, 4])
def test_device_schedule_phases_tile_the_cycle(ranks, device_schedule_run):
    """Each cycle holds every host phase once, in order, each on every
    rank's row (the rebin and its parts on rank 0's), and the phases
    leave under 5% of the cycle unaccounted for."""
    spans = _spans_of(device_schedule_run[0] if ranks == 1 else ranks)
    cycles = [(t0, t1) for name, _, t0, t1 in spans if name == "cycle"]
    assert len(cycles) == 3
    self_s = total_s = 0.0
    for i, (c0, c1) in enumerate(cycles):
        rows = {}
        for name, rank, t0, t1 in spans:
            if name != "cycle" and c0 <= t0 and t1 <= c1:
                rows.setdefault((name, t0, t1), set()).add(rank)
        order = [k[0] for k in sorted(rows, key=lambda k: k[1])]
        assert [n for n in order if "." not in n] == list(DEVICE_PHASES)
        assert sorted(n for n in order if "." in n) == sorted(REBIN_PARTS)
        for (name, _, _), got in rows.items():
            want = {0} if name.startswith("rebin") else set(range(ranks))
            assert got == want, name
        if i:               # the first cycle's launch is mostly compile
            self_s += (c1 - c0) - sum(t1 - t0 for (n, t0, t1) in rows
                                      if "." not in n)
            total_s += c1 - c0
    assert 0.0 <= self_s < 0.05 * total_s, (self_s, total_s)


def test_device_schedule_never_fences(device_schedule_run):
    """With the tracer on, nothing inside ``_run_segment`` calls
    ``jax.block_until_ready``; the host-scheduled ladder still fences
    (so the counter would see one)."""
    import jax
    sim, fences = device_schedule_run
    assert sim.observer.tracer.enabled
    assert sim.engine.segments == 3 and sim.engine.segment_aborts == 0
    assert fences == []
    host = build_simulation(_timebin_spec(
        "sedov", backend="distributed", ranks=1, transport="collective",
        residency="device", schedule="host", max_depth=2, observe=True))
    real_fence, seen = jax.block_until_ready, []

    def fence(x):
        seen.append(x)
        return real_fence(x)

    jax.block_until_ready = fence
    try:
        host.step()
    finally:
        jax.block_until_ready = real_fence
    assert seen


def test_observer_counts_waiting_as_idle():
    """``wait`` (the host blocked on the device), ``observe`` and the
    dotted parts of a task are no rank's busy work; the phases are."""
    from repro.observability import RunObserver, Span
    obs = RunObserver(ObserveSpec(enabled=True, metrics=False,
                                  device_metrics=False))
    obs.tracer.spans.extend([
        Span("cycle", 0, 0.0, 1.0, None),
        Span("plan", 0, 0.0, 0.1, {"collective": 1}),
        Span("plan", 1, 0.0, 0.1, {"collective": 1}),
        Span("wait", 0, 0.1, 0.6, {"collective": 1}),
        Span("wait", 1, 0.1, 0.6, {"collective": 1}),
        Span("rebin", 0, 0.6, 0.9, None),
        Span("rebin.unbin", 0, 0.6, 0.7, None),
        Span("observe", 0, 1.0, 1.1, None)])
    rec = obs.end_cycle(object(), {"wall": 1.0})
    assert rec["rank_busy"] == {0: pytest.approx(0.4), 1: pytest.approx(0.1)}
    assert rec["dead_frac"] == pytest.approx(0.75)
    assert rec["phase_wall"]["wait"] == pytest.approx(1.0)


def test_observe_span_belongs_to_its_cycle():
    """``end_cycle`` times itself as the ``observe`` span: the record of
    the cycle it closes carries that span's seconds, and the next cycle's
    record does not count it again."""
    from repro.observability import RunObserver, Span
    obs = RunObserver(ObserveSpec(enabled=True, metrics=False,
                                  device_metrics=False))
    obs.tracer.spans.append(Span("plan", 0, 0.0, 0.1, None))
    rec = obs.end_cycle(object(), {"wall": 1.0})
    own = obs.tracer.spans[-1]
    assert own.name == "observe"
    assert rec["phase_wall"]["observe"] == own.dur
    assert rec["phase_count"]["observe"] == 1
    assert rec["rank_busy"] == {0: pytest.approx(0.1)}
    obs.tracer.spans.append(Span("plan", 0, 2.0, 2.3, None))
    rec2 = obs.end_cycle(object(), {"wall": 1.0})
    assert rec2["phase_count"] == {"plan": 1, "observe": 1}
    assert rec2["phase_wall"]["plan"] == pytest.approx(0.3)
    assert rec2["phase_wall"]["observe"] == obs.tracer.spans[-1].dur


def test_device_programs_carry_their_names(device_schedule_run):
    """The cycle scan compiles as ``jit_cycle_scan`` and an exchange as
    ``jit_halo_permute``: the module names a device trace prefixes every
    operation with."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.sph.collectives import build_permute_program
    sim, _ = device_schedule_run
    programs = sim.engine._transport.programs._programs
    names = {key[0]: prog.__name__ for key, prog in programs.items()}
    assert names["cycle_scan"] == "cycle_scan"
    mesh = Mesh(np.array(jax.devices()[:1]), ("r",))
    prog = build_permute_program(mesh, "r", [[(0, 0)]], nrows=4, bucket=2,
                                 nfields=1)
    idx = jnp.zeros((1, 1, 2), jnp.int32)
    text = prog.lower(idx, idx, jnp.zeros((1, 1, 2), jnp.float32),
                      jnp.zeros((1, 4), jnp.float32)).as_text()
    assert text.startswith("module @jit_halo_permute")


# -------------------------------------------- ledger fidelity + sinks e2e
@pytest.mark.slow
def test_metrics_record_agrees_exactly_with_probes(tmp_path):
    spec = _timebin_spec("sedov", backend="distributed", ranks=1,
                         transport="collective", residency="device",
                         observe=True)
    sim = build_simulation(spec)
    for _ in range(2):
        sim.step()
    obs, eng = sim.observer, sim.engine
    rec = obs.records[-1]
    assert rec["compiles"] == jsonify(eng.probe.counts())
    assert rec["total_compiles"] == eng.probe.total_compiles()
    assert rec["transfers"] == jsonify(eng.transfers.stats())
    assert rec["schema"] == METRICS_SCHEMA_VERSION

    trace_path = tmp_path / "trace.json"
    metrics_path = tmp_path / "metrics.jsonl"
    doc = obs.export_chrome_trace(str(trace_path))
    obs.write_metrics_jsonl(str(metrics_path))
    assert validate_chrome_trace(doc) == []
    assert validate_chrome_trace(json.loads(trace_path.read_text())) == []
    back = read_metrics_jsonl(str(metrics_path))
    assert len(back) == 2
    assert back[-1]["transfers"] == rec["transfers"]
    assert back[-1]["total_compiles"] == rec["total_compiles"]
    # every force sub-step shows up as a fused-program slice on the row
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    nsub = sum(r["force_substeps"] for r in obs.records)
    fused = [e for e in xs
             if e["name"] in ("fused_substep", "fused_final")]
    assert len(fused) >= nsub
    # cost feedback reached the engine's model
    assert obs.records[-1]["cost_ratios"]
    assert any(v > 0 for v in obs.records[-1]["observed_units"].values())


# --------------------------------------------------- compile-probe fallback
def test_compile_probe_counts_signatures_not_minus_one():
    from repro.distributed.transport import CompileProbe
    probe = CompileProbe()
    with pytest.warns(RuntimeWarning, match="no jit cache"):
        fn = probe.register("plain", lambda x: x + 1)
    assert probe.counts() == {"plain": 0}
    fn(np.zeros(3, np.float32))
    fn(np.zeros(3, np.float32))                   # same signature: no growth
    assert probe.counts() == {"plain": 1}
    fn(np.zeros(4, np.float32))                   # new shape: new "compile"
    fn(np.zeros(3, np.float64))                   # new dtype: new "compile"
    assert probe.counts() == {"plain": 3}
    assert probe.total_compiles() == 3
    assert all(c >= 0 for c in probe.counts().values())


def test_compile_probe_keeps_jit_cache_when_present():
    import jax
    from repro.distributed.transport import CompileProbe
    probe = CompileProbe()
    fn = probe.register("jitted", jax.jit(lambda x: x * 2))
    fn(np.zeros(3, np.float32))
    assert probe.counts()["jitted"] == 1


# ------------------------------------------------------ cost-model feedback
def test_cost_model_observe_and_ratio():
    cm = CostModel(rates={"density": 2e-9})
    assert cm.observed_units("density") == 0.0
    assert cm.observed_rate("density") is None
    cm.observe("density", units=1000.0, seconds=4e-6)      # 4e-9 s/unit
    cm.observe("density", units=1000.0, seconds=4e-6)
    assert cm.observed_units("density") == 2000.0
    assert cm.observed_seconds("density") == pytest.approx(8e-6)
    assert cm.observed_rate("density") == pytest.approx(4e-9)
    ratios = cm.measured_vs_modelled()
    # measured twice the modelled baseline rate, baseline frozen pre-EMA
    assert ratios["density"] == pytest.approx(2.0)
    assert cm.modelled_baseline["density"] == pytest.approx(2e-9)
    assert cm.rates["density"] > 2e-9              # EMA pulled toward measured


# ------------------------------------------------------------- report CLI
def test_trace_report_renders_timeline_and_tables(tmp_path):
    from repro.analysis.report import (metrics_summary, render_timeline,
                                       trace_report)
    doc = chrome_trace(_toy_tracer().spans)
    text = render_timeline(doc, width=40)
    # row labels come from the trace's thread_name metadata ("rank N"
    # for rank traces, request ids for fleet traces)
    assert "rank 0 |" in text and "rank 1 |" in text
    assert "legend:" in text and "D=density" in text
    assert all(n not in UMBRELLA_SPANS
               for n in ("density", "force", "exchange1"))

    records = [{"cycle": 0, "wall": 0.5, "imbalance": 1.25,
                "dead_frac": 0.1, "updates": 216, "total_compiles": 3},
               {"cycle": 1, "wall": 0.4, "imbalance": None,
                "dead_frac": None, "updates": 216,
                "cost_ratios": {"density": 1.5},
                "observed_units": {"density": 4000.0}}]
    table = metrics_summary(records)
    assert "1.250" in table and "measured vs modelled" in table

    trace_path = tmp_path / "trace.json"
    trace_path.write_text(json.dumps(doc))
    metrics_path = tmp_path / "metrics.jsonl"
    write_metrics_jsonl(str(metrics_path), records)
    out = trace_report(str(trace_path), str(metrics_path), width=40)
    assert "task timeline" in out and "per-cycle summary" in out


# ------------------------------------- device metrics / flight recorder
def test_upgrade_record_chains_v1_to_v3():
    from repro.observability import upgrade_record
    v1 = {"schema": 1, "cycle": 3, "wall": 0.5, "imbalance": 1.2}
    up = upgrade_record(dict(v1))
    assert up["schema"] == METRICS_SCHEMA_VERSION == 3
    assert up["schema_original"] == 1
    # the v1→v2 step's columns…
    for key in ("device_metrics", "device_phase_units",
                "device_imbalance", "health"):
        assert key in up and up[key] is None
    # …then the v2→v3 step's columns, applied in the same pass
    for key in ("cell_work", "cost_calibration", "advisor"):
        assert key in up and up[key] is None
    assert up["cost_ratios"] == {} and up["observed_units"] == {}
    assert up["cycle"] == 3 and up["imbalance"] == 1.2


def test_upgrade_record_v2_to_v3_round_trip():
    from repro.observability import upgrade_record
    v2 = {"schema": 2, "cycle": 7, "device_imbalance": 1.1,
          "health": {"tripped": False},
          "cost_ratios": {"density": 1.5}}
    up = upgrade_record(dict(v2))
    assert up["schema"] == 3 and up["schema_original"] == 2
    # v2 payload survives untouched; only the missing v3 columns appear
    assert up["device_imbalance"] == 1.1
    assert up["health"] == {"tripped": False}
    assert up["cost_ratios"] == {"density": 1.5}
    assert up["cell_work"] is None and up["advisor"] is None
    # upgrading an already-current record is the identity
    assert upgrade_record(dict(up)) == up


def test_upgrade_record_rejects_newer_schema():
    from repro.observability import upgrade_record
    with pytest.raises(ValueError, match="newer"):
        upgrade_record({"schema": METRICS_SCHEMA_VERSION + 1, "cycle": 0})
    # tampered/nonsense versions that claim the future are refused too
    with pytest.raises(ValueError):
        upgrade_record({"schema": 99})


def test_report_renders_dash_for_pre_v3_records():
    from repro.analysis.report import advisor_trend, attribution_table
    old = [{"schema": 1, "cycle": 0, "wall": 0.5},
           {"schema": 2, "cycle": 1, "wall": 0.4,
            "device_imbalance": 1.1}]
    table = attribution_table(old)
    assert "-" in table and "predates schema v3" in table
    trend = advisor_trend(old)
    lines = [ln for ln in trend.splitlines() if ln.strip()]
    assert any(ln.split()[1] == "-" for ln in lines
               if ln.split() and ln.split()[0].isdigit())
    assert "no advisor records" in trend


def test_cost_model_calibrate_recovers_rates():
    rng = np.random.default_rng(0)
    true = {"density": 4e-6, "force": 9e-6, "exchange": 1e-6}
    samples = []
    for _ in range(12):
        units = {k: float(rng.uniform(1e3, 1e5)) for k in true}
        secs = sum(true[k] * u for k, u in units.items())
        samples.append((units, secs))
    cm = CostModel(rates={"density": 1e-9})
    cal = cm.calibrate(samples)
    for kind, rate in true.items():
        assert cal[kind]["rate"] == pytest.approx(rate, rel=1e-6)
        assert cal[kind]["confidence"] == pytest.approx(1.0, abs=1e-6)
    # fitted rates folded into the model's EMA stream
    assert cm.rates["density"] > 1e-9


def test_task_cost_ledger_warmup_residual_and_weights():
    from repro.observability import TaskCostLedger
    cm = CostModel(rates={"density": 1e-9})
    led = TaskCostLedger(cm, skip_first=1)
    # cycle 0: compile-dominated wall — observed, but not in the window
    led.record({"density": 100.0, "force": 100.0}, 50.0)
    assert led.snapshot()["nsamples"] == 0
    rng = np.random.default_rng(1)
    for _ in range(6):
        # unit mixes must vary cycle to cycle or the kinds are collinear
        # and only their joint rate is identifiable
        u = {"density": float(rng.uniform(50, 500)),
             "force": float(rng.uniform(50, 500))}
        led.record(u, 4e-6 * u["density"] + 8e-6 * u["force"])
    snap = led.snapshot()
    assert snap["nsamples"] == 6
    assert snap["residual"] is not None and snap["residual"] < 0.05
    assert led.rate("density") == pytest.approx(4e-6, rel=1e-3)
    assert led.rate("force") == pytest.approx(8e-6, rel=1e-3)
    cell_work = {"columns": ["drift", "density", "force", "exchange"],
                 "cells": np.array([[0.0, 10.0, 0.0, 0.0],
                                    [0.0, 0.0, 10.0, 0.0]])}
    w = led.cell_weights(cell_work)
    assert w[1] / w[0] == pytest.approx(2.0, rel=1e-3)


@pytest.mark.slow
def test_calibration_band_on_traced_sedov():
    """Acceptance: after warmup, the joint fit predicts the fused wall
    of a traced Sedov run within a pinned band (the warmup cycle and
    mid-run compile spikes are excluded from the window, like any
    benchmark's warmup)."""
    spec = _timebin_spec("sedov", backend="distributed", ranks=1,
                         transport="collective", residency="device",
                         observe=True)
    sim = build_simulation(spec)
    for _ in range(5):
        sim.step()
    cal = sim.observer.records[-1]["cost_calibration"]
    assert cal is not None and cal["kinds"]
    assert cal["nsamples"] >= 2
    assert cal["residual"] is not None and cal["residual"] < 0.5
    assert all(v["rate"] >= 0 for v in cal["kinds"].values())


def test_weighted_imbalance_counts_empty_ranks():
    from repro.observability import weighted_imbalance
    # all weight on rank 0 of 4 → max/mean = 4
    assert weighted_imbalance([0, 0], [1.0, 1.0], 4) \
        == pytest.approx(4.0)
    assert weighted_imbalance([0, 1], [1.0, 1.0], 2) \
        == pytest.approx(1.0)


@pytest.mark.slow
def test_advisor_improves_clustered_imbalance():
    """Acceptance: on a clustered scenario the advisor's replay of the
    partitioner with *measured* weights never reports worse than the
    current partition, and actually improves it."""
    spec = SimulationSpec(
        scenario="clustered", scenario_params={"n": 96, "seed": 0},
        physics=SPHConfig(alpha_visc=1.0, cfl=0.15),
        dt_max=0.02, max_depth=3, integrator="timebin",
        backend="distributed", ranks=4, transport="host",
        observe=True)
    sim = build_simulation(spec)
    advs = []
    for _ in range(2):
        sim.step()
        rec = sim.observer.records[-1]
        assert rec["cell_work"] is not None
        adv = rec["advisor"]
        assert adv is not None
        advs.append(adv)
        assert adv["advised_imbalance"] \
            <= adv["current_imbalance"] + 1e-9
    # clustered ICs leave the occupancy-seeded partition measurably
    # imbalanced; the measured-weight replay must find a better one
    assert any(a["accepted"] for a in advs)
    assert advs[-1]["advised_imbalance"] < advs[-1]["current_imbalance"]
    assert advs[-1]["per_cell_ratio"]["mean"] > 0


@pytest.mark.slow
def test_per_cell_units_match_value_columns_host_dist():
    """Host-transport distributed ladder: per-rank sums of the per-cell
    drift/density/force vectors equal the device-metrics value columns
    exactly (exchange is receiver-side truth, checked >= 0)."""
    from repro.observability import CELL_COLUMNS
    from repro.observability import device_metrics as dm
    spec = SimulationSpec(
        scenario="clustered", scenario_params={"n": 96, "seed": 0},
        physics=SPHConfig(alpha_visc=1.0, cfl=0.15),
        dt_max=0.02, max_depth=3, integrator="timebin",
        backend="distributed", ranks=4, transport="host",
        observe=True)
    sim = build_simulation(spec)
    sim.step()
    eng = sim.engine
    cw = eng.device_cell_work_last
    assert cw is not None and list(cw["columns"]) == list(CELL_COLUMNS)
    cells = np.asarray(cw["cells"])
    per_rank = np.asarray(cw["per_rank"])
    # folding halo rows onto owners conserves every column
    np.testing.assert_allclose(cells.sum(axis=0), per_rank.sum(axis=0),
                               rtol=1e-6)
    counts, values = eng.device_metrics_last
    counts, values = np.asarray(counts), np.asarray(values)
    ci = {k: i for i, k in enumerate(CELL_COLUMNS)}
    for kind in ("density", "force"):
        want = values[:, dm.VALUE_INDEX[f"{kind}_units"]].sum()
        got = per_rank[:, ci[kind]].sum()
        assert got == pytest.approx(want, rel=1e-6), kind
    assert per_rank[:, ci["drift"]].sum() == pytest.approx(
        counts[:, dm.COUNT_INDEX["drift_active"]].sum(), rel=1e-6)
    assert (cells >= 0).all()


def test_local_quadrant_density_cells_sum_to_pairs():
    kw = dict(SCENARIOS["sedov"])
    kw.update(integrator="global", backend="local", dt=0.004,
              observe=True)
    sim = build_simulation(SimulationSpec(**kw))
    sim.step()
    cw = sim.engine.device_cell_work_last
    assert cw is not None
    cells = np.asarray(cw["cells"])
    cols = list(cw["columns"])
    npairs = int(np.asarray(sim.engine.pairs.ci).shape[0])
    assert cells[:, cols.index("density")].sum() == pytest.approx(npairs)
    assert cells[:, cols.index("force")].sum() == pytest.approx(npairs)


def test_end_cycle_always_emits_v3_keys():
    """``cost_ratios`` (and friends) are always present — empty/None
    fallbacks, never missing keys — so downstream readers need no
    per-key existence checks."""
    kw = dict(SCENARIOS["sedov"])
    kw.update(integrator="global", backend="local", dt=0.004,
              observe=True)
    sim = build_simulation(SimulationSpec(**kw))
    sim.step()
    rec = sim.observer.records[-1]
    assert rec["schema"] == METRICS_SCHEMA_VERSION
    assert "cost_ratios" in rec and isinstance(rec["cost_ratios"], dict)
    assert "observed_units" in rec \
        and isinstance(rec["observed_units"], dict)
    for key in ("cell_work", "cost_calibration", "advisor"):
        assert key in rec
    # jsonl round-trip preserves the always-present contract
    buf = json.loads(json.dumps(jsonify(rec)))
    assert "cost_ratios" in buf


def test_flight_recorder_ring_dump_and_validation(tmp_path):
    from repro.observability import (COUNT_COLUMNS, VALUE_COLUMNS,
                                     FlightRecorder, read_bundle,
                                     validate_bundle)
    from repro.observability import device_metrics as dm
    fr = FlightRecorder(k=3)
    for cyc in range(5):
        counts, values = dm.zero_rows(2)
        counts[:, 0] = cyc + 1
        fr.record(cyc, counts, values)
    assert [r["cycle"] for r in fr.rows()] == [2, 3, 4]  # keeps last 3
    path = fr.dump(str(tmp_path), reason="unit test!", cycle=4,
                   extra={"note": "x"})
    manifest = validate_bundle(path)
    assert manifest["reason"] == "unit test!"
    assert manifest["cycle"] == 4 and manifest["records"] == 3
    assert manifest["ring_cycles"] == [2, 3, 4]
    assert manifest["note"] == "x"
    bundle = read_bundle(path)
    assert bundle["records"][0]["count_columns"] == list(COUNT_COLUMNS)
    assert bundle["records"][-1]["counts"][0][0] == 5
    assert len(bundle["records"][0]["values"][0]) == len(VALUE_COLUMNS)
    # tampering is caught
    mpath = tmp_path / path.split("/")[-1] / "manifest.json"
    doc = json.loads(mpath.read_text())
    doc["records"] = 99
    mpath.write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="record count"):
        validate_bundle(path)


@pytest.mark.slow
def test_nan_sentinel_trips_and_dumps_flight_bundle(tmp_path):
    """Poisoning one velocity component trips the in-program NaN sentinel
    on the very next cycle and drops a validated post-mortem bundle whose
    manifest names that cycle."""
    from repro.observability.flight import validate_bundle
    import jax.numpy as jnp
    spec = _timebin_spec("sedov", backend="distributed", ranks=1,
                         transport="collective", residency="device",
                         observe={"flight_dir": str(tmp_path)})
    sim = build_simulation(spec)
    sim.step()
    obs, eng = sim.observer, sim.engine
    assert obs.records[-1]["health"]["tripped"] is False
    assert not obs.flight.dumps

    cells = eng.state.cells
    vel = np.asarray(cells.vel).copy()
    c, p = np.argwhere(np.asarray(cells.mask) > 0)[0]
    vel[c, p, 0] = np.nan
    eng.state = eng.state._replace(cells=cells._replace(vel=jnp.asarray(vel)))
    with np.errstate(invalid="ignore"):
        sim.step()

    rec = obs.records[-1]
    assert rec["health"]["tripped"] is True
    assert rec["health"]["flags"]["flag_nan"] > 0
    assert rec["flight_dump"] == obs.flight.dumps[-1]
    manifest = validate_bundle(rec["flight_dump"])
    assert manifest["reason"] == "nan"
    assert manifest["cycle"] == 1             # tripped on the second cycle
    assert obs.registry.snapshot()["counters"]["sentinel_trips"] == 1
    assert obs.registry.snapshot()["counters"]["flight_dumps"] == 1
