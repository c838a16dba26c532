"""The trace reduction, checked on a trace recorded on one TPU v5e
(``record_trace.py``): five calls of a small program, each followed by
20 ms of host work in the span ``host_work``."""

from pathlib import Path

import pytest

from harness import trace as tracing

FIXTURE = Path(__file__).parent / "data" / "one_chip.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return tracing.summarize(str(FIXTURE))


def test_window_and_devices(summary):
    assert len(summary.devices) == 1
    assert summary.devices[0].name == "/device:TPU:0"
    # five sleeps of 20 ms lie inside the window
    assert 0.1 < summary.window_s < 0.12


def test_host_events_are_the_harness_threads(summary):
    names = {name for _, _, name in summary.host}
    assert {"host_work", "CommonPjRtLoadedExecutable::Execute"} <= names
    # the runtime's completion thread is left out
    assert "ReadSyncFlag" not in names


def test_busy_is_the_union_of_ops_inside_the_window(summary):
    ops = summary.devices[0].ops
    # 3 operations × 5 calls, but the device's timeline sits ~1 ms before
    # the host's in this trace, so the first call ran "before" the window
    assert len(ops) == 12
    assert all(summary.window[0] <= a < b <= summary.window[1]
               for a, b, _ in ops)
    busy = summary.busy_s()
    assert 0 < busy <= sum(b - a for a, b, _ in ops) * 1e-9
    assert busy < 0.01 * summary.window_s   # the host work is the window


def test_ops_are_named_by_program_and_instruction(summary):
    top = summary.top_ops()
    assert top[0][0] == "jit__lambda/fusion"
    assert {name for name, _ in top} == {
        "jit__lambda/fusion", "jit__lambda/copy-start",
        "jit__lambda/copy-done"}
    assert summary.collective_s() == 0.0


def test_idle_time_goes_to_the_host_work(summary):
    gaps = summary.idle_gaps()
    name, seconds = gaps[0]
    # the innermost host event open in the gaps is the sleep itself
    assert name == "$time sleep"
    assert seconds > 0.095
    assert sum(s for _, s in gaps) == pytest.approx(
        summary.window_s - summary.busy_s(), rel=1e-9)


@pytest.mark.parametrize("name,collective", [
    ("all-gather-start.3", True), ("all-gather-done", True),
    ("collective-permute-start.1", True), ("all-reduce.7", True),
    ("fusion.12", False), ("copy-start", False),
    ("all-gather-fusion.2", False)])
def test_collective_instructions(name, collective):
    assert bool(tracing.COLLECTIVE.fullmatch(name)) is collective


def test_instruction_and_module_names():
    assert tracing.instruction(
        "%fusion.12 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop") \
        == "fusion.12"
    assert tracing.module_name("jit_cycle(7639791831725600669)") \
        == "jit_cycle"
