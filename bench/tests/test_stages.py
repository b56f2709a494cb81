"""The force-stage and retrace readings (``bench/stages.py``) on hand-made
events: stages nested in an op, events outside any stage, two chips."""

import pytest

import stages

U = 1000  # the hand-made events are in microseconds
OP = "jit(run)/while/body/forces/cond/jit(cell_list_force)"
HLO_NAMES = {
    "conditional.1": "jit(run)/while/body/forces/cond",
    "gather.2": f"{OP}/cell_gather/gather",
    "fusion.3": f"{OP}/cell_gather/pad",
    "custom-call.4": f"{OP}/cell_kernel/pallas_call",
    "scatter.5": f"{OP}/cell_scatter/scatter-add",
    "fusion.6": "jit(run)/while/body/static_flags/gather",
}


def chip(dev, shift):
    """One chip's step: a conditional around the force pass's three stages,
    and an op outside any stage.  ``shift`` moves the chip's events."""
    ev = lambda name, start, dur: (dev, "XLA Ops", name, (start + shift) * U,
                                   dur * U, "")
    return [
        ev("conditional.1", 1000, 1000),   # self time 1000 - 100 - 500 - 300
        ev("gather.2", 1010, 60),
        ev("fusion.3", 1070, 40),
        ev("custom-call.4", 1150, 500),
        ev("scatter.5", 1690, 300),
        ev("fusion.6", 2100, 200),
    ]


def raw_events(host):
    devs = ["/device:TPU:0", "/device:TPU:1"]
    return {"devices": devs,
            "device": chip(devs[0], 0) + chip(devs[1], 20),
            "host": host}


WINDOW = [("window", 900 * U, 1600 * U)]


def test_stage_self_time_two_chips():
    t = stages.reduce(raw_events(WINDOW), HLO_NAMES, first_step=16, steps=2)
    s = t.scope_seconds()
    # mean over the two chips, self time only
    assert s["cell_gather"] == pytest.approx(100e-6)
    assert s["cell_kernel"] == pytest.approx(500e-6)
    assert s["cell_scatter"] == pytest.approx(300e-6)
    # the conditional's own 100 us and the other op's 200 us: no stage
    assert s["unattributed"] == pytest.approx(300e-6)
    assert stages.stage_ms(t, "cell_kernel") == pytest.approx(0.25)
    assert stages.stage_ms(t, "cell_gather") == pytest.approx(0.05)
    assert stages.stage_ms(t, "cell_scatter") == pytest.approx(0.15)


def test_absent_stage_reads_none():
    t = stages.reduce(raw_events(WINDOW), HLO_NAMES, first_step=16, steps=2)
    assert stages.stage_ms(t, "dense_fallback") is None
    no_stage = {k: v for k, v in HLO_NAMES.items() if "/cell_" not in v}
    t = stages.reduce(raw_events(WINDOW), no_stage, first_step=16, steps=2)
    assert all(stages.stage_ms(t, st) is None for st in stages.STAGES)


def test_retraces_inside_the_window():
    host = WINDOW + [
        ("trace_schedule", 100 * U, 50 * U),    # set-up: before the window
        ("trace_schedule", 1200 * U, 30 * U),
        ("trace_schedule", 2000 * U, 30 * U),
        ("launch", 1100 * U, 10 * U),
        ("trace_schedule", 2600 * U, 10 * U),   # after the window
    ]
    assert stages.retraces(raw_events(host)) == 2
    assert stages.retraces(raw_events(WINDOW)) == 0
    assert stages.retraces(raw_events([("trace_schedule", 0, 10)])) is None


def test_program_under_test_marks_retraces():
    assert stages.program_marks_retraces()


def test_no_profile_reads_none(tmp_path):
    class Run:
        first_step, steps = 16, 2

    ms, count = stages.run_readings(Run(), trace_dir=str(tmp_path))
    assert ms == dict.fromkeys(stages.STAGES) and count is None
