"""The trace reduction on hand-made events and on a small trace recorded on
a v5e chip (``bench/testdata/``)."""

import json
import os

import pytest

import trace as tr

OPS = {"sort": 16, "env_build": 1, "behaviors": 1, "forces": 1}
HLO = """
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f1, metadata={op_name="jit(run)/while/body/env_build/add" source_file="x.py"}
  ROOT %custom-call.2 = f32[8]{0} custom-call(%p), metadata={op_name="jit(run)/while/body/forces/forces/pallas_call"}
  %copy.3 = f32[8]{0} copy(%p)
  %while.4 = f32[8]{0} while(%p), condition=%c, body=%b, metadata={op_name="jit(run)/while"}
"""


def test_module_text_names_scopes():
    names = tr.hlo_op_names(HLO)
    assert names["fusion.1"].endswith("env_build/add")
    assert names["custom-call.2"].endswith("pallas_call")
    assert "copy.3" not in names
    assert tr.innermost_scope(names["custom-call.2"], OPS) == "forces"
    assert tr.innermost_scope("jit(run)/while/body/sort/cond/x", OPS) == "sort"
    assert tr.innermost_scope("jit(run)/while", OPS) == tr.UNATTRIBUTED


def test_union_and_self_time():
    assert tr.union_length([(0, 10), (5, 15), (20, 30)]) == 25
    assert tr.union_length([]) == 0
    # a loop event around two body events: it keeps only its own time
    assert tr.self_times([(0, 100), (10, 30), (40, 90)]) == [30, 20, 50]


U = 1000  # the hand-made events are in microseconds


def hand_made():
    dev = "/device:TPU:0"
    raw = {
        "devices": [dev],
        "device": [
            (dev, "XLA Ops", "while.4", 1000 * U, 900 * U, ""),
            (dev, "XLA Ops", "fusion.1", 1100 * U, 200 * U, ""),
            (dev, "XLA Ops", "custom-call.2", 1300 * U, 500 * U, ""),
            (dev, "XLA Ops", "copy.3", 2200 * U, 100 * U, ""),
        ],
        "host": [("window", 900 * U, 1500 * U), ("dispatch", 900 * U, 100 * U),
                 ("block_until_ready", 1000 * U, 1300 * U),
                 ("python_frame", 0, 5000 * U)],
    }
    return tr.Trace.reduce(raw, OPS, tr.hlo_op_names(HLO), first_step=16,
                           steps=2)


def test_attribution_busy_and_gaps():
    t = hand_made()
    s = t.scope_seconds()
    assert s["env_build"] == pytest.approx(200e-6)
    assert s["forces"] == pytest.approx(500e-6)
    # the while loop's own 200 us and the copy's 100 us: no op scope
    assert s[tr.UNATTRIBUTED] == pytest.approx(300e-6)
    assert t.window_s == pytest.approx(1500e-6)
    # busy: [1000, 1900) and [2200, 2300) inside the window [900, 2400)
    assert t.busy_s == pytest.approx(1000e-6)
    gaps = t.idle_gaps()
    assert [g[0] for g in gaps] == ["block_until_ready", "dispatch", "host"]
    assert [round(g[1] * 1e6) for g in gaps] == [300, 100, 100]
    assert t.firings("sort") == 1 and t.firings("forces") == 2


def test_breakdown_shape():
    b = hand_made().breakdown()
    assert b["device_ops"][0] == ["forces", pytest.approx(500e-6)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_event_names_are_instruction_names():
    text = ("%copy.214 = s32[65536]{0:T(1024)} copy(s32[65536]{0:T(1024)} "
            "%state_pool_attrs__tag__.1)")
    assert tr.instruction(text) == "copy.214"
    assert tr.instruction("fusion.3") == "fusion.3"


SIR_OPS = {"sort": 16, "env_build": 1, "behaviors": 1, "boundary": 1,
           "age": 1, "health": 1, "infectious_time": 1}


def recorded():
    import gzip
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "testdata", "sir_v5e_chunk.json.gz")
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    raw = {"devices": d["devices"], "host": [tuple(h) for h in d["host"]],
           "device": [(d["device_plane"], d["line"], n, s, dur, "")
                      for n, s, dur in d["device"]]}
    return tr.Trace.reduce(raw, SIR_OPS, d["op_names"], d["first_step"],
                           d["steps"])


def test_recorded_v5e_trace():
    """One 16-step SIR chunk recorded on a v5e chip: every event is
    attributed once (self times add up to the busy union, no nesting on the
    line), the infection gather dominates, and the sort fires once."""
    t = recorded()
    s = t.scope_seconds()
    assert sum(s.values()) == pytest.approx(t.busy_s, rel=1e-9)
    assert t.busy_s == pytest.approx(2.882814003, rel=1e-9)
    assert t.window_s == pytest.approx(2.885856221, rel=1e-9)
    assert s["behaviors"] == pytest.approx(2.690343318, rel=1e-9)
    assert s["env_build"] == pytest.approx(0.11303796, rel=1e-9)
    assert s["sort"] == pytest.approx(0.009852976, rel=1e-9)
    assert s[tr.UNATTRIBUTED] / t.busy_s < 0.03
    assert t.firings("sort") == 1 and t.firings("behaviors") == 16
    gaps = t.idle_gaps()
    assert all(g[1] >= 1e-6 for g in gaps)
    assert gaps[0][0] == "block_until_ready"
