"""Device self time per force stage, and the schedule's traces inside the
window, from the run's profile in ``.bench_trace/``.

The program names the stages of its force pass with ``jax.named_scope``
(``cell_gather``, ``cell_kernel``, ``cell_scatter``, ``dense_fallback``)
and each trace of its schedule with the host span ``trace_schedule``.  The
profile and the window program's text (``module.txt``) are read once per
run.  Stages are reduced by ``trace.Trace.reduce`` with the stage names in
place of the op names: a device event goes to the innermost stage in its
path, self time only, under the same rules as an op.  Readings from a
program that writes none of these spans are ``None``.
"""

from __future__ import annotations

import functools
import glob
import importlib.util
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(os.path.dirname(BENCH), ".bench_trace")
STAGES = ("cell_gather", "cell_kernel", "cell_scatter", "dense_fallback")
RETRACE_SPAN = "trace_schedule"


def trace_module():
    """``bench/trace.py``, as the harness loads it."""
    mod = sys.modules.get("bench_trace")
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            "bench_trace", os.path.join(BENCH, "trace.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules["bench_trace"] = mod
        spec.loader.exec_module(mod)
    return mod


def reduce(raw: dict, hlo_names: dict, first_step: int, steps: int):
    """The stage-level ``Trace`` of :func:`trace.raw_events`' plain form."""
    return trace_module().Trace.reduce(raw, dict.fromkeys(STAGES, 1),
                                       hlo_names, first_step, steps)


def stage_ms(stage_trace, stage: str):
    """Device self time of ``stage`` per simulated step (ms/step), or
    ``None`` where no event falls under it."""
    if stage_trace.steps == 0 or not stage_trace.has_scope(stage):
        return None
    return stage_trace.scope_seconds()[stage] / stage_trace.steps * 1e3


def retraces(raw: dict):
    """``trace_schedule`` spans that start inside the ``window`` span, or
    ``None`` without a window."""
    tr = trace_module()
    wins = [(s, s + d) for n, s, d in raw["host"] if n == tr.WINDOW_SPAN]
    if not wins:
        return None
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    return sum(1 for n, s, _ in raw["host"]
               if n == RETRACE_SPAN and lo <= s <= hi)


def program_marks_retraces() -> bool:
    """Whether the program under test writes ``trace_schedule`` spans (it
    lists its spans in ``repro.spans``); without them no count is read."""
    try:
        from repro import spans
    except ImportError:
        return False
    return RETRACE_SPAN in spans.HOST_SPANS


@functools.lru_cache(maxsize=1)
def _read(xplane: str, mtime: float, module_txt: str, first_step: int,
          steps: int):
    from jax.profiler import ProfileData

    tr = trace_module()
    raw = tr.raw_events(ProfileData.from_file(xplane))
    with open(module_txt) as f:
        names = tr.hlo_op_names(f.read())
    per_step = reduce(raw, names, first_step, steps)
    return {s: stage_ms(per_step, s) for s in STAGES}, retraces(raw)


def run_readings(trace, trace_dir: str = TRACE_DIR):
    """``({stage: ms/step or None}, retraces or None)`` of the run whose
    op-level ``Trace`` is ``trace``, from the newest profile under
    ``trace_dir``; every reading is ``None`` where there is none."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    module_txt = os.path.join(trace_dir, "module.txt")
    if not paths or not os.path.exists(module_txt):
        return dict.fromkeys(STAGES), None
    ms, count = _read(paths[-1], os.path.getmtime(paths[-1]), module_txt,
                      trace.first_step, trace.steps)
    return ms, count if program_marks_retraces() else None
