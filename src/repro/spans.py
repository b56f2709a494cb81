"""Names of the spans the program writes into a profiler trace.

Device scopes (``jax.named_scope``) stay in each compiled instruction's
``op_name`` metadata.  Every scheduler op runs under its own name
(``core/schedule.run_op``); inside an op, the force layer names the stages
below.  No stage name equals an op name, so a trace reduction can match
either set alone.

Host spans (``jax.profiler.TraceAnnotation``) share the profiler's clock
with the device events, so a device-idle gap can be placed under what the
host was doing in it.
"""

# Force-layer stages (device scopes).
CELL_GATHER = "cell_gather"        # pool arrays -> cell-major planar layout
CELL_KERNEL = "cell_kernel"        # the Pallas force kernel
CELL_SCATTER = "cell_scatter"      # per-slot forces -> agent order (a gather by slot)
DENSE_FALLBACK = "dense_fallback"  # candidate path taken on a cell overflow
STAGES = (CELL_GATHER, CELL_KERNEL, CELL_SCATTER, DENSE_FALLBACK)

# Host spans.
READ_STEP = "read_step"            # a chunk's start step, read to the host
LAUNCH = "launch"                  # the compiled step's dispatch
TRACE_SCHEDULE = "trace_schedule"  # one trace of the schedule: a (re)trace
HOST_SPANS = (READ_STEP, LAUNCH, TRACE_SCHEDULE)
