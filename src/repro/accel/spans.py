"""Names of the profiler spans the serving path opens.

Each span is a ``jax.profiler.TraceAnnotation``: a host event in the
profiler's own trace, on the clock of the device's events, and about a
microsecond of Python when no trace is running.  ``docs/accel.md``
(Observability) says what each covers and how to capture them.

The names live here, beside the engines, because ``serve_tm`` imports the
engines at package import: a module there would be an import cycle.
"""

BATCH = "tm.batch"  # Scheduler.run_slot_batch: the whole batch body
FORM = "tm.form"  # batch formation and shedding
H2D = "tm.h2d"  # PopcountEngine.class_sums: staging to the device
LAUNCH = "tm.launch"  # the asynchronous enqueue of the jitted step
D2H = "tm.d2h"  # the blocking read of the class sums
DEMUX = "tm.demux"  # argmax, demux and the recompile check
WAIT = "tm.wait"  # the scheduler loop asleep: no slot is due

ALL = (BATCH, FORM, H2D, LAUNCH, D2H, DEMUX, WAIT)
