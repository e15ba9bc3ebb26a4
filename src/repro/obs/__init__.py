"""Serving-time observability: the software analogue of the paper's
measured efficiency claims, wired through the whole serving stack.

The paper's headline numbers are MEASUREMENTS — per-MVM energy, TOPS/W,
EDP vs prior art (Fig. 4, Ext. Data Fig. 10) — but until this package the
serving stack could only reproduce them offline through bench scripts.
Four pieces, all host-side and outside every jit: recording adds no
device sync and no traced value, so tokens are bitwise the same with
every output on or off. Counters and histograms are updated at step
boundaries; the engine loop's phase spans also open and close around each
dispatch and wait, a few microseconds each, always on:

  * `metrics`   — process-local registry of counters / gauges /
                  log-bucketed histograms with JSON + Prometheus export.
  * `chipmeter` — per-compiled-chip dispatch meters: static `PackedPlan`
                  geometry x host-side dispatch counts x
                  `core/energy.mvm_cost` = modeled pJ/MVM, TOPS/W and
                  cumulative energy per chip / direction / request — the
                  serving-time realization of the paper's Fig. 4 energy
                  accounting (same model as bench_mapping's
                  `precision_serve_b*` rows).
  * `trace`     — `Tracer`, the one span API: engine-loop phase spans
                  (`serve.iter` and the phases nested in it) as JAX
                  profiler annotations on the profiler's clock, as the
                  `serve_phase_s{phase=...}` histogram, and, with a
                  `TraceBuffer` attached, as Chrome trace-event JSON
                  together with the per-request timelines (admit ->
                  prefill chunks -> decode steps -> finish), loadable in
                  Perfetto / chrome://tracing.
  * `jitwatch`  — jit wrappers that count traces and compile time per
                  entry point, turning the one-trace-per-plan /
                  pinned-out_shardings contract (PR 7's GSPMDSharding
                  cache-miss bug, lint rule R001) into a runtime metric
                  plus an opt-in hard assertion.

`clock` is the ONE serve-path wall clock (`timed_call` / `now` /
`stopwatch`): benchmarks/_timing re-exports it, launch/* route through it
(lint rule R006 keeps bare `time.time()` off serving-path modules), and
its measurements are what feed the metrics histograms.
"""
from . import clock  # noqa: F401
from .chipmeter import ChipMeter  # noqa: F401
from .jitwatch import JitRetraceError, JitWatcher  # noqa: F401
from .metrics import (MetricsRegistry, dict_to_prometheus,  # noqa: F401
                      merge_registries)
from .trace import TraceBuffer, Tracer  # noqa: F401
