"""Set-up seconds spent tracing the program's Python to jaxprs
(``jaxpr_trace_duration``), as the engine's ``jax.monitoring`` listener
sums them before the run's first sweep."""
import engine_log


def read(run):
    return engine_log.setup_seconds(run, "trace")
