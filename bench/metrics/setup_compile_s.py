"""Set-up seconds spent compiling programs or loading them from the
persistent compile cache (``backend_compile_duration``), as the engine's
``jax.monitoring`` listener sums them before the run's first sweep."""
import engine_log


def read(run):
    return engine_log.setup_seconds(run, "compile")
