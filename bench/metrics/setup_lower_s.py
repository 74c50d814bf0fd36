"""Set-up seconds spent lowering jaxprs to MLIR modules
(``jaxpr_to_mlir_module_duration``), as the engine's ``jax.monitoring``
listener sums them before the run's first sweep."""
import engine_log


def read(run):
    return engine_log.setup_seconds(run, "lower")
