"""Share of trace slots the macro fast path (``engine.macro``) committed,
as the program counts it after each sweep (``last_macro_hit_rate``)."""


def read(run):
    hits = [h for _, h in run["sweeps"]]
    if not hits:
        return None
    return 100.0 * sum(hits) / len(hits)
