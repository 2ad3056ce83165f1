"""Share of the window spent inside `observe_many`, the ingest of the
completions that make tasks due, from the benchmark's spans."""


def read(ctx):
    sp = ctx["spans"]
    if "ingest.observe_many" not in sp.total or ctx["window_s"] <= 0:
        return None
    return 100.0 * sp.total["ingest.observe_many"] / ctx["window_s"]
