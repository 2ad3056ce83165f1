"""Mean wall time of one `FleetRefresher.refresh` call (due tasks already
ingested), from the benchmark's span around it."""


def read(ctx):
    sp = ctx["spans"]
    n = sp.count.get("refresh.pass", 0)
    if not n:
        return None
    return 1e3 * sp.total["refresh.pass"] / n
