"""collective_exposed_ms (ms): per step, the time a device spends in
collective operations while no other operation runs on it, from the trace;
the mean over the chips.  A trace with no collective returns nothing."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.collective_exposed_s:
        return None
    t = ctx.trace
    per_dev = sum(t.collective_exposed_s.values()) / len(t.collective_exposed_s)
    return 1e3 * per_dev / t.steps
