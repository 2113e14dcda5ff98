"""idle_share (%): share of the traced window in which no operation ran on
the device, on the least busy chip of the cell (the one a synchronous step
waits for least is still the one whose idle time shows first)."""


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace
    return 100.0 * (1.0 - min(t.busy_s.values()) / t.window_s)
