"""Shared arithmetic of the ``idle_pct.*`` readers: 1 - device busy
time over the traced window, in percent."""


def read(ctx):
    s = ctx.summary
    if not s or s.busy_s <= 0 or not s.window_s:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
