"""device.idle_share: the share of the traced window in which no op ran
on a chip (%), averaged over the cell's chips. The trainer loop's host
cost (dispatch, batch upload, the sync at the end of each block) shows
here.
"""


def read(ctx):
    busy = ctx.trace_lib.busy_s(ctx.trace)
    window = ctx.trace_lib.window_s(ctx.trace)
    if not busy or window <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace_lib.mean(busy) / window)
