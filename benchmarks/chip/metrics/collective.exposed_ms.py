"""collective.exposed_ms: milliseconds per step in which an all-reduce
(the psum over 'data' in ``reduce_then_psum``) runs on a chip and no
other op runs there, averaged over the chips. Nothing to read where no
collective runs.
"""


def read(ctx):
    lib = ctx.trace_lib
    has = any(lib.COLLECTIVE.search(name) for evs in ctx.trace["devices"].values()
              for name, _, _ in evs)
    if not has or ctx.steps <= 0:
        return None
    return 1e3 * lib.mean(lib.exposed_s(ctx.trace)) / ctx.steps
