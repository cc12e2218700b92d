"""build_vps: vectors indexed by the window's completed builds over the time
from its start to the last completion."""


def read(ctx):
    log = ctx.log
    return log.items() / log.end if log.calls else None
