"""qps: queries answered in the window over the time from its start to the
return of its last request."""


def read(ctx):
    log = ctx.log
    return log.items() / log.end if log.calls else None
