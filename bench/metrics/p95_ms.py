"""p95_ms: 95th percentile over every request of the window of the time from
its scheduled send to the return of its answer, in milliseconds."""
import numpy as np


def read(ctx):
    log = ctx.log
    if not log.done:
        return None
    latency = np.asarray(log.done) - np.asarray(log.scheduled)
    late = np.asarray(log.late_s) if log.late_s else np.zeros(1)
    ctx.say(
        f"open loop: {latency.size} requests in {len(log.calls)} calls, "
        f"p50 {float(np.percentile(latency, 50)) * 1e3!r} ms, "
        f"p99 {float(np.percentile(latency, 99)) * 1e3!r} ms, "
        f"dispatch later than arrival by {float(np.mean(late)) * 1e3!r} ms on average"
    )
    return float(np.percentile(latency, 95) * 1e3)
