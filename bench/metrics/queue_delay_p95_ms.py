"""95th percentile of the scheduler's own queue delay (dispatch minus
arrival, on the serving loop's clock), over every acquisition."""


def read(ctx):
    qd = ctx.stats.get("queue_delay")
    return None if qd is None else qd["p95_s"] * 1e3
