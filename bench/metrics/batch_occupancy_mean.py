"""Mean number of acquisitions in a dispatched batch (the scheduler's
count), against the padded batch size."""


def read(ctx):
    occ = ctx.stats.get("occupancy")
    return None if occ is None else occ["mean_occupancy"]
