"""Host time blocked in the executor's ``place`` per dispatched batch:
the serving loop's own timer around the host-to-device commit. It is
the host's wait, not the transfer's duration on the device."""


def read(ctx):
    occ = ctx.stats.get("occupancy")
    if occ is None or not occ["batches"]:
        return None
    return ctx.stats["h2d_s"] / occ["batches"] * 1e3
