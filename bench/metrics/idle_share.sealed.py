"""``idle_share.stream``'s reading in the sealed aggregation cell, under a
name of its own because that cell reports ``updates_per_s.sealed``."""


def read(view):
    if view.run.get("kind") != "agg" or not view.trace.devices:
        return None
    return 100.0 * view.trace.idle_share()
