"""Share of the traced window in which no operation ran on the device, in
the streaming aggregation cell (the union of the device's operation
intervals is its busy time; averaged over the chips the cell uses)."""


def read(view):
    if view.run.get("kind") != "agg" or not view.trace.devices:
        return None
    return 100.0 * view.trace.idle_share()
