"""delivered_msgs_per_s: app messages delivered at every member of
their subgroup inside the window, counted once each, over the window's
wall time."""


def read(run):
    n = run.values.get("delivered_in_window")
    return n / run.window_s if n else None
