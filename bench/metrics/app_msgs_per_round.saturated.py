"""app_msgs_per_round.saturated: app messages the senders published
per round inside the window (Spindle sender batching)."""


def read(run):
    n = run.values.get("window_rounds", 0)
    return run.values["app_published_in_window"] / n if n else None
