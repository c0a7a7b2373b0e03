"""``device_idle_share.train``: 1 - (union of the leaf device operations'
intervals) / traced window, averaged over the chips used."""


def read(run, result):
    red = run.trace.reduction
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
