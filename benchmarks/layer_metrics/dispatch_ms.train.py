"""``dispatch_ms.train``: the host's time to enqueue one step call, not
waiting for the device; median over the window's calls."""
import statistics


def read(run, result):
    d = result.window.get("dispatch_s")
    return statistics.median(d) * 1e3 if d else None
