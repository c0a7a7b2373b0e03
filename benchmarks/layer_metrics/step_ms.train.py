"""``step_ms.train``: the median time between two step completions at the
host (``block_until_ready``), per dispatched call.  The steady step time: a
stall of the host or the machine moves the window's rate and not this."""
import statistics


def read(run, result):
    gaps = result.window.get("completion_gaps_s")
    return statistics.median(gaps) * 1e3 if gaps else None
