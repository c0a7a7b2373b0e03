"""``collective_mb.train``: megabytes (1e6 bytes) one chip hands to the
collective instructions of its step program, per step — a count from the
step's own optimized HLO (an instruction inside a loop as often as the loop
runs), not a time.  None for a step without a collective."""
import scope_reduce


def read(run, result):
    text = scope_reduce.step_hlo(run, result)
    if text is None:
        return None
    return sum(scope_reduce.collective_bytes(text).values()) / 1e6 or None
