"""``collective_mb.train``: megabytes (1e6 bytes) one chip hands to the
collective instructions of its step program, per step — a count from the
step's own optimized HLO, not a time."""
import scope_reduce


def read(run, result):
    # a traced run of a cell that lists no device reader still logs its
    # pass x scope table and its time in collectives through this call
    scope_reduce.by_phase_and_scope(run, result)
    text = scope_reduce.step_hlo(run, result)
    if text is None:
        return None
    return sum(scope_reduce.collective_bytes(text).values()) / 1e6
