"""``unscoped_share.train``: the share of the device's busy time in
operations whose ``op_name`` carries none of the declared scopes — what the
instrumentation has lost (collectives are not counted as lost)."""
import scope_reduce


def read(run, result):
    out = scope_reduce.by_phase_and_scope(run, result)
    if out is None or out["busy_s"] <= 0:
        return None
    loose = sum(cell.get(scope_reduce.UNSCOPED, 0.0)
                for cell in out["phases"].values())
    return 100.0 * loose / out["busy_s"]
