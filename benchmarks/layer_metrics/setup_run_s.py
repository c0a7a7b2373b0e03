"""``setup_run_s``: ``setup_s`` less ``setup_reach_s`` less the union of all
``jit.*`` spans inside set-up — seconds in which nothing was being traced,
lowered, fetched or compiled: the check's steps on the device, parameters
drawn and moved, host glue."""
import setup_spans


def read(run, result):
    return setup_spans.part(run, result, "run_s")
