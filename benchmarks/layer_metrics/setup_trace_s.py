"""``setup_trace_s``: union of the program's ``jit.trace`` and ``jit.lower``
spans inside set-up — host Python that the persistent cache never saves.
Logs the set-up's programs by name beside it."""
import setup_spans


def read(run, result):
    setup_spans.log_programs(run, result)
    return setup_spans.part(run, result, "trace_s")
