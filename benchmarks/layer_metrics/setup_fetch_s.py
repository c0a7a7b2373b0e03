"""``setup_fetch_s``: union of the program's ``jit.compile{cache=hit}`` spans
inside set-up — executables read from the persistent cache, deserialised and
loaded; nothing in a run without a hit."""
import setup_spans


def read(run, result):
    return setup_spans.part(run, result, "fetch_s")
