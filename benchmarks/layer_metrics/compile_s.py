"""``compile_s``: JAX's trace + lower + backend-compile seconds of the whole
set-up (``CompileMeter``); a persistent-cache hit counts its retrieval."""


def read(run, result):
    return run.setup_meter["compile_s"] if run.setup_meter else None
