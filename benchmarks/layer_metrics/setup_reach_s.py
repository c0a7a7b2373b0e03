"""``setup_reach_s``: process start to the program's ``process.ready`` mark
(``utils.compile_cache.enable_compile_cache``): interpreter, imports and JAX
reaching the chip — the machine's share of set-up."""
import setup_spans


def read(run, result):
    return setup_spans.part(run, result, "reach_s")
