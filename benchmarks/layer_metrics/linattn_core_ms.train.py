"""``linattn_core_ms.train``: device self time per step under the scope
``linattn_core`` (a linear-attention layer's convolutions, norms, decay and
chunked delta rule), all passes together."""
import scope_reduce


def read(run, result):
    return scope_reduce.scope_ms(run, result, "linattn_core")
