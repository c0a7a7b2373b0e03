"""``moe_ms.train``: device self time per step under the scope ``moe`` (the
router, top-k, grouping by expert, the grouped products and the combine),
all passes together."""
import scope_reduce


def read(run, result):
    return scope_reduce.scope_ms(run, result, "moe")
