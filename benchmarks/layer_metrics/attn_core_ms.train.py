"""``attn_core_ms.train``: device self time per step under the scope
``attn_core`` (scores, mask, softmax, PV — what a flash kernel would
replace), all passes together."""
import scope_reduce


def read(run, result):
    return scope_reduce.scope_ms(run, result, "attn_core")
