"""``attn_dsa_ms.train``: device time per step in the sparse layers'
attention over the selected positions — the whole duration of the
instructions that carry the inner name ``attn_dsa`` (inside the scope
``attn_core``), all passes together."""
import scope_reduce


def read(run, result):
    whole = scope_reduce.inner_whole_s(run, result, "attn_dsa")
    return whole and 1e3 * whole
