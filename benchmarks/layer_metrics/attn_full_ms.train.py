"""``attn_full_ms.train``: device time per step in the attention calls over
the whole causal triangle — the whole duration of the instructions that carry
the inner name ``attn_full`` (inside the scope ``attn_core``), all passes
together."""
import scope_reduce


def read(run, result):
    whole = scope_reduce.inner_whole_s(run, result, "attn_full")
    return whole and 1e3 * whole
