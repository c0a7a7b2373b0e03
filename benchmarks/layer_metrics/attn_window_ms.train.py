"""``attn_window_ms.train``: device time per step in the attention calls
whose mask is a causal BAND — the whole duration of the instructions that
carry the inner name ``attn_window`` (inside the scope ``attn_core``), all
passes together."""
import scope_reduce


def read(run, result):
    whole = scope_reduce.inner_whole_s(run, result, "attn_window")
    return whole and 1e3 * whole
