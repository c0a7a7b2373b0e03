"""``attn_mla_ms.train``: device time per step in the latent-attention calls
(192-wide scores over 128-wide values) — the whole duration of the
instructions that carry the inner name ``attn_mla`` (inside the scope
``attn_core``), every layer's and the prediction module's, all passes
together."""
import scope_reduce


def read(run, result):
    whole = scope_reduce.inner_whole_s(run, result, "attn_mla")
    return whole and 1e3 * whole
