"""``mla_latent_ms.train``: device time per step in what a latent-attention
layer does before its attention call — the low-rank down- and
up-projections of q and K/V with their norms, the rotation, the broadcast of
the shared rotated key and the concatenations: the whole duration of the
instructions that carry the inner name ``mla_latent`` (inside the scope
``attn_proj``), all passes together."""
import scope_reduce


def read(run, result):
    whole = scope_reduce.inner_whole_s(run, result, "mla_latent")
    return whole and 1e3 * whole
