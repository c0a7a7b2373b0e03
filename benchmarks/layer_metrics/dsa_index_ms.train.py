"""``dsa_index_ms.train``: device time per step in the sparse layers'
lightning indexer — the whole duration of the instructions that carry the
inner name ``dsa_index`` (inside the scope ``attn_core``: the indexer's
projections, the pooling of its keys, its scores, the top-k and the mask),
all passes together."""
import scope_reduce


def read(run, result):
    whole = scope_reduce.inner_whole_s(run, result, "dsa_index")
    return whole and 1e3 * whole
