"""``dsa_roofline.train``: per cent of its roofline at which the sparse
layers' attention ran — the family's ``dsa_attention_cost`` (operations and
bytes of one sequence over the SELECTED pairs of every sparse layer through
a train step, the yardstick pinned there, whatever the implementation
multiplies) against the whole duration of the instructions that carry the
inner name ``attn_dsa``."""
import scope_reduce


def read(run, result):
    cost = getattr(run.family, "dsa_attention_cost", None)
    return cost and scope_reduce.roofline_share(
        run, result, "attn_dsa", cost(run.config, run.workload["seq"]))
