"""``mla_roofline.train``: per cent of its roofline at which the latent
attention ran — the family's ``mla_attention_cost`` (operations and bytes of
one sequence through every latent-attention call of a train step, the
yardstick pinned there: the model's 192 and 128, whatever the kernel pads
to) against the whole duration of the instructions that carry the inner name
``attn_mla``."""
import scope_reduce


def read(run, result):
    cost = getattr(run.family, "mla_attention_cost", None)
    return cost and scope_reduce.roofline_share(
        run, result, "attn_mla", cost(run.config, run.workload["seq"]))
