"""``mfu.train``: model FLOP/s utilisation.  The operations the forward and
backward passes require per sample (the family's analytic count;
recomputation is not counted) x samples/s, over chips x the chip's bf16
peak.  An end-to-end utilisation, not a kernel's roofline share."""


def read(run, result):
    rate = result.end_to_end.get("train_samples_per_s")
    flops = result.window.get("flops_per_sample")
    if not rate or not flops:
        return None
    peak = run.peaks["bf16_flops_per_s"] * result.window["chips"]
    return 100.0 * flops * rate / peak
