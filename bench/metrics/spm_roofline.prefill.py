"""SPM kernels' share of their roofline in prefill: the least time for the
SPM forward work of the prompts prefilled while the profiler ran
(counted at their real lengths, so bucket padding is not work), over the
device time of the SPM kernel events inside prefill programs.  The bytes
bound applies."""

SPM = r"spm"
PREFILL = r"prefill"


def read(ctx):
    lens = ctx.host["traced_prompt_lens"]
    spm_s = sum(ctx.tr.op_seconds(ctx.tr.matching(ops, SPM, PREFILL))
                for ops in ctx.ops) / max(len(ctx.ops), 1)
    if not lens or spm_s <= 0:
        return None
    f = b = 0.0
    for n in lens:
        w = ctx.work.model_spm_work(ctx.shape, n)
        f, b = f + w["flops"], b + w["bytes"]
    least = max(f / ctx.peaks["peak_flops"], b / ctx.peaks["hbm_bw"])
    return 100.0 * least / spm_s
