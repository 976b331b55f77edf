"""SPM kernels' share of their roofline in a training step: the least time
the chip needs for the SPM work of the traced steps (forward and backward,
counted from the model's SPM sites by ``work.py``), over the device time
of every SPM kernel event.  The bytes bound applies: the SPM stages are
vector work, far under the matrix unit's peak."""

SPM = r"spm"


def read(ctx):
    n = ctx.host["steps_traced"]
    spm_s = sum(ctx.tr.op_seconds(ctx.tr.matching(ops, SPM))
                for ops in ctx.ops) / max(len(ctx.ops), 1)
    if not n or spm_s <= 0:
        return None
    rows = ctx.host["batch"] * ctx.host["seq"]
    f = ctx.work.model_spm_work(ctx.shape, rows)
    b = ctx.work.model_spm_work(ctx.shape, rows, backward=True)
    least = n * max((f["flops"] + b["flops"]) / ctx.peaks["peak_flops"],
                    (f["bytes"] + b["bytes"]) / ctx.peaks["hbm_bw"])
    return 100.0 * least / spm_s
