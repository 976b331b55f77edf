"""SPM kernels' share of their roofline in a training step of a mixture
of experts: ``spm_roofline.train``'s definition over this model's SPM
sites.  Per layer those are the attention maps (q, k, v, o) at
batch x seq rows and each expert's three maps (gate and up,
``hidden_size -> moe_intermediate_size``; down, back) at batch x seq x
``num_experts_per_tok`` routed rows — the rows that padding adds to fill
an expert's last tile are not work the model requires.  Forward and
backward are counted by ``work.spm_work``, whose bytes hold one map's
parameters: an expert map reads all ``num_experts`` experts' parameters,
so the other experts' are added.  The least time, the larger of FLOPs at
the peak and bytes at HBM bandwidth over the traced steps, is divided by
the device time of every op named ``spm``.  ``None`` for a configuration
without experts."""

SPM = r"spm"


def spm_work(work, shape, tokens):
    """FLOPs and HBM bytes of one step's SPM work, forward and backward,
    over ``tokens`` tokens."""
    d, hd = shape["hidden_size"], shape["head_dim"]
    q = shape["num_attention_heads"] * hd
    kv = shape["num_key_value_heads"] * hd
    f, k = shape["moe_intermediate_size"], shape["num_experts_per_tok"]
    E = shape["num_experts"]
    sites = [(d, q, 1, 1), (d, kv, 1, 1), (d, kv, 1, 1), (q, d, 1, 1),
             (d, f, k, E), (d, f, k, E), (f, d, k, E)]
    flops = nbytes = 0.0
    for a, b, per_token, held in sites:
        pbytes = work.spm_param_count(a, b) * work.PARAM_BYTES
        for backward, reads in ((False, 1), (True, 2)):
            w = work.spm_work(a, b, tokens * per_token, backward)
            flops += w["flops"]
            nbytes += w["bytes"] + (held - 1) * reads * pbytes
    L = shape["num_hidden_layers"]
    return {"flops": flops * L, "bytes": nbytes * L}


def read(ctx):
    n = ctx.host["steps_traced"]
    if "num_experts" not in ctx.shape:
        return None
    spm_s = sum(ctx.tr.op_seconds(ctx.tr.matching(ops, SPM))
                for ops in ctx.ops) / max(len(ctx.ops), 1)
    if not n or spm_s <= 0:
        return None
    w = spm_work(ctx.work, ctx.shape, ctx.host["batch"] * ctx.host["seq"])
    least = n * max(w["flops"] / ctx.peaks["peak_flops"],
                    w["bytes"] / ctx.peaks["hbm_bw"])
    return 100.0 * least / spm_s
