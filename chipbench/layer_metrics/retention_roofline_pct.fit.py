"""Roofline share of the power-retention ops: the least time the chip
could take for the work a step requires of them — the larger of required
FLOPs over the published bf16 peak and required HBM bytes over the
published bandwidth, both from the configuration's shapes alone
(``chipbench/flops_lm.py``: the forward and a backward of twice its cost)
— over the device time inside them.  What recomputation repeats is in
the time and not in the work, so the share errs low."""
from chipbench import flops_lm


def read(ctx):
    t = ctx["trace"]
    batches = t and t["span_counts"].get("fit_step")
    kernel = (ctx["facts"].get("kernel_s") or {}).get("retention")
    if not batches or not kernel or not kernel["seconds"] or \
            "retention_work" not in ctx["cfg"]:
        return None
    flops, nbytes = flops_lm.retention_train_work(
        ctx["cfg"], ctx["facts"]["batch_per_chip"])
    peak = ctx["peaks"].peak(ctx["device_kind"])
    least = max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_s"])
    return 100.0 * least * batches / kernel["seconds"]
