"""Operations one chip's share of a batch requires (chipbench/flops.py,
analytic, nothing recomputed) / device busy time / published bf16 peak:
the compute-roofline share of the step program while the device is busy.
Not an end-to-end MFU."""


def read(ctx):
    t = ctx["trace"]
    batches = t and t["span_counts"].get("fit_step")
    if not batches or not t["busy_s_first"]:
        return None
    need = ctx["flops"].train_flops_per_item(ctx["cfg"]) * \
        ctx["facts"]["batch_per_chip"] * batches
    peak = ctx["peaks"].peak(ctx["device_kind"])["bf16_flops"]
    return 100.0 * need / t["busy_s_first"] / peak
