"""What the readers of a named kernel's metrics share: its device time a
step and its roofline share, from the seconds ``chipbench/kernel_time.py``
summed under the configuration's ``trace_patterns`` (the runner hands them
over as ``facts["kernel_s"]``).  Without a trace, a device plane or a
matching event there is nothing to read: None, never a zero."""
import importlib


def seconds_and_steps(ctx, kernel):
    t = ctx["trace"]
    steps = t and t["span_counts"].get("fit_step")
    seen = (ctx["facts"].get("kernel_s") or {}).get(kernel)
    if not steps or not seen or not seen["seconds"]:
        return None
    return seen["seconds"], steps


def ms_per_step(ctx, kernel):
    """First-device time a step inside the kernel's events; what
    recomputation repeats is in it."""
    found = seconds_and_steps(ctx, kernel)
    return found and 1e3 * found[0] / found[1]


def roofline_pct(ctx, kernel, work_key):
    """The least time the chip could take for what a step requires of the
    kernel — the larger of required FLOPs over the published bf16 peak and
    required HBM bytes over the published bandwidth, both from the
    function the configuration names under *work_key*, (cfg, tokens a
    chip a step) -> (FLOPs, bytes) — over the device time inside it."""
    found = seconds_and_steps(ctx, kernel)
    if not found or work_key not in ctx["cfg"]:
        return None
    module, _, fn = ctx["cfg"][work_key].partition(":")
    flops, nbytes = getattr(importlib.import_module(module), fn)(
        ctx["cfg"], ctx["facts"]["batch_per_chip"])
    peak = ctx["peaks"].peak(ctx["device_kind"])
    floor_s = max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_s"])
    return 100.0 * floor_s * found[1] / found[0]
