"""Published peaks, keyed by ``device_kind`` as JAX reports it.  The only
place a peak is defined for the benchmark; a device that is not here is an
error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (one chip): 197 TFLOP/s bf16,
    # 16 GB HBM2e at 819 GB/s.  Copied from mxnet_tpu/telemetry/costs.py
    # PEAK_TABLE so that a later change there cannot move the yardstick.
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peak(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError("no published peak for device_kind %r in "
                       "chipbench/peaks.py; add a sourced row" % device_kind)
