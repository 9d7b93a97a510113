"""Published peaks of the chips the benchmark may run on, keyed by
``device_kind``.  The benchmark's own copy: a later PR cannot move the
yardstick by editing the program's table (``mxnet_tpu.runtime.DEVICE_PEAKS``).

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect per chip.  A device that is not listed is an error,
never a default.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError("no published peaks for device kind %r; add a row with "
                       "its source to benchmarks/harness/peaks.py"
                       % (device_kind,)) from None
