"""Work counted from shapes, and the chip peaks it is divided by."""
from __future__ import annotations

import math

#: published peaks per ``device_kind``.  Source: Google Cloud
#: documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s
#: per chip); JAX reports that chip as "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def param_count(config: dict) -> int:
    """d: weights and biases of every conv and dense layer."""
    n = 0
    for layer in config["layers"]:
        shape = layer.get("conv") or layer.get("dense")
        if shape:
            n += math.prod(shape) + shape[-1]
    return n


def macs_per_sample(config: dict) -> int:
    """Multiply-adds of one forward pass: each conv costs its kernel
    volume per output pixel (SAME keeps the size, VALID shrinks it by
    the kernel less one), each dense layer its weight count."""
    h, w, _ = config["input_shape"]
    macs = 0
    for layer in config["layers"]:
        if "conv" in layer:
            kh, kw, cin, cout = layer["conv"]
            if layer["padding"] == "VALID":
                h, w = h - kh + 1, w - kw + 1
            macs += h * w * kh * kw * cin * cout
        elif "pool" in layer:
            h, w = h // layer["pool"], w // layer["pool"]
        else:
            macs += math.prod(layer["dense"])
    return macs


def train_flops_per_sample(config: dict) -> int:
    """Forward (2 FLOPs per multiply-add) plus backward (twice the
    forward) of one training sample."""
    return 3 * 2 * macs_per_sample(config)


def flush_bytes(k: int, d: int) -> int:
    """HBM bytes one flush must move: two passes over the [K, d] f32
    stack (the divergence statistics, then the blended reduction) plus
    reading r twice and writing the [d] result.  Padding is not work."""
    return 2 * k * d * 4 + 3 * d * 4


def flush_roofline_pct(trace, k: int, config: dict, peaks: dict) -> float | None:
    """The time the traced flushes' bytes need at the chip's HBM
    bandwidth, over the summed device time of the flush kernels; None
    where the trace holds no flush kernel."""
    from bench import tracefile

    calls = trace.kernel_calls(tracefile.FLUSH_CALLS)
    spent = trace.kernel_s(tuple(tracefile.FLUSH_KERNELS))
    if not calls or spent <= 0.0:
        return None
    ideal = calls * flush_bytes(k, param_count(config)) / peaks["hbm_bytes_per_s"]
    return 100.0 * ideal / spent
