"""Model FLOP utilization of the whole training step: the training FLOPs
per sample (the architecture's ``counts``; for AlexNet ``bench.flops``)
times the window's samples per second, over the cell's chips times the
published bf16 peak.  AlexNet's step runs in float32 at the TPU's default
precision (one bf16 pass), so the bf16 peak is the chip's ceiling for it."""


def read(rec):
    rate = len(rec["steps"]) * rec["batch"] / rec["window_s"]
    return (100.0 * rec["train_flops_per_sample"] * rate
            / (rec["chips"] * rec["peak_flops"]))
