"""Share of its roofline that the device resize kernel reached: the least
time the chip could take for the kernel's calls in the window (the larger
of its FLOPs over the bf16 peak and its bytes over the HBM bandwidth, from
shapes, ``bench.flops``) over the kernel's summed device time.  The bytes
bound it at these shapes: 25.6 MB against 5.3 GFLOP a batch of 32."""
from bench.trace_reduce import matching

# the Pallas call is the custom-call named after its jitted function
KERNEL = r"^resize_convert_images"


def read(rec):
    d = rec["device"]
    if not d or not rec["uses_resize_kernel"]:
        return None
    t, n = matching(d["op_s"], KERNEL), matching(d["op_n"], KERNEL)
    if t <= 0 or n <= 0:
        return None
    least = max(rec["resize"]["flops"] / rec["peak_flops"],
                rec["resize"]["bytes"] / rec["peak_bytes_s"])
    return 100.0 * n * least / t
