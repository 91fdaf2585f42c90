"""Roofline share of the Pallas wavefront kernel
(`repro.kernels.wavefront.serialize_prefix`): the least time its calls in
the traced window could take, from the operations and bytes the FCFS
serialization needs for their shapes (`bench.work.serialize_prefix`), over
the device time of the kernel's operations in the trace.

Each fitness call of P rows scans the CN graph's wavefronts; each step
serializes P x n_cores core queues and P x n_chan channel queues of
`width` items. The kernel carries no name of its own yet; it is the one
Pallas kernel (`tpu_custom_call`) on the explorer's path, so the reader
takes every such operation in the window."""

from bench.work import roofline_s, serialize_prefix


def read(rec):
    kernel_s = sum(s for name, s in rec["trace"]["ops"].items()
                   if "tpu_custom_call" in name)
    shape = rec.get("fitness_shape")
    if not kernel_s or not shape:
        return None
    least = 0.0
    for p in rec["fitness_rows"]:
        for queues in (shape["n_cores"], shape["n_chan"]):
            w = serialize_prefix(p * queues, shape["width"])
            least += shape["n_wavefronts"] * roofline_s(
                w["flops"], w["bytes"], rec["peaks"])
    return 100.0 * least / kernel_s
