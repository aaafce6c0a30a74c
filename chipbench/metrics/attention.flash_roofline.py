"""Share of the flash kernels' roofline: the least time their calls in
the traced window could take, over the device time of those calls
(``flash_fwd``, ``flash_dq``, ``flash_dkv`` events). A call's least time
is ``chipbench/flops.py:flash_call`` for the shapes its own operands
carry in the trace (q streams, kv streams, sequence, head size): the
larger of its causal FLOPs over peak and its bytes over HBM bandwidth."""
import re

from chipbench import flops

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")
_OPERAND = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")


def call_shape(text):
    """(q streams, kv streams, sequence, head size) of one call."""
    args = text.split("custom-call(", 1)
    if len(args) < 2:
        return None
    shapes = [tuple(map(int, s)) for s in _OPERAND.findall(args[1])]
    if not shapes:
        return None
    q = shapes[0]
    return q[0], min(s[0] for s in shapes), q[1], q[2]


def read(ctx):
    red, peak = ctx["trace"], ctx["peak"]
    if red is None or peak is None:
        return None
    need = spent = 0.0
    for k in KERNELS:
        for call in red.kernel(k):
            shape = call_shape(call.text)
            if shape is None:
                return None
            need += flops.roofline_seconds(flops.flash_call(k, *shape), peak)
            spent += call.end - call.start
    if spent <= 0:
        return None
    return 100.0 * need / spent
