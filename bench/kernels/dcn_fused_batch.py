"""Work of the fused BLI (+) conv kernel (``dcn_fused_batch``), from the
layer's shapes alone.

One call serves one deformable layer for every image of a step. What the
algorithm needs per output pixel, with K*K taps and C_in input channels:
bilinear sampling at 4 multiply-adds per tap and channel, and the conv
over the sampled features at K*K*C_in*C_out multiply-adds. It reads the
input plane, the weights and, per tap, 4 neighbour indices (int32) and 4
coefficients (f32), and writes the output plane. Tile padding, one-hot
widths and grid slots of an implementation are not counted, so the
count stays the same whatever computes it.
"""

# The kernel's operation in the device trace: the Pallas custom call that
# the jitted wrapper lowers to, ``%_dcn_fused_batch_jit.1 = ... custom-call(``.
TRACE_NAME = "%_dcn_fused_batch_jit"


def in_trace(op_name: str) -> bool:
    return op_name.startswith(TRACE_NAME) and "custom-call(" in op_name


def flops(hw: int, c_in: int, c_out: int, k: int, images: int) -> int:
    taps = hw * hw * images * k * k
    return 2 * 4 * taps * c_in + 2 * taps * c_in * c_out


def bytes_moved(hw: int, c_in: int, c_out: int, k: int, images: int,
                itemsize: int = 4) -> int:
    px = hw * hw * images
    planes = px * (c_in + c_out) * itemsize
    weights = (k * k * c_in + 1) * c_out * itemsize
    packed = px * k * k * 4 * (4 + 4)
    return planes + weights + packed


def calls(layers, widths, k: int = 3) -> list[tuple[int, int]]:
    """(FLOPs, bytes) of each call: one per deformable layer per step."""
    return [(flops(l.hw, l.c_in, l.c_out, k, n),
             bytes_moved(l.hw, l.c_in, l.c_out, k, n))
            for n in widths for l in layers if l.deform]
