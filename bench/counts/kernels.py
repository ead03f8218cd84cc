"""Operations and bytes of the program's Pallas kernels, from shapes.

Each function returns the HBM bytes a call must move and the floating
point operations it must do, for the least time ``max(bytes / bandwidth,
flops / peak)`` a roofline share divides by the kernel's measured time.
"""

TILE = 512  # the fused_update op pads the flat vector to this multiple


def fused_update(n_params: int):
    """``kernels/fused_update``: w - lr * (B*(g + bv) - s*dB*gc) / max(B -
    s*dB, 1) over the flattened parameters, padded to the tile.  Reads w,
    g, bv, gc and writes the result: five passes over the vector; seven
    operations an element."""
    p = -(-int(n_params) // TILE) * TILE
    return {"bytes": 5 * p * 4, "flops": 7 * p}  # f32
