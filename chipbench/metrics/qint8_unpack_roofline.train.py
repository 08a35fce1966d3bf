"""The qint8 unpack kernel's share of its HBM roofline: read the int8
payload and scales, write float32 (``counts.qint8_unpack_bytes``), as
for the pack."""
from chipbench import codec


def read(ctx):
    return codec.roofline(ctx, "unpack")
