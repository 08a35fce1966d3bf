"""The qint8 pack kernel's share of its HBM roofline: the least time the
bytes it needs take at the chip's HBM peak (read the float32 bucket,
write the int8 payload and a float32 scale per block:
``counts.qint8_pack_bytes``, over every learner parameter on this chip,
once per qint8 reduction of the round), over the kernel's device time
per round."""
from chipbench import codec


def read(ctx):
    return codec.roofline(ctx, "pack")
