"""Model FLOP utilisation of the traced steady steps, in % of the chips'
bf16 peak (``peaks.json``)."""
from chipbench.readers import mfu_pct


def read(inputs):
    return mfu_pct(inputs)
