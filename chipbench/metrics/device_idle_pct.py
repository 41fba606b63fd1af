"""Share of the traced steady steps in which no operation ran on the
device, in %, mean over the chips."""
from chipbench.readers import idle_pct


def read(inputs):
    return idle_pct(inputs)
