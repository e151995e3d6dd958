"""Share of the traced steady window in which no operation ran on the chip
(mean over the chips used): 1 - union of device-operation intervals."""
import trace_reduce


def read(result, cell):
    return trace_reduce.idle_share(result, cell)
