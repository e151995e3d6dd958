"""Milliseconds per optimizer step in the recurrence's Mosaic kernels
(forward and backward, every layer), from the device trace of chip 0: the
operations whose label holds ``lstm_rec_``, the stem of every
`ops/pallas_lstm.py` kernel's ``name=`` (``lstm_rec_fwd_tiled.24``,
``lstm_rec_bwd.24``), inside the runs of the programs `op_scopes.json`
maps. Unlike `lstm_kernel_ms_per_step`, the head's kernels are not
counted."""
import op_scopes


def read(result, cell):
    return op_scopes.ms_per_step(
        result, cell, lambda e, scope: op_scopes.RECURRENCE in e.label)
