"""Milliseconds per optimizer step chip 0 spends in the LSTM layer stack of
the train program: the operations of device scope ``lstm`` (`lm_backbone`'s
`stacked_lstm_scan`: input projections, the recurrence kernels, dropout and
the between-layer operations, and their gradients), the ``lstm_rec_``
kernels with them (`op_scopes.py`)."""
import op_scopes


def read(result, cell):
    return op_scopes.ms_per_step(result, cell, op_scopes.in_scope("lstm"))
