"""Milliseconds per optimizer step chip 0 spends in the weight update: the
operations of device scope ``optimizer`` (`train/loop.py::step_body`: the
gradient norm and clip, the optax update, the non-finite guard), a
collective inside it excepted (``dp_sync``; `op_scopes.py`)."""
import op_scopes


def read(result, cell):
    return op_scopes.ms_per_step(result, cell,
                                 op_scopes.in_scope("optimizer"))
