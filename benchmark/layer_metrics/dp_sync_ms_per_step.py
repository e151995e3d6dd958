"""Milliseconds per optimizer step during which a collective of the
data-parallel step was under way on chip 0: the operations of device scope
``dp_sync`` (`train/sharded_update.py`: the parameters' all-gather, the
gradients' all-reduce and reduce-scatter, the norm's and the loss's
reductions) and the start-to-done spans of its asynchronous collectives on
the trace's ``Async XLA Ops`` line, merged (`op_scopes.py`). Unlike
`allreduce_ms_per_step`, every kind of collective counts."""
import op_scopes


def read(result, cell):
    return op_scopes.ms_per_step(result, cell, op_scopes.in_scope("dp_sync"),
                                 spans=True)
