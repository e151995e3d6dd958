"""Milliseconds per optimizer step chip 0 spends in the embedding: the
operations of device scope ``embed`` (`lm_backbone`'s `embed_lookup`, and
its gradient; `op_scopes.py`)."""
import op_scopes


def read(result, cell):
    return op_scopes.ms_per_step(result, cell, op_scopes.in_scope("embed"))
