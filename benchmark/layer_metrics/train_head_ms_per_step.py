"""Milliseconds per optimizer step chip 0 spends in the LM head and its
loss: the operations of device scope ``head`` (`lm_loss` / `ops/xent.py`:
the logits kernels or XLA's logits, logsumexp and target logit, the weight
and bias gradients and ``dys``), the ``lm_head_`` kernels with them
(`op_scopes.py`)."""
import op_scopes


def read(result, cell):
    return op_scopes.ms_per_step(result, cell, op_scopes.in_scope("head"))
