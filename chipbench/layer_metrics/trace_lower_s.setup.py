"""Seconds JAX spent tracing and lowering the programs compiled before the
window (the ledger's ``trace_s + lower_s``): host Python, paid warm or
cold, since the persistent cache is asked only with a lowered module in
hand."""
from chipbench import setup_ledger


def read(ctx):
    return setup_ledger.of_rows(ctx, setup_ledger.trace_lower_s)
