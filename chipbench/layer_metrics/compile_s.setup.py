"""Seconds in the backend over every compile before the window, whoever
asked for it (the trainer, the benchmark's reference programs, an eager
op): XLA's compile on a miss, the retrieval on a hit.  What ``setup_s``
pays XLA or the persistent cache; from the program's compile ledger."""
from chipbench import setup_ledger


def read(ctx):
    return setup_ledger.of_rows(ctx, setup_ledger.compile_s)
