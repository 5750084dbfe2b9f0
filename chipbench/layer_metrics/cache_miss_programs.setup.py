"""Programs compiled before the window that the persistent cache did not
serve (ledger rows with ``cache`` other than ``hit``): 0 is a warm start,
the run's ``compiles_before_window`` a cold one."""
from chipbench import setup_ledger


def read(ctx):
    return setup_ledger.of_rows(ctx, setup_ledger.cache_miss_programs)
