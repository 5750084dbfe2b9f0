"""Seconds inside the trainer's ``module_init_params`` before the window:
the initializer over the host arrays (``init_params_host``) and their
placement on the devices (``init_params_place``)."""
from chipbench import setup_ledger


def read(ctx):
    return setup_ledger.of_trainer(ctx, ("module_init_params",))
