"""Seconds covered by the trainer's root set-up spans before the window
(``module_bind``, ``module_init_params``, ``module_init_optimizer``,
``module_step_build``, ``module_first_step`` of the module whose fused step
ran): what a user of ``Module.fit`` pays at a start, without the
benchmark's checks and without import."""
from chipbench import setup_ledger


def read(ctx):
    return setup_ledger.of_trainer(ctx)
