"""What a recomputation segment keeps.

``executor.py:run_segment`` runs a segment (``force_mirroring`` +
``mirror_stage``) under ``jax.checkpoint`` with the policy below: the
backward pass computes the segment's forward again, except the values an
op handed to ``keep``.  An op keeps a residual that is no larger than its
own output and that costs a kernel (or a sort) to remake: the flash
forward's ``out`` and ``lse`` (``ops/lm.py``), the routing's integer
results (``ops/moe.py``), the retention op's output
(``ops/pallas_kernels.py``: its backward remakes the chunk states it reads
from k, v and the gate, so the output is all the replay ran the forward
for).  What is kept is the very value the replay would remake, so no
arithmetic changes.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.ad_checkpoint import checkpoint_name

from .. import telemetry as _tel

_NAME = "remat_segment_kept"
POLICY = jax.checkpoint_policies.save_only_these_names(_NAME)

_tracing = threading.local()


@contextlib.contextmanager
def segment():
    """Around the ``jax.checkpoint`` call of one segment: the forward's
    trace and, when the call itself is differentiated (``jax.vjp`` inside
    the program, as every training path of the executor has it), its ops'
    forward rules run inside.  A graph function differentiated only after
    its trace marks nothing in those rules and replays as before."""
    _tracing.depth = getattr(_tracing, "depth", 0) + 1
    try:
        yield
    finally:
        _tracing.depth -= 1


def keep(*values):
    """*values*, named for ``POLICY`` when a segment is being traced and
    as they are otherwise: outside a segment an op traces no ``name``
    equation, and under a ``jax.checkpoint`` without the policy the name
    would be ignored anyway."""
    if not getattr(_tracing, "depth", 0):
        return values
    _tel.bump("executor_remat_kept", len(values))
    return tuple(checkpoint_name(v, _NAME) for v in values)
