"""Global random state: counter-based threefry keys behind ``mx.random.seed``.

Reference analogue: the per-device parallel RNG resource
(``src/resource.cc``, ``ResourceRequest::kRandom``) seeded by
``mx.random.seed`` (``python/mxnet/random.py``).  TPU-native: one root key +
a split counter; every sampling op consumes a fresh subkey, so eager sampling
is reproducible given a seed, and jitted graphs thread keys explicitly.
"""
from __future__ import annotations

import contextlib
import threading
import types

import jax
import numpy as np

__all__ = ["seed", "next_key", "lend_root_key", "current_seed", "key_scope",
           "host_rng", "get_state", "set_state"]

# re-entrant: lend_root_key holds it while its borrower traces and launches
# a program, and a next_key() from that same thread must not deadlock
_lock = threading.RLock()
_seed = 0
_key = None  # lazily created: backend init must not run at import time
_host_rng = None  # np.random.Generator once seeded (host-side draws)
_scope = threading.local()  # per-thread key override stack (jit tracing)


def seed(seed_state, ctx="all"):
    """Seed the global RNG (reference: mx.random.seed)."""
    global _key, _seed, _host_rng
    with _lock:
        _seed = int(seed_state)
        _key = jax.random.PRNGKey(_seed)
        _host_rng = np.random.default_rng(_seed)


def host_rng():
    """The numpy RNG for host-side draws (initializers, shuffles).

    After ``mx.random.seed(n)`` this is a dedicated
    ``np.random.default_rng(n)`` Generator, so host randomness is governed
    by the framework seed instead of numpy's hidden module state (and
    never races third-party ``np.random`` users).  Before any ``seed()``
    call it falls back to the legacy ``np.random`` module so unseeded
    behavior is unchanged.  Both expose the same draw API surface used
    here (``uniform``/``normal``/``shuffle``/``permutation``).
    """
    return _host_rng if _host_rng is not None else np.random


def next_key():
    stack = getattr(_scope, "stack", None)
    if stack:
        # inside a key_scope (jit trace): split the scoped key so traced
        # programs thread randomness explicitly (may be a tracer)
        stack[-1], sub = jax.random.split(stack[-1])
        return sub
    global _key
    with _lock:
        if _key is None:
            _key = jax.random.PRNGKey(_seed)
        elif _key.committed:
            # handed back by a lend_root_key borrower: an output of its
            # program, committed to that program's device or replicated
            # over its mesh.  Keys drawn here go to any device, as the
            # uncommitted ones of PRNGKey/split always have, so bring the
            # root home once, here, and not at every consumer
            _key = jax.numpy.asarray(np.asarray(_key))
        _key, sub = jax.random.split(_key)
        return sub


@contextlib.contextmanager
def lend_root_key():
    """Lend the stream's root key to a program that advances it itself.

    ``with lend_root_key() as loan:`` holds the stream (every other
    ``next_key()`` waits).  The borrower passes ``loan.key`` into its
    compiled program, which does in its trace what :func:`next_key` does
    on the host — ``new_root, sub = jax.random.split(root)`` — and returns
    ``new_root``; the borrower stores that in ``loan.key`` before the block
    ends.  The stream is then bit-for-bit where one ``next_key()`` would
    have left it, and the host launched nothing.  If the block raises,
    the stream has not moved.

    The key handed out is the one stored: fresh from ``seed``/``next_key``,
    or the previous borrower's output, still on that program's devices —
    the borrower places it if it is not already its own.
    """
    global _key
    with _lock:
        if _key is None:
            _key = jax.random.PRNGKey(_seed)
        loan = types.SimpleNamespace(key=_key)
        yield loan
        _key = loan.key


class key_scope:
    """Thread randomness from an explicit key (used while jit-tracing)."""

    def __init__(self, key):
        self._key = key

    def __enter__(self):
        if not hasattr(_scope, "stack"):
            _scope.stack = []
        _scope.stack.append(self._key)
        return self

    def __exit__(self, *exc):
        _scope.stack.pop()


def current_seed():
    return _seed


def get_state():
    """Full RNG state as a host-side picklable dict (checkpointing).

    Captures the root jax key (as numpy), the seeded host Generator's
    bit-generator state, and — when :func:`seed` was never called — the
    legacy ``np.random`` module state, so a restored run replays the
    exact draw sequence (shuffles, initializers, key splits) either way.
    """
    with _lock:
        return {
            "seed": _seed,
            "key": None if _key is None else np.asarray(_key),
            "host": None if _host_rng is None
            else _host_rng.bit_generator.state,
            "host_legacy": np.random.get_state() if _host_rng is None
            else None,
        }


def set_state(state):
    """Restore a :func:`get_state` snapshot (checkpoint resume)."""
    global _seed, _key, _host_rng
    with _lock:
        _seed = int(state["seed"])
        _key = None if state["key"] is None \
            else jax.numpy.asarray(np.asarray(state["key"]))
        if state.get("host") is not None:
            _host_rng = np.random.default_rng(_seed)
            _host_rng.bit_generator.state = state["host"]
        else:
            _host_rng = None
            if state.get("host_legacy") is not None:
                np.random.set_state(state["host_legacy"])
