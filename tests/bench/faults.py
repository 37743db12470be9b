"""Faults planted under the timed path, for the harness tests: each must
turn a run's ``correct`` false. Each returns a context manager that
patches the program for the length of one run."""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.serve import UOTScheduler


def _patch(obj, name, fn):
    @contextlib.contextmanager
    def cm():
        orig = getattr(obj, name)
        setattr(obj, name, fn(orig))
        try:
            yield
        finally:
            setattr(obj, name, orig)
    return cm()


def _alter(P):
    """The answer with its largest entry 1% too large."""
    i = jnp.argmax(P)
    return P.reshape(-1).at[i].multiply(1.01).reshape(P.shape)


# one-shot solve: ops.solve_fused
def solve_unchanged():
    return _patch(ops, "solve_fused", lambda orig: (
        lambda K, a, b, cfg, **kw: (K, K.sum(axis=0))))


def solve_altered():
    def wrap(orig):
        def fn(K, a, b, cfg, **kw):
            P, colsum = orig(K, a, b, cfg, **kw)
            return _alter(P), colsum
        return fn
    return _patch(ops, "solve_fused", wrap)


# the service: one chunk of the scheduler's lanes, and its answers
def chunk_unchanged():
    return _patch(ops, "solve_fused_stepped", lambda orig: (
        lambda state, n_iters, cfg, **kw: state))


def chunk_half_the_lanes():
    """Advance only the first half of the lanes of every pool."""
    def wrap(orig):
        def fn(state, n_iters, cfg, **kw):
            new = orig(state, n_iters, cfg, **kw)
            L = state.P.shape[0]
            keep = jnp.arange(L) < L // 2
            return jax.tree.map(
                lambda n, o: jnp.where(
                    keep.reshape((L,) + (1,) * (n.ndim - 1)), n, o),
                new, state)
        return fn
    return _patch(ops, "solve_fused_stepped", wrap)


def answers_altered():
    def wrap(orig):
        def step(self):
            out = orig(self)
            return {rid: P * (1 + 0.01 * (P == P.max()))
                    for rid, P in out.items()}
        return step
    return _patch(UOTScheduler, "step", wrap)
