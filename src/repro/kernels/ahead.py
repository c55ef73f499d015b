"""Compile ahead: start the compiles of programs a caller is about to run.

A cold prover on a TPU spends most of its time compiling.  A sum-check
over 2^m values runs m rounds, each round length its own pair of kernel
programs, and a Merkle tree compiles one compression per level.  The
caller knows all of those shapes before its first round, so it hands
them to a small thread pool here, which lowers and compiles them while
the rounds run: XLA compiles release the GIL and overlap one another.  A
later call of the jitted function with the same shapes and static
arguments finds the executable in JAX's in-memory cache and compiles
nothing.  The caller waits on each program's future (``wait``) before
its call, so no program is compiled twice.

Lock order (ranked in repro.analysis.locks): ``_LOCK`` is a rank-70
leaf; nothing else is taken while it is held.
"""
from __future__ import annotations

import concurrent.futures
import os
import threading

import jax
import numpy as np

_LOCK = threading.Lock()
_POOL = None
_STARTED: dict = {}


def _pool() -> concurrent.futures.ThreadPoolExecutor:
    global _POOL
    if _POOL is None:
        _POOL = concurrent.futures.ThreadPoolExecutor(
            max_workers=min(8, max(2, (os.cpu_count() or 2) - 1)),
            thread_name_prefix="compile-ahead")
    return _POOL


def _sig(x):
    return (tuple(x.shape), np.dtype(x.dtype).str) if hasattr(
        x, "shape") else x


def start(fn, *args, **static) -> concurrent.futures.Future:
    """Start compiling jitted ``fn`` for ``args`` (``ShapeDtypeStruct``s
    and static arguments, passed as the call will pass them); returns
    the compile's future.  One future per program: asking again for a
    program already started returns the same future."""
    leaves, tree = jax.tree_util.tree_flatten(args)
    key = (fn, tree, tuple(_sig(a) for a in leaves),
           tuple(sorted(static.items())))
    with _LOCK:
        fut = _STARTED.get(key)
        if fut is None:
            fut = _pool().submit(lambda: fn.lower(*args, **static).compile())
            _STARTED[key] = fut
    return fut


# Imported below ``start``: a Pallas kernel lowered in the pool carries
# the source location of ``start``'s lambda in its compile-cache key, so
# a line added above it would have every kernel started here compiled
# anew.
from repro import spans  # noqa: E402


def wait(fut: concurrent.futures.Future):
    """The compile's result, once done; the time this thread blocks on
    it is compile wait of the engine call it works for."""
    if fut.done():
        return fut.result()
    with spans.compile_wait():
        return fut.result()
