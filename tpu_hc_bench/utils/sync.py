"""Device synchronization for timing and draining.

``jax.block_until_ready`` is the documented way to wait for async
dispatch, and on a local chip it is truthful: it returns when the
buffers exist.  ``drain`` is that call over a pytree.

Timing code should not call ``drain`` per step — a host sync on the
dispatch path stalls the device behind the host — but observe
completion from a side thread (see ``train.driver._ArrivalFetcher``).
"""

from __future__ import annotations

import jax


def drain(tree):
    """Block until every array in a pytree is computed; returns the tree."""
    jax.block_until_ready(tree)
    return tree


def all_processes_any(flag: bool) -> bool:
    """Cross-host agreement: True iff ANY process passed True.

    The shared primitive for run-control decisions that must be
    unanimous — e.g. "stop and checkpoint now" on preemption, where a
    signal lands on one VM but a checkpoint written by half a mesh is
    garbage.  Single-process: a plain bool.  Multi-process: a tiny
    host-level allgather, so this is a COLLECTIVE — every process must
    call it at the same point (the driver calls it at sync-window
    boundaries, the same step everywhere).
    """
    import numpy as np

    if jax.process_count() <= 1:
        return bool(flag)
    from jax.experimental import multihost_utils

    votes = multihost_utils.process_allgather(
        np.asarray([1 if flag else 0], np.int32))
    return bool(np.max(votes))
