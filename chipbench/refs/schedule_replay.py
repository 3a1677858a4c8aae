"""Plain replay of an alltoall schedule's data flow, for the check that
decides ``correct`` in the planner cell.

An alltoall over ``p`` processes has ``p * p`` blocks; block ``a * p + b``
starts on process ``a`` and must end on process ``b``.  A schedule is a list
of rounds, each a list of messages ``(src, dst, blocks)``.  In a round a
process may send only blocks it held before the round began; what it
receives is held from the next round on.  The replay keeps a dense
held-matrix and counts every breach: a message naming a process or block
that does not exist, a block sent by a process that does not hold it, and a
block that never reaches its destination.  It reads the schedule's arrays
and nothing else of the program.
"""

from __future__ import annotations

import numpy as np


def defects(p: int, src, dst, round_ptr, blk_ptr, blk_ids) -> int:
    """Breaches of the alltoall's delivery guarantee; 0 for a sound
    schedule."""
    if blk_ptr is None or blk_ids is None:
        return p * p  # no block record: nothing can be shown delivered
    src, dst = np.asarray(src), np.asarray(dst)
    blk_ptr, blk_ids = np.asarray(blk_ptr), np.asarray(blk_ids)
    procs = np.arange(p)
    held = np.zeros((p, p * p), bool)
    held[procs[:, None], procs[:, None] * p + procs[None, :]] = True
    bad = 0
    for r in range(len(round_ptr) - 1):
        lo, hi = int(round_ptr[r]), int(round_ptr[r + 1])
        nb = np.diff(blk_ptr[lo:hi + 1])
        blocks = blk_ids[blk_ptr[lo]:blk_ptr[hi]]
        senders = np.repeat(src[lo:hi], nb)
        receivers = np.repeat(dst[lo:hi], nb)
        ok = ((senders >= 0) & (senders < p) & (receivers >= 0)
              & (receivers < p) & (blocks >= 0) & (blocks < p * p))
        bad += int((~ok).sum())
        senders, receivers, blocks = senders[ok], receivers[ok], blocks[ok]
        bad += int((~held[senders, blocks]).sum())
        held[receivers, blocks] = True
    bad += int((~held[procs[None, :], procs[:, None] * p + procs[None, :]])
               .sum())
    return bad


def schedule_defects(cs) -> int:
    """:func:`defects` of a compiled schedule's arrays."""
    return defects(cs.p, cs.src, cs.dst, cs.round_ptr, cs.blk_ptr, cs.blk_ids)
