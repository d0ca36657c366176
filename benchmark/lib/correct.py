"""The comparison that decides ``correct``: how far what the timed path
produced lies from what the plain reference holds best.

Descended from ``chip_smoke.near_tie`` (PR 21): on the MXU two programs of the
same mathematics round differently, and an argmax over nearly equal scores
may flip, so outputs are not compared for equality. For every output the
program produced (a served token, a frame's label) the reference's own score
of that output is set against the reference's best score at that position:
0 where they agree, a small gap at a near tie, a gap of the order of the
scores' spread for a wrong program. The reference is teacher-forced on what
was served, so one flip does not carry into the positions after it.
"""
from __future__ import annotations

import numpy as np


def served_gaps(ref_scores: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """``ref_scores`` (n, classes) float, ``chosen`` (n,) int →
    ``max(ref_scores[i]) - ref_scores[i, chosen[i]]`` for each ``i``."""
    ref_scores = np.asarray(ref_scores, np.float32)
    chosen = np.asarray(chosen).astype(np.int64)
    if ref_scores.ndim != 2 or chosen.shape != (ref_scores.shape[0],):
        raise ValueError(f"scores {ref_scores.shape} against "
                         f"{chosen.shape} outputs")
    picked = np.take_along_axis(ref_scores, chosen[:, None], axis=1)[:, 0]
    return ref_scores.max(axis=1) - picked
