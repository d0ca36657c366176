"""What a decode engine owes the scheduler that drives it (L6 serving).

:class:`DecodeEngine` names the whole surface once. ``DecodeScheduler``
reaches its engine through these names and asks it nothing else, so this is
where a test's fake or a measuring proxy is substituted: a proxy overrides
the calls it wants to see and forwards every other name. The defaults are
those of an engine with no pool, no preemption and nothing to count.
"""
from __future__ import annotations

from typing import Optional

import numpy as np


class DecodeEngine:
    """Base of every engine a ``DecodeScheduler`` drives
    (``lm_engine.PagedLMEngine``, alone or with its own round, and
    ``speculative.SpeculativeLMEngine``)."""

    slots: int                 # fixed batch capacity
    compile_count = 0          # programs traced so far
    host_s = pull_s = 0.0      # running sums under the engine's prepare +
    #                            dispatch spans, and under its pull spans
    pool = None                # a ``KVPagePool``, where pages are kept
    pools_by_kind: dict = {}   # layer kind -> its ``KVPagePool``; ``pool``
    #                            is the first of them (below)
    # a burst engine (1..K tokens a slot a pass) defines ``step_tokens() ->
    # list[list[int]]``, which the scheduler then calls in place of ``step``,
    # with ``acceptance_rate()`` and ``spec_rounds|proposed|accepted``: the
    # paged engine itself where its family drafts on the device (its
    # ``_round``: 1 or 2 tokens a slot, ``[]`` for a slot that was not in
    # the round whose tokens came home), and ``SpeculativeLMEngine`` around
    # a paged engine with a host-side draft
    step_tokens = None

    def validate(self, tokens: np.ndarray, steps: int) -> None:
        """Raise ``ValueError`` for a request this engine can never serve
        (called at submit, before anything is queued)."""

    def admit_start(self, slot: int, tokens: np.ndarray, steps: int) -> None:
        """Queue a prompt (the array as submitted) for ``slot``;
        ``prefill_tick`` ingests it."""
        raise NotImplementedError

    def prefill_tick(self) -> "list[tuple[int, int]]":
        """One bounded piece of one pending prompt; ``[(slot, first
        token)]`` for a prompt this call finished, else ``[]``."""
        raise NotImplementedError

    def prefill_stamp(self, slot: int) -> "Optional[tuple[float, int]]":
        """``(first_chunk_t, chunks)`` of the prompt in ``slot`` on
        ``time.monotonic``; ``None`` where no lane is kept."""
        return None

    def step(self) -> np.ndarray:
        """One decode step over every slot → ``(slots,)`` tokens (an idle
        slot's entry is garbage). May raise ``PagePoolExhausted``. An
        entry below zero means "no token for this slot this pass": an
        engine may keep a step in flight and answer with the tokens of the
        step before, in which a slot that joined since has none (the paged
        engine does; one that never answers so is served as it was)."""
        raise NotImplementedError

    def collect(self) -> Optional[np.ndarray]:
        """For a caller that drives an engine by hand: bring home what
        ``step`` left in flight → ``(slots,)`` tokens no ``step`` has
        returned yet, below zero where a slot has none; ``None`` from an
        engine that keeps nothing in flight. "``step()``, then
        ``collect()``" is each step's own tokens. ``preempt``, ``restore``
        and ``close`` collect first themselves, and what they bring home
        the next ``step`` returns."""
        return None

    def release(self, slot: int) -> None:
        """``slot`` is free again: every exit of a request comes here."""

    def close(self) -> None:
        """The scheduler is closing; every slot has been released."""

    def preempt(self, slot: int) -> Optional[dict]:
        """Evict a live slot to the host and return what ``restore``
        needs; ``None`` where the engine cannot. The blob is the engine's
        own: the paged engine's holds the slot's pages of every kind of
        attention layer and, for a family with state layers, the slot's
        state in each (``"state"``: one host array per kind of state, all
        state layers' rows), every kind or none; and, where a step was in
        flight, the slot's token of it (no ``step`` returned it before the
        slot left: the ``step`` after ``restore`` does)."""
        return None

    def restore(self, slot: int, blob: dict) -> None:
        """Re-admit what ``preempt`` returned, mid-sequence."""
        raise NotImplementedError

    def projected_page_bytes(self, tokens: int, steps: int) -> int:
        """Bytes a request of ``tokens`` + ``steps`` pins at most: what
        the scheduler's memory guard reserves for it."""
        return 0

    def state_stats(self) -> Optional[dict]:
        """The cache that is a fixed cost a slot and not a cost a token
        (a recurrent state beside the pages): ``layers``, ``slots``,
        ``slots_live``, ``slot_bytes``, ``bytes``; ``None`` where the
        engine keeps none."""
        return None

    def counters(self) -> dict:
        """Running sums the engine keeps (``ServingMetrics``
        ``record_layer_counts`` takes their growth over a pass)."""
        return {}

    def admit(self, slot: int, tokens: np.ndarray, steps: int) -> int:
        """Admit and run the prefill lane until ``slot``'s prompt is in;
        its first token. For a caller that drives an engine by hand."""
        self.admit_start(slot, tokens, steps)
        while True:
            for s, first in self.prefill_tick():
                if s == slot:
                    return first
