"""Serving observability: per-scheduler aggregates + global snapshot (L6).

Builds on the same primitives the filter layer reports through
(``utils/stats.py`` — InvokeStats device/dispatch channels, and the
LatencyReservoir for tails). The counters here are counts and host-clock
sums; the serving TRACE is elsewhere: the one-shot ``Scheduler`` reports
each batch to the tracer fan-out in ``utils/trace.py``
(``notify_serving``) and, with request tracing on, as a batch span;
``DecodeScheduler`` calls neither and writes program spans
(``obs.context.span``: ``serving.pass`` and the phases under it, always
on), whose boundaries are the ones the pass counters below count at.

Per-REQUEST metrics live on the request itself (``Request.metrics``:
enqueue_time, queue_wait_s, ttft_s, total_latency_s; one-shot batches
add batch_id, bucket, device_time_s; decode requests add slot, admit_t,
first_chunk_t, first_token_t, token_t, chunks). This module aggregates
across requests/batches and exposes ``serving.metrics_snapshot()`` over
every live scheduler.
"""
from __future__ import annotations

import threading
import weakref
from typing import Dict, Optional

from ..obs import profile as obs_profile
from ..utils.stats import InvokeStats, LatencyReservoir

_registry: "weakref.WeakValueDictionary[str, object]" = \
    weakref.WeakValueDictionary()
_registry_lock = threading.Lock()
_name_counter: Dict[str, int] = {}


def register_scheduler(name: str, scheduler) -> str:
    """Track a scheduler for the global snapshot; returns the (uniquified)
    name it is registered under."""
    with _registry_lock:
        n = _name_counter.get(name, 0)
        _name_counter[name] = n + 1
        unique = name if n == 0 else f"{name}#{n}"
        _registry[unique] = scheduler
        return unique


def iter_schedulers():
    """(name, scheduler) over every live scheduler (the obs metrics
    collector reads this so the Prometheus plane and the snapshot share
    one source)."""
    with _registry_lock:
        return list(_registry.items())


def metrics_snapshot() -> dict:
    """{scheduler_name: scheduler.metrics_snapshot()} across every live
    scheduler (schedulers drop out when garbage-collected), plus — under
    the ``"fabric"`` key — every live :class:`~...service.fabric.
    ReplicaPool` snapshot (per-replica in-flight, EWMA health score,
    evict/readmit/hedge counters): the fabric autoscaler reads ONE
    snapshot instead of polling three subsystems."""
    out = {name: s.metrics_snapshot() for name, s in iter_schedulers()}
    from ..obs import metrics as obs_metrics

    fabric = obs_metrics.pools_snapshot()
    if fabric:
        out["fabric"] = fabric
    return out


class ServingMetrics:
    """One scheduler's aggregate counters + latency channels."""

    def __init__(self):
        self._lock = threading.Lock()
        # profiler request-series name ("serving:<scheduler>") — set by
        # the owning scheduler after registration; while set and the
        # profiler is ACTIVE, every finished request lands in the
        # windowed digests the SLO engine evaluates burn rates from
        self.series: Optional[str] = None
        self.submitted = 0
        self.completed = 0
        self.failed = 0
        self.shed_queue_full = 0
        self.shed_deadline = 0
        self.shed_memory = 0
        self.shed_overload = 0
        self.batches = 0
        self.batched_rows = 0      # real rows executed
        self.padded_rows = 0       # rows incl. bucket padding
        self.decode_steps = 0
        self.retired_early = 0     # decode: finished before max steps (eos)
        self.preempted = 0         # pages evicted to host (pressure)
        self.restored = 0          # preempted requests resumed
        # decode loop, per pass that did work (scheduler.py ``_loop``)
        self.passes = 0
        self.passes_with_step = 0
        self.passes_with_chunk = 0
        self.passes_with_both = 0
        self.prefill_chunks = 0
        self.host_sched_s = 0.0    # passes less the engine's spans
        self.host_engine_s = 0.0   # prepare + dispatch, both programs
        self.pull_wait_s = 0.0     # the engine's pulls, both programs
        # backend compiles jax reported while a pass ran (loads from the
        # persistent cache too) and their seconds: the decode loop's
        # thread's, by ``obs.context.compile_running``. 0 once every shape
        # is warm
        self.compiles = 0
        self.compile_s = 0.0
        # what an expert family's layers counted, both programs, summed
        # over calls and expert layers (PagedLMEngine.layer_counts)
        self.moe_experts_touched = 0   # experts that received a token
        self.moe_expert_slots = 0      # experts held x expert layers x calls
        self.moe_assignments = 0       # (token, expert) pairs served
        self.moe_max_load = 0          # largest load of one expert, summed
        # what a family that runs its stack several times a token counted,
        # both programs: ``exit_pass_<t>``, the live rows whose logits came
        # from pass t (empty for every other family)
        self.exit_passes: dict = {}
        # how far a decode step's attention follows what is visible
        # (PagedLMEngine.attn_pages), summed over steps
        self.attn_pages_read = 0       # pages the live slots held
        self.attn_pages_fetched = 0    # pages the steps' kernel copied
        self.attn_pages_padded = 0     # slots x blocks a slot may hold
        # by kind of layer, where an engine's family has two kinds: what a
        # full layer's and a window layer's attention read, and the pages
        # given back behind the window
        self.attn_pages_read_full = 0
        self.attn_pages_read_window = 0
        self.window_pages_released = 0
        # the state layers' cache (PagedLMEngine.state_slots), summed over
        # decode steps: the slots whose state a step advanced, and every
        # slot's (what the step read and wrote)
        self.state_slots_live = 0
        self.state_slots = 0
        # how the engine's decode steps ran ahead of their tokens
        # (PagedLMEngine.run_ahead): steps dispatched while the step
        # before's tokens were still on the device, steps brought home
        # early (a preempt, restore, verify round or close), slot-steps
        # whose token was dropped (the one step an EOS ending runs over),
        # and the prompts whose last launch had the pass's step dispatched
        # behind it before its token was pulled, beside those that had not
        self.steps_ahead = 0
        self.steps_collected_early = 0
        self.surplus_steps = 0
        self.joins_ahead = 0
        self.joins_drained = 0
        # device channel: batch execution time (dispatch+block, the
        # reference-comparable number); reservoirs: per-request tails
        self.device = InvokeStats()
        self.queue_wait = LatencyReservoir()
        self.ttft = LatencyReservoir()
        self.total = LatencyReservoir()

    # -- recording ----------------------------------------------------------
    def record_submit(self, n: int = 1) -> None:
        with self._lock:
            self.submitted += n

    def record_shed(self, deadline: bool = False,
                    memory: bool = False,
                    overload: bool = False) -> None:
        with self._lock:
            if memory:
                self.shed_memory += 1
            elif overload:
                self.shed_overload += 1
            elif deadline:
                self.shed_deadline += 1
            else:
                self.shed_queue_full += 1

    def record_batch(self, rows: int, padded_rows: int,
                     device_s: float) -> None:
        with self._lock:
            self.batches += 1
            self.batched_rows += rows
            self.padded_rows += padded_rows
        self.device.record(device_s)
        self.device.record_device(device_s)

    def record_request_done(self, req, failed: bool = False) -> None:
        with self._lock:
            if failed:
                self.failed += 1
            else:
                self.completed += 1
        m = req.metrics
        if "queue_wait_s" in m:
            self.queue_wait.add(m["queue_wait_s"])
        if "ttft_s" in m:
            self.ttft.add(m["ttft_s"])
        if "total_latency_s" in m:
            self.total.add(m["total_latency_s"])
        if obs_profile.ACTIVE and self.series is not None:
            obs_profile.record_request(
                self.series, m.get("total_latency_s", 0.0), ok=not failed)

    def record_decode_step(self, active: int, slots: int,
                           device_s: float) -> None:
        """``device_s`` is host wall around the engine's ``step()``: its
        dispatch and the pull of the tokens, not time on the device's
        clock (the queue's service-time estimate reads the same number)."""
        with self._lock:
            self.decode_steps += 1
            self.batched_rows += active
            self.padded_rows += slots
        self.device.record(device_s)
        self.device.record_device(device_s)

    def record_pass(self, step: bool, chunks: int, host_sched_s: float,
                    host_engine_s: float, pull_wait_s: float,
                    compiles: int = 0, compile_s: float = 0.0) -> None:
        """One pass of the decode loop that did work: whether it ran a
        decode step, how many prefill chunks (today at most one), its
        host wall split three ways (the scheduler's own code, the engine's
        prepare and dispatch, the engine's pulls), and the backend
        compiles jax reported while it ran, with their seconds."""
        with self._lock:
            self.passes += 1
            self.passes_with_step += step
            self.passes_with_chunk += chunks > 0
            self.passes_with_both += step and chunks > 0
            self.prefill_chunks += chunks
            self.host_sched_s += host_sched_s
            self.host_engine_s += host_engine_s
            self.pull_wait_s += pull_wait_s
            self.compiles += compiles
            self.compile_s += compile_s

    def record_layer_counts(self, counts: dict) -> None:
        """What the engine counted since the last pass (the growth of
        ``DecodeEngine.counters()``): its expert layers (``moe_*``, both
        programs added up), the passes its tokens left at (``exit_pass_*``),
        its steps' attention (``attn_pages_*``), its state layers' cache
        (``state_slots*``) and how its steps ran ahead
        (``steps_ahead``, ``steps_collected_early``, ``surplus_steps``,
        ``joins_ahead``, ``joins_drained``)."""
        with self._lock:
            self.attn_pages_read += counts.get("attn_pages_read", 0)
            self.attn_pages_fetched += counts.get("attn_pages_fetched", 0)
            self.attn_pages_padded += counts.get("attn_pages_padded", 0)
            self.attn_pages_read_full += counts.get(
                "attn_pages_read_full", 0)
            self.attn_pages_read_window += counts.get(
                "attn_pages_read_window", 0)
            self.window_pages_released += counts.get(
                "window_pages_released", 0)
            self.state_slots_live += counts.get("state_slots_live", 0)
            self.state_slots += counts.get("state_slots", 0)
            self.steps_ahead += counts.get("steps_ahead", 0)
            self.steps_collected_early += counts.get(
                "steps_collected_early", 0)
            self.surplus_steps += counts.get("surplus_steps", 0)
            self.joins_ahead += counts.get("joins_ahead", 0)
            self.joins_drained += counts.get("joins_drained", 0)
            self.moe_experts_touched += counts.get("moe_experts_touched", 0)
            self.moe_expert_slots += counts.get("moe_expert_slots", 0)
            self.moe_assignments += counts.get("moe_assignments", 0)
            self.moe_max_load += counts.get("moe_max_load", 0)
            for name, n in counts.items():
                if name.startswith("exit_pass_"):
                    self.exit_passes[name] = \
                        self.exit_passes.get(name, 0) + n

    def record_early_retire(self) -> None:
        with self._lock:
            self.retired_early += 1

    def record_preemption(self) -> None:
        with self._lock:
            self.preempted += 1

    def record_restore(self) -> None:
        with self._lock:
            self.restored += 1

    # -- snapshot -----------------------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            padded = self.padded_rows
            occupancy = (self.batched_rows / padded) if padded else 0.0
            out = {
                "submitted": self.submitted,
                "completed": self.completed,
                "failed": self.failed,
                "shed_queue_full": self.shed_queue_full,
                "shed_deadline": self.shed_deadline,
                "shed_memory": self.shed_memory,
                "shed_overload": self.shed_overload,
                "batches": self.batches,
                "decode_steps": self.decode_steps,
                "retired_early": self.retired_early,
                "preempted": self.preempted,
                "restored": self.restored,
                "batch_occupancy": occupancy,
                "passes": self.passes,
                "passes_with_step": self.passes_with_step,
                "passes_with_chunk": self.passes_with_chunk,
                "passes_with_both": self.passes_with_both,
                "prefill_chunks": self.prefill_chunks,
                "host_sched_s": self.host_sched_s,
                "host_engine_s": self.host_engine_s,
                "pull_wait_s": self.pull_wait_s,
                "compiles": self.compiles,
                "compile_s": self.compile_s,
                "moe_experts_touched": self.moe_experts_touched,
                "moe_expert_slots": self.moe_expert_slots,
                "moe_assignments": self.moe_assignments,
                "moe_max_load": self.moe_max_load,
                "attn_pages_read": self.attn_pages_read,
                "attn_pages_fetched": self.attn_pages_fetched,
                "attn_pages_padded": self.attn_pages_padded,
                "attn_pages_read_full": self.attn_pages_read_full,
                "attn_pages_read_window": self.attn_pages_read_window,
                "window_pages_released": self.window_pages_released,
                "state_slots_live": self.state_slots_live,
                "state_slots": self.state_slots,
                "steps_ahead": self.steps_ahead,
                "steps_collected_early": self.steps_collected_early,
                "surplus_steps": self.surplus_steps,
                "joins_ahead": self.joins_ahead,
                "joins_drained": self.joins_drained,
                **self.exit_passes,
            }
        out["device"] = self.device.snapshot()
        out["queue_wait"] = self.queue_wait.snapshot()
        out["ttft"] = self.ttft.snapshot()
        out["total_latency"] = self.total.snapshot()
        return out
