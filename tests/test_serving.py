"""Continuous-batching serving subsystem (nnstreamer_tpu/serving/).

The properties the subsystem exists for, each asserted directly:

* bucketing — same-bucket traffic compiles ONCE (JitExecutor's
  compile-count hook), so organic row counts cannot cause a recompile
  storm;
* admission control — unmeetable work sheds with a TYPED error and is
  never executed, instead of buffering unboundedly;
* priority ordering and max-wait flush — latency-sensitive traffic is
  neither queue-jumped nor starved waiting for a full bucket;
* continuous decode — sequences join a running batch between steps and
  retire early, freeing their slot (engine parity vs unbatched decode);
* multi-client coalescing — concurrent QueryServer clients sending
  batch-1 frames execute as one device batch.
"""
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu.serving import (
    AdmissionError,
    BatchFormer,
    DeadlineExceededError,
    DecodeEngine,
    DecodeScheduler,
    QueueFullError,
    Request,
    RequestQueue,
    Scheduler,
    SchedulerClosedError,
    metrics_snapshot,
)


def _req(rows=1, cols=3, fill=0.0, **kw):
    return Request((np.full((rows, cols), fill, np.float32),), **kw)


class FakeExecutor:
    """Host-native executor recording execution order (no jax, no jit —
    scheduler-policy tests must not depend on compile timing)."""

    def __init__(self):
        self.compiles = 0
        self.calls = []  # first-row fill value per executed batch

    def __call__(self, x):
        self.calls.append(float(x[0, 0]))
        return (x * 2.0,)


# ---------------------------------------------------------------------------
# BatchFormer
# ---------------------------------------------------------------------------
class TestBatchFormer:
    def test_bucket_for_rounds_up(self):
        f = BatchFormer(bucket_sizes=(1, 2, 4, 8))
        assert [f.bucket_for(r) for r in (1, 2, 3, 4, 5, 8)] == \
            [1, 2, 4, 4, 8, 8]
        # above the largest bucket: next multiple (stable signature)
        assert f.bucket_for(9) == 16

    def test_requests_never_straddle_batches(self):
        f = BatchFormer(bucket_sizes=(4,), max_wait_s=0.0)
        for rows in (3, 3, 2):
            f.add(_req(rows=rows))
        batches = f.take_ready(force=True)
        # 3+3 won't fit one 4-row bucket: each request stays whole
        assert [b.rows for b in batches] == [3, 3, 2]
        assert all(b.padded_rows == 4 for b in batches)

    def test_stack_pads_to_bucket_and_splits_back(self):
        f = BatchFormer(bucket_sizes=(4,), max_wait_s=0.0)
        r1, r2 = _req(rows=1, fill=1.0), _req(rows=2, fill=2.0)
        f.add(r1)
        f.add(r2)
        (batch,) = f.take_ready(force=True)
        (stacked,) = batch.stacked_tensors()
        assert stacked.shape == (4, 3)  # 3 real rows + 1 pad row
        assert np.all(stacked[3] == 0)
        outs = batch.split_outputs((stacked * 10,))
        assert outs[0][0].shape == (1, 3) and np.all(outs[0][0] == 10)
        assert outs[1][0].shape == (2, 3) and np.all(outs[1][0] == 20)

    def test_incompatible_shapes_never_coalesce(self):
        f = BatchFormer(bucket_sizes=(8,), max_wait_s=0.0)
        f.add(_req(rows=1, cols=3))
        f.add(_req(rows=1, cols=5))
        batches = f.take_ready(force=True)
        assert len(batches) == 2
        assert batches[0].bucket_key != batches[1].bucket_key

    def test_idle_flushes_only_exact_bucket_boundaries(self):
        f = BatchFormer(bucket_sizes=(1, 2, 4, 8), max_wait_s=60.0)
        f.add(_req(rows=2))
        # ON a bucket boundary + nothing else coming: flush now (zero
        # padding waste; waiting buys occupancy nothing)
        assert len(f.take_ready(idle=True)) == 1
        # BETWEEN boundaries: keep waiting — flushing 3 rows now pads
        # to 4 anyway, so the max-wait window may still fill the bucket
        f.add(_req(rows=3))
        assert f.take_ready(idle=True) == []

    def test_max_wait_ages_pending(self):
        f = BatchFormer(bucket_sizes=(8,), max_wait_s=0.01)
        f.add(_req(rows=1))
        assert f.take_ready() == []  # not full, not aged
        assert 0.0 <= f.next_flush_in() <= 0.01
        time.sleep(0.02)
        assert len(f.take_ready()) == 1  # aged past max_wait


# ---------------------------------------------------------------------------
# RequestQueue admission control
# ---------------------------------------------------------------------------
class TestRequestQueue:
    def test_priority_then_fifo(self):
        q = RequestQueue(max_depth=16)
        first = _req(priority=5, fill=1.0)
        urgent = _req(priority=0, fill=2.0)
        second = _req(priority=5, fill=3.0)
        for r in (first, urgent, second):
            q.put(r)
        order = [q.get(timeout=0) for _ in range(3)]
        assert order == [urgent, first, second]

    def test_queue_full_typed_shed(self):
        q = RequestQueue(max_depth=1)
        q.put(_req())
        overflow = _req()
        with pytest.raises(QueueFullError):
            q.put(overflow)
        # the future failed with the SAME typed error (observers agree)
        assert isinstance(overflow.error, QueueFullError)
        assert q.shed_full == 1

    def test_expired_at_admission(self):
        q = RequestQueue(max_depth=16)
        late = _req(deadline=time.monotonic() - 0.1)
        with pytest.raises(DeadlineExceededError):
            q.put(late)
        assert isinstance(late.error, DeadlineExceededError)

    def test_expired_while_queued_shed_at_pop(self):
        q = RequestQueue(max_depth=16)
        doomed = _req(deadline=time.monotonic() + 0.01)
        live = _req()
        q.put(doomed)
        q.put(live)
        time.sleep(0.03)
        assert q.get(timeout=0) is live
        assert doomed.done()
        assert isinstance(doomed.error, DeadlineExceededError)
        assert q.shed_deadline == 1

    def test_predictive_shed_uses_service_ewma(self):
        q = RequestQueue(max_depth=64, est_batch_rows=1,
                         predictive_shed=True)
        q.observe_service_time(10.0)  # each batch "takes" 10s
        q.put(_req())  # one batch ahead → est wait ≈ 10s
        hopeless = _req(deadline=time.monotonic() + 0.5)
        with pytest.raises(DeadlineExceededError):
            q.put(hopeless)
        # same deadline admitted fine when prediction is off
        q2 = RequestQueue(max_depth=64, est_batch_rows=1,
                          predictive_shed=False)
        q2.observe_service_time(10.0)
        q2.put(_req())
        q2.put(_req(deadline=time.monotonic() + 0.5))


# ---------------------------------------------------------------------------
# Scheduler (one-shot continuous batching)
# ---------------------------------------------------------------------------
class TestScheduler:
    def test_results_roundtrip(self):
        sched = Scheduler(lambda x: (x * 2,), bucket_sizes=(1, 2, 4),
                          max_wait_s=0.002, name="t-roundtrip")
        try:
            reqs = [sched.submit((np.full((1, 3), i, np.float32),))
                    for i in range(6)]
            for i, r in enumerate(reqs):
                (out,) = r.result(30)
                assert out.shape == (1, 3)
                np.testing.assert_allclose(np.asarray(out), i * 2.0)
        finally:
            sched.close()

    def test_same_bucket_compiles_exactly_once(self):
        # THE no-recompile-storm property: rows 1..3 all pad to the one
        # 4-row bucket, so jit sees exactly one signature.
        sched = Scheduler(lambda x: (x + 1,), bucket_sizes=(4,),
                          max_wait_s=0.001, name="t-compile")
        try:
            reqs = [sched.submit((np.ones((rows, 3), np.float32),))
                    for rows in (1, 2, 3, 1, 2, 3, 3, 2, 1)]
            for r in reqs:
                r.result(30)
            assert sched.compile_count == 1
            # a genuinely new layout (cols=5) is a new signature
            sched.submit((np.ones((1, 5), np.float32),)).result(30)
            assert sched.compile_count == 2
        finally:
            sched.close()

    def test_expired_deadline_shed_never_executed(self):
        ex = FakeExecutor()
        sched = Scheduler(executor=ex, bucket_sizes=(1,),
                          max_wait_s=0.001, name="t-shed")
        try:
            with pytest.raises(DeadlineExceededError):
                sched.submit((np.ones((1, 3), np.float32),),
                             deadline_s=-0.1)
            time.sleep(0.05)
            assert ex.calls == []  # shed at admission, not executed
            snap = sched.metrics_snapshot()
            assert snap["shed_deadline"] == 1
            assert snap["completed"] == 0
        finally:
            sched.close()

    def test_expired_in_queue_shed_is_accounted(self):
        # deadline passes while queued (loop not yet running): the pop
        # sheds it AND the scheduler's metrics see it — submitted must
        # balance against completed+failed+shed
        sched = Scheduler(lambda x: (x,), bucket_sizes=(1,),
                          max_wait_s=0.001, name="t-qshed",
                          autostart=False)
        try:
            doomed = sched.submit((np.ones((1, 3), np.float32),),
                                  deadline_s=0.01)
            time.sleep(0.03)
            sched.start()
            with pytest.raises(DeadlineExceededError):
                doomed.result(10)
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline:
                snap = sched.metrics_snapshot()
                if snap["shed_deadline"] == 1:
                    break
                time.sleep(0.005)
            assert snap["shed_deadline"] == 1
            assert snap["submitted"] == 1 and snap["completed"] == 0
        finally:
            sched.close()

    def test_priority_orders_execution(self):
        ex = FakeExecutor()
        sched = Scheduler(executor=ex, bucket_sizes=(1,),
                          max_wait_s=0.0, name="t-prio", autostart=False)
        try:
            reqs = [sched.submit((np.full((1, 3), fill, np.float32),),
                                 priority=prio)
                    for fill, prio in ((1.0, 9), (2.0, 0), (3.0, 5))]
            sched.start()
            for r in reqs:
                r.result(30)
            assert ex.calls == [2.0, 3.0, 1.0]  # lower priority first
        finally:
            sched.close()

    def test_max_wait_flushes_partial_bucket(self):
        sched = Scheduler(lambda x: (x,), bucket_sizes=(8,),
                          max_wait_s=0.01, name="t-flush")
        try:
            t0 = time.monotonic()
            req = sched.submit((np.ones((1, 3), np.float32),))
            req.result(30)
            # a lone request must not wait for 7 peers that never come —
            # generous bound: flush timer, not the 30s result timeout
            assert time.monotonic() - t0 < 5.0
            assert req.metrics["bucket"] == 8  # still padded to the bucket
        finally:
            sched.close()

    def test_per_request_metrics_and_snapshot(self):
        sched = Scheduler(lambda x: (x,), bucket_sizes=(2,),
                          max_wait_s=0.002, name="t-metrics")
        try:
            req = sched.submit((np.ones((1, 3), np.float32),))
            req.result(30)
            for field in ("enqueue_time", "queue_wait_s", "batch_id",
                          "bucket", "device_time_s", "ttft_s",
                          "total_latency_s"):
                assert field in req.metrics, field
            snap = sched.metrics_snapshot()
            assert snap["submitted"] == snap["completed"] == 1
            assert snap["batches"] == 1
            assert 0.0 < snap["batch_occupancy"] <= 1.0
            assert snap["total_latency"]["count"] == 1
            # the global registry sees this scheduler under its name
            assert "t-metrics" in metrics_snapshot()
        finally:
            sched.close()

    def test_close_fails_pending_with_typed_error(self):
        sched = Scheduler(lambda x: (x,), bucket_sizes=(8,),
                          max_wait_s=60.0, name="t-close", autostart=False)
        stranded = sched.submit((np.ones((1, 3), np.float32),))
        sched.close()
        with pytest.raises(SchedulerClosedError):
            stranded.result(1)
        with pytest.raises(SchedulerClosedError):
            sched.submit((np.ones((1, 3), np.float32),))

    def test_queue_full_through_scheduler(self):
        sched = Scheduler(lambda x: (x,), bucket_sizes=(4,),
                          max_wait_s=60.0, max_depth=2, name="t-full",
                          autostart=False)
        try:
            sched.submit((np.ones((1, 3), np.float32),))
            sched.submit((np.ones((1, 3), np.float32),))
            with pytest.raises(QueueFullError):
                sched.submit((np.ones((1, 3), np.float32),))
            assert sched.metrics_snapshot()["shed_queue_full"] == 1
        finally:
            sched.close()


# ---------------------------------------------------------------------------
# DecodeScheduler (continuous LM decode) — toy engine for policy
# ---------------------------------------------------------------------------
class ToyEngine(DecodeEngine):
    """Deterministic counter engine: next token = last + 1 (mod 97).
    Slot-independent by construction, so scheduler-policy failures
    (corrupted joins, leaked slots) show up as wrong token streams."""

    def __init__(self, slots=2):
        self.slots = slots
        self._tok = np.zeros(slots, np.int32)
        self._pending = []

    def admit_start(self, slot, tokens, steps):
        self._pending.append((slot, (int(tokens[-1]) + 1) % 97))

    def prefill_tick(self):
        slot, first = self._pending.pop(0)
        self._tok[slot] = first
        return [(slot, first)]

    def step(self):
        self._tok = (self._tok + 1) % 97
        return self._tok.copy()

    def release(self, slot):
        self._tok[slot] = 0


class LaggingToyEngine(ToyEngine):
    """The toy engine with a step in flight: ``step`` answers with the
    tokens of the step before, and below zero for a slot that was not in
    it (``DecodeEngine.step``)."""

    def __init__(self, slots=2):
        super().__init__(slots)
        self._live = np.zeros(slots, bool)
        self._flight = np.full(slots, -1, np.int32)
        self.unanswered = 0

    def prefill_tick(self):
        done = super().prefill_tick()
        self._live[done[0][0]] = True
        return done

    def step(self):
        answer, self._flight = self._flight, np.where(
            self._live, super().step(), -1).astype(np.int32)
        self.unanswered += int((self._live & (answer < 0)).sum())
        return answer

    def release(self, slot):
        super().release(slot)
        self._live[slot] = False
        self._flight[slot] = -1


def _expected(prompt_last, steps):
    return [(prompt_last + 1 + i) % 97 for i in range(steps)]


class TestDecodeScheduler:
    def test_join_and_early_finish(self):
        sched = DecodeScheduler(ToyEngine(slots=2), name="t-decode")
        try:
            long = sched.submit(np.array([5], np.int32), steps=40)
            short = sched.submit(np.array([10], np.int32), steps=3)
            # short JOINS the running batch and finishes first
            assert short.result(30)[0].tolist() == _expected(10, 3)
            assert not long.done() or len(long.tokens) > 3
            assert long.result(30)[0].tolist() == _expected(5, 40)
        finally:
            sched.close()

    def test_a_slot_with_no_token_this_pass_emits_nothing_and_loses_none(self):
        engine = LaggingToyEngine(slots=2)
        sched = DecodeScheduler(engine, name="t-lagging")
        try:
            long = sched.submit(np.array([5], np.int32), steps=40)
            short = sched.submit(np.array([10], np.int32), steps=3)
            third = sched.submit(np.array([50], np.int32), steps=7)
            assert short.result(30)[0].tolist() == _expected(10, 3)
            assert third.result(30)[0].tolist() == _expected(50, 7)
            assert long.result(30)[0].tolist() == _expected(5, 40)
            stamps = long.metrics["token_t"]
            assert len(stamps) == 40 and stamps == sorted(stamps)
        finally:
            sched.close()
        # each request had a pass with no token: the one it joined in
        assert engine.unanswered >= 3

    def test_retire_frees_slot_for_queued_request(self):
        sched = DecodeScheduler(ToyEngine(slots=1), name="t-slot1")
        try:
            reqs = [sched.submit(np.array([seed], np.int32), steps=4)
                    for seed in (1, 20, 50)]
            for seed, r in zip((1, 20, 50), reqs):
                assert r.result(30)[0].tolist() == _expected(seed, 4)
            snap = sched.metrics_snapshot()
            assert snap["completed"] == 3
            assert snap["active_slots"] == 0
        finally:
            sched.close()

    def test_eos_retires_early(self):
        sched = DecodeScheduler(ToyEngine(slots=2), name="t-eos")
        try:
            # stream from 7: 8, 9, 10, ... — eos at 10 stops step 3 of 30
            req = sched.submit(np.array([7], np.int32), steps=30, eos_id=10)
            assert req.result(30)[0].tolist() == [8, 9, 10]
            assert req.metrics["decode_steps"] == 3
            assert sched.metrics_snapshot()["retired_early"] == 1
        finally:
            sched.close()

    def test_decode_admission_control(self):
        sched = DecodeScheduler(ToyEngine(slots=1), name="t-dadmit",
                                autostart=False)
        try:
            with pytest.raises(DeadlineExceededError):
                sched.submit(np.array([1], np.int32), steps=4,
                             deadline_s=-0.1)
            with pytest.raises(ValueError):
                sched.submit(np.array([[1, 2]], np.int32), steps=4)  # 2-D
            with pytest.raises(ValueError):
                sched.submit(np.array([1], np.int32), steps=0)
        finally:
            sched.close()


# ---------------------------------------------------------------------------
# QueryServer bridge — multi-client coalescing
# ---------------------------------------------------------------------------
class TestQueryServerBridge:
    def test_concurrent_clients_share_one_device_batch(self):
        from nnstreamer_tpu.core import Buffer, Caps
        from nnstreamer_tpu.query.client import QueryClient
        from nnstreamer_tpu.query.server import QueryServer

        caps = Caps.new("other/tensors")
        server = QueryServer(port=0, caps=caps)
        sched = Scheduler(lambda x: (x + 1,), bucket_sizes=(1, 2, 4),
                          max_wait_s=0.25, name="t-qbridge")
        server.attach_scheduler(sched)
        n_clients = 4
        results = {}

        def client(i):
            c = QueryClient("127.0.0.1", server.port)
            try:
                c.connect(caps)
                c.send(Buffer([np.full((1, 3), float(i), np.float32)]))
                results[i] = c.responses.get(timeout=30)
            finally:
                c.close()

        try:
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(n_clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            for i in range(n_clients):
                np.testing.assert_allclose(
                    np.asarray(results[i].tensors[0]), i + 1.0)
            snap = sched.metrics_snapshot()
            assert snap["completed"] == n_clients
            # THE acceptance property: batch-1 frames from concurrent
            # clients executed as coalesced batches, not one per client
            assert snap["batches"] < n_clients
        finally:
            sched.close()
            server.stop()


# ---------------------------------------------------------------------------
# tensor_serving element
# ---------------------------------------------------------------------------
class TestTensorServingElement:
    def test_pipeline_roundtrip_with_metrics_meta(self):
        from nnstreamer_tpu.runtime.parse import parse_launch

        pipe = parse_launch(
            "tensor_src num-buffers=3 dimensions=3:1 types=float32 "
            "pattern=ones "
            "! tensor_serving framework=jax "
            "model=builtin://scaler?factor=2 bucket-sizes=1,2,4 "
            "max-wait-ms=2 "
            "! tensor_sink name=out")
        got = []
        pipe.get("out").connect(got.append)
        pipe.run(timeout=60)
        assert len(got) == 3
        for buf in got:
            np.testing.assert_allclose(np.asarray(buf.tensors[0]), 2.0)
            serving_meta = buf.meta["serving"]
            assert serving_meta["bucket"] in (1, 2, 4)
            assert "queue_wait_s" in serving_meta

    def test_invalid_bucket_sizes_fail_at_construction(self):
        from nnstreamer_tpu.registry.elements import make_element
        from nnstreamer_tpu.runtime.element import ElementError

        with pytest.raises(ElementError):
            make_element("tensor_serving",
                         model="builtin://scaler?factor=2",
                         bucket_sizes="0,4")

    def test_shared_key_rejects_model_mismatch(self):
        from nnstreamer_tpu.serving import (
            get_shared_scheduler,
            release_shared_scheduler,
        )

        made = []

        def factory():
            s = Scheduler(lambda x: (x,), bucket_sizes=(2,),
                          name="t-shared")
            made.append(s)
            return s

        first = get_shared_scheduler("t-key", factory, ("model-a",))
        try:
            # same key + same signature → the SAME scheduler (coalesce)
            assert get_shared_scheduler("t-key", factory,
                                        ("model-a",)) is first
            release_shared_scheduler("t-key")
            # different signature must refuse: coalescing two different
            # models through one queue would cross their traffic
            with pytest.raises(ValueError):
                get_shared_scheduler("t-key", factory, ("model-b",))
        finally:
            release_shared_scheduler("t-key")
            assert len(made) == 1
