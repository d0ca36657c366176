"""The trace reduction on the small recorded ``.xplane.pb`` kept with the
benchmark (``benchmark/testdata/small.xplane.pb``, a TPU v5e, PR 23:
``tools/record_testdata.py``), and the command end to end in its rehearsal
mode: the result line's keys, no device number from a CPU, the refusal of
anything but a TPU."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import harness, xplane  # noqa: E402
from benchmark.lib.readers import (  # noqa: E402
    device_idle_share,
    per_execution_ms,
)

BENCH = harness.load_benchmark()


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce_trace(os.path.join(
        ROOT, "benchmark", "testdata", "small.xplane.pb"))


# what record_testdata.py ran inside the trace: 4 x (prog_matmul, sleep
# 20 ms, prog_copy twice) on a 2048x2048 bfloat16 array

def test_programs_are_counted_and_timed_from_the_module_events(reduced):
    progs = reduced["programs"]
    assert set(progs) == {"prog_matmul", "prog_copy"}
    assert progs["prog_matmul"]["count"] == 4
    assert progs["prog_copy"]["count"] == 8
    # 102.4-102.6 us and 27.0-27.6 us an execution, as the dump shows
    assert progs["prog_matmul"]["total_s"] / 4 == pytest.approx(102.5e-6, rel=0.01)
    assert progs["prog_copy"]["total_s"] / 8 == pytest.approx(27.3e-6, rel=0.02)


def test_operations_belong_to_the_program_that_ran_them(reduced):
    matmul = reduced["programs"]["prog_matmul"]["ops"]
    copy = reduced["programs"]["prog_copy"]["ops"]
    assert set(matmul) == {"copy-start_bf16_2048_2048_",
                           "copy-done_bf16_2048_2048_",
                           "convolution_tanh_fusion_bf16_2048_2048_"}
    assert set(copy) == {"copy_bf16_2048_2048_",
                         "add_bitcast_fusion_bf16_2048_2048_"}
    # a program's operations fill its module events, and no more
    for prog in reduced["programs"].values():
        assert sum(prog["ops"].values()) <= prog["total_s"]
        assert sum(prog["ops"].values()) == pytest.approx(prog["total_s"],
                                                          rel=0.02)
    top = reduced["device_ops"]
    assert top[0][0] == "prog_matmul:convolution_tanh_fusion_bf16_2048_2048_"
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    assert len(top) <= 10


def test_busy_is_the_union_of_operations_and_idle_is_the_rest(reduced):
    ops = sum(sum(p["ops"].values()) for p in reduced["programs"].values())
    assert reduced["busy_s"] == pytest.approx(ops, rel=1e-6)
    assert reduced["busy_s"] == pytest.approx(628.6e-6, rel=0.01)
    # the loop slept 4 x 20 ms: the window is a little over 80 ms
    assert 0.085 < reduced["window_s"] < 0.1
    idle = device_idle_share({"trace": reduced})
    assert idle == pytest.approx(
        100 * (1 - reduced["busy_s"] / reduced["window_s"]))
    assert 99.0 < idle < 99.5


def test_idle_gaps_are_charged_to_the_host_span_that_covers_them(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert set(gaps) == {"sleep", "matmul", "copy", "unattributed"}
    assert gaps["sleep"] == pytest.approx(4 * 0.0205, rel=0.03)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert gaps["unattributed"] < 0.002


@pytest.mark.parametrize("trace", sorted(
    f for f in os.listdir(os.path.join(ROOT, "benchmark", "testdata"))
    if f.endswith(".xplane.pb")))
def test_bisecting_the_sorted_spans_charges_as_scanning_them_all_did(
        trace, monkeypatch):
    path = os.path.join(ROOT, "benchmark", "testdata", trace)
    found = xplane.reduce_trace(path)["idle_gaps"]
    # the rule as it ran until PR 38: every idle gap looks at every span
    monkeypatch.setattr(xplane._Spans, "near",
                        lambda self, g0, g1: self.spans)
    # to the last digit: the same cuts, summed in the same order
    assert found == xplane.reduce_trace(path)["idle_gaps"]
    assert found


def test_the_spans_near_a_gap_are_all_that_can_overlap_it():
    import random
    from collections import defaultdict

    rng = random.Random(38)
    spans = []
    for i in range(400):  # passes of 100 with a box or two nested in each
        t = 100.0 * i + rng.uniform(0, 5)
        spans.append(("bench:step", t, t + rng.uniform(20, 90)))
        spans.append(("bench:inner", t + 2, t + rng.uniform(3, 19)))
    # another thread's, across the passes, and a stall of thirty passes
    spans += [("bench:submit", 100.0 * i + 80, 100.0 * i + 130)
              for i in range(0, 400, 7)]
    spans.append(("bench:stall", 20050.0, 23000.0))
    rng.shuffle(spans)
    index = xplane._Spans(spans)
    assert index.starts == sorted(index.starts)
    everything, near = defaultdict(float), defaultdict(float)
    for _ in range(500):
        g0 = rng.uniform(-50, 40100)
        g1 = g0 + rng.choice((0.5, 7.0, 60.0, 450.0))
        found = index.near(g0, g1)
        assert {s for s in spans if s[1] < g1 and s[2] > g0} <= set(found)
        # and not many more: what began since the longest span still open
        assert len(found) < (20 if g1 < 20000 or g0 > 23100 else 80)
        xplane._charge(everything, g0, g1, spans)
        xplane._charge(near, g0, g1, found)
    assert dict(near) == dict(everything)  # exactly
    assert set(near) == {"step", "inner", "submit", "stall", "unattributed"}


def test_hlo_text_becomes_a_short_label():
    assert xplane.op_label(
        "%copy.9 = bf16[24,1025,32,16,64]{4,3,2,1,0:T(8,128)(2,1)} "
        "copy(bf16[24,1025,32,16,64]{4,3,2,1,0} %p)") == \
        "copy.9_bf16_24_1025_32_16_64_"
    assert xplane.op_label(
        "%copy-start = (bf16[8,8]{1,0}, u32[]{:S(2)}) copy-start(%x)") == \
        "copy-start_bf16_8_8_"
    # the kind that numbered instances share: the breakdown sums them
    assert xplane.op_label(
        "%select_convert_fusion.23 = f32[16,128,32,16,64]{4,3,2,1,0} "
        "fusion(%a)", numbered=False) == \
        "select_convert_fusion_f32_16_128_32_16_64_"
    assert xplane.program_name("jit__step(14836250070554842513)") == "_step"
    assert xplane.program_name("jit__prefill_chunk(1)") == "_prefill_chunk"


def test_a_trace_without_device_operations_is_refused(tmp_path):
    empty = tmp_path / "empty.xplane.pb"
    empty.write_bytes(b"")
    with pytest.raises(ValueError, match="no device operation"):
        xplane.reduce_trace(str(empty))


def test_readers_over_the_reduced_trace(reduced):
    facts = {"trace": reduced,
             "config": {"programs": {"decode": "prog_matmul",
                                     "prefill": "not_in_the_trace"}}}
    assert per_execution_ms(facts, "decode") == pytest.approx(0.1025, rel=0.01)
    assert per_execution_ms(facts, "prefill") is None
    assert per_execution_ms({"trace": None, "config": {}}, "decode") is None


# -- the command ---------------------------------------------------------------------

def _command(*args, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, *BENCH["command"][1].split("/")),
         *args], capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", [c["name"] for c in BENCH["workloads"]])
def test_rehearsal_prints_the_result_lines_keys_and_no_device_number(
        workload, trace, tmp_path):
    run = _command("--workload", workload, "--seed", str(2**31 + 3),
                   "--seconds", "2", "--trace", str(trace), "--rehearse",
                   env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    group = "per_layer" if trace else "end_to_end"
    entries = {m["name"]: m for m in harness.metrics_of(
        BENCH, group, workload)}
    if not trace:
        assert set(line["metrics"]) == set(entries)
    assert set(line["metrics"]) <= set(entries)
    for name, got in line["metrics"].items():
        assert got["unit"] == entries[name]["unit"]
        # a CPU run's time is never written under a device metric's name
        if entries[name]["source"] != "program_counter":
            assert got["value"] is None
    # the numbers compared are printed beside their limits
    checks = json.loads(run.stdout.strip().splitlines()[-2])["checks"]
    assert {"served_gap_max", "served_gap_mean"} <= {c["name"] for c in checks}


def test_anything_but_a_tpu_is_refused_with_no_result():
    run = _command("--workload", "opt1b3_chat", "--seed", "1", "--seconds",
                   "2", "--trace", "0")
    assert run.returncode == 2
    assert run.stdout == ""
    assert "needs 1 TPU chip" in run.stderr


def test_an_unknown_workload_is_an_error_with_no_result():
    run = _command("--workload", "no_such_cell", "--seed", "1", "--seconds",
                   "2", "--trace", "0", "--rehearse")
    assert run.returncode != 0 and run.stdout == ""
