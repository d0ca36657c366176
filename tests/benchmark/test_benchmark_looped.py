"""The cell of the Ouro-shaped configuration (``ouro_2.6b``) on the CPU at
its ``rehearsal`` sizes: a sound run is correct and leaves no page behind,
the control (the reference put through fp8) and a token altered where it is
produced read false, the traffic file's population, the new readers over
hand-built facts, and the operation counts behind the two rooflines
against hand arithmetic at the cell's published sizes."""
import json
import math
import os
import subprocess
import sys
from statistics import NormalDist

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.drivers import lm_serving, lm_serving_looped  # noqa: E402
from benchmark.lib import (  # noqa: E402
    harness,
    opcount_looped as opcount,
    peaks,
    traffic,
)
from benchmark.lib.opcount import least_seconds  # noqa: E402
from tests.benchmark.test_benchmark_correct import rehearsal_ctx  # noqa: E402

BENCH = harness.load_benchmark()
CELL = "ouro_reasoning_saturated"
_, CONFIG = harness.find_cell(BENCH, CELL)
V5E = peaks.peaks_for("TPU v5 lite")
NEW = ("loop_exit_dev_share", "loop_exit_last_pass_share",
       "loop_lines_bytes_share", "loop_layers_roofline",
       "loop_step_roofline")
SHARED = ("attn_pages_read_share", "chunk_host_ms.tpot",
          "prefill_lane_wait_p50_ms.tpot", "prefill_chunk_dev_ms.tpot",
          "ttft_p50_ms.tpot", "out_tokens_per_s", "ramp_s")


# -- the cell, rehearsed ------------------------------------------------------------

@pytest.fixture(scope="module")
def sound_run():
    ctx = rehearsal_ctx(CELL, 2**31 + 43, 2.0)
    return ctx, lm_serving_looped.run(ctx)


def test_a_sound_run_of_the_new_family_is_correct(sound_run):
    ctx, out = sound_run
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] > ctx["mix"]["clients"]
    assert set(out["end_to_end"]) == {"setup_s", "ttft_p50_ms", "tpot_p50_ms"}
    checks = {n: (v, lim) for n, v, lim in out["checks"]}
    assert checks["traffic_ran_out_s"] == (None, None)
    assert checks["served_gap_max"][0] <= checks["served_gap_max"][1]
    assert checks["served_tokens_compared"][0] > 0
    assert checks["pages_left"] == (0, 0)
    assert checks["preempted"] == (0, None)
    assert checks["prefill_launches_in_window"][0] > 0
    facts = out["facts"]
    assert facts["compiles_in_window"] == 0 and facts["ramp_s"] > 0
    # the steps of the window counted where their tokens' logits came from:
    # at the published threshold 1, all from the last of the four passes
    exits = facts["exit_passes"]
    assert sorted(exits) == [f"exit_pass_{t}" for t in (1, 2, 3, 4)]
    assert exits["exit_pass_4"] == sum(exits.values()) > 0
    assert harness.reader_for("loop_exit_last_pass_share")(facts) == 100.0


def test_the_rehearsal_is_the_published_block_at_a_small_size():
    small = {**CONFIG, **CONFIG["rehearsal"]}
    assert small["total_ut_steps"] == CONFIG["total_ut_steps"] == 4
    assert small["early_exit_threshold"] == CONFIG["early_exit_threshold"]
    assert (small["hidden_size"], small["num_hidden_layers"]) == (32, 4)
    assert small["serve_dtype"] == "float32"


def test_the_rehearse_command_prints_every_listed_metric_it_can():
    """A traced rehearsal: every per-layer metric of the cell that does not
    need a device plane has a value (a CPU's times print as null)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 5), "--seconds", "2",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, done.stdout[-1500:]
    assert line["rehearsal"] is True
    assert line["checks"]["traffic_ran_out_s"]["value"] is None
    listed = {m["name"]: m for m in harness.metrics_of(BENCH, "per_layer",
                                                       CELL)}
    missing = set(listed) - set(line["metrics"])
    # what is read from the device's plane of the trace has nothing to read
    assert all(listed[n]["source"] == "device_trace" for n in missing), missing
    counters = {n: line["metrics"][n]["value"] for n, m in listed.items()
                if m["source"] == "program_counter"}
    assert set(counters) >= {"loop_exit_last_pass_share",
                             "loop_lines_bytes_share",
                             "attn_pages_read_share",
                             "passes_with_chunk_share.tpot",
                             "batch_occupancy.tpot"}
    counts = ("compiles_in_window.tpot", "setup_fresh_compiles")
    assert all(0.0 <= v <= 100.0 for n, v in counters.items()
               if n not in counts)
    assert counters["compiles_in_window.tpot"] == 0
    assert counters["loop_exit_last_pass_share"] == 100.0
    assert 0 < counters["loop_lines_bytes_share"] < 100


@pytest.mark.parametrize("seed", [7, 2**31 + 8])
def test_the_fp8_control_fails_the_limits(seed):
    config = {**CONFIG, **CONFIG["rehearsal"], "vocab_size": 2048,
              "hidden_size": 128, "head_dim": 32,
              "max_position_embeddings": 128}
    rng = np.random.default_rng(seed)
    pairs = [(rng.integers(0, 2048, 1, dtype=np.int32),
              rng.integers(0, 2048, 100, dtype=np.int32)) for _ in range(4)]
    got = lm_serving.served_logit_gaps(config, seed, pairs, [(1, 100)],
                                       quants=("none", "fp8"))
    limits = config["check"]  # a sound run on the CPU reads 0 for both
    control = np.concatenate(got["fp8"])
    assert control.max() > limits["served_gap_max_limit"]
    assert control.mean() > limits["served_gap_mean_limit"]
    # and the published configuration's limits lie under the same control
    # (at this width it reads what it reads on the chip: a mean of 2)
    assert control.mean() > CONFIG["check"]["served_gap_mean_limit"]
    assert control.max() > CONFIG["check"]["served_gap_max_limit"]


def test_a_token_altered_in_step_is_not_correct(monkeypatch):
    real_step = lm_serving.EngineProxy.step

    def altered(self):
        return (real_step(self) + 1) % self._engine.family.vocab

    monkeypatch.setattr(lm_serving.EngineProxy, "step", altered)
    out = lm_serving_looped.run(rehearsal_ctx(CELL, 5, 2.0))
    assert out["correct"] is False
    checks = {n: (v, lim) for n, v, lim in out["checks"]}
    assert checks["served_gap_mean"][0] > checks["served_gap_mean"][1]


# -- the configuration and the traffic, to ISSUE 43's numbers ---------------------------

def test_the_configuration_keeps_every_published_key():
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog of architectures on this machine")
    with open(catalog) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    row = next(r for r in rows if r["name"] == "Ouro-2.6B")
    entry = next(c for c in BENCH["configs"] if c["name"] == "ouro_2.6b")
    assert entry["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if CONFIG.get(k, "-") != v}
    assert changed == set(CONFIG["reduced"]) == set(entry["reduced"]) == {
        "max_position_embeddings"}
    assert CONFIG["published"] == {"max_position_embeddings": 65536}
    assert CONFIG["max_position_embeddings"] == 640
    assert (CONFIG["num_hidden_layers"], CONFIG["vocab_size"],
            CONFIG["total_ut_steps"]) == (48, 49152, 4)
    assert "one v5e chip holds the whole model" in CONFIG["deployment"]
    for key in ("norms", "pass_close", "gate", "exit_rule", "biases",
                "rotary", "cache", "weights", "parameters", "serving_limit"):
        assert CONFIG["assumed"][key]
    assert "2,667,974,657" in CONFIG["assumed"]["parameters"]
    assert "1,572,864" in CONFIG["assumed"]["cache"]
    assert CONFIG["engine"] == {"slots": 8, "page_size": 16, "chunk": 128,
                                "share_prefixes": False, "pages": 320}
    assert CONFIG["kind"] == "lm_serving_looped"
    assert CONFIG["reference"] == "ouro_lm"
    assert CONFIG["check"]["why"]
    assert CONFIG["programs"] == {"decode": "_step",
                                  "prefill": "_prefill_chunk"}


def test_the_traffic_is_issue_43s_to_the_number():
    mix = traffic.load("ouro_reasoning_closed")
    assert mix["kind"] == "closed_loop_requests"
    assert (mix["clients"], mix["rounds"]) == (8, 12)
    assert mix["check_sample"] == 4
    assert mix["trace"] == {"start_s": 20.0, "seconds": 6.0}
    pop = mix["population"]

    def quantiles(median, sigma, lo, hi):
        return [min(max(int(round(median * math.exp(
            sigma * NormalDist().inv_cdf((i + 0.5) / 16)) / 16)) * 16, lo), hi)
            for i in range(16)]

    assert pop["prompts"] == quantiles(96, 0.5, 32, 192)
    assert pop["outputs"] == quantiles(352, 0.2, 256, 448)
    assert (min(pop["prompts"]), max(pop["prompts"])) == (32, 192)
    assert (min(pop["outputs"]), max(pop["outputs"])) == (256, 448)
    for order in (pop["output_order"], pop["file_order"]):
        assert sorted(order) == list(range(16))
    pairs = [[pop["prompts"][i], pop["outputs"][pop["output_order"][i]]]
             for i in range(16)]
    assert mix["requests"] == [pairs[i] for i in pop["file_order"]]
    # the longest pair is the serving limit: 8 slots never want more than
    # the pool's 320 pages of 16, so nothing is preempted or refused
    assert max(p + o for p, o in mix["requests"]) == 640 \
        == CONFIG["max_position_embeddings"]
    geo = CONFIG["engine"]
    assert geo["slots"] * 640 == geo["pages"] * geo["page_size"]
    # the seed draws token ids and never lengths, order or who waits
    a = traffic.requests(mix, 1, 48.0, 49152)
    b = traffic.requests(mix, 2**31 + 7, 48.0, 49152)
    assert len(a) == len(b) == 96
    shape = [(r["prompt"].size, r["steps"], r["after"], r["ramp"], r["due_s"])
             for r in a]
    assert shape == [(r["prompt"].size, r["steps"], r["after"], r["ramp"],
                      r["due_s"]) for r in b]
    assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, b))
    assert all(0 <= int(r["prompt"].min()) and int(r["prompt"].max()) < 49152
               for r in a)
    # client c alternates entries c and c + 8 of the file
    for i, r in enumerate(a):
        assert [r["prompt"].size, r["steps"]] == mix["requests"][i % 16]
        assert r["ramp"] == (i < 8) and r["after"] == (i - 8 if i >= 8
                                                       else None)


def test_the_cell_and_its_metrics_are_at_the_end_of_their_lists():
    assert BENCH["configs"][-1]["name"] == "ouro_2.6b"
    assert BENCH["configs"][-1]["file"] == "benchmark/configs/ouro_2.6b.json"
    cell = BENCH["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "ouro_2.6b", "ouro_reasoning_closed", 1)
    assert len(cell["why"]) <= 200
    judged = {m["name"]: m for m in BENCH["end_to_end"]}
    assert judged["tpot_p50_ms"]["workloads"][-1] == CELL
    assert CELL not in judged["ttft_p50_ms"]["workloads"]
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"][-5:]] == list(NEW)
    for name in NEW:
        harness.reader_for(name)  # every entry has a reader of its own
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", f"{name}.py"))
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "tpot_p50_ms"
        assert by_name[name]["unit"] == "%"
    layers = {m["layer"] for m in BENCH["per_layer"][:-5]}
    assert {by_name[n]["layer"] for n in NEW} <= layers
    for name in SHARED:
        assert by_name[name]["workloads"][-1] == CELL
    # four shared quantities whose lists older tests of the benchmark pin
    # cell for cell do not list this cell (PERF.md section 7): its driver
    # hands their readers the facts all the same
    for name in ("attn_full_step_share", "mlp_step_dev_share",
                 "prefill_ctx_read_share.tpot", "prefill_fill_share.tpot"):
        assert CELL not in by_name[name]["workloads"]
    assert len(BENCH["per_layer"]) == 79
    assert len(BENCH["configs"]) == 5 and len(BENCH["workloads"]) == 7
    size = os.path.getsize(os.path.join(ROOT, "BENCHMARK.json"))
    assert size < 64 * 1024


# -- scopes and readers over hand-built facts -----------------------------------------

HLO = """HloModule jit__step
%wide.region_0.3 (p: f32[4]) -> f32[4] {
  %fusion.3 = f32[8,2048]{1,0} fusion(%p), kind=kOutput, calls=%a, metadata={op_name="jit(_step)/while/body/closed_call/attn.full/dot_general"}
  %paged_line_attention.8 = f32[8,16,2048]{2,1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/while/body/closed_call/attn.full/jit(_call)/paged_line_attention/pallas_call"}
  %fusion.9 = f32[8,5632]{1,0} fusion(%p), kind=kOutput, calls=%e, metadata={op_name="jit(_step)/while/body/closed_call/mlp/dot_general"}
  ROOT %fusion.10 = f32[8,1,2048]{2,1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_step)/while/body/closed_call/loop.exit/mul"}
}
ENTRY %main.1 (p: f32[4]) -> f32[4] {
  %while.7 = (s32[], f32[8,1,2048]{2,1,0}) while(%p), condition=%c, body=%wide.region_0.3, metadata={op_name="jit(_step)/while"}
  ROOT %fusion.12 = f32[8,49152]{1,0} fusion(%p), kind=kOutput, calls=%h, metadata={op_name="jit(_step)/head/dot_general"}
  %fusion.13 = f32[8,2048]{1,0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(_step)/add"}
}
"""
OPS = {"fusion.3_f32_8_2048_": ("attn.full", 0.10),
       "paged_line_attention.8_f32_8_16_2048_": ("attn.full", 0.15),
       "fusion.9_f32_8_5632_": ("mlp", 0.60),
       "fusion.10_f32_8_1_2048_": ("loop.exit", 0.02),
       "fusion.12_f32_8_49152_": ("head", 0.05),
       "fusion.13_f32_8_2048_": (None, 0.08)}


def test_scopes_come_from_the_compiled_programs_op_names():
    got = lm_serving_looped.scopes_in(HLO)
    # the loop's body is a computation of the module like any other; the
    # while itself carries no scope
    assert got == {op: scope for op, (scope, _) in OPS.items() if scope}
    assert lm_serving_looped.scope_of(
        "jit(_step)/while/body/loop.exit/mul") == "loop.exit"
    assert lm_serving_looped.scope_of("jit(_step)/loop/mul") is None
    assert lm_serving_looped.scope_of("jit(_step)/ssm.scan/mul") is None


def _facts(**over):
    scopes = lm_serving_looped.scopes_in(HLO)
    trace = {"window_s": 6.0, "busy_s": 5.5, "programs": {
        "_step": {"count": 50, "total_s": 1.0,
                  "ops": {op: s for op, (_, s) in OPS.items()}}}}
    facts = {"trace": trace, "peaks": V5E, "config": CONFIG,
             "op_scopes": {"_step": scopes},
             "trace_bounds": (100.0, 106.0),
             "exit_passes": {"exit_pass_1": 0, "exit_pass_2": 0,
                             "exit_pass_3": 0, "exit_pass_4": 400},
             "decode_steps": [(101.0, 8, 2200, 0), (102.0, 7, 1500, 0),
                              (200.0, 8, 2200, 0)]}  # outside the trace
    facts.update(over)
    return facts


def test_the_shares_are_the_decode_programs_time_by_scope():
    facts = _facts()
    assert harness.reader_for("loop_exit_dev_share")(facts) == \
        pytest.approx(100 * 0.02)
    assert harness.reader_for("attn_full_step_share")(facts) == \
        pytest.approx(100 * 0.25)
    assert harness.reader_for("mlp_step_dev_share")(facts) == \
        pytest.approx(100 * 0.60)


def test_the_exit_share_is_the_last_passs_count_over_all():
    read = harness.reader_for("loop_exit_last_pass_share")
    assert read(_facts()) == 100.0
    assert read(_facts(exit_passes={
        "exit_pass_1": 10, "exit_pass_2": 30, "exit_pass_3": 0,
        "exit_pass_4": 60})) == pytest.approx(60.0)
    # ten passes: the last is found by number, not by the name's order
    assert read(_facts(exit_passes={"exit_pass_10": 1, "exit_pass_9": 3,
                                    "exit_pass_2": 0})) == pytest.approx(25.0)


def test_the_rooflines_are_least_time_over_the_scopes_time_in_one_step():
    facts = _facts()

    def least(cost):
        return max(cost["bytes"] / 819e9, cost["flops"] / 197e12)

    steps = [(8, 2200), (7, 1500)]
    # attention and MLP together over the time under both scopes: the
    # compiler streams a layer's MLP weights in while its attention still
    # computes, so neither scope's time alone holds all of its work
    layers = sum(least(opcount.layers_step(CONFIG, a, c))
                 for a, c in steps) / 2
    assert harness.reader_for("loop_layers_roofline")(facts) == \
        pytest.approx(100 * layers / ((0.25 + 0.60) / 50))
    whole = sum(least(opcount.step(CONFIG, a, c)) for a, c in steps) / 2
    assert harness.reader_for("loop_step_roofline")(facts) == \
        pytest.approx(100 * whole / (1.0 / 50))
    share = sum(opcount.lines_bytes(CONFIG, a, c)
                / opcount.step(CONFIG, a, c)["bytes"] for a, c in steps) / 2
    assert harness.reader_for("loop_lines_bytes_share")(facts) == \
        pytest.approx(100 * share)


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_finds_nothing_where_the_program_has_nothing(name):
    """The parent of the PR that added them has no scope map, no counts, no
    traced steps: ``None``, and nothing raised."""
    read = harness.reader_for(name)
    bare = {"config": CONFIG, "peaks": V5E, "trace": None,
            "trace_bounds": None, "decode_steps": [], "op_scopes": None}
    assert read(bare) is None
    assert read(_facts(op_scopes=None, decode_steps=[], exit_passes={},
                       trace_bounds=None)) is None
    # and a configuration without a loop's keys has none of the loop's counts
    other = harness.find_cell(BENCH, "jamba2_reasoning_saturated")[1]
    if name != "loop_exit_dev_share":
        assert read(_facts(config=other, exit_passes=None)) is None


# -- the count behind the rooflines ------------------------------------------------------

def test_weights_and_lines_are_issue_43s_numbers():
    sz = opcount.sizes(CONFIG)
    assert sz["attention"] == 4 * 2048 * 2048 == 16_777_216
    assert sz["mlp"] == 3 * 2048 * 5632 == 34_603_008
    assert sz["head"] == 49152 * 2048 == 100_663_296
    assert sz["pass_layers"] == 192 and sz["passes"] == 4
    assert sz["line"] * 2 == 8_192          # bytes a token a pass-layer
    assert sz["line"] * 2 * sz["pass_layers"] == 1_572_864
    assert sz["scores"] == 4 * 2048
    layer = sz["attention"] + sz["mlp"] + sz["norms"]
    assert layer == 51_388_416 and 48 * layer == 2_466_643_968
    assert 48 * layer + 2 * sz["head"] + sz["close"] == 2_667_974_657


def test_a_step_of_eight_over_2200_tokens_reads_23_point_4_gigabytes():
    cost = opcount.step(CONFIG, 8, 2200)
    attn = opcount.attention_step(CONFIG, 8, 2200)
    mlp = opcount.mlp_step(CONFIG, 8)
    assert attn["bytes"] == 2 * 192 * 16_777_216 + (2200 + 8) * 192 * 8_192
    assert mlp["bytes"] == 2 * 192 * 34_603_008
    small = 192 * 4 * 2048 + 4 * 4097
    assert cost["bytes"] == attn["bytes"] + mlp["bytes"] + 2 * (
        100_663_296 + 8 * 2048 + small)
    # ISSUE 43's 23.39 GB leaves out the eight new lines written and the
    # embedding rows: 23.41 with them; 28.6 ms at 819 GB/s either way
    assert cost["bytes"] == pytest.approx(23.39e9, rel=1.5e-3)
    assert cost["flops"] == pytest.approx(
        2 * 8 * (4 * 2_466_643_968 + 100_663_296) + 2200 * 192 * 4 * 2048,
        rel=1e-3)
    seconds, bound = least_seconds(cost, V5E)
    assert bound == "hbm" and seconds == pytest.approx(28.6e-3, rel=2e-3)
    # 85% of the step is the weights read once a pass
    lines = opcount.lines_bytes(CONFIG, 8, 2200)
    assert lines == (2200 + 8) * 1_572_864
    assert 0.14 < lines / cost["bytes"] < 0.16


def test_the_weights_count_once_a_pass_whatever_the_batch():
    one, eight = opcount.step(CONFIG, 1, 300), opcount.step(CONFIG, 8, 300)
    # the batch adds embedding rows and new lines, never a weight
    assert eight["bytes"] - one["bytes"] == 7 * (1_572_864 + 2 * 2048)
    assert opcount.mlp_step(CONFIG, 1)["bytes"] == \
        opcount.mlp_step(CONFIG, 8)["bytes"]
    half = opcount.step({**CONFIG, "total_ut_steps": 2}, 8, 300)
    assert half["bytes"] < 0.52 * eight["bytes"]
