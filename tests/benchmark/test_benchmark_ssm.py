"""The cell of the Jamba-shaped configuration (``jamba2_3b``) on the CPU at
its ``rehearsal`` sizes: a sound run is correct and leaves no page behind,
the control (the reference put through fp8) and a token altered where it is
produced read false, the traffic file's population, the new readers over
hand-built facts, and the operation counts behind the three rooflines
against hand arithmetic at the cell's published sizes."""
import json
import math
import os
import subprocess
import sys
from statistics import NormalDist
from types import SimpleNamespace as Span

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.drivers import lm_serving, lm_serving_ssm  # noqa: E402
from benchmark.lib import (  # noqa: E402
    harness,
    opcount_ssm_mqa as opcount,
    peaks,
    program_spans,
    traffic,
)
from tests.benchmark.test_benchmark_correct import rehearsal_ctx  # noqa: E402

BENCH = harness.load_benchmark()
CELL = "jamba2_reasoning_saturated"
_, CONFIG = harness.find_cell(BENCH, CELL)
V5E = peaks.peaks_for("TPU v5 lite")
NEW = ("ssm_step_dev_share", "attn_mqa_step_share", "mlp_step_dev_share",
       "ssm_scan_chunk_share", "state_live_share", "ssm_step_roofline",
       "ssm_chunk_scan_roofline", "ssm_mqa_step_roofline")


# -- the cell, rehearsed ------------------------------------------------------------

@pytest.fixture(scope="module")
def sound_run():
    ctx = rehearsal_ctx(CELL, 2**31 + 33, 2.0)
    return ctx, lm_serving_ssm.run(ctx)


def test_a_sound_run_of_the_new_family_is_correct(sound_run):
    ctx, out = sound_run
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] > ctx["mix"]["clients"]
    assert set(out["end_to_end"]) == {"setup_s", "ttft_p50_ms", "tpot_p50_ms"}
    checks = {n: (v, lim) for n, v, lim in out["checks"]}
    assert checks["served_gap_max"][0] <= checks["served_gap_max"][1]
    assert checks["served_tokens_compared"][0] > 0
    assert checks["pages_left"] == (0, 0)
    assert checks["prefill_launches_in_window"][0] > 0
    facts = out["facts"]
    assert facts["compiles_in_window"] == 0 and facts["ramp_s"] > 0
    # six state layers of (3 x 64) + (16 x 64) float32 values a slot
    assert facts["state"]["slot_bytes"] == 6 * (192 + 1024) * 4
    assert facts["state"]["slots"] == 4 and facts["pool_pages"] == 96
    for name in ("pool_pages_used_peak.tpot", "pool_live_share.tpot",
                 "batch_occupancy.tpot"):
        assert 0 < harness.reader_for(name)(dict(facts, metric=None)) <= 100
    assert harness.reader_for("ramp_s")(facts) == facts["ramp_s"]


def test_the_rehearsal_has_both_kinds_of_layer_and_prompts_of_many_launches(
        sound_run):
    ctx, _ = sound_run
    cfg = ctx["config"]
    kinds = [i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
             for i in range(cfg["num_hidden_layers"])]
    assert kinds == [False, False, True, False, False, False, True, False]
    chunk = cfg["engine"]["chunk"]
    pairs = ctx["mix"]["requests"]
    assert any(p > 2 * chunk and p % chunk for p, _ in pairs), "ragged"
    assert any(p < chunk for p, _ in pairs), "one launch"
    assert max(p + s for p, s in pairs) <= cfg["max_position_embeddings"]


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
    listed = {m["name"]: m for m in harness.metrics_of(BENCH, "per_layer",
                                                       CELL)}
    missing = set(listed) - set(line["metrics"])
    # what is read from the device's plane of the trace has nothing to read
    assert all(listed[n]["source"] == "device_trace" for n in missing), missing
    counters = {n: line["metrics"][n]["value"] for n, m in listed.items()
                if m["source"] == "program_counter"}
    assert set(counters) >= {"state_live_share", "attn_pages_read_share",
                             "passes_with_chunk_share.tpot",
                             "prefill_fill_share.tpot",
                             "batch_occupancy.tpot"}
    counts = ("compiles_in_window.tpot", "setup_fresh_compiles")
    assert all(0.0 <= v <= 100.0 for n, v in counters.items()
               if n not in counts)
    assert counters["compiles_in_window.tpot"] == 0
    assert counters["setup_fresh_compiles"] >= 0
    assert 0 < counters["state_live_share"] <= 100


@pytest.mark.parametrize("seed", [7, 2**31 + 8])
def test_the_fp8_control_fails_the_limits(seed):
    config = {**CONFIG, **CONFIG["rehearsal"], "vocab_size": 2048,
              "hidden_size": 64, "max_position_embeddings": 128}
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
    assert control.mean() > CONFIG["check"]["served_gap_mean_limit"]


def test_a_token_altered_in_step_is_not_correct(monkeypatch):
    real_step = lm_serving.EngineProxy.step

    def altered(self):
        return (real_step(self) + 1) % self._engine.family.vocab

    monkeypatch.setattr(lm_serving.EngineProxy, "step", altered)
    out = lm_serving_ssm.run(rehearsal_ctx(CELL, 5, 2.0))
    assert out["correct"] is False
    checks = {n: (v, lim) for n, v, lim in out["checks"]}
    assert checks["served_gap_mean"][0] > checks["served_gap_mean"][1]


# -- the configuration and the traffic, to ISSUE 33's numbers ---------------------------

def test_the_configuration_keeps_every_published_key():
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog of architectures on this machine")
    with open(catalog) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    row = next(r for r in rows if r["name"] == "AI21-Jamba2-3B")
    entry = next(c for c in BENCH["configs"] if c["name"] == "jamba2_3b")
    assert entry["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if CONFIG.get(k, "-") != v}
    assert changed == set(CONFIG["reduced"]) == set(entry["reduced"]) == {
        "max_position_embeddings"}
    assert CONFIG["published"] == {"max_position_embeddings": 262144}
    assert CONFIG["num_hidden_layers"] == 28 and CONFIG["vocab_size"] == 65536
    for key in ("norms", "positions", "state", "weights", "parameters",
                "serving_limit"):
        assert CONFIG["assumed"][key]
    geo = CONFIG["engine"]
    assert (geo["slots"], geo["chunk"], geo["share_prefixes"]) == (
        128, 128, False)
    assert geo["pages"] * geo["page_size"] == 128 * 6144
    assert 16 <= geo["page_size"] <= 64
    assert CONFIG["kind"] == "lm_serving_ssm"
    assert CONFIG["reference"] == "jamba_lm"


def test_the_traffic_is_issue_33s_to_the_number():
    mix = traffic.load("jamba_reasoning_closed")
    assert (mix["kind"], mix["clients"], mix["rounds"]) == (
        "closed_loop_requests", 128, 6)
    pairs = mix["requests"]
    assert len(pairs) == 256
    nd = NormalDist()
    mid = [(i + 0.5) / 128 for i in range(128)]

    def quantiles(median, sigma, step, lo, hi):
        return [int(min(max(round(median * math.exp(sigma * nd.inv_cdf(q))
                                  / step) * step, lo), hi)) for q in mid]

    pop = mix["population"]
    assert pop["prompts"] == quantiles(128, 0.6, 16, 48, 512)
    assert pop["outputs"] == quantiles(2560, 0.4, 64, 1024, 4096)
    for order in (pop["prompt_order"], pop["output_order"]):
        assert sorted(order) == list(range(256))  # a fixed permutation
    assert pairs == [[pop["prompts"][p % 128], pop["outputs"][o % 128]]
                     for p, o in zip(pop["prompt_order"],
                                     pop["output_order"])]
    # each quantile twice
    assert sorted(p for p, _ in pairs) == sorted(pop["prompts"] * 2)
    assert sorted(s for _, s in pairs) == sorted(pop["outputs"] * 2)
    assert min(p for p, _ in pairs) == 48 and max(p for p, _ in pairs) == 512
    assert min(s for _, s in pairs) == 1024
    assert max(s for _, s in pairs) == 4096
    assert max(p + s for p, s in pairs) == 4608 \
        <= CONFIG["max_position_embeddings"] == 6144
    # one launch of 256 for about five in six
    assert 0.8 < sum(p <= 256 for p, _ in pairs) / 256 < 0.9
    assert mix["check_sample"] in (2, 4)
    assert mix["trace"] == {"start_s": 20.0, "seconds": 10.0}
    items = traffic.requests(mix, 2**31 + 3, 48.0, CONFIG["vocab_size"])
    assert len(items) == 128 * 6 and sum(it["ramp"] for it in items) == 128
    assert [it["after"] for it in items[128:]] == list(range(640))
    # client c alternates entries c and c + 128
    for c in (0, 5, 127):
        mine = [items[c + 128 * r] for r in range(6)]
        assert [(it["prompt"].size, it["steps"]) for it in mine] == [
            tuple(pairs[c]), tuple(pairs[c + 128])] * 3
    # no client runs out in a window, with room for a program twice as fast
    assert 6 * min(s for _, s in pairs) * 0.0075 > 46


def test_the_cell_and_its_metrics_are_at_the_end_of_their_lists():
    """Found by name: a later cell, configuration or entry stands after
    them, as the contract puts it."""
    (config,) = [c for c in BENCH["configs"] if c["name"] == "jamba2_3b"]
    assert config["file"] == "benchmark/configs/jamba2_3b.json"
    (cell,) = [c for c in BENCH["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "jamba2_3b", "jamba_reasoning_closed", 1)
    assert len(cell["why"]) <= 200
    judged = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in judged["tpot_p50_ms"]["workloads"]
    cells = [c["name"] for c in BENCH["workloads"]]
    mine = {m["name"]: m for m in harness.metrics_of(BENCH, "per_layer",
                                                     CELL)}
    layers = {m["layer"] for m in BENCH["per_layer"]
              if m["name"] not in mine}
    for name, m in mine.items():
        harness.reader_for(name)  # every entry has a reader
        assert m["moves"] in ("setup_s", "tpot_p50_ms")
        if "workloads" in m:
            assert CELL in m["workloads"] and m["layer"] in layers
            assert set(m["workloads"]) <= set(
                judged[m["moves"]].get("workloads", cells))
    # what is the state family's alone lists this cell and no other
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "tpot_p50_ms"
    assert mine["ramp_s"]["moves"] == "setup_s"
    # all seven of PR 24's, the launch's and the first token's among them,
    # and what every serving cell has: by one name in every cell
    quantities = {n.rsplit(".", 1)[0] if n.endswith(".tpot") else n
                  for n in mine}
    assert quantities >= {
        "sched_self_ms_per_pass", "passes_with_chunk_share", "step_host_ms",
        "chunk_host_ms", "step_pull_wait_ms", "prefill_lane_wait_p50_ms",
        "host_serial_share", "decode_step_dev_ms", "prefill_chunk_dev_ms",
        "serving_device_idle", "batch_occupancy", "pool_live_share",
        "pool_pages_used_peak", "attn_pages_read_share",
        "prefill_fill_share", "compiles_in_window", "out_tokens_per_s",
        "gen_late_p99_ms", "ttft_p50_ms", "ramp_s"}
    assert not [n for n in mine if n.endswith((".jamba", ".ttft"))]


# -- scopes and readers over hand-built facts -----------------------------------------

HLO = """HloModule jit__step
ENTRY %main.1 (p: f32[4]) -> f32[4] {
  %fusion.3 = f32[128,10240]{1,0} fusion(%p), kind=kOutput, calls=%a, metadata={op_name="jit(_step)/ssm.in/dot_general"}
  %fusion.4 = bf16[26,128,15360]{2,1,0} fusion(%p), kind=kLoop, calls=%b, metadata={op_name="jit(_step)/ssm.conv/scatter"}
  %fusion.5 = f32[128,192]{1,0} fusion(%p), kind=kOutput, calls=%c, metadata={op_name="jit(_step)/ssm.x/dot_general"}
  %selective_scan_step.6 = (f32[128,5120]{1,0}, f32[26,128,16,5120]{3,2,1,0}) custom-call(%p), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[4]{0}}, metadata={op_name="jit(_step)/ssm.scan/jit(_slots_call)/selective_scan_step/pallas_call"}
  %fusion.7 = f32[128,2560]{1,0} fusion(%p), kind=kOutput, calls=%d, metadata={op_name="jit(_step)/ssm.out/dot_general"}
  %paged_line_attention.8 = f32[128,32,128]{2,1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/attn.full/jit(_call)/paged_line_attention/pallas_call"}
  %fusion.9 = f32[128,8192]{1,0} fusion(%p), kind=kOutput, calls=%e, metadata={op_name="jit(_step)/mlp/dot_general"}
  ROOT %fusion.12 = f32[128,65536]{1,0} fusion(%p), kind=kOutput, calls=%h, metadata={op_name="jit(_step)/head/dot_general"}
  %fusion.13 = f32[128,2560]{1,0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(_step)/add"}
}
"""
OPS = {"fusion.3_f32_128_10240_": ("ssm.in", 0.20),
       "fusion.4_bf16_26_128_15360_": ("ssm.conv", 0.03),
       "fusion.5_f32_128_192_": ("ssm.x", 0.02),
       "selective_scan_step.6_f32_128_5120_": ("ssm.scan", 0.20),
       "fusion.7_f32_128_2560_": ("ssm.out", 0.10),
       "paged_line_attention.8_f32_128_32_128_": ("attn.full", 0.04),
       "fusion.9_f32_128_8192_": ("mlp", 0.33),
       "fusion.12_f32_128_65536_": ("head", 0.05),
       "fusion.13_f32_128_2560_": (None, 0.03)}
CHUNK_OPS = {"fusion.3_f32_128_10240_": 0.30,
             "fusion.4_bf16_26_128_15360_": 0.05,
             "selective_scan_step.6_f32_128_5120_": 0.15,
             "fusion.9_f32_128_8192_": 0.50}


def test_scopes_come_from_the_compiled_programs_op_names():
    got = lm_serving_ssm.scopes_in(HLO)
    assert got == {op: scope for op, (scope, _) in OPS.items() if scope}
    assert lm_serving_ssm.scope_of("jit(_step)/ssm.scan/mul") == "ssm.scan"
    assert lm_serving_ssm.scope_of("jit(_step)/ssm/mul") is None
    assert lm_serving_ssm.scope_of("jit(_step)/attn.window/mul") is None


def _facts(**over):
    scopes = lm_serving_ssm.scopes_in(HLO)
    trace = {"window_s": 10.0, "busy_s": 8.0, "programs": {
        "_step": {"count": 50, "total_s": 1.0,
                  "ops": {op: s for op, (_, s) in OPS.items()}},
        "_prefill_chunk": {"count": 4, "total_s": 1.0, "ops": CHUNK_OPS}}}
    facts = {"trace": trace, "peaks": V5E, "config": CONFIG,
             "op_scopes": {"_step": scopes, "_prefill_chunk": scopes},
             "trace_bounds": (100.0, 110.0),
             "decode_steps": [(101.0, 128, 256000, 0),
                              (102.0, 126, 300000, 0),
                              (200.0, 128, 256000, 0)]}  # outside the trace
    facts.update(over)
    return facts


def test_step_shares_are_the_decode_programs_time_by_scope():
    facts = _facts()
    assert harness.reader_for("ssm_step_dev_share")(facts) == \
        pytest.approx(100 * 0.55)
    assert harness.reader_for("attn_mqa_step_share")(facts) == \
        pytest.approx(100 * 0.04)
    assert harness.reader_for("mlp_step_dev_share")(facts) == \
        pytest.approx(100 * 0.33)
    # of a launch's time, what lies under ssm.scan and ssm.conv
    assert harness.reader_for("ssm_scan_chunk_share")(facts) == \
        pytest.approx(100 * (0.15 + 0.05))
    # a program without the scope map, or a window without a launch, has
    # nothing to read: None, and nothing raised
    for name in NEW:  # but the two that read no scope
        if name not in ("state_live_share", "ssm_mqa_step_roofline"):
            assert harness.reader_for(name)(_facts(op_scopes=None)) is None
    no_launch = dict(_facts()["trace"], programs={
        "_step": _facts()["trace"]["programs"]["_step"]})
    assert harness.reader_for("ssm_scan_chunk_share")(
        _facts(trace=no_launch)) is None
    assert harness.reader_for("ssm_mqa_step_roofline")(
        _facts(decode_steps=[])) is None


def test_step_rooflines_are_least_time_over_the_scopes_time_in_one_step():
    facts = _facts()

    def least(cost):
        return max(cost["bytes"] / 819e9, cost["flops"] / 197e12)

    ssm = (least(opcount.ssm_step(CONFIG, 128))
           + least(opcount.ssm_step(CONFIG, 126))) / 2
    assert harness.reader_for("ssm_step_roofline")(facts) == pytest.approx(
        100 * ssm / (0.55 / 50))
    whole = (least(opcount.step(CONFIG, 128, 256000))
             + least(opcount.step(CONFIG, 126, 300000))) / 2
    assert harness.reader_for("ssm_mqa_step_roofline")(facts) == \
        pytest.approx(100 * whole / (1.0 / 50))


def test_the_spans_readers_read_the_engines_counters(monkeypatch):
    def passes(_facts):
        step = [Span(name="engine.step.prepare", attrs={
            "live": 126, "state_slots_live": 126, "state_slots": 128})]
        launch = [Span(name="engine.chunk.prepare", attrs={
            "n_valid": 96, "width": 256, "state_reset": 1})]
        old = [Span(name="engine.step.prepare", attrs={"live": 3}),
               Span(name="engine.chunk.prepare", attrs={"n_valid": 5})]
        return [(None, step), (None, step + launch), (None, old)]

    monkeypatch.setattr(program_spans, "traced_passes", passes)
    assert harness.reader_for("state_live_share")({}) == pytest.approx(
        100 * 126 / 128)
    cost = opcount.chunk_scan(CONFIG, 96)
    least = max(cost["bytes"] / 819e9, cost["flops"] / 197e12)
    assert harness.reader_for("ssm_chunk_scan_roofline")(_facts()) == \
        pytest.approx(100 * 26 * least / 0.15)
    # the parent of the PR that added them: no such attributes, nothing read
    monkeypatch.setattr(program_spans, "traced_passes", lambda f: [
        (None, [Span(name="engine.step.prepare", attrs={"live": 3}),
                Span(name="engine.chunk.prepare", attrs={"n_valid": 5})])])
    assert harness.reader_for("state_live_share")({}) is None
    assert harness.reader_for("ssm_chunk_scan_roofline")(_facts()) is None
    monkeypatch.setattr(program_spans, "traced_passes", lambda f: None)
    assert harness.reader_for("state_live_share")({}) is None


# -- the counts, against hand arithmetic at the published sizes ---------------------

def test_weights_per_layer_are_issue_33s_numbers():
    s = opcount.sizes(CONFIG)
    # W_in 2560 x 10240, W_out 5120 x 2560, W_x 5120 x 192, W_dt 160 x 5120,
    # A_log 5120 x 16, the conv 4 x 5120 + 5120, b_dt, D, the norms' 192
    assert s["mixer"] == (26214400 + 13107200 + 983040 + 819200 + 81920
                          + 20480 + 3 * 5120 + 192) == 41241792
    assert s["mlp"] == 3 * 2560 * 8192 == 62914560
    # W_q and W_o 2560 x 2560, W_k and W_v 2560 x 128
    assert s["attention"] == 2 * 6553600 + 2 * 327680 == 13762560
    assert s["embed"] == 65536 * 2560 == 167772160
    assert (s["layers"], s["state_layers"], s["attention_layers"]) == (
        28, 26, 2)
    assert s["line"] == 256 and s["heads"] == 20 and s["head_dim"] == 128
    total = opcount.parameters(CONFIG)
    assert total == (26 * 41241792 + 2 * 13762560 + 28 * (62914560 + 5120)
                     + 167772160 + 2560)
    assert round(total / 1e9, 3) == 3.029 and round(2 * total / 1e9, 2) == 6.06
    # a slot's state: 16 x 5120 float32 + 3 x 5120 bfloat16 a layer
    assert opcount.state_bytes(CONFIG) == 327680 + 30720 == 358400
    assert round(26 * 358400 / 1e6, 2) == 9.32
    # a token's lines in the two attention layers
    assert s["attention_layers"] * s["line"] * 2 == 1024


def test_a_full_step_reads_eight_point_seven_gigabytes():
    # 128 sequences of 2,000 visible tokens each (ISSUE 33's 8.7 GB, 10.6
    # ms): weights 6.06 GB, the state read and written 2.39, lines 0.26
    cost = opcount.step(CONFIG, 128, 128 * 2000)
    assert cost["bytes"] / 1e9 == pytest.approx(8.7, abs=0.01)
    assert cost["bytes"] / 819e9 * 1e3 == pytest.approx(10.6, abs=0.05)
    assert cost["bytes"] / 819e9 > cost["flops"] / 197e12  # bound by bytes
    assert cost["flops"] / 1e12 == pytest.approx(0.78, abs=0.01)
    state = 128 * 26 * 2 * 358400
    assert state / 1e9 == pytest.approx(2.39, abs=0.005)
    lines = cost["bytes"] - 2 * opcount.parameters(CONFIG) - state
    assert lines == (128 * 2000 + 128) * 1024
    # one more live sequence costs its state twice and its lines
    more = opcount.step(CONFIG, 129, 128 * 2000)
    assert more["bytes"] - cost["bytes"] == 26 * 2 * 358400 + 1024


def test_the_state_layers_of_a_step_are_half_of_it():
    cost = opcount.ssm_step(CONFIG, 128)
    assert cost["bytes"] == 2 * 26 * 41241792 + 128 * 26 * 2 * 358400
    assert cost["bytes"] / 1e9 == pytest.approx(4.53, abs=0.005)
    assert cost["flops"] == 2.0 * 128 * 26 * 41241792 \
        + 128 * 26 * 5120 * 16 * 6.0
    whole = opcount.step(CONFIG, 128, 128 * 2000)
    assert 0.50 < cost["bytes"] / whole["bytes"] < 0.54
    # a dead slot costs nothing here: the count follows the live ones
    assert opcount.ssm_step(CONFIG, 0)["bytes"] == 2 * 26 * 41241792


def test_a_launchs_scan_is_bound_by_its_bytes():
    cost = opcount.chunk_scan(CONFIG, 256)
    # u, the step sizes and y: 256 x 5120 float32 each; B and C 256 x 16;
    # A and the state in and out 16 x 5120
    assert cost["bytes"] == 4 * (3 * 256 * 5120 + 2 * 256 * 16
                                 + 3 * 16 * 5120)
    assert cost["flops"] == 6.0 * 256 * 5120 * 16
    assert cost["bytes"] / 819e9 > cost["flops"] / 197e12
    assert cost["bytes"] / 819e9 * 1e6 == pytest.approx(20.4, abs=0.1)
    half = opcount.chunk_scan(CONFIG, 128)
    assert half["flops"] * 2 == cost["flops"]
