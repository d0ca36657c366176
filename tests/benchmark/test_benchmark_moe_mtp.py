"""The cell of the EXAONE-MoE-shaped configuration
(``kexaone_236b_a23b_ep8_l5``) on the CPU at its ``rehearsal`` sizes: a sound
run is correct by both comparisons (served tokens against the reference's
main scores, drafts against its MTP scores) and leaves no page behind, the
control (the reference put through fp8), an altered token and an altered
draft read false, the nine new readers over hand-built facts, and the
operation counts behind the three rooflines against hand arithmetic at the
cell's published sizes. Everything is found by name: a later cell,
configuration or entry breaks none of it."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace as Span

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.drivers import lm_serving, lm_serving_moe_mtp  # noqa: E402
from benchmark.lib import (  # noqa: E402
    harness,
    opcount_moe_mtp as opcount,
    peaks,
    readers_moe_mtp,
    traffic,
)
from tests.benchmark.test_benchmark_correct import rehearsal_ctx  # noqa: E402

BENCH = harness.load_benchmark()
CELL = "kexaone_mtp_decode_saturated"
NAME = "kexaone_236b_a23b_ep8_l5"
_, CONFIG = harness.find_cell(BENCH, CELL)
V5E = peaks.peaks_for("TPU v5 lite")
OWN = ("mtp_accept_share", "mtp_tokens_per_round", "mtp_dev_share",
       "verify_attn_dev_share", "moe_share_dev_share",
       "moe_assignments_here_share", "gqa_verify_roofline",
       "moe_share_roofline", "moe_mtp_step_roofline")


# -- the cell, rehearsed ------------------------------------------------------------

@pytest.fixture(scope="module")
def sound_run():
    ctx = rehearsal_ctx(CELL, 2**31 + 47, 2.5)
    return ctx, lm_serving_moe_mtp.run(ctx)


def test_a_sound_run_of_the_new_family_is_correct_by_both_comparisons(
        sound_run):
    ctx, out = sound_run
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] > ctx["mix"]["clients"]
    assert set(out["end_to_end"]) == {"setup_s", "ttft_p50_ms", "tpot_p50_ms"}
    checks = {n: (v, lim) for n, v, lim in out["checks"]}
    for name in ("served_gap_max", "served_gap_mean", "draft_gap_max",
                 "draft_gap_mean"):
        assert checks[name][0] <= checks[name][1], name
    assert checks["served_tokens_compared"][0] > 0
    assert checks["drafts_compared"][0] > 0
    assert checks["pages_left_full"] == (0, 0)
    assert checks["pages_left_window"] == (0, 0)
    for name in ("preempted", "shed_queue_full", "shed_memory",
                 "shed_overload"):
        assert checks[name] == (0, 0)
    assert out["facts"]["compiles_in_window"] == 0
    assert out["facts"]["ramp_s"] > 0
    assert "traffic_ran_out_s" not in out


def test_every_round_has_its_counts_its_account_and_its_pages(sound_run):
    ctx, out = sound_run
    facts = out["facts"]
    steps, moe = facts["decode_steps"], facts["moe_steps"]
    assert len(moe) == len(steps) > 0
    assert [t for t, _ in moe] == [s[0] for s in steps]
    # four sparse layers and the MTP block, four experts held at rehearsal
    assert facts["moe_expert_slots"] == 5 * 4
    top_k = ctx["config"]["num_experts_per_tok"]
    emitted = proposed = 0
    for (t, active, context, pages), (_, c) in zip(steps, moe):
        assert c["rows"] == 2 * active
        # a call dispatches one round and brings the one before home:
        # ``proposed`` is of the first, the other two of the second
        assert 0 <= c["accepted"] <= c["emitted"] <= 2 * 4
        # what the layers counted is of the round whose tokens came home
        assert c["moe_experts_touched"] <= min(20, c["moe_assignments"])
        assert c["moe_assignments"] <= (4 * 2 * 4 + 2 * 4) * top_k
        assert c["pages_full"] == pages
        emitted, proposed = emitted + c["emitted"], proposed + c["proposed"]
    assert 0 < emitted <= 2 * proposed
    for name in ("moe_experts_touched_share", "pool_pages_used_peak.tpot",
                 "pool_live_share.tpot", "batch_occupancy.tpot"):
        assert 0 < harness.reader_for(name)(dict(facts, metric=None)) <= 100
    assert harness.reader_for("moe_max_load_over_mean")(facts) >= 1.0
    assert harness.reader_for("ramp_s")(facts) == facts["ramp_s"]


def test_the_rehearse_command_prints_a_correct_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 5), "--seconds", "2",
         "--trace", "0", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, done.stdout[-1500:]
    assert line["rehearsal"] is True
    assert set(line["metrics"]) == {"tpot_p50_ms", "setup_s"}
    assert {"draft_gap_max", "draft_gap_mean"} <= set(line["checks"])


@pytest.mark.parametrize("seed", [7, 2**31 + 8])
def test_the_fp8_control_fails_the_limits(seed):
    config = {**CONFIG, **CONFIG["rehearsal"], "vocab_size": 2048,
              "vocab_held": [0, 2048], "hidden_size": 64,
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


def test_a_token_altered_in_the_round_is_not_correct(monkeypatch):
    real = lm_serving_moe_mtp.RoundProxy.step_tokens

    def altered(self):
        vocab = self._engine.family.vocab
        return [[(t + 1) % vocab for t in burst] for burst in real(self)]

    monkeypatch.setattr(lm_serving_moe_mtp.RoundProxy, "step_tokens", altered)
    out = lm_serving_moe_mtp.run(rehearsal_ctx(CELL, 5, 2.5))
    assert out["correct"] is False
    checks = {n: (v, lim) for n, v, lim in out["checks"]}
    assert checks["served_gap_mean"][0] > checks["served_gap_mean"][1]


def test_a_draft_altered_where_it_is_noted_is_not_correct(monkeypatch):
    real = lm_serving_moe_mtp.RoundProxy.step_tokens

    def altered(self):
        out = real(self)
        vocab = self._engine.family.vocab
        for record in self._active.values():
            m, d = record["drafts"][-1]
            record["drafts"][-1] = (m, (d + 1) % vocab)
        return out

    monkeypatch.setattr(lm_serving_moe_mtp.RoundProxy, "step_tokens", altered)
    out = lm_serving_moe_mtp.run(rehearsal_ctx(CELL, 6, 2.5))
    assert out["correct"] is False
    checks = {n: (v, lim) for n, v, lim in out["checks"]}
    # the served tokens are sound: the drafts alone fail
    assert checks["served_gap_mean"][0] <= checks["served_gap_mean"][1]
    assert checks["draft_gap_mean"][0] > checks["draft_gap_mean"][1]


# -- the files and the entries --------------------------------------------------------

def test_the_configuration_keeps_every_published_width():
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog of architectures on this machine")
    with open(catalog) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    row = next(r for r in rows if r["name"] == "K-EXAONE-236B-A23B")
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if CONFIG.get(k, "-") != v}
    assert changed == set(CONFIG["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings"}
    assert CONFIG["published"] == {k: row["config"][k] for k in changed}
    # the widths: none is cut
    assert (CONFIG["hidden_size"], CONFIG["num_attention_heads"],
            CONFIG["num_key_value_heads"], CONFIG["head_dim"]) == (
        6144, 64, 8, 128)
    assert (CONFIG["intermediate_size"], CONFIG["moe_intermediate_size"],
            CONFIG["num_experts_per_tok"], CONFIG["sliding_window"]) == (
        18432, 2048, 8, 128)
    assert CONFIG["rope_parameters"]["rope_theta"] == 1000000
    # the share: an eighth of the experts and of the vocabulary, the floors
    assert CONFIG["experts_held"] == [0, 16] == [0, CONFIG["num_experts"]]
    assert CONFIG["vocab_held"] == [0, 19200] == [0, CONFIG["vocab_size"]]
    assert 8 * 16 == row["config"]["num_experts"]
    assert 8 * 19200 == row["config"]["vocab_size"]
    held = CONFIG["layer_types"][:CONFIG["num_hidden_layers"]]
    assert held == ["sliding_attention"] * 3 + ["full_attention",
                                                "sliding_attention"]
    assert CONFIG["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    for key in ("head", "qk_norm", "positions", "router", "mtp",
                "acceptance", "parameters", "batch"):
        assert CONFIG["assumed"][key]
    assert "eight" in CONFIG["deployment"]
    assert CONFIG["programs"] == {"decode": "_round",
                                  "prefill": "_prefill_chunk"}
    # the program's configuration sees the published counts and the share
    from nnstreamer_tpu.models.exaone_moe import ExaoneMoeConfig

    reference = harness.reference_for(CONFIG)
    mcfg = ExaoneMoeConfig.from_published(reference.model_config(CONFIG))
    assert (mcfg.num_experts, mcfg.held, mcfg.vocab_size, mcfg.vocab) == (
        128, (0, 16), 153600, 19200)
    assert reference.sizes(CONFIG).experts == 128
    assert reference.sizes(CONFIG).vocab == 19200


def test_the_traffic_is_issue_47s():
    mix = traffic.load("kexaone_reasoning_closed")
    assert (mix["kind"], mix["clients"]) == ("closed_loop_requests", 64)
    assert len(mix["requests"]) == 32
    prompts = sorted(p for p, _ in mix["requests"])
    outputs = sorted(s for _, s in mix["requests"])
    assert (prompts[0], prompts[-1]) == (128, 1024)
    assert (outputs[0] >= 1024, outputs[-1]) == (True, 3072)
    assert 352 <= np.median(prompts) <= 416
    assert 1984 <= np.median(outputs) <= 2112
    assert max(p + s for p, s in mix["requests"]) \
        <= CONFIG["max_position_embeddings"] == 4096
    assert mix["check_sample"] == 4
    assert mix["trace"] == {"start_s": 14.0, "seconds": 10.0}
    items = traffic.requests(mix, 2**31 + 3, 48.0, CONFIG["vocab_held"][1])
    assert len(items) == 64 * mix["rounds"]
    assert sum(it["ramp"] for it in items) == 64
    assert all(0 <= int(it["prompt"].max()) < 19200 for it in items[:64])
    # the file outlasts the window: a client's requests take far longer
    # than 48 s at any round a v5e could run (over 5 ms)
    assert mix["rounds"] * min(outputs) * 0.005 > 40


def test_the_cell_and_its_metrics_are_found_by_name():
    (config,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    assert len(config["why"]) <= 200
    (cell,) = [c for c in BENCH["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "kexaone_reasoning_closed", 1)
    assert len(cell["why"]) <= 200
    judged = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in judged["tpot_p50_ms"]["workloads"]
    cells = [c["name"] for c in BENCH["workloads"]]
    mine = {m["name"]: m for m in harness.metrics_of(BENCH, "per_layer",
                                                     CELL)}
    for name, m in mine.items():
        harness.reader_for(name)  # every entry has a reader
        assert m["moves"] in ("setup_s", "tpot_p50_ms")
        if "workloads" in m:
            assert CELL in m["workloads"]
            assert set(m["workloads"]) <= set(
                judged[m["moves"]].get("workloads", cells))
    for name in OWN:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "tpot_p50_ms"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", f"{name}.py"))
    rooflines = [n for n in OWN if n.endswith("_roofline")]
    assert len(rooflines) == 3
    assert all(mine[n]["unit"] == "%" and mine[n]["layer"] == "kernels"
               for n in rooflines)
    assert {"moe_experts_touched_share", "moe_max_load_over_mean",
            "attn_pages_read_share", "out_tokens_per_s", "ramp_s",
            "chunk_host_ms.tpot", "prefill_lane_wait_p50_ms.tpot",
            "prefill_chunk_dev_ms.tpot", "ttft_p50_ms.tpot",
            "decode_step_dev_ms.tpot", "serving_device_idle.tpot",
            "compiles_in_window.tpot"} <= set(mine)
    # and none of the lists that a test pins cell for cell
    assert not {"attn_full_step_share", "mlp_step_dev_share",
                "prefill_fill_share.tpot",
                "prefill_ctx_read_share.tpot"} & set(mine)


# -- scopes and readers over hand-built facts -----------------------------------------

HLO = """HloModule jit__round
ENTRY %main.1 (p: f32[4]) -> f32[4] {
  %fusion.7 = f32[64,128,1024]{2,1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_round)/jit(main)/attn.window/paged_line_attention"}
  %fusion.8 = f32[64,128,1024]{2,1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_round)/jit(main)/attn.full/dot_general"}
  %fusion.9 = f32[128,6144]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_round)/jit(main)/moe.experts/grouped_experts"}
  %fusion.10 = f32[128,128]{1,0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(_round)/jit(main)/moe.route/dot_general"}
  %fusion.11 = f32[128,2048]{1,0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(_round)/jit(main)/moe.shared/dot_general"}
  %fusion.12 = f32[128,18432]{1,0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(_round)/jit(main)/mlp/dot_general"}
  %fusion.13 = f32[128,19200]{1,0} fusion(%p), kind=kOutput, calls=%h, metadata={op_name="jit(_round)/jit(main)/head/dot_general"}
  %fusion.14 = f32[128,6144]{1,0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(_round)/jit(main)/mtp.embed/dot_general"}
  %fusion.15 = f32[64,128,1024]{2,1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_round)/jit(main)/mtp.block/attn.full/paged_line_attention"}
  %fusion.16 = f32[128,6144]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_round)/jit(main)/mtp.block/moe.experts/grouped_experts"}
  %fusion.17 = f32[64,19200]{1,0} fusion(%p), kind=kOutput, calls=%h, metadata={op_name="jit(_round)/jit(main)/mtp.head/dot_general"}
  ROOT %fusion.18 = f32[128,6144]{1,0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(_round)/jit(main)/add"}
}
"""
OPS = {"fusion.7_f32_64_128_1024_": ("attn.window", 0.08),
       "fusion.8_f32_64_128_1024_": ("attn.full", 0.07),
       "fusion.9_f32_128_6144_": ("moe.experts", 0.40),
       "fusion.10_f32_128_128_": ("moe.route", 0.01),
       "fusion.11_f32_128_2048_": ("moe.shared", 0.04),
       "fusion.12_f32_128_18432_": ("mlp", 0.06),
       "fusion.13_f32_128_19200_": ("head", 0.03),
       "fusion.14_f32_128_6144_": ("mtp.embed", 0.02),
       "fusion.15_f32_64_128_1024_": ("mtp.block.attn.full", 0.05),
       "fusion.16_f32_128_6144_": ("mtp.block.moe.experts", 0.11),
       "fusion.17_f32_64_19200_": ("mtp.head", 0.03),
       "fusion.18_f32_128_6144_": (None, 0.10)}
ROUNDS = 50
# what the engine writes on a round's prepare span, and the driver beside it
SPAN = {"rounds": 1, "live": 64, "rows": 128, "proposed": 64, "accepted": 3,
        "emitted": 67, "pages_fetched_full": 8000, "pages_fetched_window": 600}
COUNTS = {"moe_experts_touched": 80, "moe_assignments": 655,
          "moe_max_load": 20, "moe_expert_slots": 80, "rows": 128,
          "proposed": 64, "accepted": 3, "emitted": 67}


def test_scopes_keep_the_mtp_blocks_inner_name_beside_its_own():
    got = lm_serving_moe_mtp.scopes_in(HLO)
    assert got == {op: scope for op, (scope, _) in OPS.items() if scope}
    scope_of = lm_serving_moe_mtp.scope_of
    assert scope_of("jit(_round)/attn.full/mul") == "attn.full"
    assert scope_of("jit(_round)/mtp.block/attn.full/mul") == \
        "mtp.block.attn.full"
    assert scope_of("jit(_round)/mtp.block/add") == "mtp.block"
    assert scope_of("jit(_round)/mtp.head/dot_general") == "mtp.head"
    assert scope_of("ragged-dot-none") == "moe.experts"
    assert scope_of("jit(_round)/attn/mul") is None


@pytest.fixture
def facts(monkeypatch):
    spans = [Span(name="engine.step.prepare", attrs=dict(SPAN))]
    old = [Span(name="engine.step.prepare", attrs={"live": 64})]  # a step
    monkeypatch.setattr(readers_moe_mtp, "traced_passes",
                        lambda f: [(None, spans), (None, spans), (None, old)])
    trace = {"window_s": 10.0, "busy_s": 9.9, "programs": {
        "_round": {"count": ROUNDS, "total_s": 1.0,
                   "ops": {op: s for op, (_, s) in OPS.items()}}}}
    return {"trace": trace, "peaks": V5E, "config": CONFIG,
            "op_scopes": {"_round": lm_serving_moe_mtp.scopes_in(HLO)},
            "trace_bounds": (100.0, 110.0),
            "moe_steps": [(101.0, COUNTS), (102.0, COUNTS),
                          (200.0, COUNTS)],  # the last: outside the trace
            "moe_expert_slots": 80}


def _least(cost):
    return max(cost["bytes"] / 819e9, cost["flops"] / 197e12)


def test_mtp_accept_share_is_accepted_over_proposed(facts):
    assert harness.reader_for("mtp_accept_share")(facts) == pytest.approx(
        100 * 3 / 64)


def test_mtp_tokens_per_round_is_emitted_over_live_slots(facts):
    assert harness.reader_for("mtp_tokens_per_round")(facts) == \
        pytest.approx(67 / 64)


def test_mtp_dev_share_is_everything_under_the_mtp_layer(facts):
    assert harness.reader_for("mtp_dev_share")(facts) == pytest.approx(
        100 * (0.02 + 0.05 + 0.11 + 0.03))


def test_verify_attn_dev_share_is_the_stacks_attention_alone(facts):
    assert harness.reader_for("verify_attn_dev_share")(facts) == \
        pytest.approx(100 * (0.08 + 0.07))


def test_moe_share_dev_share_is_the_stacks_expert_layers_alone(facts):
    assert harness.reader_for("moe_share_dev_share")(facts) == \
        pytest.approx(100 * (0.40 + 0.01 + 0.04))


def test_moe_assignments_here_share_is_an_eighth_for_an_even_router(facts):
    made = (128 * 4 + 67 * 1) * 8
    assert harness.reader_for("moe_assignments_here_share")(facts) == \
        pytest.approx(100 * 655 / made)
    assert 12.0 < 100 * 655 / made < 14.5


def test_gqa_verify_roofline_is_the_fetched_lines_over_the_attn_time(facts):
    cost = opcount.gqa_verify(CONFIG, 128, 8000 * 16, 600 * 16)
    assert harness.reader_for("gqa_verify_roofline")(facts) == pytest.approx(
        100 * _least(cost) / (0.15 / ROUNDS))


def test_moe_share_roofline_takes_the_mtp_blocks_expert_layer_too(facts):
    cost = opcount.moe_round(CONFIG, 128, 80, 655)
    assert harness.reader_for("moe_share_roofline")(facts) == pytest.approx(
        100 * _least(cost) / ((0.45 + 0.11) / ROUNDS))


def test_moe_mtp_step_roofline_is_the_round_over_its_device_time(facts):
    cost = opcount.round_cost(CONFIG, 128, 8000 * 16, 600 * 16, 80, 655)
    assert harness.reader_for("moe_mtp_step_roofline")(facts) == \
        pytest.approx(100 * _least(cost) / (1.0 / ROUNDS))


@pytest.mark.parametrize("name", OWN)
def test_a_program_that_runs_no_round_leaves_the_metric_out(name, facts,
                                                            monkeypatch):
    # the parent: its steps' spans carry no ``rounds``, its driver notes no
    # ``rows``, its programs table names no ``_round``: nothing, no error
    monkeypatch.setattr(readers_moe_mtp, "traced_passes", lambda f: [
        (None, [Span(name="engine.step.prepare", attrs={"live": 64})])])
    bare = dict(facts, op_scopes=None, moe_steps=[
        (101.0, {"moe_experts_touched": 80, "moe_assignments": 655})])
    assert harness.reader_for(name)(bare) is None
    assert harness.reader_for(name)(dict(bare, trace=None,
                                         trace_bounds=None)) is None


# -- the counts, against hand arithmetic at the published sizes ---------------------

def test_weights_per_layer_are_issue_47s_numbers():
    s = opcount.sizes(CONFIG)
    # W_q 6144 x 8192, W_k and W_v 6144 x 1024, W_o 8192 x 6144
    assert s["attention"] == 2 * 50331648 + 2 * 6291456 == 113246208
    assert s["dense"] == 3 * 6144 * 18432 == 339738624
    assert s["expert"] == s["shared"] == 3 * 6144 * 2048 == 37748736
    assert s["router"] == 6144 * 128 == 786432
    assert s["head"] == 6144 * 19200 == 117964800
    assert s["eh_proj"] == 2 * 6144 * 6144 == 75497472
    assert (s["layers"], s["window_layers"], s["full_layers"],
            s["dense_layers"], s["sparse_layers"], s["mtp_layers"]) == (
        5, 4, 1, 1, 4, 1)
    assert s["line"] == 2048 and s["window"] == 128
    sparse = s["attention"] + s["router"] + s["shared"] + 16 * s["expert"]
    assert sparse == 755761152                                   # 755.8M
    total = (5 * s["attention"] + s["dense"]
             + 4 * (s["router"] + s["shared"] + 16 * s["expert"])
             + 2 * s["head"] + s["eh_proj"] + sparse)
    assert round(total * 2 / 1e9, 2) == 9.09                     # GB
    assert s["line"] * 2 == 4096                  # bytes a token a layer


def test_a_round_reads_every_weight_once_and_the_head_twice():
    # 64 slots at 2000 positions: 128 rows, every held expert reached
    lines_full, lines_window = 64 * 2000, 64 * 144
    cost = opcount.round_cost(CONFIG, 128, lines_full, lines_window, 80,
                              128 * 5)
    s = opcount.sizes(CONFIG)
    weights = (6 * s["attention"] + s["dense"] + 5 * (
        s["router"] + s["shared"] + 16 * s["expert"]) + s["eh_proj"]
        + 2 * s["head"])
    lines = ((2 * lines_full + 4 * lines_window) + 6 * 128) * 2048
    assert cost["bytes"] == pytest.approx(
        (weights + lines + 2 * 128 * 6144) * 2, rel=1e-12)
    # 4.543e9 weights' worth (the head's slice stands in twice, the
    # embedding's rows are looked up) and 1.2 GB of lines
    assert cost["bytes"] / 1e9 == pytest.approx(9.09 + 1.20, abs=0.02)
    assert cost["bytes"] / 819e9 * 1e3 == pytest.approx(12.57, abs=0.05)
    assert cost["bytes"] / 819e9 > cost["flops"] / 197e12  # bound by bytes


def test_the_expert_layers_cost_is_the_experts_reached():
    base = opcount.moe_round(CONFIG, 128, 70, 640)
    more = opcount.moe_round(CONFIG, 128, 71, 640)
    assert more["bytes"] - base["bytes"] == 2 * 37748736
    assert base["bytes"] == (70 * 37748736 + 5 * (786432 + 37748736)) * 2
    assert base["flops"] == 2.0 * (640 * 37748736
                                   + 128 * 5 * (786432 + 37748736))


def test_attention_fetches_the_window_in_four_layers_and_all_in_one():
    base = opcount.gqa_verify(CONFIG, 128, 100000, 9000)
    assert base["bytes"] == 5 * 113246208 * 2 + (
        1 * 100000 + 4 * 9000 + 5 * 128) * 2048 * 2
    assert base["flops"] == 2.0 * 128 * 5 * 113246208 + (
        1 * 100000 + 4 * 9000) * 64 * 4.0 * 128
    wider = opcount.gqa_verify(CONFIG, 128, 100016, 9000)
    assert wider["bytes"] - base["bytes"] == 16 * 2048 * 2
