"""The cell of the DeepSeek-V3-shaped configuration (``kanana2_30b_a3b_l8``)
on the CPU at its ``rehearsal`` sizes: a sound run is correct, the control
(the reference put through fp8) and a token altered where it is produced
read false, the readers of the expert and latent-attention layers over
hand-built facts, and the operation counts behind the three rooflines
against hand arithmetic at the cell's published sizes."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.drivers import lm_serving, lm_serving_moe_mla  # noqa: E402
from benchmark.lib import harness, opcount_moe_mla, peaks  # noqa: E402
from tests.benchmark.test_benchmark_correct import (  # noqa: E402
    rehearsal_ctx,
    run_counting_tokens_home,
)

BENCH = harness.load_benchmark()
CELL = "kanana2_decode_saturated"
_, CONFIG = harness.find_cell(BENCH, CELL)
V5E = peaks.peaks_for("TPU v5 lite")


# -- the cell, rehearsed ------------------------------------------------------------

@pytest.fixture(scope="module")
def sound_run():
    ctx = rehearsal_ctx(CELL, 2**31 + 27, 1.5)
    return ctx, run_counting_tokens_home(
        lm_serving_moe_mla, lm_serving_moe_mla.MoEProxy, ctx)


def test_a_sound_run_of_the_new_family_is_correct(sound_run):
    ctx, out = sound_run
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] > ctx["mix"]["clients"]
    assert set(out["end_to_end"]) == {"setup_s", "ttft_p50_ms", "tpot_p50_ms"}
    checks = {n: (v, lim) for n, v, lim in out["checks"]}
    assert checks["served_gap_max"][0] <= checks["served_gap_max"][1]
    assert checks["served_tokens_compared"][0] > 0
    assert out["facts"]["compiles_in_window"] == 0


def test_every_decode_step_of_the_window_has_its_expert_counts(sound_run):
    _, out = sound_run
    facts = out["facts"]
    steps, moe = facts["decode_steps"], facts["moe_steps"]
    assert len(moe) == len(steps) > 0
    assert [t for t, _ in moe] == [s[0] for s in steps]
    slots = facts["moe_expert_slots"]
    assert slots == 2 * 8  # two expert layers of eight at rehearsal sizes
    home = out["tokens_home"]
    for t, c in moe:
        # the counts are those of the step whose tokens this call brought
        # home: two experts a token, two expert layers, nothing dropped
        assert c["moe_assignments"] == home[t] * 2 * 2
        assert c["moe_experts_touched"] <= min(slots, c["moe_assignments"])
        assert (c["moe_experts_touched"] > 0) == (home[t] > 0)
        assert c["moe_expert_slots"] == (slots if home[t] else 0)
    # a call's batch is the one the next call's counts belong to, but for
    # a slot that joined or left between them: the window's sums agree to
    # within the joins
    assert 0 < sum(home[t] for t, _ in moe) <= sum(s[1] for s in steps)
    read = harness.reader_for("moe_experts_touched_share")
    share = read(dict(facts, metric=None))
    assert 0 < share <= 100
    assert harness.reader_for("moe_max_load_over_mean")(facts) >= 1.0


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


def test_a_token_altered_in_step_is_not_correct(monkeypatch):
    real_step = lm_serving_moe_mla.MoEProxy.step

    def altered(self):
        return (real_step(self) + 1) % self._engine.family.vocab

    monkeypatch.setattr(lm_serving_moe_mla.MoEProxy, "step", altered)
    out = lm_serving_moe_mla.run(rehearsal_ctx(CELL, 5, 1.5))
    assert out["correct"] is False
    checks = {n: (v, lim) for n, v, lim in out["checks"]}
    assert checks["served_gap_mean"][0] > checks["served_gap_mean"][1]


def test_the_configuration_keeps_every_published_width():
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog of architectures on this machine")
    with open(catalog) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    row = next(r for r in rows
               if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    entry = next(c for c in BENCH["configs"] if c["file"].endswith(
        "kanana2_30b_a3b_l8.json"))
    assert entry["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if CONFIG.get(k, "-") != v}
    assert changed == set(CONFIG["reduced"]) == {"num_hidden_layers",
                                                 "max_position_embeddings"}
    assert CONFIG["experts_held"] == [0, row["config"]["n_routed_experts"]]


# -- scopes and readers over hand-built facts -----------------------------------------

HLO = """HloModule jit__step
ENTRY %main.1 (p: f32[4]) -> f32[4] {
  %fusion.7 = bf16[32,3072,576]{2,1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_step)/jit(main)/mla/jit(_take)/gather"}
  %ragged-dot-none.19 = f32[192,768]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.9 = f32[32,128]{1,0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(_step)/jit(main)/moe.route/dot_general"}
  %fusion.11 = f32[32,2048]{1,0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(_step)/jit(main)/moe.shared/mul"}
  ROOT %fusion.12 = f32[32,128256]{1,0} fusion(%p), kind=kOutput, calls=%h, metadata={op_name="jit(_step)/jit(main)/head/dot_general"}
  %fusion.13 = f32[32,2048]{1,0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(_step)/jit(main)/add"}
}
"""


def test_scopes_come_from_the_compiled_programs_op_names():
    got = lm_serving_moe_mla.scopes_in(HLO)
    assert got == {"fusion.7_bf16_32_3072_576_": "mla",
                   "ragged-dot-none.19_f32_192_768_": "moe.experts",
                   "fusion.9_f32_32_128_": "moe.route",
                   "fusion.11_f32_32_2048_": "moe.shared",
                   "fusion.12_f32_32_128256_": "head"}
    assert lm_serving_moe_mla.scope_of("jit(_step)/mlp/mul") == "mlp"
    assert lm_serving_moe_mla.scope_of("jit(_step)/moe/mul") is None


def _facts(**over):
    scopes = lm_serving_moe_mla.scopes_in(HLO)
    step_ops = {"fusion.7_bf16_32_3072_576_": 0.30,       # mla
                "ragged-dot-none.19_f32_192_768_": 0.40,   # moe.experts
                "fusion.9_f32_32_128_": 0.05,              # moe.route
                "fusion.11_f32_32_2048_": 0.05,            # moe.shared
                "fusion.12_f32_32_128256_": 0.10,          # head
                "fusion.13_f32_32_2048_": 0.10}            # under no scope
    chunk_ops = {"fusion.7_bf16_32_3072_576_": 0.5,
                 "ragged-dot-none.19_f32_192_768_": 0.5}
    trace = {"window_s": 10.0, "busy_s": 9.0, "programs": {
        "_step": {"count": 50, "total_s": 1.0, "ops": step_ops},
        "_prefill_chunk": {"count": 10, "total_s": 1.0, "ops": chunk_ops}}}
    counts = {"moe_experts_touched": 700, "moe_assignments": 1344,
              "moe_max_load": 28, "moe_expert_slots": 896}
    facts = {"trace": trace, "peaks": V5E, "config": CONFIG,
             "op_scopes": {"_step": scopes, "_prefill_chunk": scopes},
             "trace_bounds": (100.0, 110.0),
             "decode_steps": [(101.0, 32, 32 * 1000, 0),
                              (102.0, 32, 32 * 1000, 0),
                              (200.0, 32, 32 * 1000, 0)],  # outside the trace
             "moe_steps": [(101.0, counts), (102.0, counts),
                           (200.0, counts)],
             "moe_expert_slots": 896}
    facts.update(over)
    return facts


def test_device_shares_sum_the_operations_of_both_programs_by_scope():
    facts = _facts()
    assert harness.reader_for("moe_dev_share")(facts) == pytest.approx(
        100 * (0.40 + 0.05 + 0.05 + 0.5) / 2.0)
    assert harness.reader_for("mla_dev_share")(facts) == pytest.approx(
        100 * (0.30 + 0.5) / 2.0)
    # a program without the scope map (the parent's) has nothing to read
    for name in ("moe_dev_share", "mla_dev_share", "moe_roofline",
                 "mla_decode_roofline"):
        assert harness.reader_for(name)(_facts(op_scopes=None)) is None
    assert harness.reader_for("moe_mla_step_roofline")(
        _facts(moe_steps=None)) is None


def test_counter_readers():
    facts = _facts()
    assert harness.reader_for("moe_experts_touched_share")(facts) == \
        pytest.approx(100 * 700 / 896)
    # 28 summed over 7 layers is 4 a layer; the mean is 1344 / 896 = 1.5
    assert harness.reader_for("moe_max_load_over_mean")(facts) == \
        pytest.approx(4 / 1.5)


def test_rooflines_are_least_time_over_the_scopes_time_in_one_step():
    facts = _facts()
    moe = opcount_moe_mla.moe_decode(CONFIG, 32, 700, 1344)
    least = max(moe["bytes"] / 819e9, moe["flops"] / 197e12)
    assert harness.reader_for("moe_roofline")(facts) == pytest.approx(
        100 * least / (0.50 / 50))
    mla = opcount_moe_mla.mla_decode(CONFIG, 32, 32000)
    least = max(mla["bytes"] / 819e9, mla["flops"] / 197e12)
    assert harness.reader_for("mla_decode_roofline")(facts) == pytest.approx(
        100 * least / (0.30 / 50))
    whole = opcount_moe_mla.step(CONFIG, 32, 32000, 700, 1344)
    least = max(whole["bytes"] / 819e9, whole["flops"] / 197e12)
    assert harness.reader_for("moe_mla_step_roofline")(facts) == \
        pytest.approx(100 * least / (1.0 / 50))


# -- the counts, against hand arithmetic at the published sizes ---------------------

def test_weights_per_layer_are_issue_27s_numbers():
    s = opcount_moe_mla.sizes(CONFIG)
    # W_q 2048 x 32 x 192, W_kva 2048 x 576, W_kvb 32 x 256 x 512, W_o 4096 x 2048
    assert s["attention"] == 12582912 + 1179648 + 4194304 + 8388608 == 26345472
    assert s["expert"] == 3 * 2048 * 768 == 4718592
    assert s["shared"] == 2 * s["expert"] == 9437184
    assert s["router"] == 2048 * 128 == 262144
    assert s["dense_mlp"] == 3 * 2048 * 6144 == 37748736
    assert s["head"] == 128256 * 2048 == 262668288
    assert (s["line"], s["latent"], s["moe_layers"]) == (576, 512, 7)
    # the configuration's parameters: 5.07e9, 10.14 GB in bfloat16
    total = (2 * s["head"] + 8 * s["attention"] + s["dense_mlp"]
             + 7 * (128 * s["expert"] + s["shared"] + s["router"]))
    assert round(total / 1e9, 2) == 5.07


def test_the_expert_layers_cost_is_the_experts_reached():
    none = opcount_moe_mla.moe_decode(CONFIG, 32, 0, 0)
    # router and shared experts of seven layers, two bytes a weight
    assert none["bytes"] == 2 * 7 * (262144 + 9437184)
    some = opcount_moe_mla.moe_decode(CONFIG, 32, 700, 32 * 6 * 7)
    assert some["bytes"] - none["bytes"] == 2 * 700 * 4718592
    assert some["flops"] == 2.0 * (1344 * 4718592
                                   + 32 * 7 * (262144 + 9437184))
    from benchmark.lib.opcount import least_seconds

    seconds, bound = least_seconds(some, V5E)
    assert bound == "hbm" and 8.0e-3 < seconds < 8.5e-3  # 6.7 GB at 819 GB/s


def test_latent_attention_reads_one_line_a_token():
    mla = opcount_moe_mla.mla_decode(CONFIG, 32, 32000)
    # 9216 bytes a token over eight layers: 576 values of two bytes each
    assert 8 * 576 * 2 == 9216
    assert mla["bytes"] == 8 * 26345472 * 2 + (32000 + 32) * 9216
    assert mla["flops"] == 8 * (2.0 * 32 * 26345472
                                + 32000 * 32 * 2.0 * (576 + 512))


def test_the_whole_step_adds_the_dense_layer_the_head_and_the_rows():
    whole = opcount_moe_mla.step(CONFIG, 32, 32000, 700, 1344)
    parts = (opcount_moe_mla.moe_decode(CONFIG, 32, 700, 1344)["bytes"]
             + opcount_moe_mla.mla_decode(CONFIG, 32, 32000)["bytes"])
    assert whole["bytes"] - parts == 2 * (37748736 + 262668288 + 32 * 2048)
    from benchmark.lib.opcount import least_seconds
    seconds, bound = least_seconds(whole, V5E)
    # 8.1 GB: the experts reached, 1.4 GB outside them, 0.3 GB of lines
    assert bound == "hbm" and 9.5e-3 < seconds < 10.5e-3
