"""The cell of the Mellum-shaped configuration (``mellum2_12b_a2.5b_l12``) on
the CPU at its ``rehearsal`` sizes: a sound run is correct and leaves no page
of either kind behind, the control (the reference put through fp8) and a
token altered where it is produced read false, the new readers over
hand-built facts, and the operation counts behind the three rooflines
against hand arithmetic at the cell's published sizes."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.drivers import lm_serving, lm_serving_moe_window  # noqa: E402
from benchmark.lib import (  # noqa: E402
    harness,
    opcount_moe_gqa_window as opcount,
    peaks,
    traffic,
)
from tests.benchmark.test_benchmark_correct import (  # noqa: E402
    rehearsal_ctx,
    run_counting_tokens_home,
)

BENCH = harness.load_benchmark()
CELL = "mellum2_longctx_decode"
_, CONFIG = harness.find_cell(BENCH, CELL)
V5E = peaks.peaks_for("TPU v5 lite")


# -- the cell, rehearsed ------------------------------------------------------------

@pytest.fixture(scope="module")
def sound_run():
    ctx = rehearsal_ctx(CELL, 2**31 + 31, 2.5)
    return ctx, run_counting_tokens_home(
        lm_serving_moe_window, lm_serving_moe_window.WindowProxy, ctx)


def test_a_sound_run_of_the_new_family_is_correct(sound_run):
    ctx, out = sound_run
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] > ctx["mix"]["clients"]
    assert set(out["end_to_end"]) == {"setup_s", "ttft_p50_ms", "tpot_p50_ms"}
    checks = {n: (v, lim) for n, v, lim in out["checks"]}
    assert checks["served_gap_max"][0] <= checks["served_gap_max"][1]
    assert checks["served_tokens_compared"][0] > 0
    assert checks["pages_left_full"] == (0, 0)
    assert checks["pages_left_window"] == (0, 0)
    assert out["facts"]["compiles_in_window"] == 0
    assert out["facts"]["ramp_s"] > 0


def test_the_rehearsal_crosses_the_window_in_prefill_and_in_decode(sound_run):
    ctx, _ = sound_run
    window = ctx["config"]["sliding_window"]
    chunk = ctx["config"]["engine"]["chunk"]
    pairs = ctx["mix"]["requests"]
    assert max(p + s for p, s in pairs) > 3 * window
    assert any(p > window + chunk for p, _ in pairs), "inside prefill"
    assert any(p < window < p + s for p, s in pairs), "while decoding"
    assert max(p + s for p, s in pairs) \
        <= ctx["config"]["max_position_embeddings"]


def test_every_decode_step_has_its_counts_and_its_pages_by_kind(sound_run):
    ctx, out = sound_run
    facts = out["facts"]
    steps, moe = facts["decode_steps"], facts["moe_steps"]
    assert len(moe) == len(steps) > 0
    assert [t for t, _ in moe] == [s[0] for s in steps]
    slots = facts["moe_expert_slots"]
    assert slots == 4 * 8  # four expert layers of eight at rehearsal sizes
    window, held = ctx["config"]["sliding_window"], 6
    home = out["tokens_home"]
    for (t, active, context, pages), (_, c) in zip(steps, moe):
        # the counts are those of the step whose tokens this call brought
        # home: two experts a token, four expert layers, nothing dropped
        assert c["moe_assignments"] == home[t] * 2 * 4
        assert c["moe_experts_touched"] <= min(slots, c["moe_assignments"])
        assert (c["moe_experts_touched"] > 0) == (home[t] > 0)
        # what the proxy notes itself is of the batch it holds at the call
        assert c["ctx_window"] <= min(context, active * window)
        assert c["pages_full"] == pages
        assert c["pages_window"] <= active * held
    # a call's batch is the one the next call's counts belong to, but for
    # a slot that joined or left between them: the window's sums agree to
    # within the joins
    assert sum(c["moe_assignments"] for _, c in moe) == 8 * sum(
        home[t] for t, _ in moe)
    assert 0 < sum(home[t] for t, _ in moe) <= sum(s[1] for s in steps)
    peaks_by_kind = facts["pool_pages_used_peak_by_kind"]
    assert 0 < peaks_by_kind["window"] <= 3 * held
    assert peaks_by_kind["full"] == facts["pool_pages_used_peak"]
    assert facts["pool_pages"] == 96 and facts["pool_pages_by_kind"] == {
        "full": 96, "window": 32}
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
    real_step = lm_serving_moe_window.WindowProxy.step

    def altered(self):
        return (real_step(self) + 1) % self._engine.family.vocab

    monkeypatch.setattr(lm_serving_moe_window.WindowProxy, "step", altered)
    out = lm_serving_moe_window.run(rehearsal_ctx(CELL, 5, 2.5))
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
    row = next(r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    entry = next(c for c in BENCH["configs"] if c["file"].endswith(
        "mellum2_12b_a2.5b_l12.json"))
    assert entry["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if CONFIG.get(k, "-") != v}
    assert changed == set(CONFIG["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "max_position_embeddings"}
    assert CONFIG["published"] == {"num_hidden_layers": 28,
                                   "max_position_embeddings": 131072}
    assert CONFIG["experts_held"] == [0, row["config"]["num_experts"]]
    # the rehearsal's line is whole lane rows too, so that chip_smoke's
    # window_serving leg can compile the step's kernel at those sizes
    small = CONFIG["rehearsal"]
    assert small["num_key_value_heads"] * small["head_dim"] % 128 == 0
    # three whole periods of the published pattern
    held = CONFIG["layer_types"][:CONFIG["num_hidden_layers"]]
    assert held == (["sliding_attention"] * 3 + ["full_attention"]) * 3


def test_the_traffic_is_issue_31s_to_the_number():
    mix = traffic.load("mellum_longctx_closed")
    assert (mix["kind"], mix["clients"], mix["rounds"]) == (
        "closed_loop_requests", 32, 2)
    first, second = mix["requests"][:32], mix["requests"][32:]
    prompts = [p for p, _ in first]
    assert prompts == sorted(prompts) and sum(prompts) == 113920
    assert min(prompts) == 1024 and max(prompts) == 8192
    assert all(p % 256 == 0 for p in prompts)
    # ISSUE 31's one allowed change: a step takes over 40 ms (62 measured),
    # so the short answers are 384 tokens and not 640
    short = {i: p for i, (p, s) in enumerate(first) if s == 384}
    assert short == {0: 1024, 7: 2048, 21: 4096, 31: 8192}
    assert all(s == 4096 for i, (_, s) in enumerate(first) if i not in short)
    assert second == [[1024, 4096]] * 32
    assert max(p + s for p, s in mix["requests"]) \
        == CONFIG["max_position_embeddings"] == 12288
    assert mix["check_sample"] == 4
    assert mix["trace"] == {"start_s": 28.0, "seconds": 10.0}
    items = traffic.requests(mix, 2**31 + 3, 48.0, CONFIG["vocab_size"])
    assert len(items) == 64 and sum(it["ramp"] for it in items) == 32
    assert [it["after"] for it in items[32:]] == list(range(32))


OWN = ("moe_step_dev_share", "attn_window_step_share",
       "attn_full_step_share", "window_pages_read_share",
       "moe_topk_roofline", "gqa_window_decode_roofline",
       "moe_gqa_step_roofline")


def test_the_cell_and_its_metrics_are_at_the_end_of_their_lists():
    """Found by name: a later cell, configuration or entry stands after
    them, as the contract puts it."""
    (config,) = [c for c in BENCH["configs"]
                 if c["name"] == "mellum2_12b_a2.5b_l12"]
    assert config["file"] == "benchmark/configs/mellum2_12b_a2.5b_l12.json"
    (cell,) = [c for c in BENCH["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mellum2_12b_a2.5b_l12", "mellum_longctx_closed", 1)
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
    # what is the window family's alone lists this cell and no other
    for name in OWN:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "tpot_p50_ms"
    assert mine["ramp_s"]["moves"] == "setup_s"
    assert {"moe_experts_touched_share", "moe_max_load_over_mean",
            "attn_pages_read_share", "out_tokens_per_s"} <= set(mine)
    # nothing that reads a launch or a first token of the window is listed
    assert not {n for n in mine if n.startswith((
        "prefill_", "chunk_host", "ttft_", "moe_dev_share"))}


# -- scopes and readers over hand-built facts -----------------------------------------

HLO = """HloModule jit__step
ENTRY %main.1 (p: f32[4]) -> f32[4] {
  %fusion.7 = f32[32,32,512]{2,1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/jit(main)/attn.window/paged_line_attention"}
  %fusion.8 = f32[32,32,512]{2,1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_step)/jit(main)/attn.full/dot_general"}
  %ragged-dot-none.19 = f32[256,896]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.9 = f32[32,64]{1,0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(_step)/jit(main)/moe.route/dot_general"}
  ROOT %fusion.12 = f32[32,98304]{1,0} fusion(%p), kind=kOutput, calls=%h, metadata={op_name="jit(_step)/jit(main)/head/dot_general"}
  %fusion.13 = f32[32,2304]{1,0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(_step)/jit(main)/add"}
}
"""
OPS = {"fusion.7_f32_32_32_512_": ("attn.window", 0.10),
       "fusion.8_f32_32_32_512_": ("attn.full", 0.06),
       "ragged-dot-none.19_f32_256_896_": ("moe.experts", 0.60),
       "fusion.9_f32_32_64_": ("moe.route", 0.04),
       "fusion.12_f32_32_98304_": ("head", 0.10),
       "fusion.13_f32_32_2304_": (None, 0.10)}


def test_scopes_come_from_the_compiled_programs_op_names():
    got = lm_serving_moe_window.scopes_in(HLO)
    assert got == {op: scope for op, (scope, _) in OPS.items() if scope}
    assert lm_serving_moe_window.scope_of("jit(_step)/attn.full/mul") \
        == "attn.full"
    assert lm_serving_moe_window.scope_of("jit(_step)/attn/mul") is None


def _facts(**over):
    scopes = lm_serving_moe_window.scopes_in(HLO)
    trace = {"window_s": 10.0, "busy_s": 9.0, "programs": {
        "_step": {"count": 50, "total_s": 1.0,
                  "ops": {op: s for op, (_, s) in OPS.items()}}}}
    counts = {"moe_experts_touched": 757, "moe_assignments": 3072,
              "moe_max_load": 120, "moe_expert_slots": 768,
              "ctx_window": 32 * 1024}
    facts = {"trace": trace, "peaks": V5E, "config": CONFIG,
             "op_scopes": {"_step": scopes},
             "trace_bounds": (100.0, 110.0),
             "decode_steps": [(101.0, 32, 146000, 0), (102.0, 32, 146000, 0),
                              (200.0, 32, 146000, 0)],  # outside the trace
             "moe_steps": [(101.0, counts), (102.0, counts),
                           (200.0, counts)],
             "moe_expert_slots": 768}
    facts.update(over)
    return facts


def test_step_shares_are_the_decode_programs_time_by_scope():
    facts = _facts()
    assert harness.reader_for("moe_step_dev_share")(facts) == \
        pytest.approx(100 * 0.64)
    assert harness.reader_for("attn_window_step_share")(facts) == \
        pytest.approx(100 * 0.10)
    assert harness.reader_for("attn_full_step_share")(facts) == \
        pytest.approx(100 * 0.06)
    # a program without the scope map has nothing to read
    for name in ("moe_step_dev_share", "attn_window_step_share",
                 "attn_full_step_share", "moe_topk_roofline",
                 "gqa_window_decode_roofline"):
        assert harness.reader_for(name)(_facts(op_scopes=None)) is None
    assert harness.reader_for("moe_gqa_step_roofline")(
        _facts(moe_steps=None)) is None
    assert harness.reader_for("ramp_s")(_facts()) is None


def test_rooflines_are_least_time_over_the_scopes_time_in_one_step():
    facts = _facts()

    def least(cost):
        return max(cost["bytes"] / 819e9, cost["flops"] / 197e12)

    assert harness.reader_for("moe_topk_roofline")(facts) == pytest.approx(
        100 * least(opcount.moe_decode(CONFIG, 32, 757, 3072)) / (0.64 / 50))
    assert harness.reader_for("gqa_window_decode_roofline")(facts) == \
        pytest.approx(100 * least(opcount.gqa_decode(
            CONFIG, 32, 146000, 32 * 1024)) / (0.16 / 50))
    assert harness.reader_for("moe_gqa_step_roofline")(facts) == \
        pytest.approx(100 * least(opcount.step(
            CONFIG, 32, 146000, 32 * 1024, 757, 3072)) / (1.0 / 50))


def test_window_pages_read_share_is_read_from_the_steps_spans(monkeypatch):
    from types import SimpleNamespace as Span

    from benchmark.lib import program_spans

    def passes(_facts):
        step = [Span(name="engine.step.prepare", attrs={
            "pages_read": 120, "pages_padded": 24576,
            "pages_read_full": 290 * 32, "pages_read_window": 65 * 32})]
        old = [Span(name="engine.step.prepare", attrs={
            "pages_read": 120, "pages_padded": 24576})]  # no kinds: skipped
        return [(None, step), (None, step), (None, old)]

    monkeypatch.setattr(program_spans, "traced_passes", passes)
    read = harness.reader_for("window_pages_read_share")
    assert read({}) == pytest.approx(100 * 65 / 290)
    monkeypatch.setattr(program_spans, "traced_passes", lambda f: None)
    assert harness.reader_for("window_pages_read_share")({}) is None


# -- the counts, against hand arithmetic at the published sizes ---------------------

def test_weights_per_layer_are_issue_31s_numbers():
    s = opcount.sizes(CONFIG)
    # W_q 2304 x 4096, W_k and W_v 2304 x 512, W_o 4096 x 2304
    assert s["attention"] == 9437184 + 2 * 1179648 + 9437184 == 21233664
    assert s["router"] == 2304 * 64 == 147456
    assert s["expert"] == 3 * 2304 * 896 == 6193152
    assert s["head"] == 2304 * 98304 == 226492416
    assert (s["layers"], s["window_layers"], s["full_layers"]) == (12, 9, 3)
    assert s["line"] == 1024 and s["window"] == 1024
    layer = opcount.layer_weights(CONFIG)
    assert layer == 21233664 + 147456 + 64 * 6193152 == 417742848  # 417.8M
    assert round(2 * layer / 1e9, 3) == 0.835                      # GB
    # the stage: 12 layers, embedding and head; a token's line a layer
    assert round((12 * layer + 2 * s["head"]) * 2 / 1e9, 2) == 10.93
    assert s["line"] * 2 == 2048


def test_a_step_at_mid_window_reads_twelve_gigabytes():
    # 32 sequences, 146k visible tokens in a full layer, 1024 each in a
    # window layer, every one of 12 x 64 experts reached (ISSUE 31's 12.0
    # GB); at the expected 98.6% of them it is 11.85
    cost = opcount.step(CONFIG, 32, 146000, 32 * 1024, 768, 32 * 8 * 12)
    assert cost["bytes"] / 1e9 == pytest.approx(12.0, abs=0.05)
    assert cost["bytes"] / 819e9 * 1e3 == pytest.approx(14.6, abs=0.1)
    fewer = opcount.step(CONFIG, 32, 146000, 32 * 1024, 0.986 * 768,
                         32 * 8 * 12)
    assert fewer["bytes"] / 1e9 == pytest.approx(11.85, abs=0.01)
    assert cost["bytes"] / 819e9 > cost["flops"] / 197e12  # bound by bytes
    lines = opcount.gqa_decode(CONFIG, 32, 146000, 32 * 1024)["bytes"] \
        - 12 * 21233664 * 2
    assert lines / 1e9 == pytest.approx(0.9 + 0.6, abs=0.01)
    # with one table the window layers would read every context too
    one_table = opcount.gqa_decode(CONFIG, 32, 146000, 146000)["bytes"] \
        - 12 * 21233664 * 2
    assert one_table / 1e9 == pytest.approx(3.6, abs=0.02)


def test_the_expert_layers_cost_is_the_experts_reached():
    base = opcount.moe_decode(CONFIG, 32, 700, 3072)
    more = opcount.moe_decode(CONFIG, 32, 701, 3072)
    assert more["bytes"] - base["bytes"] == 2 * 6193152
    assert base["bytes"] == (700 * 6193152 + 12 * 147456) * 2
    assert base["flops"] == 2.0 * (3072 * 6193152 + 32 * 12 * 147456)


def test_attention_reads_the_window_in_nine_layers_and_all_in_three():
    base = opcount.gqa_decode(CONFIG, 32, 100000, 32768)
    assert base["bytes"] == 12 * 21233664 * 2 + (
        3 * 100000 + 9 * 32768 + 12 * 32) * 1024 * 2
    assert base["flops"] == 2.0 * 32 * 12 * 21233664 + (
        3 * 100000 + 9 * 32768) * 32 * 4.0 * 128
    wider = opcount.gqa_decode(CONFIG, 32, 100016, 32768)
    assert wider["bytes"] - base["bytes"] == 3 * 16 * 1024 * 2
