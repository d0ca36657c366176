"""The cell of the ZAYA-shaped configuration (``zaya1_8b_pp2_l20``) on the
CPU at its ``rehearsal`` sizes: a sound run is correct and leaves no page
behind; the control (the reference put through fp8), a state a slot that is
lost between steps, a depth average that is dropped and an altered token
read false; the eight new readers over hand-built facts; and the operation
counts behind the three rooflines against hand arithmetic at the cell's
published sizes. Everything is found by name: a later cell, configuration
or entry breaks none of it."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.drivers import lm_serving, lm_serving_moe_cca  # noqa: E402
from benchmark.drivers.lm_serving_moe_mla import MoEProxy  # noqa: E402
from benchmark.lib import (  # noqa: E402
    harness,
    opcount_moe_cca as opcount,
    peaks,
    traffic,
)
from tests.benchmark.test_benchmark_correct import rehearsal_ctx  # noqa: E402

BENCH = harness.load_benchmark()
CELL = "zaya1_reasoning_deep_decode"
NAME = "zaya1_8b_pp2_l20"
_, CONFIG = harness.find_cell(BENCH, CELL)
V5E = peaks.peaks_for("TPU v5 lite")
OWN = ("cca_step_dev_share", "cca_mix_dev_share", "moe_router_dev_share",
       "moe_top1_dev_share", "head_step_dev_share", "cca_decode_roofline",
       "moe_top1_roofline", "moe_cca_step_roofline")


# -- the cell, rehearsed ------------------------------------------------------------

@pytest.fixture(scope="module")
def sound_run():
    ctx = rehearsal_ctx(CELL, 2**31 + 50, 2.5)
    return ctx, lm_serving_moe_cca.run(ctx)


def test_a_sound_run_of_the_new_family_is_correct(sound_run):
    ctx, out = sound_run
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] > ctx["mix"]["clients"]
    assert set(out["end_to_end"]) == {"setup_s", "ttft_p50_ms", "tpot_p50_ms"}
    checks = {n: (v, lim) for n, v, lim in out["checks"]}
    for name in ("served_gap_max", "served_gap_mean"):
        assert checks[name][0] <= checks[name][1], name
    assert checks["served_tokens_compared"][0] > 0
    assert checks["pages_left"] == (0, 0)
    assert checks["pages_peak"][0] <= checks["pages_peak"][1]
    for name in ("preempted", "shed_queue_full", "shed_memory",
                 "shed_overload"):
        assert checks[name] == (0, 0)
    assert out["facts"]["compiles_in_window"] == 0
    assert out["facts"]["ramp_s"] > 0
    assert "traffic_ran_out_s" not in out


def test_every_step_has_its_counts_and_the_state_is_in_the_facts(sound_run):
    ctx, out = sound_run
    facts = out["facts"]
    steps, moe = facts["decode_steps"], facts["moe_steps"]
    assert len(moe) == len(steps) > 0
    assert [t for t, _ in moe] == [s[0] for s in steps]
    # three layers of four experts at rehearsal, one assignment a live row
    assert facts["moe_expert_slots"] == 3 * 4
    slots = ctx["config"]["engine"]["slots"]
    for _, c in moe:
        assert c["moe_experts_touched"] <= min(12, c["moe_assignments"])
        assert c["moe_assignments"] <= 3 * slots
    # a slot keeps one float32 line a layer: 2 x (4 + 2) x 64 + 64 values
    state = facts["state"]
    assert state["layers"] == state["attention_layers"] == 3
    assert state["shapes"] == [[2 * 6 * 64 + 64]]
    assert state["bytes"] == slots * 3 * 832 * 4
    checks = {n: v for n, v, _ in out["checks"]}
    assert checks["state_bytes"] == state["bytes"]
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
    assert {"served_gap_max", "pages_left", "state_bytes"} <= set(
        line["checks"])


@pytest.mark.parametrize("seed", [7, 2**31 + 8])
def test_the_fp8_control_fails_the_limits(seed):
    config = {**CONFIG, **CONFIG["rehearsal"], "vocab_size": 2048,
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


def _fails_by_the_mean(out):
    assert out["correct"] is False
    checks = {n: (v, lim) for n, v, lim in out["checks"]}
    assert checks["served_gap_mean"][0] > checks["served_gap_mean"][1]
    assert checks["pages_left"] == (0, 0)


def test_a_state_lost_between_steps_is_not_correct(monkeypatch):
    """The rows a slot keeps in its attention layers, zeroed before every
    step: the convolutions and the value shift then see a sequence that
    starts at every token."""
    real = MoEProxy.step

    def forgetful(self):
        eng = self._engine
        eng._drain()
        eng._states = tuple(s * 0 for s in eng._states)
        return real(self)

    monkeypatch.setattr(MoEProxy, "step", forgetful)
    _fails_by_the_mean(lm_serving_moe_cca.run(rehearsal_ctx(CELL, 5, 2.5)))


def test_a_dropped_depth_average_is_not_correct(monkeypatch):
    """Every layer's router as if it were layer 0's: nothing comes down the
    stack."""
    from nnstreamer_tpu.models.zaya import ZayaFamily

    real = ZayaFamily.ffn_carry

    def dropped(self, blk, x, live, carry):
        return real(self, blk, x, live, None)

    monkeypatch.setattr(ZayaFamily, "ffn_carry", dropped)
    _fails_by_the_mean(lm_serving_moe_cca.run(rehearsal_ctx(CELL, 6, 2.5)))


def test_a_token_altered_in_the_step_is_not_correct(monkeypatch):
    real = MoEProxy.step

    def altered(self):
        out = np.asarray(real(self))
        return np.where(out >= 0, (out + 1) % self._engine.family.vocab, out)

    monkeypatch.setattr(MoEProxy, "step", altered)
    _fails_by_the_mean(lm_serving_moe_cca.run(rehearsal_ctx(CELL, 8, 2.5)))


# -- the files and the entries --------------------------------------------------------

def test_the_configuration_keeps_every_published_width():
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog of architectures on this machine")
    with open(catalog) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    row = next(r for r in rows if r["name"] == "ZAYA1-8B")
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if CONFIG.get(k, "-") != v}
    assert changed == set(CONFIG["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "max_position_embeddings"}
    assert CONFIG["published"] == {k: row["config"][k] for k in changed}
    # the widths: none is cut; every expert and the whole vocabulary
    assert (CONFIG["hidden_size"], CONFIG["num_attention_heads"],
            CONFIG["num_key_value_heads"], CONFIG["head_dim"]) == (
        2048, 8, 2, 128)
    assert (CONFIG["moe_intermediate_size"], CONFIG["num_experts"],
            CONFIG["num_experts_per_tok"], CONFIG["router_hidden_size"]) == (
        2048, 16, 1, 256)
    assert (CONFIG["cca_time0"], CONFIG["cca_time1"],
            CONFIG["partial_rotary_factor"]) == (2, 2, 0.5)
    assert CONFIG["vocab_size"] == 262272 and CONFIG["tie_word_embeddings"]
    assert CONFIG["experts_held"] == [0, CONFIG["num_experts"]]
    assert CONFIG["layer_types"] == ["hybrid"] * 40
    assert CONFIG["num_hidden_layers"] == 20 >= 1 + 4
    for key in ("qk_mean", "convolutions", "temperature", "value_shift",
                "positions", "residual_merge", "router", "no_skip_choice",
                "head", "carry_hand_over", "weights", "serving_limit"):
        assert CONFIG["assumed"][key], key
    assert "two-stage pipeline on two v5e chips" in CONFIG["deployment"]
    assert "chips that share a layer: 1" in CONFIG["deployment"]
    assert CONFIG["programs"] == {"decode": "_step",
                                  "prefill": "_prefill_chunk"}
    # the stated count is the tree's
    import jax
    import jax.numpy as jnp

    reference = harness.reference_for(CONFIG)
    tree = jax.eval_shape(
        lambda k: reference.program_params(k, reference.sizes(CONFIG),
                                           jnp.bfloat16), jax.random.key(0))
    held = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    assert held == CONFIG["parameters"]["held_here"] == 4688796776
    assert CONFIG["parameters"]["bytes_bfloat16"] == 2 * held
    assert opcount.layer_weights(CONFIG) == CONFIG["parameters"]["a_layer"]


def test_the_pool_holds_what_the_traffic_can_make_live():
    mix = traffic.load("zaya1_reasoning_closed")
    geo = CONFIG["engine"]
    first, second = mix["requests"][:32], mix["requests"][32:]
    long_ones = [r for r in first if r[1] == 4096]
    short = [r for r in first if r[1] != 4096]
    live = sum(c + s for c, s in long_ones) + sum(
        max(c + s for c, s in (a, b)) for a, b in zip(short, second[28:]))
    assert live == mix["population"]["live_tokens_at_most"] == 196224
    assert 11648 <= -(-live // geo["page_size"]) <= geo["pages"] == 14336
    assert geo["slots"] == mix["clients"] == 32
    # 20 layers x 2 lines of 256 bfloat16 a token
    assert geo["pages"] * geo["page_size"] * 20 * 2 * 256 * 2 == 4697620480


def test_the_traffic_is_issue_50s():
    mix = traffic.load("zaya1_reasoning_closed")
    assert (mix["kind"], mix["clients"], mix["rounds"]) == (
        "closed_loop_requests", 32, 2)
    assert len(mix["requests"]) == 64
    first, second = mix["requests"][:32], mix["requests"][32:]
    contexts = sorted(c for c, _ in first)
    assert contexts == mix["population"]["contexts"]
    assert (contexts[0], contexts[-1]) == (768, 4096)
    assert all(c % 128 == 0 for c in contexts)
    assert 1920 <= np.median(contexts) <= 2176
    assert sorted(s for _, s in first) == [384] * 4 + [4096] * 28
    # the four short ones are sent last: they join as the ramp ends and end
    # inside the window, before its traced part
    assert [s for _, s in first[28:]] == [384] * 4
    assert second == [[1024, 4096]] * 32
    assert max(c + s for c, s in mix["requests"]) \
        <= CONFIG["max_position_embeddings"] == 8192
    assert mix["check_sample"] == 4
    # ISSUE 50's traced part, as mellum's file: 10 s from second 28, the
    # long sequences 4k-5k deep; 384 tokens and the four launches behind
    # them are over before it at any step under 60 ms, so it holds no launch
    assert mix["trace"] == {"start_s": 28.0, "seconds": 10.0}
    assert 384 * 0.060 + 1.0 < mix["trace"]["start_s"]
    items = traffic.requests(mix, 2**31 + 3, 48.0, CONFIG["vocab_size"])
    assert len(items) == 64 and sum(it["ramp"] for it in items) == 32
    assert all(0 <= int(it["prompt"].max()) < 262272 for it in items[:32])
    # the long answers outlast the window at any step a v5e could run
    assert 4096 * 0.012 > 48


def test_the_cell_and_its_metrics_are_found_by_name():
    (config,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    assert len(config["why"]) <= 200
    (cell,) = [c for c in BENCH["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "zaya1_reasoning_closed", 1)
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
        assert mine[name]["source"] == "device_trace"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", f"{name}.py"))
    rooflines = [n for n in OWN if n.endswith("_roofline")]
    assert len(rooflines) == 3
    assert all(mine[n]["unit"] == "%" and mine[n]["layer"] == "kernels"
               for n in rooflines)
    assert {"moe_experts_touched_share", "moe_max_load_over_mean",
            "attn_pages_read_share", "out_tokens_per_s", "ramp_s",
            "prefill_lane_wait_p50_ms.tpot", "ttft_p50_ms.tpot",
            "decode_step_dev_ms.tpot", "serving_device_idle.tpot",
            "compiles_in_window.tpot"} <= set(mine)
    # none of the lists that a test pins cell for cell, and neither of the
    # two whose readers read launches inside the traced part: it has none
    # (as mellum's cell, which traces the same seconds, joins neither)
    assert not {"attn_full_step_share", "mlp_step_dev_share",
                "prefill_fill_share.tpot", "prefill_ctx_read_share.tpot",
                "chunk_host_ms.tpot", "prefill_chunk_dev_ms.tpot"} & set(mine)


def test_nothing_that_stood_in_the_benchmark_is_edited():
    """Against the parent commit's file, where git has one to show: every
    entry that stood is there as it stood, in its place; a list that gained
    a cell gained it at its end."""
    shown = subprocess.run(["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT,
                           capture_output=True, text=True)
    if shown.returncode:
        pytest.skip("no git history to compare with")
    old = json.loads(shown.stdout)
    if CELL in [c["name"] for c in old["workloads"]]:
        pytest.skip("the commit at HEAD has this cell already")
    for key in ("command", "paths", "run_seconds"):
        assert BENCH[key] == old[key]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(old[group], BENCH[group]):
            grown = {k: v for k, v in now.items() if k != "workloads"}
            assert grown == {k: v for k, v in was.items()
                             if k != "workloads"}
            lists = now.get("workloads", []), was.get("workloads", [])
            assert lists[0][:len(lists[1])] == lists[1]
            assert lists[0][len(lists[1]):] in ([], [CELL])
    assert len(BENCH["configs"]) == len(old["configs"]) + 1
    assert len(BENCH["workloads"]) == len(old["workloads"]) + 1
    assert len(BENCH["per_layer"]) == len(old["per_layer"]) + 8 <= 128


# -- scopes and readers over hand-built facts -----------------------------------------

HLO = """HloModule jit__step
ENTRY %main.1 (p: f32[4]) -> f32[4] {
  %fusion.1 = f32[32,1280]{1,0} fusion(%p), kind=kOutput, calls=%f, metadata={op_name="jit(_step)/attn.full/cca.in/dot_general"}
  %fusion.2 = f32[32,10,128]{2,1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_step)/attn.full/cca.mix/mul"}
  %fusion.3 = bf16[286740,16,256]{2,1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_step)/attn.full/scatter"}
  %custom-call.4 = f32[32,8,256]{2,1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/attn.full/jit(_call)/paged_line_attention/pallas_call"}
  %fusion.5 = f32[32,1,2048]{2,1,0} fusion(%p), kind=kOutput, calls=%f, metadata={op_name="jit(_step)/attn.full/cca.out/dot_general"}
  %fusion.6 = f32[32,1,2048]{2,1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_step)/attn.full/merge/add"}
  %fusion.7 = f32[32,16]{1,0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(_step)/moe.router/dot_general"}
  %fusion.8 = s32[17]{0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(_step)/moe.experts/scatter-add"}
  %custom-call.9 = f32[32,2048]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/moe.experts/jit(_call)/grouped_experts/pallas_call"}
  %fusion.10 = f32[32,1,2048]{2,1,0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(_step)/merge/add"}
  %fusion.11 = f32[32,262272]{1,0} fusion(%p), kind=kOutput, calls=%h, metadata={op_name="jit(_step)/head/dot_general"}
  ROOT %fusion.12 = f32[32,2048]{1,0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(_step)/gather"}
}
"""
OPS = {"fusion.1_f32_32_1280_": ("attn.full.cca.in", 0.03),
       "fusion.2_f32_32_10_128_": ("attn.full.cca.mix", 0.05),
       "fusion.3_bf16_286740_16_256_": ("attn.full", 0.01),
       "custom-call.4_f32_32_8_256_": ("attn.full.kernel", 0.20),
       "fusion.5_f32_32_1_2048_": ("attn.full.cca.out", 0.02),
       "fusion.6_f32_32_1_2048_": ("attn.full.merge", 0.01),
       "fusion.7_f32_32_16_": ("moe.router", 0.06),
       "fusion.8_s32_17_": ("moe.experts", 0.02),
       "custom-call.9_f32_32_2048_": ("moe.experts.kernel", 0.45),
       "fusion.10_f32_32_1_2048_": ("merge", 0.01),
       "fusion.11_f32_32_262272_": ("head", 0.09),
       "fusion.12_f32_32_2048_": (None, 0.05)}
STEPS = 50
COUNTS = {"moe_experts_touched": 272, "moe_assignments": 640,
          "moe_max_load": 100, "moe_expert_slots": 320}
CONTEXT = 32 * 3000


def test_scopes_keep_the_attention_parts_inner_names_and_find_the_kernels():
    got = lm_serving_moe_cca.scopes_in(HLO)
    assert got == {op: scope for op, (scope, _) in OPS.items() if scope}
    scope_of = lm_serving_moe_cca.scope_of
    assert scope_of("jit(_step)/attn.full/mul") == "attn.full"
    assert scope_of("jit(_step)/attn.full/cca.mix/mul") == "attn.full.cca.mix"
    assert scope_of("jit(_step)/attn.full/merge/add") == "attn.full.merge"
    assert scope_of("jit(_step)/merge/add") == "merge"
    assert scope_of("jit(_prefill_chunk)/moe.experts/jit(_call)/"
                    "grouped_experts/pallas_call") == "moe.experts.kernel"
    assert scope_of("jit(_step)/moe.router/erf") == "moe.router"
    assert scope_of("jit(_step)/cca/mul") is None


@pytest.fixture
def facts():
    trace = {"window_s": 10.0, "busy_s": 9.9, "programs": {
        "_step": {"count": STEPS, "total_s": 1.0,
                  "ops": {op: s for op, (_, s) in OPS.items()}}}}
    return {"trace": trace, "peaks": V5E, "config": CONFIG,
            "op_scopes": {"_step": lm_serving_moe_cca.scopes_in(HLO)},
            "trace_bounds": (100.0, 110.0),
            "decode_steps": [(101.0, 32, CONTEXT, 6000),
                             (102.0, 32, CONTEXT, 6000),
                             (200.0, 32, CONTEXT, 6000)],
            "moe_steps": [(101.0, COUNTS), (102.0, COUNTS),
                          (200.0, COUNTS)],  # the last: outside the trace
            "moe_expert_slots": 320}


def _least(cost):
    return max(cost["bytes"] / 819e9, cost["flops"] / 197e12)


@pytest.mark.parametrize("name,share", [
    ("cca_step_dev_share", 0.03 + 0.05 + 0.01 + 0.20 + 0.02 + 0.01),
    ("cca_mix_dev_share", 0.05), ("moe_router_dev_share", 0.06),
    ("moe_top1_dev_share", 0.02 + 0.45), ("head_step_dev_share", 0.09)])
def test_a_share_is_the_steps_time_under_its_labels(facts, name, share):
    assert harness.reader_for(name)(facts) == pytest.approx(100 * share)


def test_cca_decode_roofline_is_the_visible_lines_over_the_kernels_time(facts):
    cost = opcount.cca_decode(CONFIG, 32, CONTEXT)
    assert harness.reader_for("cca_decode_roofline")(facts) == pytest.approx(
        100 * _least(cost) / (0.20 / STEPS))


def test_moe_top1_roofline_is_the_reached_experts_over_the_kernels_time(facts):
    cost = opcount.moe_top1(CONFIG, 32, 272, 640)
    assert harness.reader_for("moe_top1_roofline")(facts) == pytest.approx(
        100 * _least(cost) / (0.45 / STEPS))


def test_moe_cca_step_roofline_is_the_step_over_its_device_time(facts):
    cost = opcount.step(CONFIG, 32, CONTEXT, 272, 640, 32)
    assert harness.reader_for("moe_cca_step_roofline")(facts) == \
        pytest.approx(100 * _least(cost) / (1.0 / STEPS))


@pytest.mark.parametrize("name", OWN)
def test_a_program_without_the_scopes_leaves_the_metric_out(name, facts):
    # a program that lacks the scopes or the counters: nothing, no error
    # (the whole step's share needs the counters alone)
    bare = dict(facts, op_scopes=None)
    if name != "moe_cca_step_roofline":
        assert harness.reader_for(name)(bare) is None
    assert harness.reader_for(name)(dict(bare, moe_steps=None)) is None
    assert harness.reader_for(name)(dict(facts, trace=None,
                                         trace_bounds=None)) is None
    other = dict(facts, config={**CONFIG, "programs": {"decode": "_round"}})
    assert harness.reader_for(name)(other) is None


# -- the counts, against hand arithmetic at the published sizes ---------------------

def test_weights_per_layer_are_issue_50s_numbers():
    s = opcount.sizes(CONFIG)
    # W_q 2048 x 1024, W_k 2048 x 256, two W_v 2048 x 128, W_o 1024 x 2048;
    # taps 2 x 1280 and 2 x 10 x 128 x 128, two biases of 1280
    assert s["cca"] == 5242880 + 332800 == 5575680
    assert s["router"] == 2048 * 256 + 2 * 65536 + 4096 + 5 * 256 + 16 \
        == 660752
    assert s["expert"] == 3 * 2048 * 2048 == 12582912
    assert s["vectors"] == 20482
    assert s["head"] == 2048 * 262272 == 537133056
    assert (s["line"], s["state"], s["layers"]) == (512, 2688, 20)
    assert s["line"] * 2 == 1024                  # bytes a token a layer
    assert opcount.layer_weights(CONFIG) == 207583506            # 207.6M
    # layer 0 lacks gamma and two merge pairs; the output norm closes it
    total = 20 * 207583506 - (256 + 4 * 2048) + s["head"] + 2048
    assert total == 4688796776 and round(total * 2 / 1e9, 2) == 9.38


def test_a_step_reads_what_issue_50_counted():
    # 32 slots at 4000 positions, 87% of the experts reached
    ctx, touched = 32 * 4000, 0.87 * 320
    cost = opcount.step(CONFIG, 32, ctx, touched, 640, 32)
    s = opcount.sizes(CONFIG)
    experts = touched * s["expert"] * 2
    lines = 20 * ctx * 512 * 2
    assert experts / 1e9 == pytest.approx(7.0, abs=0.05)
    assert lines / 1e9 == pytest.approx(2.62, abs=0.01)
    assert s["head"] * 2 / 1e9 == pytest.approx(1.07, abs=0.01)
    outside = 20 * (s["cca"] + s["router"] + s["vectors"]) * 2
    assert outside / 1e9 == pytest.approx(0.25, abs=0.01)
    state = 20 * 32 * 2688 * 4 * 2
    rows = 20 * 32 * (8 * 2048 + 8 * 1024 + 512 * 2)
    assert cost["bytes"] == pytest.approx(
        experts + lines + s["head"] * 2 + outside + state + rows, rel=1e-12)
    assert cost["bytes"] / 819e9 * 1e3 == pytest.approx(13.4, abs=0.1)
    assert cost["bytes"] / 819e9 > cost["flops"] / 197e12  # bound by bytes


def test_the_experts_cost_is_the_experts_reached_and_a_row_each():
    base = opcount.moe_top1(CONFIG, 32, 270, 640)
    more = opcount.moe_top1(CONFIG, 32, 271, 640)
    assert more["bytes"] - base["bytes"] == 2 * 12582912
    assert base["bytes"] == 270 * 12582912 * 2 + 20 * 8 * 32 * 2048
    assert base["flops"] == 2.0 * 640 * 12582912


def test_the_kernels_lines_are_the_contexts_in_every_layer():
    base = opcount.cca_decode(CONFIG, 32, 100000)
    assert base["bytes"] == 20 * (100000 * 512 * 2 + 8 * 32 * 8 * 128)
    assert base["flops"] == 20 * 100000 * 8 * 4.0 * 128
    wider = opcount.cca_decode(CONFIG, 32, 100016)
    assert wider["bytes"] - base["bytes"] == 20 * 16 * 512 * 2
