"""The benchmark's own arithmetic and generators (``benchmark/lib``), on the
CPU: traffic whose population the seed cannot change, percentile and
token-gap arithmetic, the peaks table, the byte and FLOP count behind
``decode_step_roofline``, and the contract's static rules on
``BENCHMARK.json``."""
import json
import os
import re
import statistics
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import harness, opcount, peaks, stats, traffic  # noqa: E402

BENCH = harness.load_benchmark()
MIXES = sorted({c["traffic"] for c in BENCH["workloads"]})
OPEN_LOOP = [n for n in MIXES
             if traffic.load(n)["kind"] == "open_loop_requests"]
CLOSED_LOOP = [n for n in MIXES
               if traffic.load(n)["kind"] == "closed_loop_requests"]
BIG_SEED = 2**31 + 12345


# -- traffic -------------------------------------------------------------------

def _requests(name, seed, seconds=None, vocab=50272):
    return traffic.requests(traffic.load(name), seed,
                            seconds or BENCH["run_seconds"], vocab)


def _shape(run):
    """What the seed may not change: lengths, times and who waits for whom,
    request by request."""
    return [(int(x["prompt"].size), x["steps"], x["due_s"], x["after"],
             x["ramp"]) for x in run]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_gives_the_same_requests(name):
    a, b = _requests(name, BIG_SEED), _requests(name, BIG_SEED)
    assert _shape(a) == _shape(b)
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    assert all(x["prompt"].dtype == np.int32 for x in a)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_never_change_the_population(name):
    runs = [_requests(name, seed) for seed in (1, 2, BIG_SEED)]
    # the same lengths in the same order at the same times, and so the
    # same multiset and the same number of due requests, whatever the seed
    assert _shape(runs[0]) == _shape(runs[1]) == _shape(runs[2])
    assert len(runs[0]) > 0
    # what the seed does draw: the token ids
    ids = [np.concatenate([x["prompt"] for x in r])[:64].tolist() for r in runs]
    assert ids[0] != ids[1] != ids[2]
    assert all(0 <= t < 50272 for t in ids[2])


@pytest.mark.parametrize("name", OPEN_LOOP)
def test_open_loop_arrivals_are_the_files_list_on_a_fixed_grid(name):
    mix = traffic.load(name)
    run = _requests(name, 3)
    # at the benchmark's run length the whole list is due, in its order
    assert [(int(x["prompt"].size), x["steps"]) for x in run] == [
        tuple(p) for p in mix["requests"]]
    assert [x["due_s"] for x in run] == pytest.approx(
        [i / mix["rate_per_s"] for i in range(len(run))])
    assert run[-1]["due_s"] <= BENCH["run_seconds"] - mix["drain_s"]
    assert all(x["after"] is None and not x["ramp"] for x in run)


@pytest.mark.parametrize("name", OPEN_LOOP)
def test_a_shorter_window_takes_a_prefix_of_the_list(name):
    full = _requests(name, 5, vocab=100)
    part = _requests(name, 5, seconds=BENCH["run_seconds"] / 2, vocab=100)
    assert 0 < len(part) < len(full)
    assert _shape(part) == _shape(full[:len(part)])
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(part, full))


@pytest.mark.parametrize("name", CLOSED_LOOP)
def test_closed_loop_clients_wait_for_their_own_last_request(name):
    mix = traffic.load(name)
    run = _requests(name, 7)
    clients = mix["clients"]
    assert len(run) == clients * mix["rounds"]
    for i, x in enumerate(run):
        assert x["due_s"] is None
        assert x["ramp"] == (i < clients)
        assert x["after"] == (None if i < clients else i - clients)
        assert [int(x["prompt"].size), x["steps"]] == mix["requests"][
            i % len(mix["requests"])]
    # every request fits the positions of each configuration it is sent to
    limits = [harness.find_cell(BENCH, c["name"])[1]["max_position_embeddings"]
              for c in BENCH["workloads"] if c["traffic"] == name]
    assert limits and max(p + s for p, s in mix["requests"]) <= min(limits)


def test_a_traffic_kind_without_a_generator_is_refused(tmp_path, monkeypatch):
    (tmp_path / "odd.json").write_text('{"kind": "no_such_kind"}')
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", str(tmp_path))
    with pytest.raises(ValueError, match="no generator"):
        traffic.load("odd")


def test_every_generator_is_used_by_a_listed_mix():
    folder = os.path.join(ROOT, "benchmark", "generators")
    kinds = {f[:-3] for f in os.listdir(folder)
             if f.endswith(".py") and f != "__init__.py"}
    assert kinds == {traffic.load(n)["kind"] for n in MIXES}


# -- arithmetic ------------------------------------------------------------------

@pytest.mark.parametrize("q", [0, 5, 50, 90, 95, 99, 100])
def test_percentile_is_numpys_linear_one(q):
    values = np.random.default_rng(q).exponential(1.0, 37)
    assert stats.percentile(list(values), q) == pytest.approx(
        np.percentile(values, q), rel=1e-12)


def test_percentile_refuses_nothing_and_nonsense():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


def test_spread_is_the_drivers_quartile_rule():
    values = [10.0, 10.2, 9.9, 10.4, 10.1, 9.8]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx(
        (q3 - q1) / statistics.median(values))


def test_tpot_is_the_mean_gap_between_a_requests_tokens():
    # first token at 1.0 s, fifth at 1.8 s: four gaps of 200 ms
    assert stats.tpot_ms(1.0, 1.8, 5) == pytest.approx(200.0)
    with pytest.raises(ValueError):
        stats.tpot_ms(1.0, 1.0, 1)


# -- peaks and operation counts ----------------------------------------------------

def test_unknown_device_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert peaks.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_decode_step_cost_at_opt_1_3b_sizes():
    empty = opcount.lm_decode_step(2048, 24, 8192, 50272, 0, 0)
    # 24 x (4 d^2 + 2 d f) + V d = 1.311e9 weights, two bytes each
    assert empty["bytes"] == 2 * (24 * (4 * 2048**2 + 2 * 2048 * 8192)
                                  + 50272 * 2048)
    assert empty["flops"] == 0
    full = opcount.lm_decode_step(2048, 24, 8192, 50272, 16, 16 * 300)
    kv_token = 2 * 24 * 2048 * 2  # the configuration's 196608 B a token
    assert kv_token == 196608
    assert full["bytes"] - empty["bytes"] == (16 * 300 + 16) * kv_token
    seconds, bound = opcount.least_seconds(full, peaks.peaks_for("TPU v5 lite"))
    assert bound == "hbm"          # a decode step is bound by HBM bytes
    assert 3.2e-3 < seconds < 5e-3  # 2.6 GB of weights at 819 GB/s and the cache
    assert seconds / 140.7e-3 < 0.04  # PR 22's step: under 4% of the roofline


def test_least_seconds_names_the_binding_peak():
    table = {"hbm_bytes_per_s": 10.0, "flops_per_s": 100.0}
    assert opcount.least_seconds({"bytes": 10, "flops": 10}, table) == (1.0, "hbm")
    assert opcount.least_seconds({"bytes": 1, "flops": 500}, table) == (5.0, "flops")


# -- BENCHMARK.json against the contract's static rules ----------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keeps_the_contracts_static_rules():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    names = [m["name"] for g in ("end_to_end", "per_layer") for m in BENCH[g]]
    names += [c["name"] for c in BENCH["workloads"] + BENCH["configs"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    cells = {c["name"]: c for c in BENCH["workloads"]}
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert len({(c["config"], c["traffic"]) for c in cells.values()}) == len(cells)
    assert sum(c["chips"] == 4 for c in cells.values()) <= max(1, len(cells) // 4)
    for c in cells.values():
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert c["config"] in configs and c["chips"] in (1, 4)
        assert NAME.match(c["traffic"]) and 1 <= len(c["why"]) <= 200
        traffic.load(c["traffic"])
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert any(w["config"] == c["name"] for w in cells.values())
        with open(os.path.join(ROOT, c["file"])) as fh:
            assert json.load(fh)["reduced"] == c["reduced"]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in e2e.values():
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1 and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    assert 1 <= len(BENCH["per_layer"]) <= 128
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
        assert UNIT.match(m["unit"]) and "\n" not in m["layer"]
        assert m["moves"] in e2e
        moved = e2e[m["moves"]]
        # an entry that lists no cells is read where the moved metric is
        assert set(m.get("workloads", ())) <= set(
            moved.get("workloads", cells))
    for name, cell in cells.items():
        mine = [m["name"] for m in harness.metrics_of(BENCH, "end_to_end", name)]
        assert "setup_s" in mine and len(mine) >= 2
        assert harness.metrics_of(BENCH, "per_layer", name)


# what each cell printed at PR 37, before one entry stood for a quantity in
# every cell that has it (the ledger keeps a metric's history under these)
BEFORE = {
    "opt1b3_chat": (
        "setup_compile_s compiles_in_window batch_occupancy "
        "pool_pages_used_peak pool_live_share decode_step_dev_ms "
        "decode_step_roofline serving_device_idle ttft_p50_ms.chat "
        "ttft_p90_ms.chat gen_late_p99_ms.chat queue_wait_p50_ms.chat "
        "prefill_chunks_per_req.chat prefill_chunk_dev_ms.chat "
        "sched_self_ms_per_pass passes_with_chunk_share step_host_ms "
        "chunk_host_ms step_pull_wait_ms prefill_lane_wait_p50_ms.chat "
        "host_serial_share attn_pages_read_share "
        "prefill_fill_share.chat").split(),
    "opt1b3_longprompt": (
        "setup_compile_s gen_late_p99_ms compiles_in_window.long "
        "queue_wait_p50_ms batch_occupancy.long prefill_chunks_per_req "
        "pool_pages_used_peak.long pool_live_share.long prefill_chunk_dev_ms "
        "decode_step_dev_ms.long serving_device_idle.long ttft_p90_ms "
        "tpot_p50_ms.long sched_self_ms_per_pass.long "
        "passes_with_chunk_share.long step_host_ms.long chunk_host_ms.long "
        "step_pull_wait_ms.long prefill_lane_wait_p50_ms "
        "host_serial_share.long prefill_fill_share").split(),
    "opt1b3_saturated": (
        "setup_compile_s compiles_in_window batch_occupancy "
        "pool_pages_used_peak pool_live_share decode_step_dev_ms "
        "decode_step_roofline serving_device_idle gen_late_p99_ms.sat "
        "queue_wait_p50_ms.sat prefill_chunks_per_req.sat "
        "prefill_chunk_dev_ms.sat ttft_p50_ms.sat out_tokens_per_s "
        "sched_self_ms_per_pass passes_with_chunk_share step_host_ms "
        "chunk_host_ms step_pull_wait_ms prefill_lane_wait_p50_ms.sat "
        "host_serial_share attn_pages_read_share "
        "prefill_fill_share.sat").split(),
    "kanana2_decode_saturated": (
        "setup_compile_s decode_step_dev_ms.kanana "
        "prefill_chunk_dev_ms.kanana serving_device_idle.kanana "
        "batch_occupancy.kanana passes_with_chunk_share.kanana "
        "pool_live_share.kanana pool_pages_used_peak.kanana "
        "step_host_ms.kanana chunk_host_ms.kanana step_pull_wait_ms.kanana "
        "host_serial_share.kanana sched_self_ms_per_pass.kanana "
        "compiles_in_window.kanana out_tokens_per_s.kanana ttft_p50_ms.kanana "
        "prefill_lane_wait_p50_ms.kanana gen_late_p99_ms.kanana moe_dev_share "
        "mla_dev_share moe_experts_touched_share moe_max_load_over_mean "
        "moe_roofline mla_decode_roofline moe_mla_step_roofline "
        "attn_pages_read_share prefill_fill_share.kanana").split(),
    "mellum2_longctx_decode": (
        "setup_compile_s decode_step_dev_ms.mellum serving_device_idle.mellum "
        "batch_occupancy.mellum passes_with_chunk_share.mellum "
        "pool_live_share.mellum pool_pages_used_peak.mellum "
        "step_host_ms.mellum step_pull_wait_ms.mellum "
        "host_serial_share.mellum sched_self_ms_per_pass.mellum "
        "compiles_in_window.mellum out_tokens_per_s.mellum "
        "gen_late_p99_ms.mellum attn_pages_read_share.mellum "
        "moe_experts_touched_share.mellum moe_max_load_over_mean.mellum "
        "moe_step_dev_share attn_window_step_share attn_full_step_share "
        "window_pages_read_share ramp_s moe_topk_roofline "
        "gqa_window_decode_roofline moe_gqa_step_roofline").split(),
    "jamba2_reasoning_saturated": (
        "setup_compile_s decode_step_dev_ms.jamba prefill_chunk_dev_ms.jamba "
        "serving_device_idle.jamba batch_occupancy.jamba "
        "pool_live_share.jamba pool_pages_used_peak.jamba "
        "attn_pages_read_share.jamba prefill_fill_share.jamba "
        "compiles_in_window.jamba out_tokens_per_s.jamba "
        "gen_late_p99_ms.jamba ttft_p50_ms.jamba sched_self_ms_per_pass.jamba "
        "passes_with_chunk_share.jamba step_host_ms.jamba chunk_host_ms.jamba "
        "step_pull_wait_ms.jamba prefill_lane_wait_p50_ms.jamba "
        "host_serial_share.jamba ramp_s.jamba ssm_step_dev_share "
        "attn_mqa_step_share mlp_step_dev_share ssm_scan_chunk_share "
        "state_live_share ssm_step_roofline ssm_chunk_scan_roofline "
        "ssm_mqa_step_roofline").split(),
}
# new name: the names it had, one a cell or two
RENAMED = {
    "gen_late_p99_ms.ttft": ["gen_late_p99_ms"],
    "compiles_in_window.tpot": [
        "compiles_in_window", "compiles_in_window.kanana",
        "compiles_in_window.mellum", "compiles_in_window.jamba"],
    "compiles_in_window.ttft": ["compiles_in_window.long"],
    "queue_wait_p50_ms.ttft": ["queue_wait_p50_ms"],
    "batch_occupancy.tpot": [
        "batch_occupancy", "batch_occupancy.kanana", "batch_occupancy.mellum",
        "batch_occupancy.jamba"],
    "batch_occupancy.ttft": ["batch_occupancy.long"],
    "prefill_chunks_per_req.ttft": ["prefill_chunks_per_req"],
    "pool_pages_used_peak.tpot": [
        "pool_pages_used_peak", "pool_pages_used_peak.kanana",
        "pool_pages_used_peak.mellum", "pool_pages_used_peak.jamba"],
    "pool_pages_used_peak.ttft": ["pool_pages_used_peak.long"],
    "pool_live_share.tpot": [
        "pool_live_share", "pool_live_share.kanana", "pool_live_share.mellum",
        "pool_live_share.jamba"],
    "pool_live_share.ttft": ["pool_live_share.long"],
    "prefill_chunk_dev_ms.ttft": ["prefill_chunk_dev_ms"],
    "decode_step_dev_ms.tpot": [
        "decode_step_dev_ms", "decode_step_dev_ms.kanana",
        "decode_step_dev_ms.mellum", "decode_step_dev_ms.jamba"],
    "decode_step_dev_ms.ttft": ["decode_step_dev_ms.long"],
    "serving_device_idle.tpot": [
        "serving_device_idle", "serving_device_idle.kanana",
        "serving_device_idle.mellum", "serving_device_idle.jamba"],
    "serving_device_idle.ttft": ["serving_device_idle.long"],
    "ttft_p90_ms.ttft": ["ttft_p90_ms"],
    "ttft_p50_ms.tpot": [
        "ttft_p50_ms.chat", "ttft_p50_ms.sat", "ttft_p50_ms.kanana",
        "ttft_p50_ms.jamba"],
    "ttft_p90_ms.tpot": ["ttft_p90_ms.chat"],
    "gen_late_p99_ms.tpot": [
        "gen_late_p99_ms.chat", "gen_late_p99_ms.sat",
        "gen_late_p99_ms.kanana", "gen_late_p99_ms.mellum",
        "gen_late_p99_ms.jamba"],
    "queue_wait_p50_ms.tpot": [
        "queue_wait_p50_ms.chat", "queue_wait_p50_ms.sat"],
    "prefill_chunks_per_req.tpot": [
        "prefill_chunks_per_req.chat", "prefill_chunks_per_req.sat"],
    "prefill_chunk_dev_ms.tpot": [
        "prefill_chunk_dev_ms.chat", "prefill_chunk_dev_ms.sat",
        "prefill_chunk_dev_ms.kanana", "prefill_chunk_dev_ms.jamba"],
    "tpot_p50_ms.ttft": ["tpot_p50_ms.long"],
    "sched_self_ms_per_pass.tpot": [
        "sched_self_ms_per_pass", "sched_self_ms_per_pass.kanana",
        "sched_self_ms_per_pass.mellum", "sched_self_ms_per_pass.jamba"],
    "sched_self_ms_per_pass.ttft": ["sched_self_ms_per_pass.long"],
    "passes_with_chunk_share.tpot": [
        "passes_with_chunk_share", "passes_with_chunk_share.kanana",
        "passes_with_chunk_share.mellum", "passes_with_chunk_share.jamba"],
    "passes_with_chunk_share.ttft": ["passes_with_chunk_share.long"],
    "step_host_ms.tpot": [
        "step_host_ms", "step_host_ms.kanana", "step_host_ms.mellum",
        "step_host_ms.jamba"],
    "step_host_ms.ttft": ["step_host_ms.long"],
    "chunk_host_ms.tpot": [
        "chunk_host_ms", "chunk_host_ms.kanana", "chunk_host_ms.jamba"],
    "chunk_host_ms.ttft": ["chunk_host_ms.long"],
    "step_pull_wait_ms.tpot": [
        "step_pull_wait_ms", "step_pull_wait_ms.kanana",
        "step_pull_wait_ms.mellum", "step_pull_wait_ms.jamba"],
    "step_pull_wait_ms.ttft": ["step_pull_wait_ms.long"],
    "prefill_lane_wait_p50_ms.ttft": ["prefill_lane_wait_p50_ms"],
    "prefill_lane_wait_p50_ms.tpot": [
        "prefill_lane_wait_p50_ms.chat", "prefill_lane_wait_p50_ms.sat",
        "prefill_lane_wait_p50_ms.kanana", "prefill_lane_wait_p50_ms.jamba"],
    "host_serial_share.tpot": [
        "host_serial_share", "host_serial_share.kanana",
        "host_serial_share.mellum", "host_serial_share.jamba"],
    "host_serial_share.ttft": ["host_serial_share.long"],
    "out_tokens_per_s": [
        "out_tokens_per_s.kanana", "out_tokens_per_s.mellum",
        "out_tokens_per_s.jamba"],
    "prefill_fill_share.ttft": ["prefill_fill_share"],
    "prefill_fill_share.tpot": [
        "prefill_fill_share.sat", "prefill_fill_share.chat",
        "prefill_fill_share.kanana", "prefill_fill_share.jamba"],
    "attn_pages_read_share": [
        "attn_pages_read_share.mellum", "attn_pages_read_share.jamba"],
    "moe_experts_touched_share": ["moe_experts_touched_share.mellum"],
    "moe_max_load_over_mean": ["moe_max_load_over_mean.mellum"],
    "ramp_s": ["ramp_s.jamba"],
}
OLD_TO_NEW = {old: new for new, olds in RENAMED.items() for old in olds}
STARTUP = {"setup_engine_build_s", "setup_trace_lower_s", "setup_cache_load_s",
           "setup_fresh_compile_s", "setup_fresh_compiles"}
# what every serving cell has by construction of the scheduler, the engine,
# the pool and the load generator: these entries list no cells
COMMON = {"batch_occupancy", "decode_step_dev_ms", "serving_device_idle",
          "step_host_ms", "step_pull_wait_ms", "host_serial_share",
          "sched_self_ms_per_pass", "passes_with_chunk_share",
          "compiles_in_window", "pool_live_share", "pool_pages_used_peak",
          "gen_late_p99_ms"}


def _cells_reading(bench, metric_name):
    return [c["name"] for c in bench["workloads"] if metric_name in {
        m["name"] for m in harness.metrics_of(bench, "per_layer", c["name"])}]


def test_an_entry_without_cells_is_read_where_what_it_moves_is_reported():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [c["name"] for c in BENCH["workloads"]]
    listless = [m for m in BENCH["per_layer"] if "workloads" not in m]
    assert {m["name"].split(".")[0] for m in listless} >= COMMON
    for m in listless:
        assert _cells_reading(BENCH, m["name"]) == e2e[m["moves"]].get(
            "workloads", cells)
    # the token gap's are not read where the first token is judged
    assert "opt1b3_longprompt" not in _cells_reading(
        BENCH, "batch_occupancy.tpot")
    assert _cells_reading(BENCH, "batch_occupancy.ttft") == [
        "opt1b3_longprompt"]
    assert _cells_reading(BENCH, "setup_compile_s") == cells
    # an end-to-end metric without a list is every cell's, as before
    assert all("setup_s" in {m["name"] for m in harness.metrics_of(
        BENCH, "end_to_end", c)} for c in cells)


@pytest.mark.parametrize("cell", sorted(BEFORE))
def test_every_cell_reads_every_quantity_it_read_before(cell):
    now = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", cell)}
    kept = {OLD_TO_NEW.get(old, old) for old in BEFORE[cell]}
    assert len(kept) == len(BEFORE[cell])  # no two of a cell's became one
    assert kept <= now
    # and what came with PR 38: the program's account of its start-up
    assert now - kept >= STARTUP
    for old in BEFORE[cell]:  # the same reader file serves the new name
        assert harness.reader_file(OLD_TO_NEW.get(old, old)) == \
            harness.reader_file(old)


def test_a_new_cell_in_the_moved_metrics_list_inherits_the_common_metrics():
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({**bench["workloads"][-1], "name": "made_up",
                               "traffic": "made_up_mix"})
    {m["name"]: m for m in bench["end_to_end"]}["tpot_p50_ms"][
        "workloads"].append("made_up")
    mine = {m["name"] for m in harness.metrics_of(bench, "per_layer",
                                                  "made_up")}
    assert mine == {q + ".tpot" for q in COMMON} | STARTUP | {
        "setup_compile_s"}
    assert len(COMMON) == 12
    for name in mine:
        assert callable(harness.reader_for(name))
    # no other cell's list changed, and the first-token copies stay away
    for c in BENCH["workloads"]:
        assert harness.metrics_of(bench, "per_layer", c["name"]) == \
            harness.metrics_of(BENCH, "per_layer", c["name"])


def test_every_per_layer_metric_has_a_reader_and_every_reader_a_metric():
    names = {m["name"] for m in BENCH["per_layer"]}
    for name in names:
        assert callable(harness.reader_for(name))
    folder = os.path.join(ROOT, "benchmark", "layer_metrics")
    stems = {f[:-3] for f in os.listdir(folder) if f.endswith(".py")}
    assert stems <= names | {n.rsplit(".", 1)[0] for n in names}
    with pytest.raises(FileNotFoundError):
        harness.reader_for("no_such_metric")


def test_a_reader_with_nothing_to_read_returns_nothing():
    for m in BENCH["per_layer"]:
        read = harness.reader_for(m["name"])
        assert read({"config": {}, "mix": {}, "end_to_end": {}, "trace": None,
                     "peaks": None, "window_s": 1.0, "metric": m}) is None
