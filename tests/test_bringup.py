"""Bring-up contract (PR 21): the chip smoke refuses everything but a TPU,
its checks mean what they say at a size the CPU can run, and the XLA compile
cache is placed by one function that defers to JAX_COMPILATION_CACHE_DIR."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


class TestChipSmokeRefusesTheCpu:
    def _run(self, cwd, code=None):
        t0 = time.monotonic()
        proc = subprocess.run(
            [sys.executable] + (["-c", code] if code else [SMOKE]),
            cwd=cwd, capture_output=True, text=True, timeout=120,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        return proc, time.monotonic() - t0

    def test_exits_nonzero_at_once_naming_cpu_without_a_model(self):
        # run_path so the child can report what it had imported by the
        # time it gave up: nothing of the package, so no model either
        code = (
            "import runpy, sys\n"
            "try:\n"
            f"    runpy.run_path({SMOKE!r}, run_name='__main__')\n"
            "except SystemExit as e:\n"
            "    rc = e.code\n"
            "print('IMPORTED', sorted(m for m in sys.modules\n"
            "                         if m.startswith('nnstreamer_tpu')))\n"
            "sys.exit(rc)\n")
        proc, took = self._run(ROOT, code)
        assert proc.returncode not in (0, None)
        assert "'cpu'" in proc.stderr and "needs a TPU" in proc.stderr
        assert proc.stdout.strip() == "IMPORTED []"  # and no result line
        assert took < 60

    def test_alone_in_a_directory_it_prints_no_result(self, tmp_path):
        shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
        proc, _ = self._run(tmp_path, None)
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""


class TestNearTieRule:
    def test_first_divergence(self):
        import chip_smoke

        assert chip_smoke.first_divergence([1, 2, 3], [1, 2, 3]) is None
        assert chip_smoke.first_divergence([1, 2, 3], [1, 9, 3]) == 1
        assert chip_smoke.first_divergence([1, 2], [1, 2, 3]) == 2

    def test_a_near_tie_passes_and_is_reported(self):
        import chip_smoke

        logits = [0.0, 1.000, 1.001]  # reference picked 2, we picked 1
        res = chip_smoke.near_tie("t", [5, 1], [5, 2], lambda i: logits, 0.01)
        assert res["first_diff"] == 1
        assert res["ref_top2_gap"] == pytest.approx(0.001, abs=1e-5)
        assert chip_smoke.near_tie("t", [5, 2], [5, 2], None, 0.01) == {
            "first_diff": None}

    def test_a_clear_disagreement_is_a_wrong_program(self):
        import chip_smoke

        with pytest.raises(chip_smoke.CheckFailed, match="not a near tie"):
            chip_smoke.near_tie("t", [5, 1], [5, 2],
                                lambda i: [0.0, 1.0, 3.0], 0.01)
        with pytest.raises(chip_smoke.CheckFailed):  # lengths differ
            chip_smoke.near_tie("t", [5], [5, 2], None, 0.01)


class TestLegsAtCpuSize:
    """The legs' logic at a size the CPU runs in seconds: what the smoke
    counts on the chip (compile counts, prefix hits, COW, free pages) is
    what the same script counts here."""

    def test_serving_leg_counts(self):
        import chip_smoke
        from nnstreamer_tpu.models import lm_serving

        out = chip_smoke.serving_leg(
            lm_serving.tiny, slots=4, steps=6, page_size=2,
            lengths=(11, 3, 6, 13, 3, 6, 13, 11, 6, 3))
        assert out["completed"] == out["requests"] == 12
        assert out["compile_count"] == 3 and out["spec_compile_count"] == 2
        assert out["prefix_hits_total"] >= 1
        assert out["cow_copies_total"] >= 1
        assert out["near_ties"] == []  # token-exact on the CPU
        assert out["spec_rounds"] > 0

    def test_latent_serving_leg_counts(self):
        import chip_smoke

        out = chip_smoke.latent_serving_leg(serve_dtype="float32")
        assert out["completed"] == out["requests"] == 6
        assert out["line_widths"] == [128]  # 16 + 8 values in one lane row
        # step + prefill_chunk: no page is shared, so nothing is copied
        assert out["compile_count"] == 2
        assert out["served_gap_max"] <= 1e-4  # float32 on the CPU
        assert out["moe_experts_touched"] > 0

    def test_window_serving_leg_counts(self):
        import chip_smoke

        out = chip_smoke.window_serving_leg(serve_dtype="float32")
        assert out["completed"] == out["requests"] == 6
        # step + prefill_chunk: no page is shared, so nothing is copied
        assert out["compile_count"] == 2
        assert out["window_pages_released"] > 0
        assert out["served_gap_max"] <= 1e-4  # float32 on the CPU
        assert out["moe_experts_touched"] > 0

    def test_state_serving_leg_counts(self):
        import chip_smoke

        out = chip_smoke.state_serving_leg(serve_dtype="float32")
        assert out["completed"] == out["requests"] == 10  # over 8 slots
        # step + prefill_chunk: the state's movers are not counted programs
        assert out["compile_count"] == 2
        assert out["served_gap_max"] <= 1e-4  # float32 on the CPU
        assert out["restored_gap_max"] <= 1e-4
        # six state layers of (3 x 512) + (16 x 512) float32 values a slot
        assert out["state_bytes"] == 8 * 6 * (1536 + 8192) * 4
        assert out["state_slots_live"] > 0

    def test_kernels_leg_interpreted(self):
        import chip_smoke

        out = chip_smoke.kernels_leg(B=2, H=2, T=64, D=16, interpret=True)
        for name in ("cached_decode_attention", "flash_attention"):
            for dt in ("float32", "bfloat16"):
                row = out[f"{name}[{dt}]"]
                assert row["max_abs_err"] <= row["atol"]


class TestCompileCachePlacement:
    KNOBS = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")

    @pytest.fixture(autouse=True)
    def _restore(self):
        import jax

        before = {k: getattr(jax.config, k) for k in self.KNOBS}
        yield
        for k, v in before.items():
            jax.config.update(k, v)

    def test_unset_is_one_fixed_path_in_the_checkout(self, monkeypatch):
        import jax

        from nnstreamer_tpu.utils import hw_accel

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first = hw_accel.enable_compilation_cache()
        assert first == hw_accel.enable_compilation_cache()  # twice the same
        assert first == os.path.join(ROOT, ".jax_compile_cache")
        assert jax.config.jax_compilation_cache_dir == first

    def test_placed_from_outside_nothing_is_assigned(self, monkeypatch):
        import jax

        from nnstreamer_tpu.utils import hw_accel

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/elsewhere")
        before = {k: getattr(jax.config, k) for k in self.KNOBS}
        assert hw_accel.enable_compilation_cache() == "/placed/elsewhere"
        assert {k: getattr(jax.config, k) for k in self.KNOBS} == before

    def test_one_assignment_in_the_package_and_the_cache_is_ignored(self):
        hits = []
        for base, _dirs, files in os.walk(os.path.join(ROOT,
                                                       "nnstreamer_tpu")):
            for name in files:
                if name.endswith(".py"):
                    with open(os.path.join(base, name)) as fh:
                        if "jax_compilation_cache_dir" in fh.read():
                            hits.append(name)
        assert hits == ["hw_accel.py"]
        with open(os.path.join(ROOT, ".gitignore")) as fh:
            assert ".jax_compile_cache/" in fh.read().split()


def test_result_line_has_the_contract_keys_and_no_others():
    """The driver refuses a last line with any key beside these; the legs'
    numbers go on the report line before it."""
    import chip_smoke

    summary = {"ok": True, "legs": {"stream": {"passed": True}},
               "versions": {}, "compile_cache_dir": "/x", "wall_s": 1.0,
               "device": {"platform": "tpu", "kind": "TPU v5 lite",
                          "count": 1}}
    line = json.loads(json.dumps(chip_smoke.result_line(summary)))
    assert line == {"ok": True, "device": {"platform": "tpu",
                                           "kind": "TPU v5 lite", "count": 1}}
    assert type(line["ok"]) is bool and type(line["device"]["count"]) is int
    assert chip_smoke.result_line(dict(summary, ok=False))["ok"] is False


def test_compile_clock_reads_jax_monitoring():
    """Compile seconds in the summary are jax's own, not wall time: the
    smoke reads the program's compile account."""
    import chip_smoke

    before = chip_smoke.compile_clock()
    import jax
    import jax.numpy as jnp

    jax.jit(lambda x: x * 2 + 1)(jnp.ones((3,))).block_until_ready()
    read = chip_smoke.compile_clock()
    assert set(read) == {"trace_s", "compile_s", "cache_hits", "cache_misses"}
    assert read["compile_s"] > before["compile_s"]
    assert read["trace_s"] > before["trace_s"]
    json.dumps(read)
