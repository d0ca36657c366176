"""The width of a prefill launch (serving/lm_engine.py ``prefill_width``).

The engine compiles one prefill shape and chooses its width itself: as wide
as the chip's ridge (peak FLOP/s over HBM bytes/s, scaled by a weight's
bytes), in whole pages, never under the ``chunk`` argument and at most
``max_seq``; where the chip is unknown (the CPU) the argument stands, so
every CPU suite keeps its many-chunk prompts. The rule is a pure function
of a described chip; the engine test steers its one chip lookup.
"""
import numpy as np
import pytest
from engine_util import step_now

from nnstreamer_tpu.obs import context as obs_context
from nnstreamer_tpu.serving import PagedLMEngine, lm_engine
from nnstreamer_tpu.utils import flops


class _Chip:
    def __init__(self, platform, kind):
        self.platform = platform
        self.device_kind = kind


V5E = _Chip("tpu", "TPU v5 lite")
CPU = _Chip("cpu", "cpu")


@pytest.mark.parametrize(
    "chip, chunk, max_seq, page_size, weight_bytes, want", [
        (V5E, 32, 2048, 16, 2, 256),     # opt_1.3b: 240.5 rows -> 256
        (V5E, 128, 3072, 16, 2, 256),    # kanana2_30b_a3b_l8
        (V5E, 32, 2048, 16, 4, 512),     # float32 weights: 481 rows -> 512
        (V5E, 32, 128, 16, 2, 128),      # the serving limit clips it
        (V5E, 32, 2880, 48, 2, 288),     # whole pages: 256 -> 6 pages of 48
        (V5E, 512, 2048, 16, 2, 512),    # never under the argument
        (CPU, 8, 64, 4, 4, 8),           # unknown chip: the argument stands
        (CPU, 100, 64, 4, 4, 64),        # ... under the same limit
    ])
def test_the_width_is_the_ridge_in_whole_pages(chip, chunk, max_seq,
                                               page_size, weight_bytes, want):
    assert lm_engine.prefill_width(chunk, max_seq, page_size, weight_bytes,
                                   chip) == want


def test_a_tpu_missing_from_the_tables_is_an_error():
    with pytest.raises(ValueError, match="v9000"):
        lm_engine.prefill_width(32, 2048, 16, 2, _Chip("tpu", "TPU v9000"))


def _tiny():
    from nnstreamer_tpu.models.lm_serving import tiny
    from nnstreamer_tpu.models.transformer import init_params

    return tiny.cfg, init_params(tiny.cfg, seed=0)


def _serve(engine, prompts, steps):
    """Each prompt alone through slot 0: ``[(tokens, launches)]``."""
    out = []
    for p in prompts:
        toks = [engine.admit(0, p, steps)]
        launches = engine.prefill_stamp(0)[1]
        while len(toks) < steps:
            toks.append(int(step_now(engine)[0]))
        engine.release(0)
        out.append((toks, launches))
    return out


def test_the_engine_derives_its_one_width_from_the_chip(monkeypatch):
    from nnstreamer_tpu.models.decoding import make_generate

    cfg, params = _tiny()
    rng = np.random.default_rng(30)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (19, 5, 33)]
    steps = 6
    kw = dict(slots=1, page_size=8, pages=16, share_prefixes=False)

    given = PagedLMEngine(cfg, params, chunk=16, **kw)
    assert given.chunk == 16  # the CPU: the argument stands
    # a chip whose ridge is 6 FLOPs a byte: float32 weights, 12 rows -> 16
    monkeypatch.setattr(flops, "ridge_flops_per_byte",
                        lambda device=None: 6.0)
    derived = PagedLMEngine(cfg, params, chunk=8, **kw)
    assert derived.chunk == 16

    obs_context.reset()
    got = _serve(derived, prompts, steps)
    chunks = [s.attrs for s in obs_context.finished_spans()
              if s.name == "engine.chunk.prepare"]
    assert got == _serve(given, prompts, steps)
    gen = make_generate(cfg)
    for p, (toks, _) in zip(prompts, got):
        assert toks == np.asarray(gen(params, p[None], steps))[0, p.size:] \
            .tolist()
    # 19 tokens are two launches of 16, and the span says how full each was
    assert [launches for _, launches in got] == [2, 1, 3]
    assert [(a["n_valid"], a["width"]) for a in chunks] == [
        (16, 16), (3, 16), (5, 16), (16, 16), (16, 16), (1, 16)]
    # one prefill program and one step, whatever the prompt lengths were
    assert derived.compile_count == 2
