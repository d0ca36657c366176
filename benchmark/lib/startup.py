"""The program's own account of its start-up, for the ``setup_*`` readers.

Since PR 36 the program keeps one ``jax.monitoring`` listener
(``nnstreamer_tpu.obs.context.compile_account``: every trace, lowering and
backend compile, charged to the program span that paid it, no second
counted twice) and its start-up spans (``startup_spans``: ``setup.params``,
``setup.engine``, one ``program.first_call`` a jitted program), both kept
apart from the ring, so they can be read after any number of passes.

Set-up ends where the window opens. A reader finds that moment as the
traced part's start less the offset the mix gives it (``trace_bounds`` is
stamped once the profiler has started, so the moment found lies a little
inside the window: whatever compiled there would count as set-up, and
``compiles_in_window`` says that nothing does).
"""
from __future__ import annotations


def split(facts):
    """``{engine_build_s, trace_lower_s, cache_load_s, fresh_compile_s,
    fresh_compiles}`` of this process up to the window's opening, or
    ``None`` with nothing to read: no traced part, or a program without
    the account (before PR 36)."""
    bounds = facts.get("trace_bounds")
    if not bounds:
        return None
    from nnstreamer_tpu.obs import context

    if not hasattr(context, "compile_account"):
        return None
    spec = facts["mix"]["trace"]
    opened = bounds[0] - min(
        spec["start_s"], max(facts["window_s"] - spec["seconds"], 0.0))
    totals = context.compile_account(until=opened)["totals"]
    build = [s for s in context.startup_spans()
             if s.name.startswith("setup.") and s.start_s < opened]
    if not build:
        return None
    # jax's seconds charged to the build's spans are in the sums below
    charged = sum(s.attrs.get(k, 0.0) for s in build
                  for k in ("trace_s", "lower_s", "compile_s"))
    return {"engine_build_s": sum(s.dur_s for s in build) - charged,
            "trace_lower_s": totals["trace_own_s"] + totals["lower_own_s"],
            "cache_load_s": totals["load_s"],
            "fresh_compile_s": totals["fresh_s"],
            "fresh_compiles": totals["fresh"]}
