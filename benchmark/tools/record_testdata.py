"""Record the small device trace the trace-reduction tests read.

Run on the chip (``chiprun -- python3 benchmark/tools/record_testdata.py``):
two named jitted programs, a known number of calls each, host spans around
them, python tracer off so the file stays small. Writes the ``.xplane.pb``
and a text dump of its planes and lines to ``chiprun_out/testdata/``; the
``.pb`` is then copied to ``benchmark/testdata/`` by hand and committed.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, ProfileOptions

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, jax selected {dev.platform}", file=sys.stderr)
        return 2
    out = os.path.join("chiprun_out", "testdata")
    os.makedirs(out, exist_ok=True)

    @jax.jit
    def prog_matmul(x):
        return jnp.tanh(x @ x)

    @jax.jit
    def prog_copy(x):
        return jnp.transpose(x).copy() + 1.0

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    prog_matmul(x).block_until_ready()
    prog_copy(x).block_until_ready()

    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    logdir = os.path.join(out, "trace")
    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(logdir, profiler_options=opts)
    t0 = time.monotonic()
    for _ in range(4):
        with jax.profiler.TraceAnnotation("bench:matmul"):
            prog_matmul(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench:sleep"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench:copy"):
            prog_copy(x).block_until_ready()
            prog_copy(x).block_until_ready()
    window_s = time.monotonic() - t0
    jax.profiler.stop_trace()

    pb = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                "*.xplane.pb"))[-1]
    shutil.copy(pb, os.path.join(out, "small.xplane.pb"))
    lines = [f"window_s {window_s}", f"size {os.path.getsize(pb)}"]
    pd = ProfileData.from_file(pb)
    for pl in pd.planes:
        lines.append(f"PLANE {pl.name!r} stats={dict(pl.stats)}")
        for ln in pl.lines:
            evs = list(ln.events)
            lines.append(f"  LINE {ln.name!r} events={len(evs)}")
            for e in evs[:12]:
                lines.append(f"      {e.name!r} start={e.start_ns} "
                             f"dur={e.duration_ns} stats={dict(e.stats)}")
    with open(os.path.join(out, "dump.txt"), "w") as fh:
        fh.write("\n".join(lines))
    print(json.dumps({"window_s": window_s, "size": os.path.getsize(pb),
                      "kind": dev.device_kind,
                      "mem": dev.memory_stats()}))
    shutil.rmtree(logdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
