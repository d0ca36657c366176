"""Stream sharding with ordered re-join across query workers.

One live stream round-robins across two worker pipelines
(tensor_shard), each worker transforms its share, and tensor_unshard
restores global order by sequence number — the multi-host
stream-sharding topology of SURVEY.md §5.8 on loopback.

    python examples/sharded_stream.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from nnstreamer_tpu.runtime.parse import parse_launch  # noqa: E402


def start_worker(server_id: int):
    pipe = parse_launch(
        f"tensor_query_serversrc name=src id={server_id} port=0 "
        "caps=other/tensors,format=static,dimensions=1,types=float32 "
        "! tensor_filter framework=jax model=builtin://scaler?factor=10 "
        f"! tensor_query_serversink id={server_id}")
    pipe.play()
    deadline = time.monotonic() + 5
    while pipe.get("src").bound_port == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    return pipe, pipe.get("src").bound_port


def main() -> None:
    w0, p0 = start_worker(110)
    w1, p1 = start_worker(111)
    client = parse_launch(
        "appsrc name=in caps=other/tensors,format=static,dimensions=1,types=float32 "
        "! tensor_shard name=s "
        f"s.src_0 ! tensor_query_client host=127.0.0.1 port={p0} ! u.sink_0 "
        f"s.src_1 ! tensor_query_client host=127.0.0.1 port={p1} ! u.sink_1 "
        "tensor_unshard name=u ! tensor_sink name=out")
    out = []
    client.get("out").connect(
        lambda b: out.append(float(np.asarray(b.tensors[0])[0])))
    client.play()
    src = client.get("in")
    for i in range(12):
        src.push_buffer(np.full(1, float(i), np.float32))
        time.sleep(0.01)
    deadline = time.monotonic() + 10
    while len(out) < 12 and time.monotonic() < deadline:
        time.sleep(0.02)
    client.stop()
    w0.stop()
    w1.stop()
    print(f"in order, each x10 by alternating workers: {out}")
    assert out == [float(i * 10) for i in range(12)], out


if __name__ == "__main__":
    main()
