"""Node health-check workload: timed matmul + cross-host collective.

Parity: dlrover/trainer/torch/node_check/nvidia_gpu.py:26 and utils.py:59-90
— the reference times a bf16 matmul plus 10 rounds of a 16M-element
allgather over NCCL; slow/failed nodes are bisected by the master's paired
rendezvous. The TPU version exercises the same two failure surfaces:

- **chip compute**: a jitted bf16 matmul big enough to hit the MXU;
- **ICI/DCN path**: a jitted all-reduce across every device of the paired
  group (XLA collective over the real interconnect: ICI between the chips
  of one host, DCN between hosts).

Fault injection for tests mirrors ``MOCK_ERR_RANK`` (utils.py:50):
``DLROVER_TPU_MOCK_ERR_RANK=<process_id>`` makes that rank raise.
"""

from __future__ import annotations

import json
import os
import sys
import time


def write_result(elapsed: float, path: str = ""):
    path = path or os.getenv("DLROVER_TPU_CHECK_RESULT_FILE", "")
    if not path:
        return
    local_rank = os.getenv("DLROVER_TPU_LOCAL_RANK", "0")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(f"{path}.{local_rank}", "w") as f:
        json.dump({"elapsed": elapsed}, f)


def _workload_scale():
    """(matmul_size, matmul_rounds, collective_elems, collective_rounds).

    On an accelerator the load must *sustain* the MXU and the interconnect
    long enough that a degraded chip/link separates from healthy noise —
    the reference's check is 10 rounds of a 16M-element allgather plus a
    matmul (node_check/utils.py:59-90), not a one-shot kernel. 8192^2 bf16
    matmuls (~1.1 TFLOP each) x 30 chained rounds ≈ tens of TFLOPs of MXU
    time; 16M fp32 elements x 10 chained collectives ≈ 640 MB moved.
    On CPU (tests, smoke runs) the same shapes would dominate the suite,
    so they drop to token sizes. Env overrides for either case:
    DLROVER_TPU_CHECK_{MM_SIZE,MM_ROUNDS,COLL_ELEMS,COLL_ROUNDS}.
    """
    import jax

    platform = jax.devices()[0].platform
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"node check knows the TPU and the CPU backend, not "
            f"{platform!r}"
        )
    on_accel = platform == "tpu"
    mm_size = 8192 if on_accel else 256
    mm_rounds = 30 if on_accel else 3
    elems = (1 << 24) if on_accel else (1 << 16)
    coll_rounds = 10 if on_accel else 3
    mm_size = int(os.getenv("DLROVER_TPU_CHECK_MM_SIZE", mm_size))
    mm_rounds = int(os.getenv("DLROVER_TPU_CHECK_MM_ROUNDS", mm_rounds))
    elems = int(os.getenv("DLROVER_TPU_CHECK_COLL_ELEMS", elems))
    coll_rounds = int(os.getenv("DLROVER_TPU_CHECK_COLL_ROUNDS", coll_rounds))
    return mm_size, mm_rounds, elems, coll_rounds


def matmul_rounds(rounds: int, size: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def mm(a):
        # normalize so chained rounds stay ~1.0 (bf16 ones would hit inf
        # after two rounds; keep the MXU on real numbers)
        return (a @ a) * jnp.bfloat16(1.0 / size)

    a = jnp.ones((size, size), dtype=jnp.bfloat16)
    b = mm(a)  # compile outside the timed region
    float(jnp.sum(b))
    t0 = time.monotonic()
    for _ in range(rounds):
        a = mm(a)
    # fetch a scalar that depends on the whole chain, so the timing
    # covers execution and not only dispatch
    float(jnp.sum(a))
    return time.monotonic() - t0


def collective_rounds(rounds: int, elems: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("x",))
    sharding = NamedSharding(mesh, P("x"))
    n = jax.device_count()
    local = np.ones(
        (elems // n * jax.local_device_count(),), np.float32
    )
    x = jax.make_array_from_process_local_data(sharding, local)

    @jax.jit
    def allreduce(v):
        return jnp.sum(v) / v.size * jnp.ones_like(v)

    allreduce(x).block_until_ready()
    t0 = time.monotonic()
    for _ in range(rounds):
        x = allreduce(x)
    x.block_until_ready()
    # fetch one local element of the last round (see matmul_rounds)
    np.asarray(x.addressable_shards[0].data[:1])
    return time.monotonic() - t0


def main() -> int:
    from dlrover_tpu.trainer.elastic.distributed import init_elastic

    ctx = init_elastic()
    mock_err = os.getenv("DLROVER_TPU_MOCK_ERR_RANK", "")
    if mock_err and int(mock_err) == ctx.process_id:
        raise RuntimeError(f"mock error on rank {ctx.process_id}")
    mm_size, mm_rounds, elems, coll_rounds = _workload_scale()
    t = matmul_rounds(mm_rounds, mm_size)
    import jax

    # one process can own several chips (a whole TPU host): the
    # interconnect leg runs whenever there is more than one device,
    # not only when there is more than one process
    if jax.device_count() > 1:
        t += collective_rounds(coll_rounds, elems)
    mock_slow = os.getenv("DLROVER_TPU_MOCK_SLOW_RANK", "")
    if mock_slow and int(mock_slow) == ctx.process_id:
        time.sleep(2.0)
        t += 2.0
    write_result(t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
