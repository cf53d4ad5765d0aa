"""Test env: run JAX on a virtual 8-device CPU mesh so multi-chip sharding
logic is exercised without TPU hardware (SURVEY.md §4 lesson)."""

import os

os.environ.setdefault("DLROVER_TPU_SOCKET_DIR", "/tmp/dlrover_tpu_test/sockets")
# the job name namespaces IPC sockets and shm segments: one per xdist
# worker, or two test files that each run a checkpoint saver at the same
# time share ``ckpt_lock_0.sock`` and the ``job_0_0`` segment (one's
# teardown unlinks the other's socket, one's saver holds the other's
# shard lock: "saver busy"). Assigned, not ``setdefault``: the xdist
# controller imports this file first and its workers inherit its
# environment, so a default set there would be every worker's name
_worker = os.getenv("PYTEST_XDIST_WORKER")
if _worker:
    os.environ["DLROVER_TPU_JOB_NAME"] = "t" + _worker
else:
    os.environ.setdefault("DLROVER_TPU_JOB_NAME", "tmain")
# trainers built inside the test process (and the workers tests launch)
# must not write the persistent compile cache into the checkout; a test
# that wants the cache places it with JAX_COMPILATION_CACHE_DIR
os.environ.setdefault("DLROVER_TPU_COMPILE_CACHE", "off")

import jax  # noqa: E402

from dlrover_tpu.utils.device import configure_devices  # noqa: E402

configure_devices("cpu:8")
jax.devices()

import pytest  # noqa: E402

# -- fast tier (review r4 #9) ---------------------------------------------
# `pytest -m fast` proves the core in ~2 minutes on one CPU: protocol /
# IPC, flash checkpoint, the whole control plane, the data planes, and
# ONE numerics-parity test per parallelism scheme. Compile-heavy parity
# sweeps and multi-process soaks stay in the full suite / slow tier.
_FAST_FILES = {
    "test_common.py",
    "test_master.py",
    "test_flash_checkpoint.py",
    "test_incremental_ckpt.py",
    "test_k8s.py",
    "test_brain.py",
    "test_elastic_agent.py",
    "test_monitors.py",
    "test_elastic_data.py",
    "test_autoscale.py",
    "test_master_failover.py",
    "test_remote_feed.py",
    "test_shm_feed.py",
}
_FAST_IDS = (
    # one parity test per parallelism: dp/fsdp/tp mesh, ring SP,
    # Ulysses SP, expert parallel, pipeline
    "TestModelParallelism::test_forward_invariant_to_mesh",
    "TestRingAttention::test_matches_dense",
    "TestUlyssesAttention::test_matches_dense",
    "TestMoE::test_expert_parallel_matches_dense_top1",
    "test_pipeline_forward_matches_plain",
)


def pytest_collection_modifyitems(config, items):
    for item in items:
        if "slow" in item.keywords:
            continue
        name = os.path.basename(str(item.fspath))
        if name in _FAST_FILES or any(
            fid in item.nodeid for fid in _FAST_IDS
        ):
            item.add_marker(pytest.mark.fast)
