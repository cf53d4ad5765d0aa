"""Constant catalogs for dlrover-tpu.

Parity: dlrover/python/common/constants.py:291-file (NodeType/NodeStatus/
JobExitReason/TrainingExceptionLevel catalogs), restated for a TPU stack:
the schedulable unit is a *host* of a TPU slice, and a "node group" is a
slice (all hosts of a slice fail and restart together — the reference's
node-unit concept, rdzv_manager.py:129).
"""


class PlatformType:
    LOCAL = "local"
    KUBERNETES = "k8s"


class NodeType:
    MASTER = "master"
    WORKER = "worker"
    # TF-PS parity types (sparse/elastic-PS layer):
    PS = "ps"
    CHIEF = "chief"
    EVALUATOR = "evaluator"


class NodeStatus:
    INITIAL = "initial"
    PENDING = "pending"
    RUNNING = "running"
    SUCCEEDED = "succeeded"
    FAILED = "failed"
    DELETED = "deleted"
    BREAKDOWN = "breakdown"  # hardware fault detected by health check


class TaskType:
    TRAIN = "train"
    EVAL = "eval"
    # streaming: no shard ready yet, worker should retry (not exhausted)
    WAIT = "wait"


class NodeEventType:
    ADDED = "added"
    MODIFIED = "modified"
    DELETED = "deleted"


class NodeExitReason:
    SUCCEEDED = "succeeded"
    KILLED = "killed"
    OOM = "oom"
    FATAL_ERROR = "fatal_error"
    HARDWARE_ERROR = "hardware_error"
    UNKNOWN_ERROR = "unknown_error"
    RELAUNCHED = "relaunched"
    # deliberately removed by a scale-down; the rank may come back later
    # with a fresh relaunch budget
    SCALED_DOWN = "scaled_down"
    # evicted by the platform (spot/preemptible reclaim): a SCHEDULED
    # departure — the replacement does not burn relaunch budget, the
    # gap is booked to the `eviction` goodput category, and the Brain
    # prices the job's floor/dwell accordingly
    PREEMPTED = "preempted"
    # convicted of silent data corruption by the paired-device audit
    # vote (parallel/sdc.py): the chip computes wrong-but-finite
    # numbers, so it must NEVER rejoin — permanent rendezvous
    # quarantine until hardware replacement, and the scheduler treats
    # the host as absent capacity
    SDC_QUARANTINED = "sdc_quarantined"


class JobExitReason:
    SUCCEEDED = "succeeded"
    CODE_ERROR = "code_error"
    WORKER_OOM = "worker_oom"
    WORKER_ERROR = "worker_error"
    HANG_ERROR = "hang_error"
    RDZV_TIMEOUT_ERROR = "rdzv_timeout_error"
    PENDING_TIMEOUT = "pending_timeout"
    UNKNOWN_ERROR = "unknown_error"


class TrainingExceptionLevel:
    PROCESS_ERROR = "process_error"
    NODE_ERROR = "node_error"
    RDZV_ERROR = "rdzv_error"
    WARNING = "warning"
    INFO = "info"


class RendezvousName:
    ELASTIC_TRAINING = "elastic-training"
    NETWORK_CHECK = "network-check"


class NetworkFailureReason:
    NO_INIT = "not_initialized"
    NODE_FAILURE = "node_failure"
    WAITING_NODE = "waiting_node"


class TrainingLoopStatus:
    START = 1
    END = 2
    PENDING = 3


class JobStage:
    """Lifecycle stage of the whole job on the master."""

    INIT = "init"
    RUNNING = "running"
    SUSPENDED = "suspended"
    STOPPING = "stopping"
    STOPPED = "stopped"


class CheckpointConstant:
    MODEL_STATES_NAME = "model_states"
    TRAIN_STATE_NAME = "train_state"
    TRACKER_FILE = "latest_step"
    SAVE_TIMEOUT = 600


class ConfigPath:
    """Runtime paral-config plumbing (master -> agent -> dataloader).

    Parity: dlrover/python/common/constants.py ConfigPath + the paral-config
    file loop (elastic_agent/config/paral_config_tuner.py:30).
    """

    ENV_PARAL_CONFIG = "DLROVER_TPU_PARAL_CONFIG_PATH"
    PARAL_CONFIG = "/tmp/dlrover_tpu/auto_paral_config.json"
    ENV_RUNTIME_METRICS = "DLROVER_TPU_RUNTIME_METRICS_PATH"
    RUNTIME_METRICS = "/tmp/dlrover_tpu/runtime_metrics.json"
    # master->worker command relay (flight dumps / profiler captures):
    # the agent's WorkerCommandRelay polls the master and mirrors
    # pending commands here; the trainer polls the file at log cadence
    ENV_WORKER_COMMANDS = "DLROVER_TPU_WORKER_COMMANDS_PATH"
    WORKER_COMMANDS = "/tmp/dlrover_tpu/worker_commands.json"


class NodeEnv:
    """Env vars the agent exports into training processes."""

    MASTER_ADDR = "DLROVER_TPU_MASTER_ADDR"
    NODE_ID = "DLROVER_TPU_NODE_ID"
    NODE_RANK = "DLROVER_TPU_NODE_RANK"
    NODE_NUM = "DLROVER_TPU_NODE_NUM"
    JOB_NAME = "DLROVER_TPU_JOB_NAME"
    # JAX distributed bootstrap (the TPU analog of MASTER_ADDR/PORT +
    # NCCL rendezvous): our master owns coordinator assignment.
    COORDINATOR_ADDR = "DLROVER_TPU_COORDINATOR_ADDR"
    PROCESS_ID = "DLROVER_TPU_PROCESS_ID"
    NUM_PROCESSES = "DLROVER_TPU_NUM_PROCESSES"
    RESTART_COUNT = "DLROVER_TPU_RESTART_COUNT"
    # the agent's to set and the worker's to read, nobody else's: one
    # JSON object with ``t_spawn``, the agent's ``time.monotonic()`` just
    # before it started this process (one clock for a host), and, after
    # a restart, the record ``_restart_workers`` had kept by then
    # (``reason``, ``restart``, ``detect_tick_s``,
    # ``persist_before_restart_s``, ``stop_workers_s``,
    # ``shm_lock_reset_s``, ``rendezvous_s``).
    # ``init_elastic()`` folds it into the worker's start-up record
    SPAWN_TIMELINE = "DLROVER_TPU_SPAWN_TIMELINE"
    GRAFT_PLATFORM = "JAX_PLATFORMS"


class DefaultValues:
    SERVICE_PORT = 0  # pick a free port
    RDZV_TIMEOUT_SECS = 600
    PENDING_TIMEOUT_SECS = 900
    HANG_TIMEOUT_SECS = 1800
    HEARTBEAT_INTERVAL_SECS = 15
    MONITOR_INTERVAL_SECS = 5
    MAX_RELAUNCH_COUNT = 3
    SHARD_QUEUE_TIMEOUT = 600


class NodeCheckResult:
    """Outcome of a node health (network) check round."""

    NORMAL = "normal"
    FAULT = "fault"
    STRAGGLER = "straggler"
