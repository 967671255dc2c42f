"""The ``sharedtpu/`` label and annotation vocabulary.

TPU-native counterpart of the reference's ``sharedgpu/`` domain
(``pkg/scheduler/constants.go:3-28``). Labels are written by the user on a
workload; annotations are written back by the scheduler at reserve time.
"""

DOMAIN = "sharedtpu/"

# --- user-facing labels -----------------------------------------------------
# Coscheduling pod group (constants.go:6-11).
POD_GROUP_NAME = DOMAIN + "group_name"
POD_GROUP_HEADCOUNT = DOMAIN + "group_headcount"
POD_GROUP_THRESHOLD = DOMAIN + "group_threshold"

# Pod priority: 0 = opportunistic, 1-100 = guarantee (constants.go:13-15,
# pod.go:175-199). Pods in the same group must share a priority.
POD_PRIORITY = DOMAIN + "priority"

# Upper limit / guaranteed fraction of chip compute time over the accounting
# window (constants.go:16-19). Fractions in (0, 1] share a chip; integers > 1
# request whole chips.
POD_TPU_LIMIT = DOMAIN + "tpu_limit"
POD_TPU_REQUEST = DOMAIN + "tpu_request"

# HBM request in bytes (constants.go:20-21).
POD_TPU_MEMORY = DOMAIN + "tpu_mem"

# Chip model constraint, e.g. "tpu-v4" / "tpu-v5e" (constants.go:22-23).
POD_TPU_MODEL = DOMAIN + "tpu_model"

# Scheduling deadline in seconds (≙ a sharedgpu/deadline-style label):
# a pod still unbound this long after submit resolves "timed-out"
# instead of retrying forever. 0/absent = no deadline.
POD_DEADLINE = DOMAIN + "deadline"

# Per-tenant service-level objectives (doc/observability.md, SLO plane):
# comma-separated objectives, e.g. "grant-wait-p99<=50ms,availability>=99.9".
# Parsed by obs/slo.py; declared per namespace at submit time.
POD_SLO = DOMAIN + "slo"

# Workload class for SLO attribution and (ROADMAP item 1) priority
# isolation: "latency" | "best-effort". Absent = best-effort.
POD_CLASS = DOMAIN + "class"
TPU_CLASSES = ("latency", "best-effort")

# --- scheduler-written annotations (constants.go:25-27) ---------------------
POD_TPU_CHIP_ID = DOMAIN + "tpu_chip_id"     # ≙ sharedgpu/gpu_uuid
POD_CELL_ID = DOMAIN + "cell_id"
POD_GROUP_RANK = DOMAIN + "group_rank"       # survives engine restarts
POD_MANAGER_PORT = DOMAIN + "tpu_manager_port"

# --- environment contract into the workload container -----------------------
# ≙ NVIDIA_VISIBLE_DEVICES / LD_PRELOAD / POD_MANAGER_PORT / POD_NAME
# injection (pod.go:435-457). On TPU the client process must NOT grab the
# chip (single-tenant per process); it is pointed at its pod manager and the
# chip stays owned by the proxy.
# The chip GRANT: global chip ids ("TPU-v5-lite-<host>-<index>"), optionally
# carved ("chip@x.y"). It lives in the repo's own namespace because libtpu
# parses TPU_VISIBLE_CHIPS itself (a list of local chip indices): handed a
# chip id there, the directly attached runtime finds no device at all and
# the pod's backend never starts. attach._pin_visible_devices translates
# the grant into the runtime's own variables.
ENV_VISIBLE_CHIPS = "KUBESHARE_TPU_VISIBLE_CHIPS"
# Node mesh shape ("2x4") accompanying a carved grant (entries "chip@x.y",
# doc/gang.md) so the torus-aware block check in gang/carve.py can
# validate wrap-around carves. Absent for seed-format assignments;
# carve-unaware consumers ignore both. Not KUBESHARE_TPU_MESH: that name
# is the workload's own axis spec ("dp=2,sp=2,tp=2", parallel/runner.py).
ENV_MESH_SHAPE = "KUBESHARE_TPU_NODE_MESH"
ENV_POD_MANAGER_PORT = "KUBESHARE_TPU_POD_MANAGER_PORT"
ENV_POD_NAME = "KUBESHARE_TPU_POD_NAME"
ENV_SCHEDULER_IP = "KUBESHARE_TPU_SCHEDULER_IP"

# Transparent-attach contract (≙ the LD_PRELOAD zero-touch contract,
# pod.go:445-457): a sitecustomize shim on PYTHONPATH reads these and
# routes an UNMODIFIED JAX workload through the isolation runtime — see
# kubeshare_tpu/attach.py. The chip-proxy port is node-local state the
# launcher daemon owns; the share parameters come from the binding.
ENV_CHIP_PROXY_PORT = "KUBESHARE_TPU_CHIP_PROXY_PORT"
ENV_TPU_REQUEST = "KUBESHARE_TPU_REQUEST"
ENV_TPU_LIMIT = "KUBESHARE_TPU_LIMIT"
ENV_TPU_MEMORY = "KUBESHARE_TPU_MEM"
ENV_ATTACH_MODE = "KUBESHARE_TPU_ATTACH"  # proxy | gate | off (default auto)
# Gang/distributed contract (≙ the reference's torchelastic env in its
# distribute manifests): the scheduler injects group identity + size +
# this member's rank; the COORDINATOR address is wired by the manifest
# (e.g. a headless service on rank 0) and consumed by parallel.runner.
ENV_GROUP_NAME = "KUBESHARE_TPU_GROUP"
ENV_NUM_PROCESSES = "KUBESHARE_TPU_NUM_PROCESSES"
ENV_PROCESS_ID = "KUBESHARE_TPU_PROCESS_ID"
ENV_COORDINATOR = "KUBESHARE_TPU_COORDINATOR"
ENV_RENDEZVOUS_TIMEOUT_S = "KUBESHARE_TPU_RENDEZVOUS_TIMEOUT_S"

# Library/host paths (pod.go:23-26, cmd/kubeshare-query-ip/main.go:22-34).
LIBRARY_PATH = "/var/lib/kubeshare-tpu/library"
SCHEDULER_IP_FILE = LIBRARY_PATH + "/schedulerIP.txt"

# Node actuation directories (pkg/config/config.go:19-22): per-chip client
# lists consumed by the node launcher daemon via inotify.
SCHEDULER_DIR = "/var/lib/kubeshare-tpu/scheduler"
CONFIG_DIR = SCHEDULER_DIR + "/config"
PORT_DIR = SCHEDULER_DIR + "/podmanagerport"
LOG_DIR = "/var/log/kubeshare-tpu"

# Node label that opts a node into TPU sharing (≙ SharedGPU=true,
# pkg/scheduler/node.go:18-26).
NODE_SHARED_TPU_LABEL = "SharedTPU"

# Pod-manager port pool: 512 ports from 50050 per node
# (pkg/scheduler/scheduler.go:351, node.go:11-15).
POD_MANAGER_PORT_START = 50050
POD_MANAGER_PORT_RANGE = 512

# Gemini-parity token scheduler constants
# (docker/kubeshare-gemini-scheduler/launcher.py:27-29, 75-80).
SCHD_PORT_START = 49901
BASE_QUOTA_MS = 300.0
MIN_QUOTA_MS = 20.0
WINDOW_MS = 10000.0

# Name under which the scheduler registers (scheduler.go:35-56's
# Name = "kubeshare-scheduler").
SCHEDULER_NAME = "kubeshare-tpu-scheduler"

# Well-known control-plane service ports (deploy/registry.yaml:63,
# deploy/scheduler.yaml:47; ≙ the reference's collector 9004 / aggregator
# 9005 ports, cmd/kubeshare-collector/main.go + cmd/kubeshare-aggregator).
REGISTRY_PORT = 9006
SCHEDULER_PORT = 9007

# Health plane defaults (doc/health.md). The reference implicitly ages
# out dead nodes via Prometheus scrape staleness (~5 s scrape + 5-10 s
# query window); the lease TTL plays that role explicitly here.
LEASE_TTL_S = 5.0            # heartbeat lease lifetime
HEALTH_MISS_THRESHOLD = 3    # missed TTLs before a suspect node is dead
HEALTH_RECOVER_K = 3         # consecutive fresh beats to leave quarantine
HEALTH_QUARANTINE_S = 30.0   # minimum hold-down after a death
