"""Production serving tier over the ragged inference engine.

The reference stack splits serving across two repos: DeepSpeed's inference
v2 ragged engine (the scheduler + kernels) and DeepSpeed-MII on top (the
frontend, replica routing, and deployment surface). This package is our
MII-role tier, stdlib-only:

- :mod:`protocol` — request/response dataclasses, validation, SSE framing
- :mod:`engine_loop` — per-replica background step-loop driver
  (``put()``/``step()`` pump, per-request token streams, graceful drain)
- :mod:`router` — least-outstanding-tokens placement + KV-aware admission
  control + bounded queues (429 backpressure)
- :mod:`frontend` — ``http.server`` HTTP surface: ``POST /v1/completions``
  (JSON + SSE), ``GET /healthz``, ``GET /metrics``
- :mod:`faults` — deterministic fault-injection harness (named injection
  points at the real seams; drives the dispatch watchdog, crash
  containment, and replica-failover machinery — docs/FAULT_TOLERANCE.md)
- :mod:`cluster` — disaggregated prefill/decode serving: role-tagged
  replicas, KV-handoff transfer, a cluster-wide prefix index, and an
  SLO-burn-driven decode-pool autoscaler

See docs/SERVING.md for the architecture walkthrough.
"""

from deepspeed_tpu.serving.cluster import (  # noqa: F401
    ClusterConfig,
    ClusterPrefixIndex,
    DecodeAutoscaler,
    InMemoryTransferChannel,
    ServingCluster,
    build_cluster_server,
    transfer_beats_prefill,
)
from deepspeed_tpu.serving.engine_loop import (  # noqa: F401
    EngineLoop,
    ReplicaDraining,
    ReplicaStats,
    StreamError,
    TokenStream,
)
from deepspeed_tpu.serving.frontend import (  # noqa: F401
    ServingFrontend,
    build_server,
)
from deepspeed_tpu.serving.protocol import (  # noqa: F401
    FINISH_CANCELLED,
    FINISH_LENGTH,
    FINISH_STOP,
    FINISH_TIMEOUT,
    CompletionRequest,
    CompletionResponse,
    ProtocolError,
    decode_sse,
    encode_sse,
    sse_done,
)
from deepspeed_tpu.serving.router import (  # noqa: F401
    DeadlineExceeded,
    Draining,
    Overloaded,
    ReplicaRouter,
    RouterConfig,
    plan_placement,
)
from deepspeed_tpu.utils.faults import (  # noqa: F401
    POINT_ALLOC,
    POINT_CKPT_COLLECT,
    POINT_CKPT_COMMIT,
    POINT_CKPT_FLUSH,
    POINT_CKPT_LATEST,
    POINT_CKPT_LOAD,
    POINT_DISPATCH,
    POINT_H2D,
    POINT_LOOP,
    POINT_READBACK,
    POINT_SUBMIT,
    FatalFaultError,
    FaultError,
    FaultInjector,
    FaultSpec,
    classify_transient,
    get_fault_injector,
)
